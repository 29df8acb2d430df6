"""Tokens (rows x sequence length, all chips together) through the counted
steps over the seconds from the first counted dispatch to the last loss
ready."""


def read(run):
    f = run.facts
    if "steps" not in f:
        return None
    return f["steps"] * f["rows_per_step"] * f["units_per_row"] \
        / f["window_s"]
