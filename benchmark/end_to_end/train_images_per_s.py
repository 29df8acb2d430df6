"""Images through the counted steps over the seconds from the first counted
dispatch to the last loss ready (block_until_ready), whole window."""


def read(run):
    f = run.facts
    if "steps" not in f:
        return None
    return f["steps"] * f["rows_per_step"] / f["window_s"]
