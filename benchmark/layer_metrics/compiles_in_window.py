"""Compilations inside the measured window: programs.program_summary()
["compiles"] plus jax lowering events (train), HEALTH retraces (serve),
after minus before.  Must be 0, else `correct` is false."""


def read(run):
    f = run.facts
    if "compiles_in_window" not in f:
        return None
    return f["compiles_in_window"] + f.get("lowerings_in_window", 0)
