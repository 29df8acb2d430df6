"""Process start to the first counted step or the first scheduled request:
imports, build, export, compile or cache load, warm-up, the correctness
check.  Host clock, taken by the driver."""


def read(run):
    return run.facts.get("setup_s")
