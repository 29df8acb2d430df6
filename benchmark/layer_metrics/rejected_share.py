"""Serve front: serve.rejected over requests sent, METRICS after minus
before."""


def read(run):
    f = run.facts
    if "counters" not in f:
        return None
    return 100.0 * f["counters"].get("serve.rejected", 0) \
        / max(1, f["attempted"])
