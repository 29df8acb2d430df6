"""Device: 1 - union of op intervals over the traced window, mean over the
cell's devices."""


def read(run):
    trace = run.facts.get("trace")
    return None if not trace else 100.0 * trace["idle_share"]
