"""Batcher: mean of step_phase_seconds{phase=queue_wait} in the window."""


def read(run):
    q = (run.facts.get("phases") or {}).get("queue_wait")
    return None if not q or not q["count"] else 1e3 * q["sum"] / q["count"]
