"""Device: memory_stats()["peak_bytes_in_use"] of the fullest chip after
the window."""


def read(run):
    peak = run.facts.get("memory_peak_bytes")
    return None if not peak else peak / 1e9
