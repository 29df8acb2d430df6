"""The 99th percentile of the same latencies; refused by stats.percentile
unless ten samples lie beyond it (1,000 requests in the window)."""
from benchmark.harness import stats


def read(run):
    lat = run.facts.get("latency_s")
    return None if not lat else 1e3 * stats.percentile(lat, 99.0)
