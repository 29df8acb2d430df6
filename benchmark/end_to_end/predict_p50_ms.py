"""Median latency of a PREDICT, from the instant it was due on the schedule
to its answer in the client; a refused, failed or timed-out request counts
as the client timeout."""
from benchmark.harness import stats


def read(run):
    lat = run.facts.get("latency_s")
    return None if not lat else 1e3 * stats.median(lat)
