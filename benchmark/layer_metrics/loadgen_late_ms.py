"""Load generator (the benchmark's own): p99 of (actual send - due).  A run
whose lateness exceeds a tenth of predict_p50_ms is not `correct`."""
from benchmark.harness import stats


def read(run):
    late = run.facts.get("late_s")
    return None if not late else 1e3 * stats.percentile(late, 99.0)
