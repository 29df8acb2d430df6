"""Step compiler: median host time of one ``step.step()`` call with no
sync - the enqueue (plan, state gather, dispatch, write-back)."""
from benchmark.harness import stats


def read(run):
    calls = run.facts.get("step_call_s")
    return None if not calls else 1e3 * stats.median(calls)
