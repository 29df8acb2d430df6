"""Sharding + exchange: summed device time of all-gather / reduce-scatter /
all-reduce / all-to-all events per step, mean over the devices (trace)."""


def read(run):
    trace = run.facts.get("trace")
    if not trace or not trace.get("step_events"):
        return None
    return 1e3 * trace["collective_s"] / trace["step_events"]
