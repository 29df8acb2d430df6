"""The part of the collective time during which no other operation runs on
that device, per step, mean over the devices (trace)."""


def read(run):
    trace = run.facts.get("trace")
    if not trace or not trace.get("step_events"):
        return None
    return 1e3 * trace["collective_exposed_s"] / trace["step_events"]
