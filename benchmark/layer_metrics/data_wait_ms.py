"""Input pipeline: step_phase_seconds{phase=data_wait} (what the consumer
of io.DevicePrefetcher waited) summed over the untraced part of the
window, per counted step."""


def read(run):
    f = run.facts
    if "data_wait_s" not in f:
        return None
    return 1e3 * f["data_wait_s"] / f["host_steps"]
