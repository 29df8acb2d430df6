"""Kernels, as one program: the least time the chips could take for one
step - max(required operations / peak FLOP/s, least bytes / peak bytes/s),
from ops_and_bytes - over the device time of one step program in the trace
(median duration of the step's module events).  No kernel of the measured
paths has a name of its own yet; a named kernel's own <kernel>_roofline
comes with the PR that brings it."""
from benchmark.harness import peaks


def bound(ops, peak, chips):
    """(least seconds, which bound) of one step on `chips` chips."""
    compute = ops["flops"] / (chips * peak["bf16_flops_per_s"])
    memory = ops["bytes"] / (chips * peak["hbm_bytes_per_s"])
    return max(compute, memory), "compute" if compute >= memory else "memory"


def read(run):
    f = run.facts
    trace = f.get("trace")
    if not trace or "ops" not in f or not trace.get("step_busy_s"):
        return None
    least, which = bound(f["ops"], peaks.peaks_for(f["device"]["kind"]),
                         run.cell["chips"])
    run.note(step_roofline_bound=which, least_step_s=least,
             step_busy_s=trace["step_busy_s"])
    return 100.0 * least / trace["step_busy_s"]
