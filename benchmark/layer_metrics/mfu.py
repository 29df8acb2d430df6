"""Model FLOP/s utilization: the configuration's required operations of one
step (ops_and_bytes: matrix products, forward + backward, nothing
recomputed) x steps/s over chips x the bf16 peak of harness/peaks.py."""
from benchmark.harness import peaks


def read(run):
    f = run.facts
    if "ops" not in f or f["device"]["platform"] != "tpu":
        return None
    peak = peaks.peaks_for(f["device"]["kind"])["bf16_flops_per_s"]
    rate = f["host_steps"] / f["host_window_s"]
    return 100.0 * f["ops"]["flops"] * rate / (run.cell["chips"] * peak)
