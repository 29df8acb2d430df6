"""Kernels: the banded attention products' share of their roofline.  The
least time the chip could take for the REQUIRED operations of one step's
sliding-window cores - `attention_core_window` of
ops_and_bytes(...)["detail"]["forward"] (q.k and p.v over the head's
lanes, every head and sliding layer, the pairs INSIDE the band alone),
forward once and backward twice, nothing recomputed, over the bf16 peak
(mla_core_roofline.py's convention) - over the device time a step under
`attention_window/attention_core`.  The same work whatever implements it:
a kernel that visits blocks the band only crosses pays for them here."""
from benchmark.harness import peaks, scope_time_swa


def read(run):
    f = run.facts
    took = scope_time_swa.ms(run, "attention_window/attention_core")
    core = f.get("ops", {}).get("detail", {}).get("forward", {}) \
        .get("attention_core_window")
    if not took or core is None:
        return None
    peak = peaks.peaks_for(f["device"]["kind"])["bf16_flops_per_s"]
    least = 1e3 * 3 * core / (run.cell["chips"] * peak)
    run.note(window_core_least_ms=least, window_core_ms=took)
    return 100.0 * least / took
