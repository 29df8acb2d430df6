"""Kernels: the causal attention products' share of their roofline on the
latent-attention shape (head size 256).  The least time the chip could
take for the REQUIRED operations of one step's attention cores -
`mla_core` of ops_and_bytes(...)["detail"]["forward"] (q.k over 256
lanes, p.v over 256, every head and block, causal: half the square),
forward once and backward twice, nothing recomputed, over the bf16 peak -
over the device time a step under `attention_core`, which the recomputed
forward is part of.  The same work whatever implements it."""
from benchmark.harness import peaks, scope_time


def read(run):
    f = run.facts
    took = scope_time.ms(run, "attention_core")
    core = f.get("ops", {}).get("detail", {}).get("forward", {}) \
        .get("mla_core")
    if not took or core is None:
        return None
    peak = peaks.peaks_for(f["device"]["kind"])["bf16_flops_per_s"]
    least = 1e3 * 3 * core / (run.cell["chips"] * peak)
    run.note(mla_core_least_ms=least, attention_core_ms=took)
    return 100.0 * least / took
