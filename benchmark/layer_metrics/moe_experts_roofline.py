"""Kernels: the grouped products' share of their roofline.  The least time
the chip could take for the held experts' products of one step -
max(`moe_routed` of ops_and_bytes(...)["detail"]["forward"], the routed
products at their EXPECTED load, over the bf16 peak; the held experts'
weights read once over the HBM peak), forward once and backward twice -
over the device time a step under `moe/experts`.  The same count whatever
implements the products; an earlier line says which bound."""
from benchmark.harness import peaks, scope_time


def read(run):
    f = run.facts
    took = scope_time.ms(run, "moe/experts")
    detail = f.get("ops", {}).get("detail", {})
    routed = detail.get("forward", {}).get("moe_routed")
    if not took or routed is None:
        return None
    peak = peaks.peaks_for(f["device"]["kind"])
    compute = routed / peak["bf16_flops_per_s"]
    memory = detail["held_expert_weight_bytes"] / peak["hbm_bytes_per_s"]
    least = 1e3 * 3 * max(compute, memory) / run.cell["chips"]
    run.note(moe_experts_least_ms=least, moe_experts_ms=took,
             moe_experts_bound="compute" if compute >= memory else "memory")
    return 100.0 * least / took
