"""Kernels: the selective scans' share of their roofline.  The least time
the chip could take for one step's scans - max(`ssm_scan` of
ops_and_bytes(...)["detail"]["forward"], the required products of the
chunked dual form at the published chunk, over the bf16 peak;
`ssm_scan_bytes`, x, B, C, dt and z read and y written once, over the HBM
peak), forward once and backward twice, nothing recomputed - over the
device time a step under `ssm/scan`.  The same count whatever implements
the scan; an earlier line says which bound."""
from benchmark.harness import peaks, scope_time_ssm


def read(run):
    f = run.facts
    took = scope_time_ssm.ms(run, "ssm/scan")
    detail = f.get("ops", {}).get("detail", {})
    products = detail.get("forward", {}).get("ssm_scan")
    if not took or products is None:
        return None
    peak = peaks.peaks_for(f["device"]["kind"])
    compute = products / peak["bf16_flops_per_s"]
    memory = detail["ssm_scan_bytes"] / peak["hbm_bytes_per_s"]
    least = 1e3 * 3 * max(compute, memory) / run.cell["chips"]
    run.note(ssm_scan_least_ms=least, ssm_scan_ms=took,
             ssm_scan_bound="compute" if compute >= memory else "memory")
    return 100.0 * least / took
