"""Kernels: how much of `ssm_scan_ms` runs in kernels of the program's own
(Pallas: `custom-call` ops whose `op_name` path holds `ssm/scan`), over
`ssm_scan_ms`.  0 where the scan is a composition of XLA's own products
and fusions (harness/scope_time_ssm.py)."""
from benchmark.harness import scope_time_ssm


def read(run):
    whole = scope_time_ssm.ms(run, "ssm/scan")
    kernels = scope_time_ssm.ms(run, scope_time_ssm.KERNELS)
    if not whole or kernels is None:
        return None
    return 100.0 * kernels / whole
