"""Kernels: device time a step under the scope `ssm` (every Mamba-2 mixer:
the two projections, the convolution, the scan with its skip and gate,
the grouped norm), forward, recomputed forward and backward
(harness/scope_time_ssm.py)."""
from benchmark.harness import scope_time_ssm


def read(run):
    return scope_time_ssm.ms(run, "ssm")
