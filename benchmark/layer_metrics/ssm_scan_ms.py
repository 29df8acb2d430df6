"""Kernels: device time a step under the scope `ssm/scan` (the selective
scan of every Mamba-2 mixer, with its skip and gate), forward, recomputed
forward and backward (harness/scope_time_ssm.py)."""
from benchmark.harness import scope_time_ssm


def read(run):
    return scope_time_ssm.ms(run, "ssm/scan")
