"""Kernels: device time a step under the scope `mtp` (the multi-token-
prediction module: its two norms, `eh_proj` and its expert block; its
pass through the shared head is under `lm_head`), forward, recomputed
forward and backward (harness/scope_time.py)."""
from benchmark.harness import scope_time


def read(run):
    return scope_time.ms(run, "mtp")
