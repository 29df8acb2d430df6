"""Kernels: device time a step under the scope `mla` (the latent-attention
block of every decoder block and of the MTP module: norms, projections,
rotary and the attention core), forward, recomputed forward and backward
(harness/scope_time.py)."""
from benchmark.harness import scope_time


def read(run):
    return scope_time.ms(run, "mla")
