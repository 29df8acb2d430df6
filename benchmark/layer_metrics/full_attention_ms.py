"""Kernels: device time a step under the scope `attention_full` (every
full-attention layer: the four projections, rotary, the causal core, the
head gate), forward, recomputed forward and backward
(harness/scope_time_swa.py)."""
from benchmark.harness import scope_time_swa


def read(run):
    return scope_time_swa.ms(run, "attention_full")
