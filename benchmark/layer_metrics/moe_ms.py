"""Kernels: device time a step under the scope `moe` (every expert layer,
the MTP module's too: router, dispatch, the held experts' grouped
products, combine, the shared expert), forward, recomputed forward and
backward (harness/scope_time.py)."""
from benchmark.harness import scope_time


def read(run):
    return scope_time.ms(run, "moe")
