"""Kernels: device time a step of the ops whose `op_name` path contains
`attention_core` (ops/attention.py opens that scope around the jnp
composition and the Pallas path alike), forward and backward
(harness/program_trace.py)."""
from benchmark.harness import program_trace


def read(run):
    return program_trace.scope(run, "attention_ms")
