"""Step compiler: device time a step of the ops whose `op_name` path passes
through `transpose(`: what jax.value_and_grad makes of the `forward`
scope, the backward pass (harness/program_trace.py)."""
from benchmark.harness import program_trace


def read(run):
    return program_trace.scope(run, "backward_ms")
