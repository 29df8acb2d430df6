"""Step compiler: device time a step of the ops the step program traced
under its `forward` scope (step.py) and not through `transpose(`; self
times of the "XLA Ops" rows joined to `programs.program_scopes` by
instruction name (harness/program_trace.py)."""
from benchmark.harness import program_trace


def read(run):
    return program_trace.scope(run, "forward_ms")
