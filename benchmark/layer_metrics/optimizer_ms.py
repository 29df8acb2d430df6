"""Step compiler: device time a step of the ops under the step program's
`optimizer` scope (step.py, around apply_optimizer); an update XLA fused
into a weight gradient is counted by scope_unattributed instead
(harness/program_trace.py)."""
from benchmark.harness import program_trace


def read(run):
    return program_trace.scope(run, "optimizer_ms")
