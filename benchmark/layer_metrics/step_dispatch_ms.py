"""Step compiler: median host time of the `mx.step.dispatch` span of the
traced window: the call of the step program with its ~1,000 arguments
(step.py via telemetry.phase)."""
from benchmark.harness import program_trace


def read(run):
    return program_trace.span(run, "mx.step.dispatch")
