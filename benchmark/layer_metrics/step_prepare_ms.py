"""Step compiler: median host time of the `mx.step.prepare` span of the
traced window: _plan, _lr_rows, cache lookup, _gather_state, donation
copies, batch placement (step.py via telemetry.phase)."""
from benchmark.harness import program_trace


def read(run):
    return program_trace.span(run, "mx.step.prepare")
