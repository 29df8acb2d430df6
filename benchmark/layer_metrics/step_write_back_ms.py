"""Step compiler: median host time of the `mx.step.write_back` span of the
traced window: _write_back, owned-buffer bookkeeping, engine counters,
note_step with its observers (step.py via telemetry.phase)."""
from benchmark.harness import program_trace


def read(run):
    return program_trace.span(run, "mx.step.write_back")
