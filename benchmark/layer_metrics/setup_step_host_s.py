"""Step compiler: seconds of set-up inside the step's phases
(`step.prepare`, `compiled_step`, `step.write_back` of the warm-up steps,
the consumer's `data_wait`), less the initialisation and the compiles
inside them (harness/setup_spans.py)."""
from benchmark.harness import setup_spans


def read(run):
    return setup_spans.seconds(run, "step_host")
