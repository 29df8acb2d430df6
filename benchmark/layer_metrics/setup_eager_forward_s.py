"""Gluon front end: seconds of set-up inside the span `forward`, the
top-level eager `Block.__call__` (the deferred-shape pass, the check's
logits, a router balancing), less the initialisation and the compiles
inside it: dispatching the operators one by one (harness/setup_spans.py)."""
from benchmark.harness import setup_spans


def read(run):
    return setup_spans.seconds(run, "eager_forward")
