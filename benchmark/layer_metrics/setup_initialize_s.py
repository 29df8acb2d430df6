"""Gluon front end: seconds of set-up inside the span `initialize`
(`Block.initialize`, `Block.cast`, a deferred initialisation finished on
the first call, the optimizer state a `CompiledStep` or an `Updater` makes
and the copies `_own_state` takes), less the compiles inside it
(harness/setup_spans.py)."""
from benchmark.harness import setup_spans


def read(run):
    return setup_spans.seconds(run, "initialize")
