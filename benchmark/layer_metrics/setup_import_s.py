"""Process start: seconds of set-up inside the span `import` (the first
line of `mxnet_tpu/__init__.py` to its last: the package and, where the
process had not imported it yet, jax), less the compiles inside it
(harness/setup_spans.py)."""
from benchmark.harness import setup_spans


def read(run):
    return setup_spans.seconds(run, "import")
