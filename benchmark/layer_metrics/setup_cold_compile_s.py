"""Program registry + compile cache: seconds of set-up inside
`compile.backend` spans: backend-compile spans of jax with no
persistent-cache hit inside, XLA compiling.  0 in a run the same tree has
warmed; where it is not, the `setup_spans` line names the programs
(harness/setup_spans.py)."""
from benchmark.harness import setup_spans


def read(run):
    return setup_spans.seconds(run, "cold_compile")
