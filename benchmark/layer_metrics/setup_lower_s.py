"""Program registry + compile cache: seconds of set-up inside
`compile.lower` spans: jax's `jaxpr_to_mlir_module_duration` of every jit
and what `Program._compile` spends in `.lower()` around it
(harness/setup_spans.py)."""
from benchmark.harness import setup_spans


def read(run):
    return setup_spans.seconds(run, "lower")
