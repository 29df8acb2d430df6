"""Program registry + compile cache: seconds of set-up inside
`compile.cache_load` spans: backend-compile spans of jax in which a
persistent-cache hit was recorded, and what `Program._compile` spends in
`.compile()` around them (harness/setup_spans.py)."""
from benchmark.harness import setup_spans


def read(run):
    return setup_spans.seconds(run, "cache_load")
