"""Program registry + compile cache: seconds of set-up inside
`compile.trace` spans, jax's `jaxpr_trace_duration` of every jit of the
process (the step, each eager operator, the reference), less the compiles
of operators that run inside a trace (harness/setup_spans.py)."""
from benchmark.harness import setup_spans


def read(run):
    return setup_spans.seconds(run, "trace")
