"""Outside the program's spans: the seconds of set-up no span of the
program covers: the harness's own code, reaching the chip, host waits on
the device from the harness's `jax` calls.  The `setup_spans` line gives
every such stretch over half a second with the spans on both sides
(harness/setup_spans.py)."""
from benchmark.harness import setup_spans


def read(run):
    return setup_spans.seconds(run, "unspanned")
