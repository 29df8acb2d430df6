"""Kernels: what of `moe_ms` is spent AROUND the products - the router
(`moe/route`: scores, top-k), the dispatch (`moe/dispatch`: the sort of the
assignments and the gather of their rows) and the combine (`moe/combine`:
the weighted sum back to tokens) over the whole of `moe`
(harness/scope_time.py)."""
from benchmark.harness import scope_time


def read(run):
    whole = scope_time.ms(run, "moe")
    parts = [scope_time.ms(run, "moe/" + p)
             for p in ("route", "dispatch", "combine")]
    if not whole or any(p is None for p in parts):
        return None
    return 100.0 * sum(parts) / whole
