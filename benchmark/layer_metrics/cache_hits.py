"""Compile cache: jax persistent-cache hit events during set-up (the
replica's compile_cache.xla_hits counter in a serve cell)."""


def read(run):
    return run.facts.get("cache_hits")
