"""Counts of what jax compiled, from jax's own monitoring events (copied
from ``chip_smoke._LoweringCount``).  Every in-memory jit-cache miss
lowers, whether or not the persistent cache then supplies the executable,
so ``lowerings`` inside the window must stay 0."""

LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
HIT = "/jax/compilation_cache/cache_hits"


class CompileCount:
    def __init__(self):
        from jax import monitoring
        self.lowerings = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def close(self):
        from jax import monitoring
        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)

    def _on_duration(self, name, _secs, **_kw):
        if name == LOWER:
            self.lowerings += 1

    def _on_event(self, name, **_kw):
        if name == HIT:
            self.cache_hits += 1
