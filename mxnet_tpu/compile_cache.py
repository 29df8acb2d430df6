"""jax's persistent compilation cache, armed and counted, and jax's
compile events as spans of the start-up timeline.

There is one compile cache: jax's own, keyed on the optimized-HLO hash.
A warm process still traces and lowers each program but skips XLA
optimization and codegen.  :func:`activate` (called by
``programs.register_program``) arms it for every process and maps jax's
cache-hit/miss monitoring events onto the ``compile_cache.xla_hits`` /
``compile_cache.xla_misses`` counters.

**What every jit of the process paid, by name.**  jax emits a time span
for each trace, each lowering and each backend compile, with the jitted
function's name (``mx_step_step``, ``mx_op_FullyConnected``, a
reference's ``<lambda>``).  :func:`activate` listens and keeps each as a
span of ``telemetry``'s buffer: ``compile.trace``, ``compile.lower`` and,
for the backend event, ``compile.cache_load`` where a persistent-cache
hit was recorded inside it (same thread), ``compile.backend`` where XLA
compiled.  ``telemetry.startup_breakdown`` reduces them; the seconds of
the last two also add up in ``compile_cache.xla_load_seconds`` /
``compile_cache.xla_compile_seconds`` (:func:`stats`).

**Where it lives.**  ``JAX_COMPILATION_CACHE_DIR`` where set (jax reads
it itself; ``tools/launch.py --compile-cache DIR`` exports it to every
rank), else the fixed ``<checkout>/.jax_cache`` — the path is part of
jax's cache key, so a directory that moves never hits.  Under the
harness's ``MX_FORCE_CPU=1`` pin there is no default directory: a
CPU-pinned process compiles nothing worth keeping, and XLA:CPU's loader
writes two long error lines to stderr per cache hit (machine-feature
check, jaxlib 0.9.0) — enough to fill the undrained stderr pipe of a
supervised child and block it.  The listeners are registered either way.
"""
from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any, Dict, Optional

from .base import force_cpu
from . import telemetry as _telemetry

__all__ = ["activate", "xla_cache_dir", "DEFAULT_XLA_DIR", "stats",
           "reset_stats"]

logger = logging.getLogger("mxnet_tpu.compile_cache")

#: where jax's cache goes when JAX_COMPILATION_CACHE_DIR does not place
#: it: next to the package, in the checkout
DEFAULT_XLA_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")

_activate_lock = threading.Lock()
_activated = False
_listening = False

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_SPAN_OF = {"/jax/core/compile/jaxpr_trace_duration": "compile.trace",
            "/jax/core/compile/jaxpr_to_mlir_module_duration":
                "compile.lower",
            _BACKEND: "compile.backend"}


class _LastHit(threading.local):
    at = 0.0        # time.time() of this thread's newest cache hit


_last_hit = _LastHit()


def xla_cache_dir() -> Optional[str]:
    """The directory jax's persistent compilation cache uses in this
    process: ``JAX_COMPILATION_CACHE_DIR`` where set, else
    ``<checkout>/.jax_cache``; None (no cache) under ``MX_FORCE_CPU=1``
    with no directory given."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    return None if force_cpu() else DEFAULT_XLA_DIR


def _counter(name, doc):
    return _telemetry.registry.counter(name, doc=doc)


def _on_jax_event(name: str, **kw) -> None:
    if name == _HIT:
        _last_hit.at = time.time()
        _counter("compile_cache.xla_hits",
                 "XLA-level persistent-cache hits (jax compilation "
                 "cache: trace paid, XLA compile skipped)").inc()
    elif name == _MISS:
        _counter("compile_cache.xla_misses",
                 "XLA-level persistent-cache misses (cold compile, "
                 "entry written for the next process)").inc()


def hit_between(start: float, end: float) -> bool:
    """Whether this thread's newest persistent-cache hit lies in
    ``[start, end]`` (``time.time()`` seconds): jax records a hit inside
    the backend-compile span that it spares the compile."""
    return start <= _last_hit.at <= end


def _on_jax_span(event: str, start: float, end: float, fun_name: str = "",
                 **kw) -> None:
    """One trace / lowering / backend compile of any jit of the process,
    as jax reports it when it ends (``time.time()`` stamps)."""
    name = _SPAN_OF.get(event)
    if name is None:
        return
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        fun_name = fun_name[4:-1]   # the trace event gives the bare name
    if event == _BACKEND:
        if hit_between(start, end):
            name = "compile.cache_load"
            _counter("compile_cache.xla_load_seconds",
                     "seconds in backend-compile spans that held a "
                     "persistent-cache hit").inc(end - start)
        else:
            _counter("compile_cache.xla_compile_seconds",
                     "seconds in backend-compile spans XLA compiled "
                     "in").inc(end - start)
    _telemetry.record_span(name, _telemetry.wall_to_perf(start),
                           _telemetry.wall_to_perf(end), cat="compile",
                           fun_name=fun_name)


def _listen() -> None:
    global _listening
    with _activate_lock:
        if _listening:
            return
        _listening = True
    from jax import monitoring as _mon
    _mon.register_event_listener(_on_jax_event)
    _mon.register_event_time_span_listener(_on_jax_span)


def activate() -> None:
    """Arm jax's persistent compilation cache for this process and listen
    to jax's compile events (idempotent).  Called by
    ``programs.register_program``, so every jit site — AOT or light —
    finds its XLA compile again in the next process.  Where
    JAX_COMPILATION_CACHE_DIR is set jax reads it itself and no directory
    is set in code."""
    global _activated
    if _activated:
        return
    _listen()
    xla_dir = xla_cache_dir()
    if xla_dir is None:
        return              # nothing to arm (yet): see xla_cache_dir
    with _activate_lock:
        if _activated:
            return
        _activated = True
    import jax
    if xla_dir != os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        try:
            os.makedirs(xla_dir, exist_ok=True)
        except OSError as e:
            logger.warning("compile_cache: cannot create %s (%s); jax's "
                           "persistent cache stays off", xla_dir, e)
            return
        jax.config.update("jax_compilation_cache_dir", xla_dir)
    # default thresholds skip sub-second/small programs — exactly
    # the long tail a warm restart re-pays 100x of
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def stats() -> Dict[str, Any]:
    """Roll-up for the serve ready line and the chaos DONE line."""
    reg = _telemetry.registry
    return {
        "dir": xla_cache_dir(),
        "xla_hits": reg.value("compile_cache.xla_hits"),
        "xla_misses": reg.value("compile_cache.xla_misses"),
        "xla_load_seconds": reg.value("compile_cache.xla_load_seconds"),
        "xla_compile_seconds":
            reg.value("compile_cache.xla_compile_seconds"),
    }


def reset_stats() -> None:
    """Zero the cache counters (tests; registry instruments persist)."""
    for inst in list(_telemetry.registry.instruments()):
        if inst.name.startswith("compile_cache.") and \
                isinstance(inst, _telemetry.Counter):
            inst.set(0)
