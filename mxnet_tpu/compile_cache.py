"""Persistent compiled-program cache (ISSUE 13 tentpole).

Every process used to pay full XLA compile cost on every cold start —
the program census measured 24s of compile wall-time for the eager
bench lane, re-paid by every supervisor respawn, chaos restart and
serve replica spawn.  The Julia→TPU AOT work (arxiv 1810.09868) treats
compiled XLA executables as serializable artifacts and TF-Serving
(arxiv 1605.08695) makes warm-up-before-traffic a first-class servable
lifecycle phase; this module is both: an on-disk store of serialized
XLA executables that a warm restart *deserializes* (~20ms) instead of
re-tracing and re-compiling (seconds), keyed so that nothing stale can
ever load.

**Key envelope.**  One cache entry is addressed by
``sha256(name | trace signature | function fingerprint | jit spec |
environment envelope)`` where

* *trace signature* is the program registry's cache key verbatim
  (tree structure + per-leaf shape/dtype/weak-type/sharding — see
  :func:`mxnet_tpu.programs.signature_of`), canonicalized to text;
* *function fingerprint* hashes the traced callable's code objects
  recursively (bytecode + nested consts + names), so an edited program
  body can never collide with its previous self;
* *jit spec* covers ``donate_argnums``/``static_argnums``/shardings —
  two sites jitting one body with different donation sets are distinct
  executables;
* *environment envelope* is (jax version, jaxlib version, backend
  platform, device kinds + count, python major.minor, a content hash
  of the mxnet_tpu library source, ``MX_COMPILE_CACHE_SALT``) — any
  skew is a MISS, never a wrong load.  The envelope is additionally
  stored INSIDE each entry and re-verified on load, so a key-scheme
  bug still cannot resurrect an executable built by a different
  toolchain.

**Fallback semantics.**  Every failure path — absent entry, envelope
skew, truncated or corrupt payload, an executable the backend refuses
to deserialize, an out-tree that will not pickle (e.g. the hybridize
train path's vjp closure) — is counted (``compile_cache.misses`` /
``compile_cache.errors``) and falls back to a normal compile.  The
cache can only ever cost a read; it can never fail a program.

**Write discipline.**  Entries are written to a per-process temp file
and published with ``os.replace`` (the checkpoint.save_sharded
pattern), so concurrent writers are last-write-wins and a reader can
never observe a torn entry; a crash mid-write leaves only a ``.tmp-*``
dropping that the next :func:`store` to the same key overwrites.

Hot-path contract (mxlint-rooted): cache I/O happens only inside
``Program._compile`` — the cold path that was about to pay seconds of
XLA compile anyway.  :func:`cache_key`/:func:`signature_token` are
pure string/hash work over host metadata; nothing here may sync a
device or run on a per-dispatch path.
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import threading
import time
from typing import Any, Dict, Optional, Tuple

from .base import force_cpu, get_env
from . import telemetry as _telemetry

__all__ = ["enabled", "cache_dir", "envelope", "cache_key",
           "signature_token", "function_fingerprint", "load", "store",
           "stats", "reset_stats", "entry_path", "SCHEMA"]

logger = logging.getLogger("mxnet_tpu.compile_cache")

# bumped when the on-disk entry layout changes; a schema mismatch is an
# ordinary miss (old entries are simply dead weight, never read wrong)
SCHEMA = 1


def enabled() -> bool:
    """MX_COMPILE_CACHE non-empty = the persistent cache is on."""
    return bool(get_env("MX_COMPILE_CACHE", "") or "")


def cache_dir() -> str:
    return str(get_env("MX_COMPILE_CACHE", "") or "")


# ---------------------------------------------------------------------------
# Key construction
# ---------------------------------------------------------------------------

_lib_fp_lock = threading.Lock()
_lib_fp: Optional[str] = None


def _library_fingerprint() -> str:
    """Content hash over the mxnet_tpu package's python source.  A
    library edit (new trace body, changed donation set, fixed kernel)
    invalidates every entry — conservative by design: deserializing a
    stale executable silently computes the OLD code's answer."""
    global _lib_fp
    with _lib_fp_lock:
        if _lib_fp is not None:
            return _lib_fp
    h = hashlib.sha256()
    root = os.path.dirname(os.path.abspath(__file__))
    # sort dirnames IN PLACE while the walk is live: os.walk's pruning
    # contract (skip __pycache__) and deterministic order both depend
    # on mutating the list before descent
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for fname in sorted(filenames):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            h.update(os.path.relpath(path, root).encode())
            try:
                # ONE walk of the library source per process (memoized
                # above); runs under the first executable build — the
                # cold path that was about to pay seconds of XLA compile
                with open(path, "rb") as f:  # mxlint: disable=host-sync-in-hot-path
                    h.update(f.read())
            except OSError:
                h.update(b"?")
    fp = h.hexdigest()[:16]
    with _lib_fp_lock:
        _lib_fp = fp
    return fp


def envelope() -> Dict[str, str]:
    """The environment identity an entry is only valid under.  Stored in
    every entry and re-checked on load; any mismatch is a miss."""
    import sys
    import jax
    try:
        devs = jax.devices()
        kinds = ",".join(sorted({d.device_kind for d in devs}))
        n = len(devs)
        backend = jax.default_backend()
    except Exception:           # backend not initializable: key degrades
        kinds, n, backend = "?", 0, "?"
    jaxlib_ver = ""
    try:
        import jaxlib
        jaxlib_ver = getattr(jaxlib, "__version__", "")
    except Exception:
        pass
    return {
        "schema": str(SCHEMA),
        "jax": jax.__version__,
        "jaxlib": jaxlib_ver,
        "backend": backend,
        "device_kinds": kinds,
        "device_count": str(n),
        "python": "%d.%d" % sys.version_info[:2],
        "library": _library_fingerprint(),
        "salt": str(get_env("MX_COMPILE_CACHE_SALT", "") or ""),
    }


def _leaf_token(sig) -> str:
    """One registry leaf signature as stable text.  Aval leaves render
    shape/dtype/weak-type plus the sharding's str() (device placement &
    PartitionSpec both key the executable); everything else via repr."""
    if isinstance(sig, tuple) and sig and sig[0] == "aval":
        _, aval, sharding = sig
        return "aval:%s:%s:%s:%s" % (
            tuple(int(s) for s in aval.shape), aval.dtype,
            bool(getattr(aval, "weak_type", False)),
            "" if sharding is None else str(sharding))
    return repr(sig)


def signature_token(sig: Tuple) -> str:
    """Canonical text form of a programs.signature_of() value."""
    treedef, leaf_sigs = sig
    return "%s|%s" % (str(treedef),
                      ";".join(_leaf_token(s) for s in leaf_sigs))


_ADDR_RE = None


def _stable_repr(obj) -> str:
    """repr() with memory addresses stripped — `<function f at 0x7f..>`
    must hash identically across processes."""
    global _ADDR_RE
    if _ADDR_RE is None:
        import re
        _ADDR_RE = re.compile(r" at 0x[0-9a-fA-F]+")
    return _ADDR_RE.sub("", repr(obj))


_FP_MAX_DEPTH = 8


def function_fingerprint(fn) -> str:
    """Recursive hash of a callable's code objects (bytecode, nested
    code consts, names) AND the host values it closes over (closure
    cells, argument defaults, ``functools.partial`` bindings).

    The closure walk is the load-bearing half: trace bodies like
    ``_traced_step_window`` bake closed-over host config — weight
    decays, rescale factors, metric kernels, return flags — into the
    compiled program as constants, invisibly to the trace signature.
    Two configurations with identical shapes MUST key differently or a
    warm restart would deserialize the other config's executable and
    silently train with its constants.  Nested functions recurse (a
    closed-over Gluon block contributes its architecture ``repr``);
    frozenset constants hash in sorted order and memory addresses are
    stripped (set repr order and ids are per-process-randomized).
    Opaque objects degrade to their stable repr — the residual
    collision risk (two objects whose repr AND every reachable
    shape/value coincide while their traces differ) is documented in
    ARCHITECTURE.md's invalidation rules."""
    import functools as _ft
    h = hashlib.sha256()
    seen = set()

    def const_token(c) -> str:
        if hasattr(c, "co_code"):
            walk(c)
            return "<code>"
        if isinstance(c, (frozenset, set)):
            return "fs{%s}" % ",".join(sorted(const_token(x) for x in c))
        if isinstance(c, tuple):
            return "(%s)" % ",".join(const_token(x) for x in c)
        return _stable_repr(c)

    def walk(code):
        if id(code) in seen:
            return
        seen.add(id(code))
        h.update(code.co_code)
        h.update(",".join(code.co_names).encode())
        h.update(",".join(code.co_varnames).encode())
        for const in code.co_consts:
            h.update(const_token(const).encode())

    def feed_value(v, depth):
        if depth > _FP_MAX_DEPTH:
            h.update(b"<depth>")
            return
        if callable(v) and (hasattr(v, "__code__")
                            or isinstance(v, _ft.partial)):
            feed(v, depth)
        elif isinstance(v, (list, tuple)):
            h.update(b"seq%d" % len(v))
            for x in v:
                feed_value(x, depth + 1)
        elif isinstance(v, dict):
            for k in sorted(v, key=repr):
                h.update(_stable_repr(k).encode())
                feed_value(v[k], depth + 1)
        else:
            try:
                h.update(_stable_repr(v)[:2000].encode())
            except Exception:
                h.update(type(v).__name__.encode())

    def feed(obj, depth=0):
        if id(obj) in seen or depth > _FP_MAX_DEPTH:
            return
        seen.add(id(obj))
        if isinstance(obj, _ft.partial):
            feed(obj.func, depth + 1)
            feed_value(tuple(obj.args), depth + 1)
            for k in sorted(obj.keywords or {}):
                h.update(k.encode())
                feed_value(obj.keywords[k], depth + 1)
            return
        obj = getattr(obj, "__wrapped__", obj)
        code = getattr(obj, "__code__", None)
        if code is None:
            h.update(_stable_repr(obj).encode())
            return
        walk(code)
        for d in (getattr(obj, "__defaults__", None) or ()):
            feed_value(d, depth + 1)
        for k in sorted(getattr(obj, "__kwdefaults__", None) or {}):
            h.update(k.encode())
            feed_value(obj.__kwdefaults__[k], depth + 1)
        cells = getattr(obj, "__closure__", None) or ()
        for name, cell in zip(code.co_freevars, cells):
            h.update(name.encode())
            try:
                feed_value(cell.cell_contents, depth + 1)
            except ValueError:        # empty cell
                h.update(b"<empty>")

    feed(fn)
    return h.hexdigest()[:16]


def cache_key(name: str, sig: Tuple, fn=None,
              jit_kw: Optional[Dict[str, Any]] = None) -> str:
    """The entry's file-name identity (sha256 hex)."""
    h = hashlib.sha256()
    h.update(name.encode())
    h.update(b"\0")
    h.update(signature_token(sig).encode())
    h.update(b"\0")
    if fn is not None:
        h.update(function_fingerprint(fn).encode())
    h.update(b"\0")
    kw = jit_kw or {}
    h.update(json.dumps({k: repr(v) for k, v in sorted(kw.items())},
                        sort_keys=True).encode())
    h.update(b"\0")
    h.update(json.dumps(envelope(), sort_keys=True).encode())
    return h.hexdigest()


def entry_path(key: str) -> str:
    return os.path.join(cache_dir(), key[:2], key + ".xcache")


# ---------------------------------------------------------------------------
# Telemetry
# ---------------------------------------------------------------------------

def _counter(name, doc, **labels):
    return _telemetry.registry.counter(name, doc=doc,
                                       labels=labels or None)


def _c_hits():
    return _counter("compile_cache.hits",
                    "programs warm-started from the persistent "
                    "compiled-program cache (deserialize, no compile)")


def _c_misses(reason: str):
    return _counter("compile_cache.misses",
                    "persistent-cache lookups that fell back to a "
                    "normal compile, by reason",
                    reason=reason)


def _c_errors():
    return _counter("compile_cache.errors",
                    "persistent-cache read/write failures (corrupt "
                    "entry, unserializable executable, I/O error) — "
                    "all non-fatal, all fell back to compile")


def _c_writes():
    return _counter("compile_cache.writes",
                    "executables serialized into the persistent cache")


def _c_bytes(direction: str):
    return _counter("compile_cache.bytes",
                    "persistent-cache payload bytes moved",
                    direction=direction)


def _h_deser():
    return _telemetry.registry.histogram(
        "compile_cache_deserialize_seconds",
        doc="wall-clock time to load+deserialize one cached executable")


def stats() -> Dict[str, Any]:
    """Roll-up for bench reports / the serve spawn banner."""
    reg = _telemetry.registry
    reasons = {}
    for inst in reg.instruments():
        if inst.name == "compile_cache.misses":
            reasons[inst.labels.get("reason", "?")] = inst.value
    return {
        "enabled": enabled(),
        "dir": cache_dir() or None,
        "hits": reg.value("compile_cache.hits"),
        "misses": sum(reasons.values()),
        "miss_reasons": reasons,
        "errors": reg.value("compile_cache.errors"),
        "writes": reg.value("compile_cache.writes"),
        "xla_cache_hits": reg.value("compile_cache.xla_hits"),
        "xla_cache_misses": reg.value("compile_cache.xla_misses"),
    }


def reset_stats() -> None:
    """Zero the cache counters (tests; registry instruments persist)."""
    reg = _telemetry.registry
    for inst in list(reg.instruments()):
        if inst.name.startswith("compile_cache") and \
                isinstance(inst, _telemetry.Counter):
            inst.set(0)


# ---------------------------------------------------------------------------
# The XLA-level second layer: jax's persistent compilation cache
# ---------------------------------------------------------------------------
#
# The executable store above needs an arrays-only in/out tree (pickled
# alongside the payload).  The hybridize TRAIN lane ships a vjp closure
# across its jit boundary — per-process function objects that can
# neither pickle nor key stably — so those programs can never use the
# store.  jax's own persistent compilation cache (keyed on the
# optimized-HLO hash, so it needs no tree serialization) covers exactly
# that residue, and every program when MX_COMPILE_CACHE is unset: a warm
# process still pays TRACING but skips XLA optimization+codegen.
# activate() arms it for every process — the path is part of the cache
# key, so it is a FIXED one — and maps jax's cache-hit/miss monitoring
# events onto compile_cache.xla_hits / xla_misses.

_activate_lock = threading.Lock()
_activated = False

#: where jax's cache goes when neither JAX_COMPILATION_CACHE_DIR nor
#: MX_COMPILE_CACHE places it: next to the package, in the checkout
DEFAULT_XLA_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def xla_cache_dir() -> Optional[str]:
    """The directory activate() sets for jax's persistent compilation
    cache: ``<MX_COMPILE_CACHE>/xla`` if the operator gave one, else the
    fixed ``<checkout>/.jax_cache``.  None under the harness's
    MX_FORCE_CPU=1 pin with no directory given: a CPU-pinned process
    compiles nothing worth keeping, and XLA:CPU's loader writes two long
    error lines to stderr per cache hit (machine-feature check, jaxlib
    0.9.0) — enough to fill the undrained stderr pipe of a supervised
    child and block it."""
    if enabled():
        return os.path.join(cache_dir(), "xla")
    if force_cpu():
        return None
    return DEFAULT_XLA_DIR


def _on_jax_event(name: str, **kw) -> None:
    if name == "/jax/compilation_cache/cache_hits":
        _counter("compile_cache.xla_hits",
                 "XLA-level persistent-cache hits (jax compilation "
                 "cache: trace paid, XLA compile skipped)").inc()
    elif name == "/jax/compilation_cache/cache_misses":
        _counter("compile_cache.xla_misses",
                 "XLA-level persistent-cache misses (cold compile, "
                 "entry written for the next process)").inc()


def activate() -> None:
    """Arm jax's persistent compilation cache for this process
    (idempotent).  Called by ``programs.register_program``, whether or
    not MX_COMPILE_CACHE is set, so every jit site — AOT or light —
    finds its XLA compile again in the next process.  Where
    JAX_COMPILATION_CACHE_DIR is set jax reads it itself and no
    directory is set in code."""
    global _activated
    if _activated:
        return
    from_env = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    xla_dir = None if from_env else xla_cache_dir()
    if xla_dir is None and not from_env:
        return              # nothing to arm (yet): see xla_cache_dir
    with _activate_lock:
        if _activated:
            return
        _activated = True
    import jax
    from jax import monitoring as _mon
    if xla_dir is not None:
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
    _mon.register_event_listener(_on_jax_event)


# ---------------------------------------------------------------------------
# Load / store
# ---------------------------------------------------------------------------

def load(name: str, key: str):
    """Deserialize the cached executable for `key`, or None.

    Every failure mode is a counted miss (and for corrupt payloads an
    error too); this function never raises.  A hit returns a live
    ``jax.stages.Compiled`` — donation aliasing, memory_analysis and
    cost_analysis all intact."""
    if not enabled():
        return None
    path = entry_path(key)
    t0 = time.perf_counter()
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError:
        _c_misses("absent").inc()
        return None
    try:
        entry = pickle.loads(blob)
        if not isinstance(entry, dict) or entry.get("schema") != SCHEMA:
            raise ValueError("bad schema %r" %
                             (entry.get("schema")
                              if isinstance(entry, dict) else type(entry)))
        if entry.get("envelope") != envelope():
            # belt over the key's own envelope hash: version/topology
            # skew can NEVER load (e.g. a key-construction bug)
            _c_misses("envelope").inc()
            logger.info("compile_cache: envelope skew for %r (%s); "
                        "recompiling", name, path)
            return None
        from jax.experimental import serialize_executable as _se
        compiled = _se.deserialize_and_load(*entry["payload"])
    except Exception as e:
        _c_misses("corrupt").inc()
        _c_errors().inc()
        logger.warning("compile_cache: unreadable entry for %r (%s: %s); "
                       "recompiling", name, type(e).__name__, e)
        # best-effort removal so the poisoned entry is not re-parsed on
        # every future cold start
        try:
            os.remove(path)
        except OSError:
            pass
        return None
    dt = time.perf_counter() - t0
    _c_hits().inc()
    _c_bytes("read").inc(len(blob))
    _h_deser().observe(dt)
    logger.info("compile_cache: warm-started %r in %.1fms (%d bytes)",
                name, dt * 1e3, len(blob))
    return compiled


def store(name: str, key: str, compiled) -> bool:
    """Serialize `compiled` under `key` (temp + atomic rename).  Returns
    False (counted, never raises) when the executable cannot be
    serialized or the write fails."""
    if not enabled():
        return False
    try:
        from jax.experimental import serialize_executable as _se
        payload = _se.serialize(compiled)
        blob = pickle.dumps({
            "schema": SCHEMA,
            "name": name,
            "envelope": envelope(),
            "created": time.time(),
            "payload": payload,
        }, protocol=pickle.HIGHEST_PROTOCOL)
    except Exception as e:
        _c_errors().inc()
        logger.info("compile_cache: %r is not serializable (%s: %s); "
                    "this program stays compile-on-start",
                    name, type(e).__name__, e)
        return False
    path = entry_path(key)
    tmp = "%s.tmp-%d-%d" % (path, os.getpid(), threading.get_ident())
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)   # last-write-wins; readers never see torn
    except OSError as e:
        _c_errors().inc()
        logger.warning("compile_cache: write failed for %r (%s); "
                       "continuing uncached", name, e)
        try:
            os.remove(tmp)
        except OSError:
            pass
        return False
    _c_writes().inc()
    _c_bytes("written").inc(len(blob))
    return True
