"""mx.profiler — profiling API rebuilt over ``jax.profiler``.

Reference parity: ``python/mxnet/profiler.py`` (set_config, set_state,
start/stop/pause/resume, dump, dumps, Task/Frame/Event/Counter/Marker) and
``src/profiler/profiler.cc`` (Profiler::DumpProfile, the aggregate stats
table).

TPU-first design: the reference's engine hooks every op execution and writes
a chrome-trace JSON; here the *device-side* story belongs to XLA — we
delegate hardware tracing to ``jax.profiler.start_trace`` (xplane, viewable
in TensorBoard/Perfetto/XProf) — while the *host-side* per-op statistics the
MXNet API promises (the ``dumps()`` table, the ``dump()`` chrome trace) are
collected in the eager dispatch layer (``ndarray.invoke`` wraps each op in a
span when the profiler is running) and by the user-facing instrumentation
objects below.

Eager dispatch is asynchronous (XLA computations are enqueued, not awaited),
so a span measures *dispatch* latency by default — matching what the host
thread actually does.  Set ``MXNET_PROFILER_SYNC=1`` (or
``set_config(sync=True)``) to block on each op's outputs inside its span,
trading throughput for true per-op execution times, the moral equivalent of
the reference's ``NaiveEngine`` profiling mode.
"""
from __future__ import annotations

import contextlib
import json
import sys
import threading
import time
from collections import defaultdict
from typing import Dict, List, Optional

from .base import get_env

__all__ = [
    "set_config", "set_state", "state", "start", "stop", "pause", "resume",
    "dump", "dumps", "dump_profile", "Domain", "Task", "Frame", "Event",
    "Counter", "Marker", "scope", "host_span",
]

# module-level fast flags read by the dispatch hot loop -----------------------
RUNNING = False          # profiler collecting?
IMPERATIVE = False       # collect eager op spans?

_lock = threading.RLock()
_config = {
    "filename": "profile.json",
    "profile_all": False,
    "profile_symbolic": False,
    "profile_imperative": False,
    "profile_memory": False,
    "profile_api": False,
    "aggregate_stats": True,
    "continuous_dump": False,
    "sync": get_env("MXNET_PROFILER_SYNC", dtype=bool),
    # directory for jax.profiler xplane traces; None disables device tracing
    "device_trace_dir": None,
}
_jax_trace_active = False
_paused = False

# chrome-trace events: (name, category, ts_us, dur_us, tid)
_events: List[tuple] = []
# aggregate: name -> [count, total_us, min_us, max_us]
_agg: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0, float("inf"), 0.0])
_counters: List[tuple] = []   # (name, ts_us, value)
_t0 = time.perf_counter()


def _now_us() -> float:
    return (time.perf_counter() - _t0) * 1e6


def set_config(**kwargs):
    """Configure the profiler (reference: profiler.set_config).

    Accepts the reference's kwargs (``filename``, ``profile_all``,
    ``profile_symbolic``, ``profile_imperative``, ``profile_memory``,
    ``profile_api``, ``aggregate_stats``, ``continuous_dump``) plus the
    rebuild's ``sync`` (block per op for exact times) and
    ``device_trace_dir`` (enable jax.profiler xplane capture there).
    """
    with _lock:
        for k, v in kwargs.items():
            if k not in _config:
                raise ValueError("profiler.set_config: unknown option %r" % k)
            _config[k] = v


def set_state(state_: str = "stop"):
    """'run' starts collection, 'stop' ends it (reference: set_state)."""
    global RUNNING, IMPERATIVE, _jax_trace_active, _paused
    if state_ not in ("run", "stop"):
        raise ValueError("profiler state must be 'run' or 'stop'")
    with _lock:
        run = state_ == "run"
        RUNNING = run
        _paused = False
        IMPERATIVE = run and (_config["profile_all"] or _config["profile_imperative"])
        tdir = _config["device_trace_dir"]
        if run and tdir and not _jax_trace_active:
            import jax
            jax.profiler.start_trace(tdir)
            _jax_trace_active = True
        elif not run and _jax_trace_active:
            import jax
            try:
                jax.profiler.stop_trace()
            finally:
                _jax_trace_active = False
        if not run and _config["continuous_dump"]:
            dump()


def state() -> str:
    return "run" if RUNNING else "stop"


def start():
    set_state("run")


def stop():
    set_state("stop")


def pause():
    """Temporarily suspend collection without closing the trace."""
    global IMPERATIVE, _paused
    with _lock:
        _paused = True
        IMPERATIVE = False


def resume():
    global IMPERATIVE, _paused
    with _lock:
        _paused = False
        IMPERATIVE = RUNNING and (_config["profile_all"] or _config["profile_imperative"])


def record_span(name: str, category: str, ts_us: float, dur_us: float):
    """Append one completed span (called from dispatch and Task/Frame/Event)."""
    with _lock:
        _events.append((name, category, ts_us, dur_us, threading.get_ident()))
        if _config["aggregate_stats"]:
            a = _agg[name]
            a[0] += 1
            a[1] += dur_us
            a[2] = min(a[2], dur_us)
            a[3] = max(a[3], dur_us)


class _OpSpan:
    """Context manager wrapped around one eager op dispatch.

    Also annotates the host timeline for jax.profiler so op names show up
    in the xplane trace (jax.profiler.TraceAnnotation).
    """
    __slots__ = ("name", "t0", "ann")

    def __init__(self, name: str):
        self.name = name
        self.ann = None

    def __enter__(self):
        if _jax_trace_active:
            import jax
            self.ann = jax.profiler.TraceAnnotation(self.name)
            self.ann.__enter__()
        self.t0 = _now_us()
        return self

    def __exit__(self, *exc):
        record_span(self.name, "operator", self.t0, _now_us() - self.t0)
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False


def op_span(name: str) -> _OpSpan:
    return _OpSpan(name)


def want_sync() -> bool:
    return _config["sync"]


# -- user instrumentation objects (reference: profiler.Task/Frame/Event...) ---

class Domain:
    """A named grouping for instrumentation objects."""

    def __init__(self, name: str):
        self.name = name

    def __repr__(self):
        return "Domain(%s)" % self.name


class _DurationObject:
    _category = "task"

    def __init__(self, domain: Optional[Domain] = None, name: str = "task"):
        if isinstance(domain, str) and name == "task":  # Event(name) form
            domain, name = None, domain
        self.domain = domain
        self.name = name
        self._t0 = None

    def start(self):
        self._t0 = _now_us()

    def stop(self):
        if self._t0 is None:
            raise RuntimeError("%s %r stopped before start" %
                               (type(self).__name__, self.name))
        record_span(self.name, self._category, self._t0, _now_us() - self._t0)
        self._t0 = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


class Task(_DurationObject):
    _category = "task"


class Frame(_DurationObject):
    _category = "frame"


class Event(_DurationObject):
    _category = "event"


class Counter:
    """A named monotonic-timestamped counter (reference: profiler.Counter)."""

    def __init__(self, domain: Optional[Domain] = None, name: str = "counter",
                 value: int = 0):
        if isinstance(domain, str) and name == "counter":
            domain, name = None, domain
        self.domain = domain
        self.name = name
        self._value = value
        self._record()

    def _record(self):
        with _lock:
            _counters.append((self.name, _now_us(), self._value))

    def set_value(self, value):
        self._value = value
        self._record()

    def increment(self, delta=1):
        self._value += delta
        self._record()

    def decrement(self, delta=1):
        self._value -= delta
        self._record()

    def __iadd__(self, delta):
        self.increment(delta)
        return self

    def __isub__(self, delta):
        self.decrement(delta)
        return self


class Marker:
    """An instant event (reference: profiler.Marker.mark)."""

    def __init__(self, domain: Optional[Domain] = None, name: str = "marker"):
        if isinstance(domain, str) and name == "marker":
            domain, name = None, domain
        self.domain = domain
        self.name = name

    def mark(self, scope_: str = "process"):
        record_span(self.name, "marker", _now_us(), 0.0)


class scope:
    """Context manager: annotate everything inside with a name prefix.

    Inside jit traces this is ``jax.named_scope`` (names land in the XLA HLO
    and the device profile); eagerly it opens a span.
    """

    def __init__(self, name: str):
        self.name = name
        self._span = _OpSpan(name)
        self._named = None

    def __enter__(self):
        import jax
        self._named = jax.named_scope(self.name)
        self._named.__enter__()
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        return self._named.__exit__(*exc)


def host_span(name: str, step_num: Optional[int] = None):
    """The ONE hook that puts the program's own host spans on jax's
    profiler clock: a ``jax.profiler.TraceAnnotation("mx." + name)`` (a
    ``StepTraceAnnotation`` when `step_num` is given), which a running
    ``jax.profiler`` session records on the host plane beside the device
    trace.  Outside a session it is a C++ check and an object.  A process
    that never imported jax (the numpy-only kvstore server) gets a no-op:
    this module must not be what imports it."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    if step_num is not None:
        return jax.profiler.StepTraceAnnotation("mx." + name,
                                                step_num=step_num)
    return jax.profiler.TraceAnnotation("mx." + name)


# -- output -------------------------------------------------------------------

def dump(finished: bool = True, profile_process: str = "worker"):
    """Write collected spans as a chrome-trace JSON to ``filename``.

    Reference: Profiler::DumpProfile writes the same ``traceEvents`` format;
    the file opens in chrome://tracing / Perfetto.  Device-side xplane traces
    (if ``device_trace_dir`` was set) are written by jax.profiler at stop().
    """
    with _lock:
        events = []
        for name, cat, ts, dur, tid in _events:
            events.append({"name": name, "cat": cat, "ph": "X",
                           "ts": ts, "dur": dur, "pid": 0, "tid": tid})
        for name, ts, value in _counters:
            events.append({"name": name, "cat": "counter", "ph": "C",
                           "ts": ts, "pid": 0,
                           "args": {"value": value}})
        payload = {"traceEvents": events, "displayTimeUnit": "ms"}
        with open(_config["filename"], "w") as f:
            json.dump(payload, f)
        if finished:
            _events.clear()
            _counters.clear()


dump_profile = dump  # deprecated reference alias


def dumps(reset: bool = False, format: str = "table") -> str:
    """Aggregate per-op statistics (reference: MXAggregateProfileStatsPrint).

    ``format='table'`` renders the reference-style text table;
    ``format='json'`` returns a JSON object keyed by op name.
    """
    with _lock:
        if format == "json":
            out = json.dumps({
                name: {"count": int(c), "total_us": t, "min_us": mn,
                       "max_us": mx, "avg_us": t / c if c else 0.0}
                for name, (c, t, mn, mx) in sorted(_agg.items())
            })
        else:
            lines = ["Profile Statistics:",
                     "%-40s %-12s %-14s %-12s %-12s %-12s" %
                     ("Name", "Total Count", "Time (us)", "Min (us)",
                      "Max (us)", "Avg (us)")]
            for name, (c, t, mn, mx) in sorted(_agg.items(),
                                               key=lambda kv: -kv[1][1]):
                lines.append("%-40s %-12d %-14.1f %-12.1f %-12.1f %-12.1f" %
                             (name[:40], c, t, mn, mx, t / c if c else 0.0))
            out = "\n".join(lines)
        if reset:
            _agg.clear()
        return out


def reset():
    """Drop all collected data."""
    with _lock:
        _events.clear()
        _counters.clear()
        _agg.clear()
