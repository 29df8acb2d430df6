"""Async double-buffered host→device input pipeline (ISSUE 13).

The step-phase histograms (PR 8) exist to expose exactly one stall this
module removes: ``data_wait`` — the training loop blocking on batch
preparation + the synchronous host→device transfer before every step.
:class:`DevicePrefetcher` runs both on a background thread, one batch
*ahead* of the consumer (double-buffered by default, ``depth``
configurable via ``MX_PREFETCH_DEPTH``), so the device transfer of
batch N+1 overlaps the device compute of batch N, and the loop's
``data_wait`` share collapses to the queue handoff.

Semantics are exactly the synchronous loop's:

* **bit-parity** — ``jax.device_put`` moves bytes; it never rounds,
  casts or reorders, so a prefetched run's loss trajectory is
  bit-identical to the unprefetched one (test-pinned on the
  deterministic MLP).
* **bounded** — the queue holds at most ``depth`` batches; the
  producer blocks (stop-aware, bounded polls) when the consumer falls
  behind, so prefetching can never balloon host/device memory by more
  than ``depth`` batches.
* **clean shutdown** — :meth:`close` (idempotent; also ``with`` exit
  and ``__del__``) stops the producer, drains the queue and joins the
  thread with a bounded wait; a producer blocked on a full queue
  observes the stop event within one poll tick.  A wedged *source*
  iterator cannot wedge ``close()``.
* **error transparency** — a source that raises surfaces the exception
  (chained, naming the source) from the consumer's next ``next()``
  call, not on a background thread's stderr.

The wait the consumer *does* pay is measured: each ``next()`` records
its block time into ``step_phase_seconds{phase=data_wait}`` via
``telemetry.observe_phase`` (the cross-thread form — the wait starts on
the consumer thread against work finishing on the producer thread).
The clock is injectable for deterministic tests.

Hot-path contract (mxlint-rooted): ``__next__`` is queue handoff +
clock reads only — the device transfer, any host-side transform and
the source's own work all live on the producer thread.  No disk I/O,
no device sync on the consumer side.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Callable, Iterable, Optional

import jax

from ..base import MXNetError, get_env
from ..ndarray.ndarray import NDArray
from .. import profiler as _profiler
from .. import telemetry as _telemetry

__all__ = ["DevicePrefetcher", "prefetch_depth"]

_POLL_S = 0.05          # stop-aware bounded wait tick


def prefetch_depth() -> int:
    """MX_PREFETCH_DEPTH: batches in flight ahead of the consumer
    (2 = classic double buffering)."""
    try:
        val = get_env("MX_PREFETCH_DEPTH", 2, int)
        n = 2 if val is None else int(val)
    except (TypeError, ValueError):
        n = 2
    return max(1, n)        # 0 clamps: the consumer needs >= 1 slot


class _Stop:
    """Queue sentinel: source exhausted."""


class _Err:
    __slots__ = ("exc",)

    def __init__(self, exc):
        self.exc = exc


def _to_device(x, device):
    if x is None:
        return None
    if isinstance(x, NDArray):
        return NDArray(jax.device_put(x._jax, device), ctx=x.ctx)
    return jax.device_put(x, device)


class DevicePrefetcher:
    """Iterate `source` one batch ahead, device-putting each leaf.

    ``source`` is any iterable of array pytrees (tuples/lists/dicts of
    numpy arrays, jax arrays or NDArrays).  ``transform`` (optional)
    runs on the PRODUCER thread before the transfer — host-side batch
    assembly belongs there, not in the training loop.  ``device=None``
    uses jax's default placement (``jax.device_put`` with no target).
    """

    def __init__(self, source: Iterable, device=None,
                 depth: Optional[int] = None,
                 transform: Optional[Callable[[Any], Any]] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self._source = source
        self._device = device
        self._depth = depth if depth is not None else prefetch_depth()
        if self._depth < 1:
            raise MXNetError("DevicePrefetcher depth must be >= 1, got %d"
                             % self._depth)
        self._transform = transform
        self._clock = clock
        self._q: deque = deque()
        self._cv = threading.Condition()
        self._stop = threading.Event()
        self._closed = False
        self._thread = threading.Thread(
            target=self._run, name="DevicePrefetcher",
            daemon=True)
        self._thread.start()

    # -- producer -----------------------------------------------------------
    def _put(self, item) -> bool:
        """Bounded, stop-aware enqueue; False once stopped."""
        with self._cv:
            while len(self._q) >= self._depth:
                if self._stop.is_set():
                    return False
                self._cv.wait(timeout=_POLL_S)
            if self._stop.is_set():
                return False
            self._q.append(item)
            self._cv.notify_all()
        return True

    def _run(self):
        it = iter(self._source)
        while not self._stop.is_set():
            try:
                try:
                    batch = next(it)
                except StopIteration:
                    self._put(_Stop)
                    return
                if self._transform is not None:
                    batch = self._transform(batch)
                batch = jax.tree_util.tree_map(
                    lambda x: _to_device(x, self._device), batch,
                    is_leaf=lambda x: isinstance(x, NDArray))
            except Exception as e:      # surfaced by the consumer's next()
                err = MXNetError(
                    "DevicePrefetcher: source %s raised %s: %s"
                    % (type(self._source).__name__, type(e).__name__, e))
                err.__cause__ = e
                self._put(_Err(err))
                self._put(_Stop)
                return
            if not self._put(batch):
                return

    # -- consumer -----------------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        if self._closed:
            raise MXNetError("DevicePrefetcher is closed")
        t0 = self._clock()
        # the wait is also `mx.data_wait` on jax's profiler clock
        with _profiler.host_span("data_wait"), self._cv:
            while not self._q:
                if self._stop.is_set() or not self._thread.is_alive():
                    # producer died without a sentinel (interpreter
                    # teardown edge): treat as exhausted
                    if not self._q:
                        raise StopIteration
                    break
                self._cv.wait(timeout=_POLL_S)
            item = self._q.popleft()
            self._cv.notify_all()
        _telemetry.observe_phase("data_wait", self._clock() - t0)
        if item is _Stop:
            raise StopIteration
        if isinstance(item, _Err):
            raise item.exc
        return item

    next = __next__

    # -- lifecycle ----------------------------------------------------------
    def close(self) -> None:
        """Stop the producer and release the thread.  Idempotent; never
        blocks unbounded (a source wedged mid-``next`` keeps its daemon
        thread, which exits at its next queue interaction)."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        with self._cv:
            self._q.clear()
            self._cv.notify_all()
        self._thread.join(timeout=2.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass    # interpreter shutdown: locks/threads may be gone
