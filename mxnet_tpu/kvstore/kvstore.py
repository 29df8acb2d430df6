"""KVStore: data-parallel gradient aggregation.

Reference: include/mxnet/kvstore.h (KVStore::Create), src/kvstore/
kvstore_local.h (KVStoreLocal — CPU reduce), comm.h (CommDevice — on-device
tree reduce), kvstore_nccl.h (KVStoreNCCL), kvstore_dist.h (parameter
server), python/mxnet/kvstore/kvstore.py.

TPU-native (SURVEY.md §5.8): the NCCL/ps-lite transports are replaced by
XLA collectives.
  * ``local`` / ``device`` — single-process multi-device reduce+broadcast
    (the reference's CommCPU/CommDevice); here one jitted sum over the
    device copies, placed back per device.
  * ``ici``   — the north-star store: allreduce = `psum` over a
    `jax.sharding.Mesh` data-parallel axis; rides ICI within a slice and
    DCN across slices (XLA inserts the hierarchy).  Multi-host ranks come
    from `jax.distributed` (mxnet_tpu.parallel.init_process_group).
  * ``dist_sync``/``nccl``/``horovod`` — aliases onto the collective
    path (sync DP on dedicated TPU pods is strictly better via
    collectives; SURVEY.md §2.1 KVStore: dist row).
  * ``dist_async`` — the one PS capability with NO collective
    equivalent: a real parameter server (kvstore/server.py over TCP)
    applies every worker's push immediately, no barriers — reference
    kvstore_dist_server.h DataHandleEx async semantics.  Launch with
    ``tools/launch.py -n W -s S`` — keys hash-shard across the S
    servers (MX_PS_ROOTS).
"""
from __future__ import annotations

import pickle
import time as _real_time
from typing import Callable, Dict, List, Optional

import numpy as _np
import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..device import Context, cpu
from ..ndarray.ndarray import NDArray
from .. import ndarray as nd

__all__ = ["KVStore", "create", "KVStoreLocal", "KVStoreDevice",
           "KVStoreICI", "KVStoreDistAsync"]


def _key(k):
    # int keys stay ints: the Trainer numbers params 0..n and the Updater's
    # optimizer looks them up in int-keyed param_dict/lr_mult tables
    return k if isinstance(k, int) else str(k)


def _sum_arrays_body(arrays):
    out = arrays[0]
    for a in arrays[1:]:
        out = out + a
    return out


def _make_sum_arrays():
    # light mode: this runs per KEY per push on the eager exchange —
    # jax.jit's C++ dispatch stays; a trivial add-reduction's
    # memory_analysis is not worth a per-dispatch Python signature walk
    from ..programs import register_program
    return register_program("kvstore.sum", _sum_arrays_body,
                            mode="light", specializing=True)


_sum_arrays = _make_sum_arrays()


class KVStore:
    """Base interface (reference: python/mxnet/kvstore/kvstore.py)."""

    def __init__(self):
        self._store: Dict[str, NDArray] = {}
        self._updater = None
        self._optimizer = None
        # persisted key→bucket layouts, keyed by the ordered (key, shape,
        # dtype, stype) signature of a batched push/pull (see bucketing.py)
        self._bucket_cache: Dict = {}

    # -- identity ----------------------------------------------------------
    @property
    def type(self) -> str:
        raise NotImplementedError

    @property
    def rank(self) -> int:
        return 0

    @property
    def num_workers(self) -> int:
        return 1

    # -- data path ---------------------------------------------------------
    def init(self, key, value):
        """Register initial value(s) (reference: KVStore.init)."""
        keys, values = self._normalize(key, value)
        for k, v in zip(keys, values):
            if k in self._store:
                continue
            vv = v[0] if isinstance(v, (list, tuple)) else v
            self._store[k] = vv.copy()

    def push(self, key, value, priority=0):
        keys, values = self._normalize(key, value)
        vlists = [v if isinstance(v, (list, tuple)) else [v] for v in values]
        merged = self._reduce_many(keys, vlists)
        stored_list = []
        for k in keys:
            stored = self._store.get(k)
            if stored is None:
                raise MXNetError("key %s has not been initialized" % k)
            stored_list.append(stored)
        if self._updater is not None:
            # ONE batched updater call: with an aggregate-enabled optimizer
            # the server-side update is a fused pytree dispatch, not a
            # per-key loop
            self._updater(list(keys), merged, stored_list)
        else:
            for stored, m in zip(stored_list, merged):
                stored._set_jax(m.as_in_context(stored.context)._jax)

    def _reduce_many(self, keys, vlists) -> List[NDArray]:
        """Merge each key's device copies (and, in subclasses, exchange
        across workers — where fusion buckets coalesce the wire ops)."""
        out = []
        for k, v in zip(keys, vlists):
            m = self._reduce(v, key=k)
            if isinstance(m, NDArray):
                self._note_wire_value(m)
            out.append(m)
        return out

    # -- wire accounting (tools/bandwidth.py) -----------------------------
    def _wire_nbytes(self, n_elems: int, itemsize: int,
                     floating: bool = True) -> int:
        """Bytes an n-element gradient payload occupies in its exchange
        representation (compressed wire format, bf16 cast, or full
        width).  On the collective path this is the payload entering the
        allreduce; on the PS path the bytes actually sent."""
        gc = getattr(self, "_gc", None)
        if gc is not None and floating:
            return gc.wire_nbytes(int(n_elems))
        if getattr(self, "_compress_bf16", False) and floating and \
                itemsize == 4:
            return 2 * int(n_elems)
        return int(n_elems) * int(itemsize)

    def _note_wire_value(self, m) -> None:
        if not isinstance(m, NDArray):
            return      # sparse payloads are nnz-keyed; not accounted here
        floating = jnp.issubdtype(m._jax.dtype, jnp.floating)
        from ..engine import engine as _engine
        _engine.count_wire_bytes(
            self._wire_nbytes(m.size, m._jax.dtype.itemsize, floating))

    # -- whole-step-compiled exchange (ISSUE 7; sharded variant ISSUE 14) --
    def build_exchange_body(self, keys, arrays, layout=None):
        """Pure-traceable single-worker exchange body for the compiled
        train step (mxnet_tpu.step.CompiledStep): what ONE worker's
        batched push+pull observes of this store's wire, expressed as a
        jax-pure function so the whole gradient exchange inlines into
        the step's single XLA program.

        ``arrays`` are per-key templates (NDArray; shape/dtype only).
        Returns a :class:`TraceableExchange` — or None when the
        transport cannot be traced (host-blocking RPCs on the PS store,
        cross-process collectives that need the SPMD mesh lane) and the
        caller must fall back to the eager pipeline.

        The base store's semantics (local/device): per-key error-feedback
        quantization when gradient compression is installed (exactly
        :meth:`_reduce`'s wire model), bf16 cast-roundtrip under the
        bf16 mode, identity otherwise.

        ``layout`` (a :class:`~mxnet_tpu.parallel.SpecLayout`) selects
        the reduce-scatter/all-gather variant: each quantized payload is
        sharding-constrained onto the layout's fsdp shards before the
        error-feedback kernel runs, so under GSPMD the gradient sum
        reaches each chip as a reduce-scatter, quantization happens
        shard-local, and the residual state stays sharded per chip
        (``residual_shardings`` tells the step how to place/donate it).
        Sharding never changes the math — the replicated body and the
        sharded body compute identical values.
        """
        if self._updater is not None or self._optimizer is not None:
            return None     # server-side optimizer: push is not a pure exchange
        keys = [_key(k) for k in keys]
        gc = getattr(self, "_gc", None)
        bf16 = getattr(self, "_compress_bf16", False)
        plan = []           # per position: (mode, payload sharding or None)
        specs = []          # (wire_key, residual shape, residual dtype)
        shardings = []      # residual placement, aligned with specs
        wire_bytes = 0
        for k, a in zip(keys, arrays):
            floating = jnp.issubdtype(jnp.dtype(str(a.dtype)), jnp.floating)
            if gc is not None and floating:
                if gc.type == "int8":
                    # the fsdp rs-grain int8 path lives on the ICI
                    # store's bucketed body; the base per-key body keeps
                    # the replicated kernel (residual replicated)
                    sh = None if layout is None else layout.replicated()
                    plan.append(("int8", sh))
                    specs.append((k, (int(a.size),), jnp.float32))
                else:
                    # 2bit is elementwise: the residual simply lives on
                    # the gradient's sheet shards; no mid-body
                    # constraints needed (the step already constrains
                    # the gradients themselves)
                    sh = None if layout is None else \
                        layout.sharding(layout.sheet_spec(tuple(a.shape)))
                    plan.append(("2bit", sh))
                    specs.append((k, tuple(a.shape),
                                  jnp.dtype(str(a.dtype))))
                shardings.append(sh)
                wire_bytes += gc.wire_nbytes(int(a.size))
                continue
            if bf16 and floating and _np.dtype(str(a.dtype)).itemsize == 4:
                plan.append(("bf16", None))
                wire_bytes += 2 * int(a.size)
            else:
                plan.append(("none", None))
                wire_bytes += int(a.size) * _np.dtype(str(a.dtype)).itemsize
        block = gc.block if gc is not None and gc.type == "int8" else 0
        threshold = gc.threshold if gc is not None else 0.0

        def body(grads, residuals):
            from ..ops import quantization as _qops
            res_it = iter(residuals)
            new_grads, new_res = [], []
            for (mode, _sh), g in zip(plan, grads):
                if mode == "int8":
                    deq, nr = _qops._roundtrip_int8_kernel(
                        g.reshape(-1), next(res_it), block)
                    new_grads.append(deq.reshape(g.shape).astype(g.dtype))
                    new_res.append(nr)
                elif mode == "2bit":
                    q, nr = _qops._quantize_2bit_kernel(
                        g, next(res_it), jnp.asarray(threshold, g.dtype))
                    new_grads.append(q)
                    new_res.append(nr)
                elif mode == "bf16":
                    new_grads.append(
                        g.astype(jnp.bfloat16).astype(g.dtype))
                else:
                    new_grads.append(g)
            return new_grads, new_res

        return TraceableExchange(specs, body, wire_bytes,
                                 residual_shardings=shardings)

    # -- overlap-scheduled exchange (ISSUE 5) ------------------------------
    def begin_exchange(self, keys, vlists):
        """Open an overlap-scheduled batched exchange: the caller feeds
        per-key readiness events (gradients finalizing during backward)
        and each fusion bucket's exchange launches the moment its last
        member lands; ``drain()`` launches stragglers and commits every
        result (store slot + pull targets).  Returns None on stores that
        cannot overlap (host-blocking RPC transports)."""
        keys = [_key(k) for k in keys]
        vlists = [v if isinstance(v, (list, tuple)) else [v]
                  for v in vlists]
        return _ExchangeSession(self, keys, vlists)

    def _exchange_unit(self, kind, obj, keys, vlists):
        """Launch one exchange unit (async dispatch; no host sync).  Base
        stores have no cross-worker wire: a unit is the per-key local
        merge."""
        if kind == "solo":
            m = self._reduce(vlists[obj], key=keys[obj])
            self._note_wire_value(m)
            return m
        out = []
        for p in obj.positions:
            m = self._reduce(vlists[p], key=keys[p])
            self._note_wire_value(m)
            out.append(m)
        return out

    def _commit_unit(self, kind, obj, result, keys, vlists):
        """Write a launched unit's result into the store slot and every
        pull target — the push+pull contract, deferred to drain time so
        gradients observed between backward and step() keep their
        un-exchanged values."""
        if kind == "solo":
            self._commit_key(keys[obj], result, vlists[obj])
            return
        for p, m in zip(obj.positions, result):
            self._commit_key(keys[p], m, vlists[p])

    def _commit_key(self, k, merged, targets):
        stored = self._store.get(k)
        if stored is None:
            raise MXNetError("key %s has not been initialized" % k)
        stored._set_jax(merged.as_in_context(stored.context)._jax)
        for t in targets:
            stored.copyto(t)

    def _bucket_plans(self, keys, arrays, reverse=False):
        """Cached stable key→bucket layout for a batched exchange.

        `arrays` supplies shapes/dtypes (NDArray or numpy).  Returns
        (buckets, solo_positions); callers gate on bucketing being
        applicable (multi-key, no attached optimizer).  The cache key
        includes the bucket capacity and packing order: changing
        ``MX_KVSTORE_BUCKET_KB`` mid-process (tests, tuning sweeps) must
        re-plan, not serve a stale layout — and ``MX_KVSTORE_BUCKET_KB=0``
        cleanly disables bucketing (everything solo, per-key path)."""
        from .bucketing import bucket_bytes, plan_buckets
        cap = bucket_bytes()
        # elastic membership (ISSUE 16): stores carrying a bucket salt
        # (the membership epoch of their incarnation) roll every bucket
        # CRC on resize — replanning stays coordination-free AND a stale
        # pre-resize server accumulator can never alias a new bucket
        salt = getattr(self, "_bucket_salt", None) or None
        sig = tuple((k, tuple(a.shape), str(a.dtype),
                     getattr(a, "stype", "default"))
                    for k, a in zip(keys, arrays))
        cache_key = (sig, cap, bool(reverse), salt)
        cached = self._bucket_cache.get(cache_key)
        if cached is None:
            cached = plan_buckets(
                keys, [s[1] for s in sig], [s[2] for s in sig],
                [_np.dtype(a.dtype).itemsize for a in arrays],
                [s[3] for s in sig], cap, reverse=reverse, salt=salt)
            self._bucket_cache[cache_key] = cached
        return cached

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        keys, outs = self._normalize(key, out)
        for k, o in zip(keys, outs):
            stored = self._store[k]
            targets = o if isinstance(o, (list, tuple)) else [o]
            for t in targets:
                stored.copyto(t)

    def pushpull(self, key, value, out=None, priority=0):
        """Fused push+pull — THE data-parallel allreduce (reference:
        MXKVStorePushPullEx; SURVEY.md §3.5)."""
        self.push(key, value, priority)
        if out is not None:
            self.pull(key, out, priority)

    def broadcast(self, key, value, out=None, priority=0):
        self.init(key, value)
        if out is not None:
            self.pull(key, out, priority)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Pull only `row_ids` rows of the stored value (reference:
        KVStore.row_sparse_pull).  One jitted gather per target; the result
        lands in `out` (RowSparseNDArray: contents swapped in; dense
        NDArray: full pull fallback) or is returned."""
        if row_ids is None:
            return self.pull(key, out, priority)
        from ..ndarray.sparse import RowSparseNDArray, _as_idx
        keys, outs = self._normalize(key, out)
        # row_ids forms: one ids array (NDArray/numpy/list of ints) shared by
        # every key, or a list of such matching the key list
        is_per_key = isinstance(row_ids, (list, tuple)) and len(row_ids) and \
            not isinstance(row_ids[0], (int, _np.integer))
        ids_per_key = list(row_ids) if is_per_key else [row_ids] * len(keys)
        results = []
        for k, o, ids in zip(keys, outs, ids_per_key):
            stored = self._store[k]
            targets = o if isinstance(o, (list, tuple)) else [o]
            per_target = ids if isinstance(ids, (list, tuple)) and len(ids) \
                and not isinstance(ids[0], (int, _np.integer)) else \
                [ids] * len(targets)
            for t, tid in zip(targets, per_target):
                tid = _as_idx(tid, stored.context)
                rows = nd.invoke("take", stored, tid, axis=0)
                if isinstance(t, RowSparseNDArray):
                    t._assign(rows, tid)
                elif isinstance(t, NDArray):
                    stored.copyto(t)  # dense target: full pull
                else:
                    results.append(RowSparseNDArray(rows, tid, stored.shape))
        return results or None

    # -- optimizer ---------------------------------------------------------
    def set_optimizer(self, optimizer):
        from ..optimizer import get_updater
        self._optimizer = optimizer
        self._updater = get_updater(optimizer)

    def set_gradient_compression(self, compression_params):
        """Reference: kvstore.py set_gradient_compression →
        src/kvstore/gradient_compression.cc.

        ``{'type': '2bit', 'threshold': t}`` — the reference's exact
        scheme: per-key residual error feedback, each pushed gradient
        quantized to {-t, 0, +t} per worker before the reduce
        (gradient_compression.py).  ``{'type': 'int8', 'block': b}`` —
        per-block symmetric int8 with error feedback; the collective
        payload carries int8 codes + one f32 scale per `b` elements
        (MX_GRAD_COMPRESS_BLOCK default) and is merged scale-aware
        (dequant-sum-requant) inside the allreduce.  ``{'type': 'bf16'}``
        — TPU-extra: cast payloads to bfloat16 before the allreduce
        (half the ICI/DCN bytes).  An unknown type raises ValueError
        (matching upstream MXNet) instead of silently not compressing."""
        params = dict(compression_params or {})
        ctype = params.get("type")
        self._gc = None
        self._compress_bf16 = False
        if ctype in ("2bit", "int8"):
            from .gradient_compression import GradientCompression
            self._gc = GradientCompression(
                type=ctype,
                threshold=float(params.get("threshold", 0.5)),
                block=params.get("block"))
            return
        if ctype == "bf16":
            self._compress_bf16 = True
            return
        if ctype is not None:
            raise ValueError(
                "Unsupported gradient compression type %r (supported: "
                "'2bit', 'int8', 'bf16')" % (ctype,))

    def _maybe_compress(self, x):
        """bf16 cast applied to gradient payloads before the reduce."""
        if getattr(self, "_compress_bf16", False) and \
                jnp.issubdtype(x.dtype, jnp.floating) and \
                x.dtype != jnp.bfloat16.dtype:
            return x.astype(jnp.bfloat16), x.dtype
        return x, None

    def save_optimizer_states(self, fname, dump_optimizer=False):
        assert self._updater is not None, "Cannot save states for fused optimizer"
        with open(fname, "wb") as f:
            f.write(self._updater.get_states(dump_optimizer))

    def load_optimizer_states(self, fname):
        assert self._updater is not None, "Cannot load states for fused optimizer"
        with open(fname, "rb") as f:
            self._updater.set_states(f.read())

    def _barrier(self):
        pass

    # -- helpers -----------------------------------------------------------
    @staticmethod
    def _normalize(key, value):
        if isinstance(key, (list, tuple)):
            return [_key(k) for k in key], list(value)
        return [_key(key)], [value]

    def _reduce(self, values: List[NDArray], key=None) -> NDArray:
        merged = self._reduce_local(values)
        # error-feedback quantization of the per-process merged gradient
        # (reference: worker quantizes AFTER its local multi-GPU reduce,
        # before the wire — kvstore_dist.h PushImpl).  2bit emits ±t/0
        # levels; int8 is a per-block quantize→dequantize roundtrip (one
        # jitted dispatch) — what a single worker observes of the wire.
        gc = getattr(self, "_gc", None)
        if gc is not None and key is not None and \
                jnp.issubdtype(merged._jax.dtype, jnp.floating):
            from ..engine import engine as _engine
            _engine.count_dispatch()
            merged = NDArray(gc.quantize(key, merged._jax),
                             ctx=merged.context)
        return merged

    def _reduce_local(self, values: List[NDArray]) -> NDArray:
        if len(values) == 1:
            return values[0]
        target = values[0].context
        comp = [self._maybe_compress(v._jax) for v in values]
        orig_dtype = comp[0][1]
        vals = [(x if v.context == target else
                 jax.device_put(x, target.jax_device))
                for (x, _), v in zip(comp, values)]
        from ..engine import engine as _engine
        _engine.count_dispatch()
        out = _sum_arrays(vals)
        if orig_dtype is not None:
            out = out.astype(orig_dtype)
        return NDArray(out, ctx=target)


class TraceableExchange:
    """One store's gradient exchange as a pure function (ISSUE 7).

    ``residual_specs`` names the error-feedback residual state the body
    threads through — ``[(wire_key, shape, dtype)]`` in the exact order
    the body consumes/produces them; the compiled step reads each via
    ``GradientCompression.peek_residual`` (donated jit input) and writes
    the returned state back with ``put_residual`` after the dispatch, so
    eager and compiled steps share one residual store (checkpoint /
    mode-switch continuity).  ``wire_bytes`` is the static per-step wire
    accounting (``engine.count_wire_bytes``) the eager path would have
    recorded for the same exchange.
    """

    def __init__(self, residual_specs, body, wire_bytes: int = 0,
                 residual_shardings=None):
        self.residual_specs = list(residual_specs)
        self._body = body
        self.wire_bytes = int(wire_bytes)
        # sharded lane (ISSUE 14): each residual's NamedSharding, aligned
        # with residual_specs (None entries replicate) — the EF state
        # stays sharded per chip across dispatches
        self.residual_shardings = list(
            residual_shardings if residual_shardings is not None
            else [None] * len(self.residual_specs))

    def __call__(self, grads, residuals):
        """(new_grads, new_residuals) — pure, safe under an outer jit."""
        return self._body(grads, residuals)


class _ExchangeSession:
    """One overlap-scheduled batched gradient exchange (ISSUE 5).

    Created by :meth:`KVStore.begin_exchange` BEFORE backward runs; the
    trainer's grad-ready hooks call :meth:`notify_key` as autograd
    finalizes each leaf gradient, and the moment a fusion bucket's last
    member (across every device copy) lands, that bucket's exchange
    launches — an async XLA dispatch that overlaps with the rest of
    backward.  Buckets are planned in REVERSE parameter order
    (bucketing.plan_buckets(reverse=True)): backward produces late-layer
    gradients first, so the first buckets close (and their collectives
    fly) while early layers are still differentiating.

    Results are committed (store slot + pull targets) only at
    :meth:`drain` — called by Trainer._allreduce_grads before the
    optimizer applies — so code inspecting gradients between backward and
    step() still sees the un-exchanged values.  A notify for an
    already-launched unit (double backward, grad_req='add') marks the
    session stale and drain relaunches everything from the arrays'
    current values — overlap degrades to the serialized exchange, never
    to wrong gradients.
    """

    def __init__(self, store: "KVStore", keys, vlists):
        from .bucketing import ReadinessPlanner
        self._store = store
        self._keys = keys
        self._vlists = vlists
        buckets: List = []
        solo = range(len(keys))
        if len(keys) > 1 and store._optimizer is None and \
                all(isinstance(v[0], NDArray) for v in vlists):
            buckets, solo = store._bucket_plans(
                keys, [v[0] for v in vlists], reverse=True)
        copies = max(len(v) for v in vlists) if vlists else 1
        self._planner = ReadinessPlanner(buckets, list(solo), copies=copies)
        self._pos_of_key = {k: i for i, k in enumerate(keys)}
        self._results: Dict[int, object] = {}
        self._snaps: Dict[int, List] = {}
        self._launched: set = set()

    def notify_key(self, key, copy: int = 0) -> None:
        """Gradient for `key` (device copy `copy`) is final; launch any
        unit this closes."""
        pos = self._pos_of_key.get(_key(key))
        if pos is None:
            return
        for u in self._planner.note(pos, copy):
            self._launch(u)

    def _unit_inputs(self, u: int) -> List:
        """Snapshot of a unit's input buffers (the jax array OBJECTS, not
        bare ids — holding the refs rules out id reuse after gc).
        NDArray writes replace the underlying array object (`_set_jax`),
        so an identity mismatch at drain time means some input was
        rewritten after the unit launched (e.g. manual grad scaling
        between backward and step()) and the launched exchange read a
        stale value."""
        kind, obj = self._planner.unit(u)
        poss = obj.positions if kind == "bucket" else [obj]
        return [v._jax for p in poss for v in self._vlists[p]]

    def _wire_keys(self, u: int) -> List:
        """Wire keys a unit's exchange may quantize under: the bucket's
        CRC name (int8 bucket path) plus/or its member keys (per-key
        quantize paths)."""
        kind, obj = self._planner.unit(u)
        if kind == "solo":
            return [self._keys[obj]]
        return [obj.name] + [self._keys[p] for p in obj.positions]

    def _launch(self, u: int) -> None:
        kind, obj = self._planner.unit(u)
        gc = getattr(self._store, "_gc", None)
        if gc is not None:
            # error feedback makes a launch stateful: checkpoint the
            # residuals it will consume so a RElaunch (stale session /
            # rewritten input) first un-does the discarded payload's EF
            # step instead of double-stepping the residual
            wk = self._wire_keys(u)
            if u in self._launched:
                gc.rollback(wk)
            else:
                gc.checkpoint(wk)
        self._launched.add(u)
        self._snaps[u] = self._unit_inputs(u)
        self._results[u] = self._store._exchange_unit(
            kind, obj, self._keys, self._vlists)

    def _inputs_unchanged(self, u: int) -> bool:
        snap, cur = self._snaps[u], self._unit_inputs(u)
        return len(snap) == len(cur) and \
            all(a is b for a, b in zip(snap, cur))

    def abort(self) -> None:
        """Discard the session without committing anything: roll back the
        error-feedback residuals every launched unit consumed and drop
        the checkpoints.  Used when the exchange key set changed under an
        armed session (e.g. a param unfrozen between steps) — the caller
        falls back to a fresh serialized exchange."""
        gc = getattr(self._store, "_gc", None)
        if gc is not None:
            for u in self._launched:
                wk = self._wire_keys(u)
                gc.rollback(wk)
                gc.commit(wk)
        self._launched.clear()
        self._results.clear()
        self._snaps.clear()

    def drain(self) -> None:
        """Launch every remaining unit, then commit all results."""
        if self._planner.stale:
            # values changed under launched exchanges: redo everything
            self._results.clear()
            for u in self._planner.all_units():
                self._launch(u)
        else:
            for u in self._planner.pending():
                self._launch(u)
            for u in sorted(self._results):
                # input rewritten since launch: the exchange read a stale
                # value — relaunch from the current buffers (overlap
                # degrades to serialized, never to wrong gradients)
                if not self._inputs_unchanged(u):
                    self._launch(u)
        for u in sorted(self._results):
            kind, obj = self._planner.unit(u)
            self._store._commit_unit(kind, obj, self._results[u],
                                     self._keys, self._vlists)
        gc = getattr(self._store, "_gc", None)
        if gc is not None:
            for u in self._launched:
                gc.commit(self._wire_keys(u))
        self._launched.clear()
        self._results.clear()
        self._snaps.clear()


class KVStoreLocal(KVStore):
    """Single-process store, reduce on first device (reference:
    KVStoreLocal + CommCPU)."""

    @property
    def type(self):
        return "local"


class KVStoreDevice(KVStoreLocal):
    """Reduce on device (reference: CommDevice tree reduce; tree/ring
    topology choice belongs to XLA now)."""

    @property
    def type(self):
        return "device"


class KVStoreICI(KVStoreLocal):
    """Collective store over the TPU mesh (reference role: KVStoreNCCL +
    KVStoreDist's dist_sync contract; SURVEY.md §5.8 `kvstore='ici'`).

    Single-host: device-copies are reduced with one jitted sum (XLA emits
    ICI transfers).  Multi-host (`jax.process_count() > 1` after
    mxnet_tpu.parallel.init_process_group): every push additionally
    allreduces across processes — a jitted sum over a global 1-axis mesh,
    lowered by XLA to collectives over ICI within a slice and DCN across
    slices.  The dist_sync contract matches the reference
    (src/kvstore/kvstore_dist.h KVStoreDist::PushPullImpl): a pull after N
    workers push returns the N-worker SUM.
    """

    def __init__(self):
        super().__init__()
        self._rank = 0
        self._size = 1
        try:
            import jax.distributed  # noqa: F401
            self._rank = jax.process_index()
            self._size = jax.process_count()
        except Exception:
            pass
        self._mesh = None
        self._home_dev = None
        self._xsum_cache: Dict = {}

    @property
    def type(self):
        return "ici"

    @property
    def rank(self):
        return self._rank

    @property
    def num_workers(self):
        return self._size

    def init(self, key, value):
        """Multi-process init carries the reference's dist contract: the
        stored value is RANK 0's (kvstore_dist.h: only one worker's init
        reaches the server), so a subsequent broadcast/pull hands every
        worker identical weights regardless of local RNG state."""
        super().init(key, value)
        if self._size > 1:
            keys, _ = self._normalize(key, value)
            for k in keys:
                stored = self._store[k]
                payload = stored._jax if self._rank == 0 else \
                    jnp.zeros_like(stored._jax)
                agreed = self._cross_process_sum(payload)
                stored._set_jax(jax.device_put(agreed.addressable_data(0),
                                               stored.context.jax_device))

    # -- cross-process allreduce -------------------------------------------
    def _ensure_mesh(self):
        """1-axis mesh with ONE device per process: the locally merged
        value is already a single array, so a per-process representative
        device is all the collective needs (a Mesh may legally span a
        subset of devices; every process contributes its device 0)."""
        if self._mesh is None:
            import numpy as np
            from jax.sharding import Mesh
            firsts = {}
            for d in sorted(jax.devices(), key=lambda d: d.id):
                firsts.setdefault(d.process_index, d)
            devs = [firsts[p] for p in sorted(firsts)]
            self._home_dev = firsts[self._rank]
            self._mesh = Mesh(np.array(devs), ("dp",))
        return self._mesh

    def _cross_process_sum(self, x):
        """Cross-process allreduce: stack each process's payload as one
        shard of a (num_workers, ...) global array, jitted sum over the
        mesh axis, result replicated — XLA lowers this to a collective
        over ICI/DCN.  Exact for integer dtypes (no padding, no scaling)."""
        mesh = self._ensure_mesh()
        from jax.sharding import NamedSharding, PartitionSpec as P
        key = (x.shape, str(x.dtype))
        fn = self._xsum_cache.get(key)
        if fn is None:
            # light census: explicit shardings make AOT lowering here
            # depend on the global mesh layout; plain jit dispatch
            # keeps the collective path untouched while the registry
            # still counts its (re)traces and compile time
            from ..programs import register_program
            fn = register_program(
                "kvstore.cross_sum", lambda y: jnp.sum(y, axis=0),
                mode="light",
                in_shardings=NamedSharding(mesh, P("dp")),
                out_shardings=NamedSharding(mesh, P()))
            self._xsum_cache[key] = fn
        shard = jax.device_put(x[None], self._home_dev)
        stacked = jax.make_array_from_single_device_arrays(
            (self._size,) + tuple(x.shape),
            NamedSharding(mesh, P("dp")), [shard])
        from ..engine import engine as _engine
        _engine.count_dispatch()
        return fn(stacked)

    def _cross_reduce_one(self, merged: NDArray) -> NDArray:
        """Cross-process allreduce of ONE locally merged value."""
        payload, orig_dtype = self._maybe_compress(merged._jax)
        from ..engine import engine as _engine
        _engine.count_wire_bytes(payload.size * payload.dtype.itemsize)
        out = self._cross_process_sum(payload)
        if orig_dtype is not None:
            out = out.astype(orig_dtype)
        # out is replicated over the global mesh; its local shard IS the
        # full value — re-home it on the store's device
        out = jax.device_put(out.addressable_data(0),
                             merged.context.jax_device)
        return NDArray(out, ctx=merged.context)

    def _wire_nbytes(self, n_elems: int, itemsize: int,
                     floating: bool = True) -> int:
        gc = getattr(self, "_gc", None)
        if gc is not None and gc.type == "2bit" and floating:
            # the collective ships 2bit LEVELS full-width (±t/0 must sum
            # exactly inside the allreduce) — only the PS TCP wire ships
            # the packed n/4-byte format, so report honest bytes here
            return int(n_elems) * int(itemsize)
        return super()._wire_nbytes(n_elems, itemsize, floating)

    # -- quantized collective (ISSUE 5: EQuARX-style int8 allreduce) -------
    def _int8_active(self, x=None) -> bool:
        gc = getattr(self, "_gc", None)
        return gc is not None and gc.type == "int8" and \
            (x is None or jnp.issubdtype(x.dtype, jnp.floating))

    def _cross_sum_quantized(self, q, scales):
        """AllReduce of the COMPACT payload: every process contributes its
        (int8 codes, per-block scales); inside the jitted collective each
        worker's shard is dequantized at its own scales, summed, and the
        sum requantized at a fresh merged scale — so both directions of
        the exchange stay int8-narrow on the wire (EQuARX's
        dequant-sum-requant).  Returns the replicated (q_sum, scales_sum)
        local shards."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..ops import quantization as _qops
        mesh = self._ensure_mesh()
        key = ("q8sum", q.shape, scales.shape)
        fn = self._xsum_cache.get(key)
        if fn is None:
            from ..programs import register_program
            fn = register_program(
                "kvstore.q8_cross_sum",
                _qops._dequant_sum_requant_kernel, mode="light",
                in_shardings=(NamedSharding(mesh, P("dp")),
                              NamedSharding(mesh, P("dp"))),
                out_shardings=(NamedSharding(mesh, P()),
                               NamedSharding(mesh, P())))
            self._xsum_cache[key] = fn
        def _stack(x):
            shard = jax.device_put(x[None], self._home_dev)
            return jax.make_array_from_single_device_arrays(
                (self._size,) + tuple(x.shape),
                NamedSharding(mesh, P("dp")), [shard])
        from ..engine import engine as _engine
        _engine.count_dispatch()
        qo, so = fn(_stack(q), _stack(scales))
        return qo.addressable_data(0), so.addressable_data(0)

    def _exchange_flat(self, wire_key, flat: NDArray) -> NDArray:
        """Full int8-compressed exchange of one FLAT float payload:
        quantize (error feedback, residual keyed by `wire_key`) →
        compact allreduce → dequantize once."""
        from ..engine import engine as _engine
        gc = self._gc
        x = flat._jax
        _engine.count_wire_bytes(gc.wire_nbytes(x.size))
        if self._size <= 1:
            _engine.count_dispatch()
            out = gc.quantize(wire_key, x)     # fused roundtrip
        else:
            _engine.count_dispatch()
            q, scales = gc.compress_device(wire_key, x)
            qo, so = self._cross_sum_quantized(q, scales)
            _engine.count_dispatch()
            out = gc.decompress_device((qo, so), x.size).astype(x.dtype)
        out = jax.device_put(out, flat.context.jax_device)
        return NDArray(out, ctx=flat.context)

    def _reduce(self, values: List[NDArray], key=None) -> NDArray:
        if key is not None and isinstance(values[0], NDArray) and \
                self._int8_active(values[0]._jax):
            merged = self._reduce_local(values)
            flat = NDArray(merged._jax.reshape(-1), ctx=merged.context)
            out = self._exchange_flat(key, flat)
            return NDArray(out._jax.reshape(merged.shape),
                           ctx=merged.context)
        merged = super()._reduce(values, key=key)
        if self._size > 1:
            merged = self._cross_reduce_one(merged)
        return merged

    def _exchange_bucket(self, b, members: List[NDArray]) -> NDArray:
        """One fusion bucket's exchange: concat the locally merged member
        payloads, cross the wire (int8-quantized under the bucket's name,
        plain collective, or local passthrough), return the flat result.
        Shared by the serialized batched exchange (:meth:`_reduce_many`)
        and the overlap session (:meth:`_exchange_unit`)."""
        flat = jnp.concatenate([m._jax.reshape(-1) for m in members])
        from ..engine import engine as _engine
        _engine.count_dispatch()   # the concat launch
        ctx = members[0].context
        if self._int8_active(flat):
            return self._exchange_flat(b.name, NDArray(flat, ctx=ctx))
        if self._size > 1:
            return self._cross_reduce_one(NDArray(flat, ctx=ctx))
        out = NDArray(flat, ctx=ctx)   # local / non-float: no wire to cross
        self._note_wire_value(out)
        return out

    def _reduce_many(self, keys, vlists) -> List[NDArray]:
        """Batched exchange: local per-key reduce (+ optional error-
        feedback quantize), then the cross-process allreduce coalesced
        into fusion buckets — O(#buckets) collectives per step instead of
        O(#keys).  With int8 compression each bucket's payload is
        quantized per-bucket (residual keyed by the bucket name) and
        allreduced compact."""
        int8 = self._int8_active()
        if int8:
            # local merge only: quantization happens per exchange payload
            # (bucket or solo), not per key
            merged = [self._reduce_local(v) for v in vlists]
        else:
            merged = [KVStore._reduce(self, v, key=k)
                      for k, v in zip(keys, vlists)]
            if self._size <= 1:
                for m in merged:
                    self._note_wire_value(m)
                return merged
        buckets = []
        solo = range(len(keys))
        if len(keys) > 1 and self._optimizer is None:
            eligible = all(isinstance(m, NDArray) for m in merged)
            if eligible:
                buckets, solo = self._bucket_plans(keys, merged)
        for b in buckets:
            out = self._exchange_bucket(b, [merged[p] for p in b.positions])
            for p, off, size, shape in b.slices():
                piece = out._jax[off:off + size].reshape(shape)
                merged[p] = NDArray(piece, ctx=merged[p].context)
        for p in solo:
            if int8 and isinstance(merged[p], NDArray) and \
                    jnp.issubdtype(merged[p]._jax.dtype, jnp.floating):
                # _reduce's int8 path: flatten → _exchange_flat → reshape
                merged[p] = self._reduce([merged[p]], key=keys[p])
            elif self._size > 1 and isinstance(merged[p], NDArray):
                merged[p] = self._cross_reduce_one(merged[p])
            else:
                self._note_wire_value(merged[p])
        return merged

    def _exchange_unit(self, kind, obj, keys, vlists):
        """Overlap-session unit launch: the bucket path concatenates,
        exchanges (quantized when int8 compression is on), and returns
        the split pieces; solo keys ride the per-key exchange."""
        if kind == "solo":
            m = self._reduce(vlists[obj], key=keys[obj])
            if self._size <= 1 and not (isinstance(m, NDArray) and
                                        self._int8_active(m._jax)):
                self._note_wire_value(m)
            return m
        merged = [self._reduce_local(vlists[p]) if self._int8_active()
                  else KVStore._reduce(self, vlists[p], key=keys[p])
                  for p in obj.positions]
        out = self._exchange_bucket(obj, merged)
        pieces = []
        for (_p, off, size, shape), m in zip(obj.slices(), merged):
            pieces.append(NDArray(out._jax[off:off + size].reshape(shape),
                                  ctx=m.context))
        return pieces

    def build_exchange_body(self, keys, arrays, layout=None):
        """ICI's traceable body mirrors :meth:`_reduce_many`'s
        single-process semantics: int8 compression quantizes per FUSION
        BUCKET (concat → error-feedback roundtrip keyed by the bucket's
        CRC name → split), solo/2bit/bf16 keys ride the per-key base
        body.  Multi-process exchange needs the SPMD mesh lane
        (parallel.TrainStep) — the compiled Gluon step falls back to the
        eager pipeline there.

        With ``layout`` (ISSUE 14) this is the **reduce-scatter /
        all-gather** variant next to the existing allreduce: each flat
        bucket payload is sharding-constrained over the layout's fsdp
        axis before the error-feedback roundtrip, so GSPMD delivers the
        gradient sum to each chip as a reduce-scatter of the int8
        (codes, scales) grain, quantization and the residual update run
        shard-local, and the dequantized pieces all-gather back into
        each consumer's layout only where the optimizer apply needs
        them.  Residuals stay sharded per chip (``residual_shardings``).
        """
        if self._size > 1:
            return None
        gc = getattr(self, "_gc", None)
        if gc is None or gc.type != "int8" or \
                self._updater is not None or self._optimizer is not None:
            return super().build_exchange_body(keys, arrays, layout=layout)
        keys = [_key(k) for k in keys]
        buckets: List = []
        solo = range(len(keys))
        if len(keys) > 1:
            eligible = all(isinstance(a, NDArray) for a in arrays)
            if eligible:
                buckets, solo = self._bucket_plans(keys, arrays)
        solo = list(solo)
        block = gc.block
        from ..ops.quantization import rs_block_bytes
        # the reduce-scatter grain (fsdp>1): every flat payload pads to
        # whole blocks per shard so shard-local quantization IS logical
        # blockwise quantization; residuals live at the PADDED length,
        # fsdp-sharded (a lane switch rolls them — the shape mismatch
        # hands back fresh zeros, same as a bucket-layout change)
        fsdp = 0 if layout is None else int(layout.fsdp)
        use_rs = fsdp > 1

        def _payload(n):
            """(residual length, residual sharding) of one n-elem flat
            int8 payload under the active layout."""
            if not use_rs:
                return int(n), (None if layout is None
                                else layout.replicated())
            npad = rs_block_bytes(int(n), block, fsdp)
            from jax.sharding import PartitionSpec as _P
            return npad, layout.sharding(_P(layout.fsdp_axis))

        specs = []
        shardings = []
        wire_bytes = 0
        solo_modes = []
        bucket_pads = []
        for b in buckets:
            npad, sh = _payload(b.total)
            specs.append((b.name, (npad,), jnp.float32))
            shardings.append(sh)
            bucket_pads.append(npad)
            wire_bytes += gc.wire_nbytes(int(b.total))
        solo_pads = []
        for p in solo:
            a = arrays[p]
            floating = jnp.issubdtype(jnp.dtype(str(a.dtype)), jnp.floating)
            if floating:
                npad, sh = _payload(a.size)
                specs.append((keys[p], (npad,), jnp.float32))
                shardings.append(sh)
                solo_pads.append(npad)
                wire_bytes += gc.wire_nbytes(int(a.size))
                solo_modes.append("int8")
            else:
                wire_bytes += int(a.size) * _np.dtype(str(a.dtype)).itemsize
                solo_modes.append("none")
                solo_pads.append(0)

        def _quantize_flat(flat, res, npad):
            from jax import lax as _lax
            from ..ops import quantization as _qops
            if not use_rs:
                return _qops._roundtrip_int8_kernel(flat, res, block)
            n = flat.shape[0]
            if npad > n:
                flat = jnp.concatenate(
                    [flat, jnp.zeros((npad - n,), flat.dtype)])
            # XLA:CPU SPMD miscompiles a `concatenate` whose consumer is
            # sharded: the operands get partitioned over the OTHER mesh
            # axes and the pieces psum'd, so values arrive multiplied by
            # the data-axis size.  Pinning the concat result replicated
            # before the manual shard_map kernel sidesteps it — the
            # kernel itself then reshards to the fsdp grain (the
            # reduce-scatter) from a known-good replicated value.
            flat = _lax.with_sharding_constraint(flat, layout.replicated())
            deq, nr = _qops.rs_roundtrip_int8(flat, res, block,
                                              layout.mesh,
                                              layout.fsdp_axis)
            return deq[:n], nr

        def body(grads, residuals):
            res_it = iter(residuals)
            new_grads = list(grads)
            new_res = []
            for b, npad in zip(buckets, bucket_pads):
                flat = jnp.concatenate(
                    [grads[p].reshape(-1) for p in b.positions])
                deq, nr = _quantize_flat(flat, next(res_it), npad)
                new_res.append(nr)
                for p, off, size, shape in b.slices():
                    new_grads[p] = deq[off:off + size].reshape(shape).astype(
                        grads[p].dtype)
            for p, mode, npad in zip(solo, solo_modes, solo_pads):
                if mode == "int8":
                    g = grads[p].reshape(-1)
                    deq, nr = _quantize_flat(g, next(res_it), npad)
                    new_grads[p] = deq.reshape(
                        grads[p].shape).astype(grads[p].dtype)
                    new_res.append(nr)
            return new_grads, new_res

        return TraceableExchange(specs, body, wire_bytes,
                                 residual_shardings=shardings)

    def _barrier(self):
        if self._size > 1:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices("mx_kvstore_barrier")


def _ps_addr():
    """Parameter-server address from the launcher env, or None."""
    import os
    from ..base import get_env
    addr = get_env("MX_PS_ROOT") or \
        os.environ.get("DMLC_PS_ROOT_URI")
    if not addr:
        return None
    if ":" not in addr:
        addr = "%s:%s" % (addr, os.environ.get("DMLC_PS_ROOT_PORT", "9600"))
    return addr


def _ps_addrs():
    """ALL server addresses (MX_PS_ROOTS, comma-separated) — keys shard
    across them by hash (reference: kvstore_dist.h key->server
    assignment + MXNET_KVSTORE_BIGARRAY_BOUND sharding role)."""
    from ..base import get_env
    roots = get_env("MX_PS_ROOTS")
    if roots:
        return [a.strip() for a in roots.split(",") if a.strip()]
    one = _ps_addr()
    return [one] if one else []


class KVStoreDistAsync(KVStore):
    """Async parameter-server store (reference: KVStoreDist with
    dist_async — src/kvstore/kvstore_dist_server.h DataHandleEx async
    path): each worker's push is applied by the server THE MOMENT it
    arrives (server-side optimizer), pulls return whatever is current,
    and workers never wait for each other.  Server addresses from
    MX_PS_ROOTS (tools/launch.py -s N; keys hash-shard across servers)
    or MX_PS_ROOT (single server).

    Fault tolerance (ps-lite resender role, rebuilt over
    mxnet_tpu.fault): every RPC is SEQ-tagged and retried under a
    :class:`~mxnet_tpu.fault.RetryPolicy` — a dropped connection or a
    server restart triggers transparent reconnect and an idempotent
    replay of the in-flight request (the server's replay cache
    guarantees exactly-once application), with a loud terminal
    MXNetError only after ``MX_KVSTORE_RETRY_DEADLINE`` seconds.  A
    background heartbeat thread PINGs each server every
    ``MX_KVSTORE_HEARTBEAT`` seconds on its own connections so a
    compute-bound worker is never evicted as stale."""

    def __init__(self):
        super().__init__()
        import os
        import threading
        import uuid
        from . import server as _srv
        from .. import fault as _fault
        self._srv_mod = _srv
        self._fault = _fault
        addrs = _ps_addrs()
        if not addrs:
            raise MXNetError(
                "kvstore 'dist_async' needs a parameter server: launch "
                "with tools/launch.py -n <workers> -s <servers> "
                "(MX_PS_ROOTS/MX_PS_ROOT unset)")
        self._addrs = list(addrs)
        from ..base import get_env
        self._rank = int(get_env("MX_PROCESS_ID") or
                         os.environ.get("DMLC_WORKER_ID", 0))
        self._size = int(get_env("MX_NUM_PROCESSES") or
                         os.environ.get("DMLC_NUM_WORKER", 1))
        # liveness is per RANK server-side; the uuid distinguishes a
        # restarted worker's replay cache from its predecessor's
        self._client_id = "r%d:%s" % (self._rank, uuid.uuid4().hex[:12])
        import socket
        self._socks = []
        # connect-retry budget rides the injectable clock (fault.now/
        # fault.sleep) and the documented retry knob, so chaos tests
        # fast-forward it under use_virtual_time() instead of burning a
        # real minute per dead server
        connect_deadline = get_env("MX_KVSTORE_RETRY_DEADLINE", dtype=float)
        for addr in self._addrs:
            host, port = addr.rsplit(":", 1)
            deadline = _fault.Deadline(connect_deadline or 60.0)
            while True:  # the launcher starts servers concurrently:
                try:     # retry until each binds (ps-lite scheduler role)
                    self._socks.append(socket.create_connection(
                        (host, int(port)), timeout=120))
                    break
                except (ConnectionRefusedError, OSError):
                    if deadline.expired():
                        raise
                    if _fault.is_virtual():
                        # the server binds in REAL time: a pure virtual
                        # tick would burn the whole budget in microseconds
                        # before it ever gets a chance — yield briefly,
                        # then charge the tick so a truly dead server
                        # still fails fast in virtual seconds
                        _real_time.sleep(0.005)  # mxlint: disable=wall-clock-in-fault-path
                    _fault.sleep(0.2)
        self._lock = threading.Lock()
        self._seq = 0
        self._seq_lock = threading.Lock()
        self._bucket_inited: set = set()
        # elastic membership (ISSUE 16): under MX_ELASTIC the worker
        # announces itself with JOIN at init (a no-op for ranks the
        # server already seeded) and the launcher hands every worker of
        # one incarnation the SAME membership epoch via MX_ELASTIC_EPOCH
        # — the bucket salt must be agreed BEFORE the first plan, not
        # observed racily while a join storm is still in flight.
        self._elastic = bool(get_env("MX_ELASTIC", 0, int))
        self._membership_epoch = get_env("MX_ELASTIC_EPOCH", 0, int) or 0
        self._bucket_salt = self._membership_epoch or None
        # hierarchical exchange (ISSUE 16): the cross-slice return leg
        # pulls int8 (PULLQ) instead of fp32 — opt-in, gradient/
        # accumulate mode only (a server-side optimizer needs exact
        # full-width weights back)
        self._hier = bool(get_env("MX_EXCHANGE_HIERARCHICAL", 0, int))
        self._hb_stop = threading.Event()
        self._hb_thread = None
        self._start_heartbeat()
        if self._elastic:
            self.join()

    # -- resilience plumbing ------------------------------------------------
    def _retry_policy(self):
        from ..fault import RetryPolicy
        return RetryPolicy.from_env()

    def _next_seq(self):
        with self._seq_lock:
            self._seq += 1
            return self._seq

    def _recv_timeout(self, cmd="PULL"):
        """Per-request reply deadline.  BARRIER legitimately blocks up to
        the server's barrier timeout, so its reply window must exceed it
        (a shorter window would replay the barrier and double-count this
        worker)."""
        from ..base import get_env
        if cmd == "BARRIER":
            t = get_env("MX_KVSTORE_BARRIER_TIMEOUT", 120.0, float)
            return (t if t and t > 0 else 120.0) + 30.0
        t = get_env("MX_KVSTORE_RECV_TIMEOUT", 0.0, float)
        return t if t and t > 0 else 30.0

    def _kill_sock(self, idx):
        sock = self._socks[idx]
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass
        self._socks[idx] = None

    def _ensure_sock(self, idx):
        """Reconnect a dead connection (server restart recovery path)."""
        import socket
        sock = self._socks[idx]
        if sock is not None:
            return sock
        host, port = self._addrs[idx].rsplit(":", 1)
        sock = socket.create_connection((host, int(port)), timeout=5)
        # the 5s bound is for CONNECT only — leave sends as generous as
        # the original __init__ connections (a big sendall over a slow
        # link must not be capped at the connect timeout)
        sock.settimeout(120)
        self._socks[idx] = sock
        return sock

    def _start_heartbeat(self):
        import socket as _socket
        import threading
        from ..base import get_env
        interval = get_env("MX_KVSTORE_HEARTBEAT", dtype=float)
        if not interval or interval <= 0:
            return

        def run():
            # dedicated connections: a heartbeat must not contend with a
            # long-blocking data RPC (e.g. a worker waiting in BARRIER)
            socks = [None] * len(self._addrs)
            while not self._hb_stop.wait(interval):
                for i, addr in enumerate(self._addrs):
                    try:
                        if socks[i] is None:
                            host, port = addr.rsplit(":", 1)
                            socks[i] = _socket.create_connection(
                                (host, int(port)), timeout=2)
                        self._srv_mod.send_msg(
                            socks[i], ("PING", self._client_id))
                        self._srv_mod.recv_msg(socks[i], timeout=2)
                    except (ConnectionError, OSError, TimeoutError):
                        if socks[i] is not None:
                            try:
                                socks[i].close()
                            except OSError:
                                pass
                        socks[i] = None    # reconnect next beat
            for s in socks:
                if s is not None:
                    try:
                        s.close()
                    except OSError:
                        pass

        self._hb_thread = threading.Thread(target=run, daemon=True,
                                           name="mx-kvstore-heartbeat")
        self._hb_thread.start()

    @property
    def type(self):
        return "dist_async"

    @property
    def rank(self):
        return self._rank

    @property
    def num_workers(self):
        return self._size

    def _server_of(self, key) -> int:
        """key -> server index (stable hash; reference key->server
        assignment)."""
        import zlib
        return zlib.crc32(str(key).encode()) % len(self._socks)

    # -- big-array sharding (reference: MXNET_KVSTORE_BIGARRAY_BOUND in
    # kvstore_dist.h — tensors over the bound split EVENLY across ALL
    # servers instead of hashing whole to one) -----------------------------
    @property
    def _bigarray_bound(self):
        from ..base import get_env
        return get_env("MXNET_KVSTORE_BIGARRAY_BOUND", 1_000_000, int)

    def _shard_plan(self, size):
        """[(server, start, stop)] flat slices, or None for whole-key
        routing.  Deterministic in (size, n_servers, bound) so every
        worker computes the same plan with no coordination."""
        n_srv = len(self._socks)
        if n_srv <= 1 or size < self._bigarray_bound:
            return None
        bounds = [size * i // n_srv for i in range(n_srv + 1)]
        return [(i, bounds[i], bounds[i + 1]) for i in range(n_srv)
                if bounds[i + 1] > bounds[i]]

    @staticmethod
    def _part_key(key, i):
        return "%s::part%d" % (key, i)

    def _send_np(self, cmd, k, arr_np):
        """INIT/PUSH routing: whole key by hash, or sliced across all
        servers when over the big-array bound."""
        plan = self._shard_plan(arr_np.size)
        if plan is None:
            self._rpc(cmd, k, arr_np)
            return
        flat = arr_np.ravel()
        for i, s, e in plan:
            self._rpc_on(i, cmd, self._part_key(k, i), flat[s:e])

    @staticmethod
    def _count_pull_bytes(n) -> None:
        """Pull-leg wire accounting — a counter of its own so the push-
        leg ``engine.wire_bytes`` tools/bandwidth.py reads is untouched;
        tools/bandwidth.py --hierarchical reads both to compare the flat
        and two-tier exchanges end to end."""
        from .. import telemetry as _telemetry
        _telemetry.registry.counter(
            "kvstore.pull_wire_bytes",
            doc="bytes received on the pull leg of the dist_async "
                "exchange (PULLQ compact tuples or full-width "
                "arrays)").inc(int(n))

    def _pull_hier(self, k):
        """Hierarchical cross-slice return leg (ISSUE 16): PULLQ ships
        the merged value per-block int8 — ~4x fewer wire bytes than the
        fp32 PULL.  The pull leg's quantization error is bounded by the
        per-block absmax scale and is NOT error-fed-back (the server
        encode is stateless), which is why this tier is opt-in
        (MX_EXCHANGE_HIERARCHICAL) for the gradient/accumulate exchange
        rather than the default pull."""
        from . import wire_codec as _wc
        gc = self._wire_gc()
        block = gc.block if gc is not None and \
            getattr(gc, "type", None) == "int8" else 256
        payload = self._rpc("PULLQ", k, int(block))
        if _wc.is_wire_payload(payload):
            scales = _np.asarray(payload[6])
            self._count_pull_bytes(len(payload[5]) + scales.nbytes)
            return _wc.decode_wire(payload)
        arr = _np.asarray(payload)          # non-float key: full width
        self._count_pull_bytes(arr.nbytes)
        return arr

    # -- as-ready hierarchical bucket exchange (ISSUE 16) -------------------
    def _hier_pool_get(self):
        """Lazy bounded thread pool for the as-ready bucket pulls; pool
        threads keep their own sockets (a dedicated connection per
        (thread, server), heartbeat-style) so concurrent bucket RPCs
        never contend on the main _lock-serialized connections."""
        if getattr(self, "_hier_pool", None) is None:
            import concurrent.futures as _fut
            import threading as _threading
            from ..base import get_env
            n = max(1, get_env("MX_EXCHANGE_PARALLEL", 4, int) or 4)
            self._hier_pool = _fut.ThreadPoolExecutor(
                max_workers=n, thread_name_prefix="mx-kv-exchange")
            self._hier_tls = _threading.local()
        return self._hier_pool

    def _rpc_dedicated(self, idx, msg):
        """One SEQ-enveloped RPC on this pool thread's OWN connection to
        server ``idx``, retried under the same RetryPolicy as the main
        path.  The envelope's client id carries a per-thread suffix —
        the rank prefix (liveness) is preserved, but each thread gets
        its own replay slot, so concurrent in-flight sequence numbers
        can never clobber one another's exactly-once entry."""
        import socket as _socket
        import threading as _threading
        tls = self._hier_tls
        if not hasattr(tls, "socks"):
            tls.socks = {}
        cid = "%s#x%d" % (self._client_id, _threading.get_ident())
        seq = self._next_seq()
        wrapped = ("SEQ", cid, seq, msg)
        timeout = self._recv_timeout(msg[0])
        policy = self._retry_policy()
        for _attempt in policy:
            sock = tls.socks.get(idx)
            try:
                if sock is None:
                    host, port = self._addrs[idx].rsplit(":", 1)
                    sock = _socket.create_connection(
                        (host, int(port)), timeout=5)
                    sock.settimeout(120)
                    tls.socks[idx] = sock
                self._srv_mod.send_msg(sock, wrapped)
                ok, payload = self._srv_mod.recv_msg(sock, timeout=timeout)
            except (ConnectionError, OSError, TimeoutError) as e:
                if sock is not None:
                    try:
                        sock.close()
                    except OSError:
                        pass
                tls.socks[idx] = None
                policy.note(e)
                continue
            if not ok:
                raise MXNetError("dist_async server %d: %s"
                                 % (idx, payload))
            return payload
        raise MXNetError(
            "dist_async server %d (%s) unreachable: %r retried for %.3gs "
            "(MX_KVSTORE_RETRY_DEADLINE exceeded); last error: %s"
            % (idx, self._addrs[idx], msg[0], policy.deadline,
               policy.last_error))

    def _hier_bucket_pull(self, name):
        """One bucket's cross-slice return leg on a pool thread: PULLQ
        (int8, ~4x fewer wire bytes), decoded host-side."""
        from . import wire_codec as _wc
        gc = self._wire_gc()
        block = gc.block if gc is not None and \
            getattr(gc, "type", None) == "int8" else 256
        payload = self._rpc_dedicated(self._server_of(name),
                                      ("PULLQ", name, int(block)))
        if _wc.is_wire_payload(payload):
            scales = _np.asarray(payload[6])
            self._count_pull_bytes(len(payload[5]) + scales.nbytes)
            return _wc.decode_wire(payload)
        arr = _np.asarray(payload)
        self._count_pull_bytes(arr.nbytes)
        return arr

    def _pull_np(self, k, shape, size):
        import numpy as _onp
        plan = self._shard_plan(size)
        if plan is None:
            if self._hier:
                return self._pull_hier(k)
            arr = self._rpc("PULL", k)
            self._count_pull_bytes(_np.asarray(arr).nbytes)
            return arr
        # pipeline: issue every part request on its own socket FIRST,
        # then collect replies — wall-clock ~max(parts), not sum(parts)
        # (the concurrency is the point of big-array sharding).  PULL is
        # idempotent, so a failed round simply re-issues every part with
        # fresh seqs under the retry policy.
        from .. import telemetry as _telemetry
        policy = self._retry_policy()
        timeout = self._recv_timeout("PULL")
        with _telemetry.rpc_span("kv.client.PULL_SHARDED") as span:
            tctx = span.wire_context()
            for _attempt in policy:
                try:
                    with self._lock:
                        for i, _s, _e in plan:
                            sock = self._ensure_sock(i)
                            self._fault.fire(
                                "kvstore.send",
                                on_close=lambda i=i: self._kill_sock(i))
                            inner = ("PULL", self._part_key(k, i))
                            env = ("SEQ", self._client_id,
                                   self._next_seq(), inner)
                            self._srv_mod.send_msg(
                                sock, env if tctx is None
                                else env + (tctx,))
                        parts = []
                        bad = None
                        for i, _s, _e in plan:
                            # drain EVERY pending reply even after a
                            # failure: an unread response left buffered
                            # would be misread as the next RPC's answer
                            # (desync)
                            ok, payload = self._srv_mod.recv_msg(
                                self._socks[i], timeout=timeout)
                            if not ok and bad is None:
                                bad = (i, payload)
                            parts.append(payload)
                        if bad is not None:
                            raise MXNetError(
                                "dist_async server %d: %s" % bad)
                    return _onp.concatenate(
                        [_onp.asarray(p).ravel()
                         for p in parts]).reshape(shape)
                except (ConnectionError, OSError, TimeoutError) as e:
                    for i, _s, _e in plan:
                        self._kill_sock(i)
                    policy.note(e)
                    self._note_retry(span, -1, -1, e)
        raise MXNetError(
            "dist_async sharded pull of %r failed for %.3gs "
            "(MX_KVSTORE_RETRY_DEADLINE); last error: %s"
            % (k, policy.deadline, policy.last_error))

    def _rpc_on(self, idx, *msg):
        """One RPC with transparent recovery: on a dropped/ timed-out
        connection, reconnect and REPLAY the same (client_id, seq)
        envelope — the server's replay cache makes the retry idempotent
        (a PUSH applied before the reply was lost is answered from cache,
        never re-applied).  Gives up loudly after the retry deadline.

        Distributed tracing (ISSUE 8): the RPC runs under a client span
        whose (trace_id, span_id) ride the SEQ envelope, so the server's
        handler span becomes this span's child — one causally linked
        trace across the socket; each retry is an instant child event."""
        from .. import telemetry as _telemetry
        seq = self._next_seq()
        timeout = self._recv_timeout(msg[0])
        policy = self._retry_policy()
        if msg[0] == "STOP":
            # shutdown is best-effort: don't spend the full recovery
            # deadline on a server that is already gone
            policy.deadline = min(policy.deadline, 5.0)
        with _telemetry.rpc_span("kv.client.%s" % msg[0]) as span:
            tctx = span.wire_context()
            wrapped = ("SEQ", self._client_id, seq, msg) if tctx is None \
                else ("SEQ", self._client_id, seq, msg, tctx)
            for _attempt in policy:
                with self._lock:
                    try:
                        sock = self._ensure_sock(idx)
                        self._fault.fire(
                            "kvstore.send",
                            on_close=lambda: self._kill_sock(idx))
                        self._srv_mod.send_msg(sock, wrapped)
                        self._fault.fire(
                            "kvstore.recv",
                            on_close=lambda: self._kill_sock(idx))
                        ok, payload = self._srv_mod.recv_msg(
                            sock, timeout=timeout)
                    except (ConnectionError, OSError, TimeoutError) as e:
                        self._kill_sock(idx)
                        policy.note(e)
                        self._note_retry(span, idx, seq, e)
                        continue
                if not ok:
                    raise MXNetError("dist_async server %d: %s"
                                     % (idx, payload))
                return payload
        raise MXNetError(
            "dist_async server %d (%s) unreachable: %r retried for %.3gs "
            "(MX_KVSTORE_RETRY_DEADLINE exceeded); last error: %s"
            % (idx, self._addrs[idx], msg[0], policy.deadline,
               policy.last_error))

    @staticmethod
    def _note_retry(span, idx, seq, err) -> None:
        """Account one reconnect-and-replay: registry counter (rides the
        flight-recorder step records) + an instant child event on the
        RPC span (rides the merged chrome trace)."""
        from .. import telemetry as _telemetry
        _telemetry.registry.counter(
            "kvstore.client_retries",
            doc="dist_async RPC reconnect-and-replay attempts").inc()
        span.event("retry", server=idx, seq=seq, error=str(err))

    def _rpc(self, *msg):
        """Route by key for data commands; controller commands go wider
        (SET_OPT to every server, BARRIER to server 0)."""
        cmd = msg[0]
        if cmd in ("INIT", "PUSH", "PULL", "PULLQ"):
            return self._rpc_on(self._server_of(msg[1]), *msg)
        if cmd in ("SET_OPT", "STOP", "JOIN", "LEAVE"):
            # controller fan-out: every server installs the optimizer /
            # shuts down / applies the membership change (the barrier
            # quorum lives on server 0, but each shard server sizes its
            # own liveness table too; a STOP or LEAVE reaching only
            # server 0 would leak the rest)
            out = None
            for i in range(len(self._socks)):
                try:
                    out = self._rpc_on(i, *msg)
                except MXNetError:
                    if cmd not in ("STOP", "LEAVE"):
                        # STOP/LEAVE are best-effort per server: on the
                        # way OUT, a server that is already gone is fine
                        raise
            return out
        return self._rpc_on(0, *msg)        # BARRIER, MEMBERS

    # -- elastic membership (ISSUE 16) --------------------------------------
    def join(self):
        """Announce this worker's rank to every server's live membership
        table.  Idempotent: a rank the server already counts is a no-op
        (no epoch bump), so fixed-size jobs can send it unconditionally.
        Returns ``(epoch, members)`` as the last server reported."""
        payload = self._rpc("JOIN", self._client_id)
        epoch, members = payload
        self._membership_epoch = max(self._membership_epoch, int(epoch))
        return int(epoch), list(members)

    def leave(self):
        """Voluntarily retire this worker's rank from the quorum (the
        preemption-drain path: the supervisor's SIGTERM gives notice, the
        fit loop checkpoints at the epoch boundary, then leaves).  Best-
        effort per server — on the way out a dead server is fine."""
        payload = self._rpc("LEAVE", self._client_id)
        if payload is not None:
            self._membership_epoch = max(self._membership_epoch,
                                         int(payload[0]))
        return payload

    def members(self):
        """``(epoch, [ranks])`` of server 0's live membership table (the
        barrier quorum lives there, same as BARRIER routing)."""
        epoch, members = self._rpc("MEMBERS")
        return int(epoch), list(members)

    @property
    def membership_epoch(self) -> int:
        """The membership epoch this store incarnation is salted under
        (MX_ELASTIC_EPOCH at init, raised by observed JOIN replies)."""
        return self._membership_epoch

    def metrics(self, fmt: str = "json"):
        """Per-server telemetry scrape over the METRICS wire verb
        (ISSUE 12): returns one decoded exposition per server —
        ``fmt='json'`` a registry-snapshot dict, ``'prometheus'`` the
        text exposition.  Read-only and idempotent; this is the same
        surface the fleet collector (mxnet_tpu/fleet.py) scrapes."""
        import json as _json
        from .wire_codec import decode_text
        out = []
        for i in range(len(self._socks)):
            payload = self._rpc_on(i, "METRICS", fmt)
            text = decode_text(payload)
            out.append(_json.loads(text) if fmt == "json" else text)
        return out

    def init(self, key, value):
        keys, values = self._normalize(key, value)
        for k, v in zip(keys, values):
            vv = v[0] if isinstance(v, (list, tuple)) else v
            self._send_np("INIT", k, vv.asnumpy())
            self._store[k] = vv.copy()       # local mirror for shape/dtype

    def _buckets_active(self, keys):
        """Bucketing is a pure-gradient-exchange optimization: with a
        server-side optimizer installed the server must see each key
        individually (per-key lr/wd/state), so buckets are off."""
        return len(keys) > 1 and self._optimizer is None and \
            self._updater is None

    def begin_exchange(self, keys, vlists):
        """No overlap on the PS store: its RPCs are host-blocking socket
        roundtrips — launching them mid-backward would serialize backward
        behind the wire instead of hiding it.  The Trainer falls back to
        the batched push/pull."""
        return None

    def build_exchange_body(self, keys, arrays, layout=None):
        """Untraceable: the exchange crosses a TCP socket mid-step (the
        server applies pushes the moment they arrive), so there is no
        pure function of the local gradients to inline — the compiled
        step lane (MX_STEP_COMPILE) falls back to the eager pipeline on
        this transport."""
        return None

    def _wire_gc(self):
        """The compact-wire compressor, when one is installed (2bit/int8;
        bf16 is a collective-path cast with no numpy dtype, so the PS
        wire ships it full-width)."""
        return getattr(self, "_gc", None)

    def _push_payload(self, wire_key, nd_value):
        """One PUSH: compressed wire tuple (payload + scales + dtype tag,
        dequantized server-side) or the full-width numpy array.

        Keys over the big-array bound are NOT compressed: INIT slices
        them across every server (``key::partN`` pieces), so a compact
        whole-key PUSH would target a server that only holds parts and
        fail 'not initialized' — they take the sharded full-width path
        instead (the bound already marks them as bandwidth-amortized)."""
        from ..engine import engine as _engine
        gc = self._wire_gc()
        if gc is not None and isinstance(nd_value, NDArray) and \
                jnp.issubdtype(nd_value._jax.dtype, jnp.floating) and \
                self._shard_plan(int(nd_value.size)) is None:
            wire = gc.encode(wire_key, nd_value._jax)
            _engine.count_wire_bytes(gc.wire_nbytes(nd_value.size))
            self._rpc("PUSH", wire_key, wire)
            return
        arr = nd_value.asnumpy()
        _engine.count_wire_bytes(arr.nbytes)
        self._send_np("PUSH", wire_key, arr)

    def push(self, key, value, priority=0):
        keys, values = self._normalize(key, value)
        vlists = [v if isinstance(v, (list, tuple)) else [v] for v in values]
        # local device merge only — wire compression (error feedback,
        # residual per wire key) happens at _push_payload, so the payload
        # is quantized exactly once
        merged = [self._reduce_local(v) if self._wire_gc() is not None
                  else self._reduce(v, key=k)
                  for k, v in zip(keys, vlists)]
        buckets = []
        solo = range(len(keys))
        if self._buckets_active(keys):
            # plan from the NDArrays, not densified numpy: the signature
            # must keep stype so the paired pull (planned from same-stype
            # targets) derives the identical layout
            buckets, solo = self._bucket_plans(keys, merged)
        for b in buckets:
            # concatenate ON DEVICE, then ONE host transfer per bucket —
            # a per-key asnumpy loop would reintroduce O(#keys) syncs
            flat = jnp.concatenate(
                [merged[p]._jax.reshape(-1) for p in b.positions])
            if b.name not in self._bucket_inited:
                # zero-init so the server's accumulator contract (pull =
                # init + sum of pushes) returns exactly the pushed sums
                self._send_np("INIT", b.name,
                              _np.zeros((b.total,),
                                        _np.dtype(str(flat.dtype))))
                self._bucket_inited.add(b.name)
            # one wire op per bucket; the SEQ-tagged retry layer now
            # replays buckets, not keys
            self._push_payload(b.name, NDArray(flat))
        for p in solo:
            self._push_payload(keys[p], merged[p])

    @staticmethod
    def _commit_bucket(b, flat, target_lists):
        """Scatter one pulled bucket to its member targets, homing each
        piece on the TARGET's device — a default-ctx array labeled with
        t's context would feed mixed-device operands into later jits."""
        flat = _np.asarray(flat).ravel()
        for p, off, size, shape in b.slices():
            piece = flat[off:off + size].reshape(shape)
            for t in target_lists[p]:
                t._set_jax(nd.array(piece, ctx=t.context)
                           .astype(t.dtype)._jax)

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        keys, outs = self._normalize(key, out)
        target_lists = [o if isinstance(o, (list, tuple)) else [o]
                        for o in outs]
        firsts = [ts[0] for ts in target_lists]
        buckets = []
        solo = range(len(keys))
        if self._buckets_active(keys):
            # same signature as the paired push (grads pull into same-stype,
            # same-shaped buffers), so the derived layout agrees — even for
            # a worker that never pushed itself (bucket names are a pure
            # function of the signature)
            buckets, solo = self._bucket_plans(keys, firsts)
        solo = list(solo)
        if self._hier and len(buckets) > 1:
            # as-ready cross-slice tier (ISSUE 16): every bucket's PULLQ
            # flies concurrently on its own connection and COMMITS the
            # moment its reply lands — a straggling server shard (or
            # slice behind it) delays only its own buckets, never the
            # whole pull.  Commits happen on THIS thread (the
            # as_completed loop), so target mutation stays single-
            # threaded.
            import concurrent.futures as _fut
            ex = self._hier_pool_get()
            futs = {ex.submit(self._hier_bucket_pull, b.name): b
                    for b in buckets}
            for f in _fut.as_completed(futs):
                b = futs[f]
                try:
                    flat = f.result()
                except MXNetError:
                    solo.extend(b.positions)
                    continue
                self._commit_bucket(b, flat, target_lists)
        else:
            for b in buckets:
                try:
                    flat = self._pull_np(b.name, (b.total,), b.total)
                except MXNetError:
                    # bucket absent server-side (nothing pushed this
                    # layout yet — e.g. pulling broadcast weights):
                    # per-key fallback for exactly this bucket's
                    # members, never silent staleness
                    solo.extend(b.positions)
                    continue
                self._commit_bucket(b, flat, target_lists)
        for p in sorted(solo):
            arr = self._pull_np(keys[p], firsts[p].shape,
                                int(firsts[p].size))
            for t in target_lists[p]:
                t._set_jax(nd.array(arr, ctx=t.context)
                           .astype(t.dtype)._jax)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Pull CURRENT server rows (the base implementation reads the
        local init-time mirror, which a server-side optimizer has long
        moved past)."""
        keys, outs = self._normalize(key, out)
        for k in keys:
            mirror = self._store.get(k)
            if mirror is not None:           # init populated shape/dtype
                arr = self._pull_np(k, mirror.shape, int(mirror.size))
            else:
                # key init'd by another worker only: whole-key pull (a
                # big SHARDED key still needs a local init for its shape)
                arr = self._rpc("PULL", k)
            self._store[k] = nd.array(arr)     # refresh mirror, then gather
        return super().row_sparse_pull(key, out=out, priority=priority,
                                       row_ids=row_ids)

    def set_optimizer(self, optimizer):
        """Ship the optimizer to the server (reference: the pickled
        set_optimizer controller message).  The server keeps the FIRST
        installation (state preservation); the trailing barrier guarantees
        no worker pushes before the optimizer is installed."""
        self._rpc("SET_OPT", pickle.dumps(optimizer))
        self._optimizer = optimizer
        # updates happen server-side: no local updater
        self._updater = None
        if self._size > 1:
            self._barrier()

    def _barrier(self):
        self._rpc("BARRIER", None)

    def stop_server(self):
        try:
            self._rpc("STOP", None)
        except MXNetError:
            pass
        self.close()

    def close(self):
        """Stop the heartbeat thread and drop every connection.  (A
        voluntary departure calls :meth:`leave` FIRST — close alone
        keeps the rank in the quorum, which is what a worker that will
        be respawned under the same rank wants.)"""
        self._hb_stop.set()
        if self._hb_thread is not None:
            self._hb_thread.join(timeout=2)
            self._hb_thread = None
        if getattr(self, "_hier_pool", None) is not None:
            self._hier_pool.shutdown(wait=False)
            self._hier_pool = None
        with self._lock:
            for i in range(len(self._socks)):
                self._kill_sock(i)


_STORES = {
    "local": KVStoreLocal,
    "device": KVStoreDevice,
    "ici": KVStoreICI,
    # collective path covers these transports on TPU:
    "nccl": KVStoreICI,
    "dist": KVStoreICI,
    "dist_sync": KVStoreICI,
    "dist_device_sync": KVStoreICI,
    "dist_async": KVStoreDistAsync,
    "horovod": KVStoreICI,
}


def create(name: str = "local") -> KVStore:
    """Reference: kvstore.create / KVStore::Create."""
    import os
    if not isinstance(name, str):
        raise TypeError("name must be a string")
    key = name.lower()
    if key == "dist_async" and _ps_addr() is None:
        # no PS in the deployment: degrade to the sync collective store
        # with a loud note, like the reference refuses to start without
        # a tracker (here multi-process jobs still work, just synchronously)
        import warnings
        warnings.warn("kvstore 'dist_async' requested without a parameter "
                      "server (launch with tools/launch.py -s <servers>); "
                      "using the synchronous collective store instead")
        return KVStoreICI()
    if key not in _STORES:
        raise MXNetError("unknown KVStore type %r (have %s)"
                         % (name, sorted(_STORES)))
    return _STORES[key]()


# ---------------------------------------------------------------------------
# Program contracts (ISSUE 11): the gradient-exchange bodies' declared
# donation/HBM invariants.  The exchange bodies normally inline into
# the compiled step's single program; contracting them STANDALONE keeps
# the proof per-transport — the int8/2bit error-feedback residuals are
# the donated state, and the verifier shows each survives as an output
# alias under every compression mode before any TPU sees the job.
# Builders run only inside `python -m tools.mxlint --contracts`.
# ---------------------------------------------------------------------------

def _exchange_contract_cases():
    from ..programs import ContractCase, register_program
    from ..device import cpu
    cases = []
    shapes = [(96, 4), (256,)]
    for mode in ("int8", "2bit", "none"):
        kv = KVStoreLocal()
        if mode != "none":
            kv.set_gradient_compression({"type": mode})
        templates = [NDArray(jnp.zeros(s, jnp.float32), ctx=cpu())
                     for s in shapes]
        body = kv.build_exchange_body(list(range(len(shapes))), templates)
        pname = "kvstore.exchange_%s" % mode
        prog = register_program(pname, body, donate_argnums=(1,))
        grads = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
        residuals = [jax.ShapeDtypeStruct(tuple(s), jnp.dtype(dt))
                     for _wk, s, dt in body.residual_specs]
        cases.append(ContractCase(pname, (grads, residuals),
                                  label=mode, target=prog))
    return cases


def _sum_contract_cases():
    from ..programs import ContractCase
    arrs = tuple(jax.ShapeDtypeStruct((128, 8), jnp.float32)
                 for _ in range(4))
    return [ContractCase("kvstore.sum", (arrs,), label="sum4",
                         target=_sum_arrays)]


def _declare_kvstore_contracts():
    from ..programs import declare_contract
    declare_contract(
        "kvstore.exchange", _exchange_contract_cases,
        donate_argnums=(1,),
        temp_budget_bytes=1 << 20,
        description="single-worker traceable exchange bodies (int8 / "
                    "2bit / uncompressed): error-feedback residuals "
                    "donate in-place; gradients rebind to the returned "
                    "merged values")
    declare_contract(
        "kvstore.sum", _sum_contract_cases,
        donate_argnums=(),
        temp_budget_bytes=1 << 20,
        description="per-key eager reduction body (light census mode): "
                    "no donations — the summands are live parameter "
                    "gradients owned by their devices")


_declare_kvstore_contracts()
