"""Whole-program compiled training step (ISSUE 7 tentpole).

Reference: MXNet's defining trick is ``hybridize()`` — run eager, then
cache the whole graph as one CachedOp (src/imperative/cached_op.cc).  The
Julia→TPU full-program compilation work (arxiv 1810.09868) and TF1-style
graph execution (arxiv 1605.08695) make the same argument for the
*training loop*: compile the whole step, not kernels.  PR 3 made the
eager Gluon step O(1) dispatches; this module collapses those remaining
~dozen programs — loss forward, backward, the bucketed (int8/2bit
error-feedback quantized) gradient exchange, the fused multi-tensor
optimizer apply and device-side metric accumulation — into **one donated
``jax.jit``** per step, with a ``lax.scan`` multi-step window
(``MX_STEP_SCAN=N``) that keeps N prefetched batches on device per host
round-trip and folds gradient accumulation into the scanned body.

Semantics mirror hybridize: the first call traces, a shape/dtype change
retraces (the cache key is the input/param avals), ``invalidate()`` is
the ``_clear_cached_op`` equivalent, and parameter values are *read
fresh and written back every dispatch* — external mutation (checkpoint
restore, manual ``set_data``) between steps is picked up automatically
because the NDArray chunks, not device-side captures, remain the source
of truth.  lr/wd (and Adam-family bias correction) arrive as traced
scalars computed on host per step, so LR schedulers never retrigger
compilation.

Eager remains the debug path: configurations the trace cannot express —
the PS/dist_async transport (its exchange crosses a socket mid-step),
multi-process collectives (the SPMD mesh lane ``parallel.TrainStep``
owns those), optimizers without a pure tree kernel, ``grad_req='add'``,
sparse gradients — fall back to the eager pipeline with a one-time
warning, and :meth:`CompiledStep.step` keeps working either way.

State continuity: optimizer slot state lives in the Trainer's Updater
``states`` (donated in, written back out each dispatch), and
error-feedback residuals live in the kvstore's GradientCompression store
— so ``Trainer.save_states``/checkpoint sidecars round-trip the donated
state, and switching compiled↔eager mid-training continues the exact
trajectory.
"""
from __future__ import annotations

import logging
import warnings
from typing import Dict, List, Optional

import numpy as _np
import jax
import jax.numpy as jnp
from jax import lax

from .base import MXNetError, get_env
from .device import cpu
from .ndarray.ndarray import NDArray
from . import autograd
from . import profiler as _profiler
from . import telemetry as _telemetry
from .ops import random as _ops_random
from .ops.attention import attention_partition_scope
from .ops.optimizer import tree_body
from .gluon.block import _flatten_nds
from .gluon.parameter import (DeferredInitializationError,
                              _ParamOverrideScope)

__all__ = ["CompiledStep", "scan_window", "step_compile_enabled",
           "metric_trace_kernel"]

logger = logging.getLogger("mxnet_tpu.step")


def step_compile_enabled() -> bool:
    """MX_STEP_COMPILE=1 — the whole-step-compiled lane is on."""
    return bool(get_env("MX_STEP_COMPILE", dtype=bool))


def scan_window() -> int:
    """MX_STEP_SCAN window size (N batches per dispatch); 0/1 = per-step."""
    try:
        n = int(get_env("MX_STEP_SCAN", 0, int) or 0)
    except (TypeError, ValueError):
        n = 0
    return max(n, 0)


def metric_trace_kernel(metric):
    """(kernel, argspec) folding `metric` into a whole-step jit, or None
    (caller accumulates eagerly from the returned outputs instead).
    argspec names the kernel's operand order: 'pred_label', 'label_pred'
    or 'loss' (see EvalMetric._trace_kernel)."""
    if metric is None:
        return None
    get = getattr(metric, "_trace_kernel", None)
    return get() if get is not None else None


def metric_cache_key(metric, metric_info):
    """Trace-identity of a folded metric: class + argspec + the
    kernel-affecting config (axis/eps/ignore_label/...), so two
    same-class metrics with different hyperparameters never share a
    cached executable."""
    if metric_info is None:
        return None
    cfg = tuple(sorted((k, repr(v)) for k, v in
                       getattr(metric, "_kwargs", {}).items()))
    return (type(metric).__name__, metric_info[1], cfg)


def _as_jax(x):
    return x._jax if isinstance(x, NDArray) else jnp.asarray(x)


def _as_nd(x, ctx):
    return x if isinstance(x, NDArray) else NDArray(jnp.asarray(x), ctx=ctx)


def _placed(a, sharding):
    """Place `a` onto `sharding` iff it is not already there (sharded
    lane steady state: an equality check, no transfer)."""
    if a is None or sharding is None:
        return a
    if getattr(a, "sharding", None) == sharding:
        return a
    return jax.device_put(a, sharding)


class CompiledStep:
    """One Gluon training step as a single donated XLA program.

    Built over a live ``gluon.Trainer`` — its parameters, optimizer,
    kvstore (exchange + compression) and updater state are the state the
    compiled program donates and writes back, so eager and compiled
    steps are interchangeable mid-run.

    ``step(data, label)`` is the hybridize-style drop-in for the eager
    record/backward/Trainer.step/metric sequence; ``run_window(data,
    label, accum=k)`` executes a stacked window of micro-batches under
    one ``lax.scan`` dispatch with gradient accumulation folded in.
    """

    def __init__(self, net, loss_fn, trainer, metric=None, layout=None):
        self._net = net
        self._loss_fn = loss_fn
        self._trainer = trainer
        self._metric = metric
        # sharded lane (ISSUE 14): a parallel.SpecLayout turns this step
        # into an SPMD program over the layout's mesh — parameters and
        # optimizer state live sheet-sharded (fsdp) / tensor-split (tp),
        # the batch splits over data×fsdp, gradients reduce-scatter onto
        # the parameter shards and XLA all-gathers updated params just
        # in time inside the SAME one donated jit.  None (+ unset env
        # knobs) keeps the replicated behavior bit-identical.
        if layout is None:
            from .parallel.speclayout import layout_from_env
            layout = layout_from_env()
        self._layout = layout
        self._shard_kv = None   # lazily built exchange store (sharded lane)
        self._cache: Dict = {}
        self._fallback_reason: Optional[str] = None
        self._warned = False
        # donation safety: ONLY buffers this step produced itself (last
        # dispatch's outputs) are donated as-is — a foreign array may be
        # aliased elsewhere (kvstore init/broadcast slots share the
        # initial param buffers; set_data/as_in_context alias on same
        # device+dtype), and donating it would delete every alias's
        # view.  Foreign inputs are copied once before donation; the
        # refs list pins the owned arrays so ids cannot be reused.
        self._owned: set = set()
        self._owned_refs: List = []
        # plan cache: the trace-static view of the trainer (exchange
        # body, bucket specs, mp grouping, slot-state layout) is rebuilt
        # only when its cheap signature changes — not O(n_params) of
        # Python per dispatch on the host hot path
        self._plan_cached = None
        self._plan_sig = None
        self._announced = False     # the first dispatch's INFO line

    # -- cache control (hybridize semantics) -------------------------------
    @property
    def compiled(self) -> bool:
        return self._fallback_reason is None

    @property
    def fallback_reason(self) -> Optional[str]:
        return self._fallback_reason

    def invalidate(self) -> None:
        """Drop every cached executable (the `_clear_cached_op` of this
        lane) — the next call retraces from the current configuration."""
        self._cache.clear()
        self._plan_cached = None
        self._plan_sig = None

    def _fall(self, reason: str):
        self._fallback_reason = reason
        if not self._warned:
            self._warned = True
            warnings.warn("CompiledStep: falling back to the eager "
                          "pipeline (%s)" % reason, stacklevel=3)
        return None

    # -- plan: the trace-static view of the trainer ------------------------
    def _plan_signature(self):
        """What can change the plan between steps: kvstore identity and
        compression config, bucket capacity, grad_req flips, context
        set.  Cheap attribute reads only — checked every dispatch."""
        tr = self._trainer
        # sharded lane: materialize the lazily-created exchange store
        # BEFORE keying on it, or the signature flips between step 1
        # (id(None)) and step 2 (id(store)) and forces a full plan
        # rebuild on the second dispatch
        kv = tr._kvstore if self._layout is None else \
            (tr._kvstore or self._ensure_shard_kv())
        gc = getattr(kv, "_gc", None) if kv is not None else None
        from .kvstore.bucketing import bucket_bytes
        opt = tr._optimizer
        return (id(kv), tr._update_on_kvstore, id(opt),
                None if self._layout is None
                else self._layout.signature(),
                tuple(p._grad_req for p in tr._params),
                tuple(id(c) for c in (tr._contexts or ())),
                None if gc is None
                else (gc.type, gc.block, gc.threshold),
                getattr(kv, "_compress_bf16", False) if kv else False,
                bucket_bytes(),
                # trace-static optimizer hyperparams (the supported
                # kinds'): a mid-run mutation must rebuild spec statics
                opt.clip_gradient, getattr(opt, "momentum", None),
                getattr(opt, "beta1", None), getattr(opt, "beta2", None),
                getattr(opt, "epsilon", None),
                getattr(opt, "correct_bias", None))

    def _plan(self):
        if self._fallback_reason is not None:
            return None
        tr = self._trainer
        if not tr._kv_initialized:
            tr._init_kvstore()
        if tr._params_to_init:
            tr._init_params()
        sig = self._plan_signature()
        if self._plan_cached is not None and sig == self._plan_sig:
            return self._plan_cached
        if tr._update_on_kvstore:
            return self._fall("server-side optimizer (update_on_kvstore)")
        opt = tr._optimizer
        spec = opt._compiled_spec()
        if spec is None:
            return self._fall("optimizer %s has no pure tree kernel"
                              % type(opt).__name__)
        kv = tr._kvstore
        if kv is not None and kv.num_workers > 1:
            return self._fall("multi-process exchange needs the SPMD mesh "
                              "lane (parallel.TrainStep)")
        trainable_idx, frozen_params = [], []
        for i, p in enumerate(tr._params):
            if p._data is None:
                raise DeferredInitializationError(
                    "Parameter %s is not initialized yet" % p.name)
            if p.grad_req == "add":
                return self._fall("grad_req='add' (use run_window(accum=k) "
                                  "for compiled gradient accumulation)")
            if p.grad_req == "null":
                frozen_params.append(p)
            elif p._grad_stype == "row_sparse":
                return self._fall("row_sparse gradients take the per-key "
                                  "gather/scatter path")
            else:
                trainable_idx.append(i)
        ctxs = tr._contexts
        trainable = [tr._params[i] for i in trainable_idx]
        layout = self._layout
        shardings = frozen_shardings = compute_shardings = None
        if layout is not None:
            if len(ctxs) > 1:
                return self._fall(
                    "SpecLayout sharded lane is SPMD over the mesh — "
                    "use ONE Trainer context (the mesh owns the devices)")
            # per-parameter placement: rules > Block.sharding_spec hook >
            # kind defaults > fsdp sheet (speclayout resolution order)
            specs = layout.resolve(self._net)
            by_id = {}
            for name, p in self._net.collect_params().items():
                by_id[id(p)] = specs.get(name)

            def _spec_of(p):
                sp = by_id.get(id(p))
                if sp is None:
                    sp = layout.param_spec(p.name, tuple(p.shape), p.dtype)
                return sp

            shardings = tuple(layout.sharding(_spec_of(p))
                              for p in trainable)
            compute_shardings = tuple(
                layout.sharding(layout.compute_spec(_spec_of(p)))
                for p in trainable)
            # frozen/aux state (BatchNorm stats) mutates inside forward
            # on every chip's shard of the batch: replicate it
            frozen_shardings = tuple(layout.replicated()
                                     for _ in frozen_params)
            # adopt: the parameter (and dormant grad) buffers live
            # SHARDED from here — the NDArray chunks stay the source of
            # truth, but their jax value is the global mesh array, so
            # per-chip HBM drops with the fsdp axis and steady-state
            # gathers are no-ops
            from .parallel.speclayout import place_value as _place
            for p, s in list(zip(trainable, shardings)) + \
                    list(zip(frozen_params, frozen_shardings)):
                nd_ = p._data[ctxs[0]]
                nd_._set_jax(_place(nd_._jax, s))
                g_nd = nd_._grad       # None: not allocated yet (lazy)
                if g_nd is not None:
                    g_nd._set_jax(_place(g_nd._jax, s))
        exchange = None
        if kv is not None and len(ctxs) > 1:
            # the eager exchange set: every trainable param crosses the
            # store when there is more than one device copy to merge
            exchange = kv.build_exchange_body(
                trainable_idx, [p.data(ctxs[0]) for p in trainable])
            if exchange is None:
                return self._fall("kvstore %r exchange is not traceable "
                                  "(host-blocking transport)" % kv.type)
        elif layout is not None:
            # sharded quantized wire: the trainer's compression config
            # rides a process-local exchange store (single-context
            # trainers never build one of their own) whose body becomes
            # the reduce-scatter/all-gather variant
            kvx = self._ensure_shard_kv()
            if kvx is not None:
                exchange = kvx.build_exchange_body(
                    trainable_idx, [p.data(ctxs[0]) for p in trainable],
                    layout=layout)
                if exchange is None:
                    return self._fall(
                        "kvstore %r exchange is not traceable under the "
                        "sharded lane" % kvx.type)
        # optimizer slot state, created through the SAME updater store the
        # eager path uses (and every save_states/checkpoint reads)
        mp_flags = []
        with _telemetry.phase("initialize"):
            for d, upd in enumerate(tr._updaters):
                for pos, i in enumerate(trainable_idx):
                    w = trainable[pos].data(ctxs[d])
                    if i not in upd.states:
                        upd.states[i] = \
                            upd.optimizer.create_state_multi_precision(i, w)
                        upd.states_synced[i] = True
                    if d == 0:
                        mp_flags.append(
                            bool(opt._is_mp_state(w, upd.states[i])))
        groups: Dict[bool, List[int]] = {}
        for pos, mp in enumerate(mp_flags):
            groups.setdefault(mp, []).append(pos)
        state_shardings = w32_shardings = residual_shardings = None
        if layout is not None:
            # ZeRO: optimizer state lives on its parameter's shards from
            # init — re-place the (just-created) slot NDArrays so the
            # sharded layout IS the stored state, not a per-dispatch copy
            from .parallel.speclayout import place_value as _place
            upd0 = tr._updaters[0]
            state_shardings, w32_shardings = [], []
            for pos, i in enumerate(trainable_idx):
                p = trainable[pos]
                pspec = _spec_of(p)
                inner, w32 = spec["unpack"](upd0.states[i], mp_flags[pos])
                cols = []
                for s_nd in inner:
                    ssh = layout.sharding(
                        layout.state_spec(pspec, tuple(s_nd.shape)))
                    s_nd._set_jax(_place(s_nd._jax, ssh))
                    cols.append(ssh)
                state_shardings.append(tuple(cols))
                if w32 is not None:
                    wsh = layout.sharding(
                        layout.state_spec(pspec, tuple(w32.shape)))
                    w32._set_jax(_place(w32._jax, wsh))
                    w32_shardings.append(wsh)
                else:
                    w32_shardings.append(None)
            state_shardings = tuple(state_shardings)
            w32_shardings = tuple(w32_shardings)
            residual_shardings = tuple(
                sh if sh is not None else layout.replicated()
                for sh in (exchange.residual_shardings
                           if exchange is not None else ()))
        plan = {
            "spec": spec,
            "trainable_idx": trainable_idx,
            "trainable": trainable,
            "frozen": frozen_params,
            "ctxs": ctxs,
            "exchange": exchange,
            "mp_flags": tuple(mp_flags),
            "mp_groups": sorted(groups.items()),
            "clip": -1.0 if opt.clip_gradient is None
                    else float(opt.clip_gradient),
            # sharded lane (ISSUE 14): every donated state group's
            # placement, resolved once per plan
            "layout": layout,
            "shardings": shardings,
            "compute_shardings": compute_shardings,
            "frozen_shardings": frozen_shardings,
            "state_shardings": state_shardings,
            "w32_shardings": w32_shardings,
            "residual_shardings": residual_shardings,
            "replicated": None if layout is None else layout.replicated(),
            "gc": getattr(kv, "_gc", None) if layout is None
                  else getattr(self._shard_kv or kv, "_gc", None),
        }
        self._plan_cached = plan
        self._plan_sig = sig
        return plan

    def _ensure_shard_kv(self):
        """The sharded lane's exchange store: the trainer's own kvstore
        when it has one, else a lazily created process-local 'ici' store
        carrying the trainer's compression config (a single-context
        Trainer never builds a store of its own) — the error-feedback
        residual state lives there exactly like the replicated lane's
        store-resident residuals, so checkpoints and census attribution
        see one consistent owner.  None when no compression is
        configured (plain FSDP: constraint-only exchange)."""
        tr = self._trainer
        if tr._kvstore is not None:
            return tr._kvstore
        if self._shard_kv is None and tr._compression_params:
            from .kvstore import create as _kv_create
            kv = _kv_create("ici")
            kv.set_gradient_compression(tr._compression_params)
            self._shard_kv = kv
        return self._shard_kv

    # -- trace builders ----------------------------------------------------
    def _make_forward(self, plan):
        net, loss_fn = self._net, self._loss_fn
        trainable, frozen = plan["trainable"], plan["frozen"]

        def run_forward(t_vals, f_vals, rng, x_vals, y_val):
            overrides: Dict[int, NDArray] = {}
            fr_nds = []
            for p, v in zip(trainable, t_vals):
                overrides[id(p)] = NDArray(v, ctx=cpu())
            for p, v in zip(frozen, f_vals):
                nd_ = NDArray(v, ctx=cpu())
                overrides[id(p)] = nd_
                fr_nds.append(nd_)
            x_nds = [NDArray(v, ctx=cpu()) for v in x_vals]
            y_nd = NDArray(y_val, ctx=cpu())
            with _ParamOverrideScope(overrides), \
                    _ops_random.trace_key_scope(rng), \
                    autograd._Scope(False, True):
                out = net(*x_nds)
                loss = loss_fn(out, y_nd)
            out_leaves: List[NDArray] = []
            _flatten_nds(out, out_leaves)
            loss_leaves: List[NDArray] = []
            _flatten_nds(loss, loss_leaves)
            # aux state (BatchNorm running stats) mutated during forward:
            # the frozen params' fresh values ride the scan carry
            new_f = tuple(nd_._jax for nd_ in fr_nds)
            return ([l._jax for l in loss_leaves],
                    [o._jax for o in out_leaves], new_f)

        def forward_backward(t_vals, f_vals, rng, x_vals, y_val):
            def loss_of(tv):
                # backward needs no scope of its own: value_and_grad
                # names the transposed ops transpose(jvp(forward))/...
                # the flash kernels are opaque to GSPMD: they read the
                # layout here and run per shard (ops/attention.py)
                with jax.named_scope("forward"), \
                        attention_partition_scope(plan.get("layout")):
                    losses, outs, new_f = run_forward(tv, f_vals, rng,
                                                      x_vals, y_val)
                # backward() seeds a ones cotangent on the loss: the
                # gradient of the elementwise SUM is exactly that
                total = losses[0].sum()
                for extra in losses[1:]:
                    total = total + extra.sum()
                out0 = outs[0] if outs else losses[0]
                return total, (losses[0], out0, new_f)

            (_tot, aux), grads = jax.value_and_grad(
                loss_of, has_aux=True)(tuple(t_vals))
            loss0, out0, new_f = aux
            return loss0, out0, grads, new_f

        return forward_backward

    def _build_fn(self, plan, n_steps, accum, rescale, wds, decays_on,
                  metric_info, return_outs):
        spec = plan["spec"]
        body = tree_body(spec["kind"])
        statics = dict(spec["static"])
        n_state = spec["n_state"]
        mp_groups = plan["mp_groups"]
        exchange = plan["exchange"]
        clip = plan["clip"]
        shardings = plan.get("shardings")
        compute_shardings = plan.get("compute_shardings")
        forward_backward = self._make_forward(plan)

        def _traced_step_window(t_vals, f_vals, opt_states, w32s,
                                residuals, mstate, lr_rows, decay_rows,
                                rng, xs, ys):
            # NB every helper below is NESTED in this jitted function on
            # purpose: mxlint's jit-purity rule walks the jitted def's
            # own AST, so the whole step body is machine-checked for
            # host syncs / wall-clock / env reads (ISSUE 7 satellite).
            def apply_optimizer(t_vals, grads, opt_states, w32s, lr_row,
                                decay_row):
                new_t = list(t_vals)
                new_states = list(opt_states)
                new_w32 = list(w32s)
                for mp, poss in mp_groups:
                    ws = tuple(t_vals[p] for p in poss)
                    gs = tuple(grads[p] for p in poss)
                    cols = [tuple(opt_states[p][j] for p in poss)
                            for j in range(n_state)]
                    args = [ws, gs] + cols
                    args.append(tuple(w32s[p] for p in poss)
                                if mp else None)
                    args.append(lr_row[jnp.asarray(poss, jnp.int32)])
                    if decays_on:
                        args.append(decay_row[jnp.asarray(poss,
                                                          jnp.int32)])
                    out_w, out_states, out_w32 = body(
                        *args, wds=tuple(wds[p] for p in poss),
                        rescale_grad=rescale, clip_gradient=clip, mp=mp,
                        **statics)
                    for j, p in enumerate(poss):
                        new_t[p] = out_w[j]
                        if out_states is not None:
                            new_states[p] = tuple(col[j]
                                                  for col in out_states)
                        if mp and out_w32 is not None:
                            new_w32[p] = out_w32[j]
                return tuple(new_t), tuple(new_states), tuple(new_w32)

            def accumulate_metric(mstate, loss0, out0, y_mb):
                if metric_info is None or mstate is None:
                    return mstate
                kernel, order = metric_info
                msum, minst = mstate
                if order == "loss":
                    return tuple(kernel(msum, minst, loss0))
                if order == "label_pred":
                    return tuple(kernel(msum, minst, y_mb, out0))
                return tuple(kernel(msum, minst, out0, y_mb))

            def one_step(carry, inp):
                t_vals, f_vals, opt_states, w32s, residuals, mstate = carry
                lr_row, decay_row, rngs, x_row, y_row = inp
                # FSDP just-in-time all-gather (ISSUE 14): parameters are
                # STORED sheet-sharded over fsdp but COMPUTE whole (tp
                # splits stay); constraining to the compute spec here
                # makes XLA emit the gather right before the forward —
                # and re-emit it inside every scan iteration, so a
                # window never holds gathered copies across steps
                t_use = t_vals if compute_shardings is None else tuple(
                    lax.with_sharding_constraint(v, s)
                    for v, s in zip(t_vals, compute_shardings))

                def micro(mcarry, minp):
                    f_v, g_acc, mst = mcarry
                    key, x_mb, y_mb = minp
                    loss0, out0, grads, new_f = forward_backward(
                        t_use, f_v, key, x_mb, y_mb)
                    with jax.named_scope("metric"):
                        mst = accumulate_metric(mst, loss0, out0, y_mb)
                    g_acc = tuple(a + g for a, g in zip(g_acc, grads))
                    return (new_f, g_acc, mst), (loss0, out0)

                init = (f_vals,
                        tuple(jnp.zeros(v.shape, v.dtype)
                              for v in t_vals),
                        mstate)
                if accum == 1:
                    mcarry, (loss0, out0) = micro(
                        init, (rngs[0], tuple(x[0] for x in x_row),
                               y_row[0]))
                    losses = loss0[None]
                    outs = out0[None]
                else:
                    mcarry, (losses, outs) = lax.scan(
                        micro, init, (rngs, x_row, y_row))
                f_vals, g_sum, mstate = mcarry
                with jax.named_scope("exchange"):
                    if shardings is not None:
                        # the reduce-scatter point (ISSUE 14): the
                        # gradient sum over the data×fsdp-sharded batch
                        # lands directly on each parameter's shards —
                        # GSPMD fuses the cross-chip sum and the scatter
                        # into one collective, and the updated params
                        # all-gather just in time at the next forward's
                        # use sites
                        g_sum = tuple(lax.with_sharding_constraint(g, s)
                                      for g, s in zip(g_sum, shardings))
                    if exchange is not None:
                        new_g, new_res = exchange(list(g_sum),
                                                  list(residuals))
                        g_sum = tuple(new_g)
                        residuals = tuple(new_res)
                with jax.named_scope("optimizer"):
                    t_vals, opt_states, w32s = apply_optimizer(
                        t_vals, g_sum, opt_states, w32s, lr_row,
                        decay_row)
                out_row = (losses, outs) if return_outs else losses
                return (t_vals, f_vals, opt_states, w32s, residuals,
                        mstate), out_row

            # window xs leaves arrive (n_steps*accum, B, ...); the
            # single-step path passes the bare (B, ...) micro-batch.
            # Either way the (window, micro-batch) grid is laid out
            # inside the trace (a reshape — free in XLA).
            if n_steps * accum == 1:
                x_grid = tuple(x[None, None] for x in xs)
                y_grid = ys[None, None]
            else:
                x_grid = tuple(x.reshape((n_steps, accum) + x.shape[1:])
                               for x in xs)
                y_grid = ys.reshape((n_steps, accum) + ys.shape[1:])
            keys = jax.random.split(rng, n_steps * accum).reshape(
                (n_steps, accum) + rng.shape)
            carry = (t_vals, f_vals, opt_states, w32s, residuals, mstate)
            if n_steps == 1:
                # unrolled single step: a length-1 lax.scan would wrap
                # the whole model in a while-loop body, which XLA (CPU
                # especially) optimizes far more conservatively
                carry, row = one_step(
                    carry, (lr_rows[0],
                            None if decay_rows is None else decay_rows[0],
                            keys[0], tuple(x[0] for x in x_grid),
                            y_grid[0]))
                stacked = jax.tree_util.tree_map(lambda a: a[None], row)
            else:
                carry, stacked = lax.scan(
                    one_step, carry,
                    (lr_rows, decay_rows, keys, x_grid, y_grid))
            if return_outs:
                losses, outs = stacked
                outs = outs.reshape((n_steps * accum,) + outs.shape[2:])
            else:
                losses, outs = stacked, None
            losses = losses.reshape((n_steps * accum,) + losses.shape[2:])
            return carry + (losses, outs)

        # AOT census (ISSUE 10): the whole-step program's compile time,
        # memory_analysis footprint and retrace diffs are first-class
        # registry outputs — a CompiledStep invalidation shows up as a
        # `step.*` retrace with the offending arg named
        from .programs import register_program
        pname = "step.step" if n_steps * accum == 1 else "step.window"
        return register_program(pname, _traced_step_window,
                                donate_argnums=(0, 1, 2, 3, 4, 5))

    # -- host-side per-window bookkeeping ----------------------------------
    def _lr_rows(self, plan, n_steps, batch_size):
        tr = self._trainer
        opt = tr._optimizer
        spec = plan["spec"]
        idxs = plan["trainable_idx"]
        rescale = tr._scale / batch_size
        opt.rescale_grad = rescale
        # advance EVERY device copy's update-count table (Updater.__call__
        # keys per-device tables) so an eager<->compiled switch continues
        # one num_update trajectory on all replicas; lr comes off the
        # primary table
        ctx0 = plan["ctxs"][0]
        for c in plan["ctxs"][1:]:
            opt._set_current_context((c.canonical_type, c.device_id))
            for _ in range(n_steps):
                opt._update_count(idxs)
        opt._set_current_context((ctx0.canonical_type, ctx0.device_id))
        lr_rows, decay_rows = [], []
        wds = None
        for _ in range(n_steps):
            opt._update_count(idxs)
            raw = opt._get_lrs(idxs)
            if wds is None:
                wds = tuple(opt._get_wds(idxs))
            if spec.get("decay_fn") is not None:
                decay_rows.append([spec["decay_fn"](i, lr, wd)
                                   for i, lr, wd in zip(idxs, raw, wds)])
            if spec.get("lr_fn") is not None:
                raw = [spec["lr_fn"](i, lr) for i, lr in zip(idxs, raw)]
            lr_rows.append(raw)
        # packing HOST floats (scheduler lr / bias-correction values) into
        # the traced lr matrix — no device buffer is read here
        lrs = jnp.asarray(_np.asarray(lr_rows, _np.float32))  # mxlint: disable=host-sync-in-hot-path
        decays = None
        if decay_rows:
            decays = jnp.asarray(_np.asarray(decay_rows, _np.float32))  # mxlint: disable=host-sync-in-hot-path
        return rescale, wds, lrs, decays

    def _own_state(self, plan):
        """Make the parameters, masters and optimizer slots this step's
        own before they are donated: an array the step did not produce
        may be aliased elsewhere (``set_data`` of a shared buffer) and is
        copied - ONE AT A TIME, the copy stored back into its NDArray
        before the next is made, so the first step of a model whose state
        fills most of the device (10 GB of 16) holds the state once plus
        one array, not twice.  Steady state: every array is the last
        step's output, nothing is copied."""
        spec, ctx0 = plan["spec"], plan["ctxs"][0]
        holders = [p.data(ctx0) for p in plan["trainable"]] \
            + [p.data(ctx0) for p in plan["frozen"]]
        upd = self._trainer._updaters[0]
        for pos, i in enumerate(plan["trainable_idx"]):
            inner, w32 = spec["unpack"](upd.states[i],
                                        plan["mp_flags"][pos])
            holders.extend(inner)
            if w32 is not None:
                holders.append(w32)
        # a view is left out: `donatable` copies its value
        foreign = [nd_ for nd_ in holders
                   if nd_._index is None and nd_._vshape is None
                   and id(nd_._jax) not in self._owned]
        if not foreign:
            return
        with _telemetry.phase("initialize"):
            for nd_ in foreign:
                mine = jnp.array(nd_._jax, copy=True)
                nd_._set_jax(mine)
                self._owned_refs.append(mine)
                self._owned.add(id(mine))

    def _gather_state(self, plan):
        tr = self._trainer
        spec = plan["spec"]
        ctx0 = plan["ctxs"][0]
        t_vals = tuple(p.data(ctx0)._jax for p in plan["trainable"])
        f_vals = tuple(p.data(ctx0)._jax for p in plan["frozen"])
        upd = tr._updaters[0]
        opt_states, w32s = [], []
        for pos, i in enumerate(plan["trainable_idx"]):
            inner, w32 = spec["unpack"](upd.states[i],
                                        plan["mp_flags"][pos])
            opt_states.append(tuple(s._jax for s in inner))
            w32s.append(w32._jax if w32 is not None else None)
        residuals = ()
        if plan["exchange"] is not None:
            gc = plan["gc"]
            if plan["exchange"].residual_specs:
                residuals = tuple(
                    gc.peek_residual(wk, shape, dtype)
                    for wk, shape, dtype in
                    plan["exchange"].residual_specs)
        mstate = None
        if self._metric is not None and \
                metric_trace_kernel(self._metric) is not None:
            ds = getattr(self._metric, "_dev_sum", None)
            if ds is None:
                mstate = (jnp.zeros((), jnp.float32),
                          jnp.zeros((), jnp.int32))
            else:
                mstate = (ds, self._metric._dev_inst)
        opt_states = tuple(opt_states)
        w32s = tuple(w32s)
        if plan.get("layout") is not None:
            # defensive re-placement: steady-state buffers are already
            # mesh-resident (adopted at plan time, written back sharded),
            # so these are == checks; only external mutation (set_data,
            # checkpoint restore) between steps pays a device_put here
            t_vals = tuple(_placed(v, s)
                           for v, s in zip(t_vals, plan["shardings"]))
            f_vals = tuple(_placed(v, s)
                           for v, s in zip(f_vals,
                                           plan["frozen_shardings"]))
            opt_states = tuple(
                tuple(_placed(c, cs) for c, cs in zip(cols, css))
                for cols, css in zip(opt_states, plan["state_shardings"]))
            w32s = tuple(_placed(w, s)
                         for w, s in zip(w32s, plan["w32_shardings"]))
            residuals = tuple(
                _placed(r, s)
                for r, s in zip(residuals, plan["residual_shardings"]))
            if mstate is not None:
                mstate = tuple(_placed(m, plan["replicated"])
                               for m in mstate)
        return t_vals, f_vals, opt_states, w32s, residuals, mstate

    def _write_back(self, plan, new_t, new_f, new_states, new_w32,
                    new_res, new_mstate):
        tr = self._trainer
        spec = plan["spec"]
        ctxs = plan["ctxs"]

        def place(val, ctx, d):
            return val if d == 0 else jax.device_put(val, ctx.jax_device)

        for d, ctx in enumerate(ctxs):
            for pos, p in enumerate(plan["trainable"]):
                p._data[ctx]._set_jax(place(new_t[pos], ctx, d))
            for pos, p in enumerate(plan["frozen"]):
                p._data[ctx]._set_jax(place(new_f[pos], ctx, d))
            upd = tr._updaters[d]
            for pos, i in enumerate(plan["trainable_idx"]):
                inner, w32 = spec["unpack"](upd.states[i],
                                            plan["mp_flags"][pos])
                for s_nd, val in zip(inner, new_states[pos]):
                    s_nd._set_jax(place(val, ctx, d).astype(s_nd.dtype))
                if w32 is not None and new_w32[pos] is not None:
                    w32._set_jax(place(new_w32[pos], ctx, d))
        if plan["exchange"] is not None and new_res:
            gc = plan["gc"]
            for (wk, _shape, _dtype), val in zip(
                    plan["exchange"].residual_specs, new_res):
                gc.put_residual(wk, val)
        if new_mstate is not None:
            self._metric._dev_sum, self._metric._dev_inst = new_mstate

    # -- dispatch ----------------------------------------------------------
    def _run(self, n_steps, accum, xs, ys, batch_size, transfers):
        """One window dispatch: xs/ys leaves shaped (n_steps*accum, B,
        ...).  Returns (ctx, losses, outs_or_None) with jax arrays, or
        None when there is no plan (the caller steps eagerly).

        The host's part of a step is three phases, each an ``mx.*`` span
        on jax's profiler clock (docs/ARCHITECTURE.md, span taxonomy):
        ``step.prepare``, the dispatch, ``step.write_back``."""
        from .engine import engine as _engine
        with _telemetry.phase("step.prepare"):
            try:
                plan = self._plan()
            except DeferredInitializationError:
                plan = None   # first call finishes deferred init eagerly
            if plan is None:
                return None
            rescale, wds, lr_rows, decay_rows = self._lr_rows(
                plan, n_steps, batch_size)
            metric_info = metric_trace_kernel(self._metric)
            return_outs = self._metric is not None and metric_info is None
            layout = plan.get("layout")
            key = (n_steps, accum, rescale, wds, plan["clip"],
                   None if layout is None else layout.signature(),
                   plan["spec"]["kind"],
                   tuple(sorted(plan["spec"]["static"].items())),
                   plan["mp_flags"],
                   tuple((tuple(x.shape), str(x.dtype)) for x in xs),
                   (tuple(ys.shape), str(ys.dtype)),
                   tuple((p.shape, str(p.dtype))
                         for p in plan["trainable"]),
                   tuple((p.shape, str(p.dtype)) for p in plan["frozen"]),
                   tuple((wk, tuple(s), str(jnp.dtype(dt)))
                         for wk, s, dt in
                         (plan["exchange"].residual_specs
                          if plan["exchange"] is not None else ())),
                   metric_cache_key(self._metric, metric_info),
                   return_outs)
            fn = self._cache.get(key)
            if fn is None:
                # profiler blind spot fix (ISSUE 8): a retrace is the
                # expensive rare event that used to hide inside the first
                # dispatch — it gets its own phase span so hybridize-style
                # recompiles are visible in dumps() and the flight recorder
                with _telemetry.phase("retrace", annotation="step.retrace"):
                    fn = self._build_fn(plan, n_steps, accum, rescale, wds,
                                        decay_rows is not None,
                                        metric_info, return_outs)
                    self._cache[key] = fn
            self._own_state(plan)
            state = self._gather_state(plan)

            def donatable(a):
                if a is None or id(a) in self._owned:
                    return a
                return jnp.array(a, copy=True)   # foreign: may be aliased

            state = tuple(jax.tree_util.tree_map(donatable, s)
                          for s in state)
            rng = _ops_random.next_key()
            if layout is not None:
                # the batch crosses to the mesh sharded over data×fsdp
                # (axis 0 of each micro-batch; axis 1 of stacked window
                # leaves) — the ONE transfer the dispatch budget charges.
                # rng is a committed single-device jit output: replicate
                # it onto the mesh or the dispatch mixes incompatible
                # device sets.
                bdim = 0 if n_steps * accum == 1 else 1
                xs = tuple(jax.device_put(
                    x, layout.sharding(layout.batch_spec_for(x.shape,
                                                             bdim)))
                    for x in xs)
                ys = jax.device_put(
                    ys, layout.sharding(layout.batch_spec_for(ys.shape,
                                                              bdim)))
                rng = jax.device_put(rng, plan["replicated"])
                transfers = max(transfers, 1)
        # distinct span names so scan windows and single compiled steps
        # aggregate separately in profiler.dumps() (the eager-only
        # blind spot this satellite closes)
        span_name = "compiled_step" if n_steps * accum == 1 \
            else "compiled_window"
        with _telemetry.phase(span_name, annotation="step.dispatch"):
            out = fn(*state, lr_rows, decay_rows, rng, xs, ys)
        if not self._announced:
            # why the job took this long to its first step, and whether
            # it compiled or loaded (docs/ARCHITECTURE.md, start-up
            # timeline)
            self._announced = True
            if logger.isEnabledFor(logging.INFO):
                logger.info("first compiled step dispatched %s",
                            _telemetry.startup_line())
        with _telemetry.phase("step.write_back"):
            (new_t, new_f, new_states, new_w32, new_res, new_mstate,
             losses, outs) = out
            self._write_back(plan, new_t, new_f, new_states, new_w32,
                             new_res, new_mstate)
            self._owned_refs = [
                a for a in jax.tree_util.tree_leaves(
                    (new_t, new_f, new_states, new_w32, new_res,
                     new_mstate))
                if a is not None]
            self._owned = {id(a) for a in self._owned_refs}
            _engine.count_step_window(n_steps * accum,
                                      dispatches=1 + transfers)
            if plan["exchange"] is not None:
                _engine.count_wire_bytes(
                    plan["exchange"].wire_bytes * n_steps)
            _telemetry.note_step(steps=n_steps * accum,
                                 batch_size=batch_size,
                                 extra={"compiled": True})
        return plan["ctxs"][0], losses, outs

    def _step_span(self):
        """``mx.step``: the whole of one step() / run_window() call, a
        step annotation numbered by the trainer's update count.  No phase
        of its own: the step record (note_step) has the step's time, and
        its parts are the phases."""
        return _profiler.host_span(
            "step", step_num=self._trainer._optimizer.num_update)

    def step(self, data, label, batch_size=None):
        """One training step (forward + backward + exchange + update +
        metric) in ONE dispatch; returns the loss (eager shape)."""
        with self._step_span():
            datas = data if isinstance(data, (list, tuple)) else (data,)
            B = int(_as_jax(datas[0]).shape[0])
            batch_size = batch_size or B
            xs = tuple(_as_jax(d) for d in datas)
            y = _as_jax(label)
            ran = self._run(1, 1, xs, y, batch_size, transfers=0)
            if ran is None:
                return self._eager_step(datas, label, batch_size)
            ctx0, losses, outs = ran
            if outs is not None:
                self._metric.update([_as_nd(y, ctx0)],
                                    [NDArray(outs[0], ctx=ctx0)])
            return NDArray(losses.reshape(losses.shape[1:]), ctx=ctx0)

    def run_window(self, data, label, batch_size=None, accum=1):
        """N-step scan window: `data` leaves are (n_micro, B, ...) with
        ``n_micro = n_steps * accum`` — every `accum` consecutive
        micro-batches accumulate into one optimizer step.  The whole
        window is ONE device dispatch (plus the batch transfer); returns
        the per-micro-batch losses, shape (n_micro, ...)."""
        datas = data if isinstance(data, (list, tuple)) else (data,)
        accum = max(1, int(accum))
        xs = tuple(_as_jax(d) for d in datas)
        y = _as_jax(label)
        n_micro = int(xs[0].shape[0])
        if n_micro % accum:
            raise MXNetError("run_window: %d micro-batches do not divide "
                             "into accum=%d groups" % (n_micro, accum))
        n_steps = n_micro // accum
        B = int(xs[0].shape[1])
        batch_size = batch_size or B * accum
        with self._step_span():
            ran = self._run(n_steps, accum, xs, y, batch_size, transfers=1)
            if ran is None:
                if accum > 1:
                    raise MXNetError(
                        "run_window(accum=%d) has no eager fallback (%s); "
                        "use grad_req='add' accumulation on the eager path"
                        % (accum, self._fallback_reason))
                losses = [self._eager_step(
                    tuple(NDArray(x[t], ctx=self._trainer._contexts[0])
                          for x in xs),
                    NDArray(y[t], ctx=self._trainer._contexts[0]),
                    batch_size).mean()._jax
                    for t in range(n_micro)]
                return NDArray(jnp.stack(losses),
                               ctx=self._trainer._contexts[0])
            ctx0, losses, outs = ran
            if outs is not None:
                flat = outs.reshape((-1,) + outs.shape[2:])
                self._metric.update(
                    [NDArray(y.reshape((-1,) + y.shape[2:]), ctx=ctx0)],
                    [NDArray(flat, ctx=ctx0)])
            return NDArray(losses, ctx=ctx0)

    # -- the debug path ----------------------------------------------------
    def _eager_step(self, datas, label, batch_size):
        ctxs = self._trainer._contexts
        if len(ctxs) > 1:
            # classic DP eager loop: the batch splits across the device
            # copies, each runs its own forward/backward, the Trainer's
            # exchange merges — same math the compiled lane traces
            B = int(_as_jax(datas[0]).shape[0])
            per = B // len(ctxs)
            losses, out0, y0 = [], None, None
            with autograd.record():
                for d, ctx in enumerate(ctxs):
                    sl = slice(d * per, (d + 1) * per if
                               d < len(ctxs) - 1 else B)
                    x_nds = [NDArray(jax.device_put(_as_jax(x)[sl],
                                                    ctx.jax_device),
                                     ctx=ctx) for x in datas]
                    y_nd = NDArray(jax.device_put(_as_jax(label)[sl],
                                                  ctx.jax_device), ctx=ctx)
                    out = self._net(*x_nds)
                    loss = self._loss_fn(out, y_nd)
                    loss.backward()
                    losses.append(loss)
                    if d == 0:
                        out0, y0 = out, y_nd
            self._trainer.step(batch_size)
            if self._metric is not None:
                o = out0[0] if isinstance(out0, (list, tuple)) else out0
                self._metric.update([y0], [o])
            return losses[0]
        ctx = ctxs[0]
        x_nds = [_as_nd(d, ctx) for d in datas]
        y_nd = _as_nd(label, ctx)
        with autograd.record():
            out = self._net(*x_nds)
            loss = self._loss_fn(out, y_nd)
        loss.backward()
        self._trainer.step(batch_size)
        if self._metric is not None:
            out0 = out[0] if isinstance(out, (list, tuple)) else out
            self._metric.update([y_nd], [out0])
        return loss


# ---------------------------------------------------------------------------
# Program contracts (ISSUE 11): the whole-step programs' declared
# donation/HBM invariants and window closure.  The builder assembles a
# small canonical model + Trainer (momentum SGD, so real slot state is
# in the donated tree) and hands the verifier the EXACT traced bodies
# `step.step` / `step.window` the runtime registers, with abstract
# (ShapeDtypeStruct) state/batch trees — `python -m tools.mxlint
# --contracts` lowers them device-free and proves all six donated
# state groups alias outputs, the temp footprint fits the declared
# budget, and the window set is trace-closed.
# ---------------------------------------------------------------------------

_CONTRACT_WINDOWS = (1, 4)      # the single step + one scan window
_CONTRACT_BATCH = 8
_CONTRACT_IN = 16


def _contract_step() -> "CompiledStep":
    import mxnet_tpu as mx
    from .gluon import nn, Trainer
    from .gluon.loss import SoftmaxCrossEntropyLoss
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, in_units=_CONTRACT_IN, activation="relu"))
    net.add(nn.Dense(8, in_units=32))
    net.initialize(mx.init.Xavier())
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1, "momentum": 0.9})
    return CompiledStep(net, SoftmaxCrossEntropyLoss(), trainer)


def _abstract(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def _step_abstract_args(cs, plan, n_steps):
    """The abstract argument tree a window of `n_steps` dispatches with
    — shared by the cases and the closure's resolve, so the closure
    proof checks the SAME signature construction the cases compiled."""
    state = _abstract(cs._gather_state(plan))
    n_params = len(plan["trainable_idx"])
    lr_rows = jax.ShapeDtypeStruct((n_steps, n_params), jnp.float32)
    key = _ops_random.next_key()
    rng = jax.ShapeDtypeStruct(key.shape, key.dtype)
    if n_steps == 1:
        xs = (jax.ShapeDtypeStruct((_CONTRACT_BATCH, _CONTRACT_IN),
                                   jnp.float32),)
        ys = jax.ShapeDtypeStruct((_CONTRACT_BATCH,), jnp.float32)
    else:
        xs = (jax.ShapeDtypeStruct((n_steps, _CONTRACT_BATCH,
                                    _CONTRACT_IN), jnp.float32),)
        ys = jax.ShapeDtypeStruct((n_steps, _CONTRACT_BATCH), jnp.float32)
    return state + (lr_rows, None, rng, xs, ys)


def _step_contract_case(cs, plan, n_steps):
    from .programs import ContractCase
    rescale, wds, _lr_rows, _decays = cs._lr_rows(plan, n_steps,
                                                  _CONTRACT_BATCH)
    fn = cs._build_fn(plan, n_steps, 1, rescale, wds, decays_on=False,
                      metric_info=None, return_outs=False)
    pname = "step.step" if n_steps == 1 else "step.window"
    return ContractCase(pname, _step_abstract_args(cs, plan, n_steps),
                        label="w%d" % n_steps, target=fn)


import functools as _functools


@_functools.lru_cache(maxsize=4)
def _step_contract_built(configured_window: int):
    """Keyed by the CONFIGURED scan window so a long-lived process that
    changes MX_STEP_SCAN between verifies never reuses a closure built
    for the old window set."""
    from .programs import ContractClosure
    cs = _contract_step()
    plan = cs._plan()
    assert plan is not None, cs.fallback_reason
    cases = [_step_contract_case(cs, plan, n) for n in _CONTRACT_WINDOWS]

    # window-set closure: the windows the step lane can actually
    # dispatch are the single step plus the CONFIGURED scan window
    # (MX_STEP_SCAN at verify time) — each must land on a declared
    # case's signature, so an operator config outside the contracted
    # window set fails the static proof instead of retracing at runtime
    points = sorted({1, configured_window} | set(_CONTRACT_WINDOWS))
    closure = ContractClosure(
        points, lambda n: _step_abstract_args(cs, plan, int(n)))
    return cases, closure


def _declare_step_contracts():
    from .programs import declare_contract

    declare_contract(
        "step.train",
        lambda: _step_contract_built(scan_window() or 1)[0],
        donate_argnums=(0, 1, 2, 3, 4, 5),
        temp_budget_bytes=8 << 20,
        closure=lambda: _step_contract_built(scan_window() or 1)[1],
        description="whole-step compiled train programs: params, frozen "
                    "aux, optimizer slots, fp32 masters, EF residuals "
                    "and metric state all donate and write back; the "
                    "batch, lr matrix and rng key survive; trace "
                    "signatures closed over the configured window set")


_declare_step_contracts()


# ---------------------------------------------------------------------------
# Sharded-step contracts (ISSUE 14): the SpecLayout lane's donation/HBM
# proofs over every supported mesh class.  Each class builds the SAME
# canonical model as the replicated contract, lays it out through a
# SpecLayout over a fake mesh (the verifier forces 8 CPU devices, like
# tests/conftest), and lowers the EXACT `step.step` body the runtime
# would dispatch — with the abstract argument tree carrying the REAL
# NamedShardings, so the aliasing proof covers the sharded donation
# (params, slots, masters all sheet-sharded) and the trace-closure
# proves the {dp, dp×fsdp, dp×fsdp×tp} points land on declared
# signatures instead of retracing at runtime.
# ---------------------------------------------------------------------------

# mesh classes the sharded lane contracts: label -> (axes, shape).
# The dp2/dp3/dp4 rows are elastic-resize coverage (ISSUE 16): every
# data-parallel world size a mid-job resize can land on (within the
# 8-device contract mesh) gets its own declared signature, so a job
# that shrinks 4->3 or grows 2->4 dispatches onto a contracted program
# instead of retracing where the closure proof promised none.
_SHARD_MESH_CLASSES = (
    ("dp", ("data",), (8,)),
    ("dp2", ("data",), (2,)),
    ("dp3", ("data",), (3,)),
    ("dp4", ("data",), (4,)),
    ("dp_fsdp", ("data", "fsdp"), (4, 2)),
    ("dp_fsdp_tp", ("data", "fsdp", "tp"), (2, 2, 2)),
)


def _abstract_sharded(tree):
    """Like :func:`_abstract` but KEEPING each leaf's sharding — the
    sharded cases must lower with the placements the runtime uses, or
    the donation/temp proofs describe a program that never ships."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=getattr(a, "sharding",
                                                        None)),
        tree)


def _contract_sharded_step(axes, shape) -> "CompiledStep":
    import mxnet_tpu as mx
    from .gluon import Trainer
    from .parallel.mesh import make_mesh
    from .parallel.speclayout import SpecLayout
    need = 1
    for s in shape:
        need *= int(s)
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            "sharded step contract needs %d devices (have %d); run under "
            "the contracts CLI or tests/conftest, which force an 8-device "
            "CPU mesh" % (need, len(devs)))
    mesh = make_mesh(axes=axes, shape=shape, devices=devs[:need])
    cs = _contract_step()
    cs._layout = SpecLayout.infer(mesh)
    return cs


def _sharded_case(label, axes, shape):
    from .programs import ContractCase
    cs = _contract_sharded_step(axes, shape)
    plan = cs._plan()
    assert plan is not None, cs.fallback_reason
    rescale, wds, _lr, _dec = cs._lr_rows(plan, 1, _CONTRACT_BATCH)
    fn = cs._build_fn(plan, 1, 1, rescale, wds, decays_on=False,
                      metric_info=None, return_outs=False)
    args = _sharded_abstract_args(cs, plan)
    return ContractCase("step.step", args, label=label, target=fn)


def _sharded_abstract_args(cs, plan):
    layout = plan["layout"]
    state = _abstract_sharded(cs._gather_state(plan))
    n_params = len(plan["trainable_idx"])
    lr_rows = jax.ShapeDtypeStruct((1, n_params), jnp.float32)
    key = _ops_random.next_key()
    rng = jax.ShapeDtypeStruct(key.shape, key.dtype,
                               sharding=plan["replicated"])
    xs_shape = (_CONTRACT_BATCH, _CONTRACT_IN)
    ys_shape = (_CONTRACT_BATCH,)
    xs = (jax.ShapeDtypeStruct(
        xs_shape, jnp.float32,
        sharding=layout.sharding(layout.batch_spec_for(xs_shape, 0))),)
    ys = jax.ShapeDtypeStruct(
        ys_shape, jnp.float32,
        sharding=layout.sharding(layout.batch_spec_for(ys_shape, 0)))
    return state + (lr_rows, None, rng, xs, ys)


@_functools.lru_cache(maxsize=1)
def _sharded_contract_built():
    from .programs import ContractClosure
    cases = {}
    for label, axes, shape in _SHARD_MESH_CLASSES:
        cases[label] = _sharded_case(label, axes, shape)

    def resolve(label):
        # re-derive the dispatch signature from the runtime's own state
        # construction for that mesh class — a drift between what the
        # lane dispatches and what the cases compiled is a closure miss
        for lbl, axes, shape in _SHARD_MESH_CLASSES:
            if lbl == label:
                cs = _contract_sharded_step(axes, shape)
                plan = cs._plan()
                return _sharded_abstract_args(cs, plan)
        return None

    closure = ContractClosure([lbl for lbl, _a, _s in
                               _SHARD_MESH_CLASSES], resolve)
    return list(cases.values()), closure


def _declare_sharded_step_contracts():
    from .programs import declare_contract
    declare_contract(
        "step.train_sharded",
        lambda: _sharded_contract_built()[0],
        donate_argnums=(0, 1, 2, 3, 4, 5),
        # per-mesh-class ceiling: the sharded step's temp footprint must
        # not exceed the replicated budget — reduce-scatter/all-gather
        # staging is transient and bounded by the gathered param bytes
        temp_budget_bytes=8 << 20,
        closure=lambda: _sharded_contract_built()[1],
        description="SpecLayout sharded step programs: the same six "
                    "donated state groups as step.train, sheet-/tensor-"
                    "sharded over the mesh; donation aliasing must "
                    "survive sharding, and the {dp, dp2, dp3, dp4, "
                    "dp×fsdp, dp×fsdp×tp} mesh classes — including "
                    "every data-parallel size an elastic resize can "
                    "reach — are trace-closed")


_declare_sharded_step_contracts()
