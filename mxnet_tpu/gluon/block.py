"""Gluon Block / HybridBlock / CachedOp.

Reference: python/mxnet/gluon/block.py (class Block — child/param
registration via __setattr__, collect_params, save_parameters /
load_parameters with structural names; class HybridBlock — hybridize,
_build_cache, _call_cached_op) and src/imperative/cached_op.cc
(CachedOp::Forward, OptimizeGraph, static_alloc).

TPU-native design (SURVEY.md §3.4 TPU mapping): ``hybridize()`` IS
``jax.jit``.  On call, the block's Python ``forward`` is traced once per
(input avals, param avals, mode) into a pure function of
(trainable-params, frozen-params, rng, inputs); jax.jit caches the compiled
XLA executable — the reference's CachedOp graph-optimization + static memory
planning are XLA's problem now.  Training uses the split-executable pattern:
one jitted forward that *returns its vjp* (a jax.tree_util.Partial whose
residuals stay in HBM) + one jitted backward applying it, so the steady-state
train step is exactly two XLA dispatches and the autograd tape records a
single fused node (SURVEY.md §7.2 item 1).
"""
from __future__ import annotations

import contextlib
import functools
import re
import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as _np
import jax
import jax.numpy as jnp

from ..base import MXNetError, RECOMPUTE_KEEP, recomputed_block_trace
from ..device import Context, current_context, cpu
from ..ndarray import ndarray as _nd_mod
from ..ndarray.ndarray import NDArray
from .. import autograd
from .. import initializer as init_mod
from .. import telemetry as _telemetry
from ..ops import random as _ops_random
from .parameter import (Parameter, Constant, ParameterDict,
                        DeferredInitializationError, _ParamOverrideScope,
                        _overrides)

__all__ = ["Block", "HybridBlock", "SymbolBlock", "trace_scope"]


class _EagerCall(threading.local):
    """Whether this thread is inside a top-level eager ``Block.__call__``:
    that call alone is a ``forward`` phase, its children are not."""
    active = False


_eager_call = _EagerCall()


def trace_scope(name: str):
    """``jax.named_scope(name)`` inside a whole-program trace (a compiled
    step, a hybridize cache), nothing in an eager call: how code that is
    no block of the net (a loss) puts its ops under a scope a device
    trace can be read by."""
    return jax.named_scope(name) if _overrides() is not None \
        else contextlib.nullcontext()


class Block:
    """Base building block (reference: gluon.Block).

    Children and parameters are registered automatically on attribute
    assignment.  ``collect_params`` walks the tree producing structural
    names ("encoder.0.weight"), the 2.x naming scheme used by
    save_parameters/load_parameters.
    """

    def __init__(self, prefix: Optional[str] = None, params=None):
        # Use object.__setattr__: these must exist before __setattr__ logic.
        object.__setattr__(self, "_children", OrderedDict())
        object.__setattr__(self, "_reg_params", OrderedDict())
        object.__setattr__(self, "_forward_hooks", OrderedDict())
        object.__setattr__(self, "_forward_pre_hooks", OrderedDict())
        self._prefix = prefix or ""
        # v1.x compat: self.params.get('weight', shape=...) creates params
        self._params = ParameterDict(self._prefix, shared=params)
        self._scope_counter = 0

    # -- registration ------------------------------------------------------
    def __setattr__(self, name: str, value: Any) -> None:
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
                object.__setattr__(value, "_scope_name", name)
        elif isinstance(value, Parameter):
            reg = self.__dict__.get("_reg_params")
            if reg is not None:
                reg[name] = value
        object.__setattr__(self, name, value)

    def __delattr__(self, name: str) -> None:
        self._children.pop(name, None)
        self._reg_params.pop(name, None)
        object.__delattr__(self, name)

    def register_child(self, block: "Block", name: Optional[str] = None) -> None:
        if name is None:
            name = str(len(self._children))
        self._children[name] = block
        object.__setattr__(block, "_scope_name", name)
        object.__setattr__(self, "_child_" + name, block)

    def register_forward_hook(self, hook: Callable) -> "_HookHandle":
        return _HookHandle(self._forward_hooks, hook)

    def register_forward_pre_hook(self, hook: Callable) -> "_HookHandle":
        return _HookHandle(self._forward_pre_hooks, hook)

    # -- params ------------------------------------------------------------
    @property
    def params(self) -> ParameterDict:
        """Own (directly registered) parameters (v1.x surface)."""
        for n, p in self._reg_params.items():
            key = self._params.prefix + n
            if key not in self._params:
                self._params[key] = p
        return self._params

    def collect_params(self, select: Optional[str] = None) -> ParameterDict:
        """All parameters in this tree, keyed by structural name."""
        out = ParameterDict(self._prefix)
        pattern = re.compile(select) if select else None
        for name, param in self._iter_params():
            if pattern and not pattern.search(name):
                continue
            param._structural_name = name
            out[name] = param
        return out

    def _iter_params(self, prefix: str = ""):
        for name, param in self._reg_params.items():
            yield (prefix + name if not prefix else prefix + name), param
        for cname, child in self._children.items():
            yield from child._iter_params(prefix + cname + ".")

    def sharding_spec(self, layout):
        """Per-parameter PartitionSpec overrides for sharded training
        (the SpecLayout hook, ISSUE 14).  Called by
        :meth:`mxnet_tpu.parallel.SpecLayout.resolve` on every block in
        the tree; return ``{param-attr-name-or-Parameter:
        jax.sharding.PartitionSpec}`` to pin a layout for this block's
        OWN parameters (``self._reg_params`` names, e.g. ``"weight"``),
        or an empty mapping to accept the layout's defaults (embeddings
        and linears split on ``tp``, everything else sheet-sharded on
        ``fsdp``).  A ``PartitionSpec()`` value forces replication;
        entries naming axes the mesh lacks (or that do not divide the
        dimension) degrade to replication rather than erroring, so one
        declaration serves every mesh class."""
        return {}

    def initialize(self, init=None, ctx=None, verbose: bool = False,
                   force_reinit: bool = False) -> None:
        with _telemetry.phase("initialize"):
            self.collect_params().initialize(init, ctx, verbose,
                                             force_reinit)

    def zero_grad(self):
        self.collect_params().zero_grad()

    def reset_ctx(self, ctx):
        self.collect_params().reset_ctx(ctx)

    def cast(self, dtype) -> None:
        # one phase for the tree: a nested same-name phase counts once
        with _telemetry.phase("initialize"):
            for child in self._children.values():
                child.cast(dtype)
            for param in self._reg_params.values():
                param.cast(dtype)

    def apply(self, fn: Callable) -> "Block":
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def setattr(self, name, value):
        for _, param in self._iter_params():
            setattr(param, name, value)

    def share_parameters(self, shared: Dict[str, Parameter]) -> "Block":
        """2.x API: graft `shared` params into matching structural slots."""
        if isinstance(shared, ParameterDict):
            shared = dict(shared.items())
        structural = {name: (holder, attr)
                      for name, holder, attr in self._iter_param_slots()}
        for name, param in shared.items():
            if name in structural:
                holder, attr = structural[name]
                holder._reg_params[attr] = param
                object.__setattr__(holder, attr, param)
        return self

    def _iter_param_slots(self, prefix: str = ""):
        for attr in list(self._reg_params):
            yield prefix + attr, self, attr
        for cname, child in self._children.items():
            yield from child._iter_param_slots(prefix + cname + ".")

    # -- save / load -------------------------------------------------------
    def save_parameters(self, filename: str, deduplicate: bool = False) -> None:
        """Reference: Block.save_parameters — structural names, NDArray
        dict file format (readable by mx.nd.load)."""
        params = self.collect_params()
        arg_dict = {}
        seen = {}
        for name, param in params.items():
            if deduplicate and id(param) in seen:
                continue
            seen[id(param)] = name
            arg_dict[name] = param._reduce()
        _nd_mod.save(filename, arg_dict)

    def load_parameters(self, filename: str, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current") -> None:
        loaded = _nd_mod.load(filename)
        params = self.collect_params()
        loaded = {k[4:] if k.startswith(("arg:", "aux:")) else k: v
                  for k, v in loaded.items()}
        if not allow_missing:
            for name in params.keys():
                if name not in loaded:
                    raise AssertionError(
                        "Parameter %s is missing in %s. Set allow_missing=True "
                        "to ignore missing parameters" % (name, filename))
        for name, value in loaded.items():
            if name not in params:
                if not ignore_extra:
                    raise AssertionError(
                        "Parameter %s loaded from %s is not present in the "
                        "Block. Set ignore_extra=True to ignore" % (name, filename))
                continue
            param = params[name]
            if cast_dtype:
                if dtype_source == "saved":
                    param.cast(value.dtype)
                else:
                    value = value.astype(param.dtype)
            if param._data is None and param._deferred_init is None:
                param.initialize(ctx=ctx or cpu())
            param.set_data(value)

    save_params = save_parameters     # deprecated v1.x aliases
    load_params = load_parameters

    # -- call / forward ----------------------------------------------------
    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        # remember input avals so export() can re-trace without a sample
        # (reference: CachedOp keeps the traced graph; here the trace is
        # reconstructed on demand from shapes)
        if args and all(isinstance(a, NDArray) for a in args):
            object.__setattr__(self, "_last_input_avals",
                               [(a.shape, str(a.dtype)) for a in args])
        if _overrides() is None:
            if _eager_call.active:
                out = self._call_impl(*args, **kwargs)
            else:
                # the top-level eager call (a hybridized block's cached
                # program too): one more thread-local read a child
                _eager_call.active = True
                try:
                    with _telemetry.phase("forward"):
                        out = self._call_impl(*args, **kwargs)
                finally:
                    _eager_call.active = False
        else:
            # inside a whole-program trace (compiled step, hybridize
            # cache) the block's structural name - the attribute its
            # parent holds it under, else the class name - scopes every
            # op traced inside: a compiled program's op paths read
            # forward/BERTModel/encoder/3/attention/...  The eager path
            # pays the one thread-local read above (a scope around every
            # eager block call cost 4 % of a small net's forward).
            with jax.named_scope(self.__dict__.get("_scope_name")
                                 or type(self).__name__):
                out = self._call_recomputed(args, kwargs) \
                    if self.__dict__.get("_recompute") \
                    else self._call_impl(*args, **kwargs)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def recompute(self, active: bool = True) -> "Block":
        """Mark this block as RECOMPUTED in the backward pass: inside a
        whole-program trace (``CompiledStep``, a hybridized ancestor) its
        forward runs under ``jax.checkpoint`` and the backward pass runs
        it again (the ops of that second run carry
        ``checkpoint/rematted_computation`` on their path).  An eager call
        ignores the mark.

        What the block keeps from its first run: its inputs, and what an
        operator tags ``base.recompute_keep`` -

        * a router's choices (``parallel.moe.topk_choice``): a discrete
          decision a second run can make the other way (k int32 a token)
          - and their scores beside them (``topk_route``: k float32), so
          that the second run holds neither the top-k nor the selection
          nor the router's product;
        * the expert layer's sort order, its inverse and the group sizes
          (``held_expert_ffn``: 2k int32 a token), not sorted again -
          and the sizes choose the buffer, so every run takes the one
          the first took;
        * the flash kernels' output and logsumexp
          (``ops/attention.py``): as many bytes as the block's input and
          4 a head and row, so that the second run holds no forward
          kernel - its q, k and v are made again by the projections.

        Everything else - norms, projections, the experts' products, the
        composition's scores where the kernels do not run - is made
        again.  ``programs.program_summary()`` counts what a program's
        recomputed blocks keep (``recompute_kept_values``,
        ``recompute_kept_bytes``).  Returns the block, so a model's
        definition can write ``self.layer = Layer(...).recompute()``."""
        object.__setattr__(self, "_recompute", bool(active))
        return self

    def _call_recomputed(self, args, kwargs):
        """``_call_impl`` under ``jax.checkpoint``.  The block's own
        parameters and its NDArray arguments are the checkpointed
        function's operands; aux state written inside (BatchNorm's
        statistics, an expert layer's counters) comes back as outputs and
        is stored into the enclosing trace's values, and random ops draw
        from a key handed in, so no tracer of the inner trace escapes."""
        outer = _overrides()
        own, seen = [], set()
        for _, p in self._iter_params():
            if id(p) in outer and id(p) not in seen:
                seen.add(id(p))
                own.append((p, outer[id(p)]))
        in_leaves: List[NDArray] = []
        template = _flatten_nds(args, in_leaves)
        ctx = _first_ctx(args) or cpu()
        keyed = getattr(_ops_random._root(), "trace_holder", None) is not None
        key = _ops_random.next_key() if keyed else None
        shape = {}

        def body(p_vals, in_vals, key):
            inner, fresh = dict(outer), []
            for (p, _), v in zip(own, p_vals):
                fresh.append(NDArray(v, ctx=cpu()))
                inner[id(p)] = fresh[-1]
            rebuilt = _rebuild(template,
                               [NDArray(v, ctx=ctx) for v in in_vals], [0])
            with _ParamOverrideScope(inner), recomputed_block_trace(), \
                    (_ops_random.trace_key_scope(key) if keyed
                     else contextlib.nullcontext()):
                out = self._call_impl(*rebuilt, **kwargs)
            leaves: List[NDArray] = []
            shape["out"] = _flatten_nds(out, leaves)
            written = {i: nd._jax for i, nd in enumerate(fresh)
                       if nd._chunk.version > 0}
            return tuple(o._jax for o in leaves), written

        keep = jax.checkpoint_policies.save_only_these_names(RECOMPUTE_KEEP)
        outs, written = jax.checkpoint(body, policy=keep)(
            tuple(nd._jax for _, nd in own),
            tuple(x._jax for x in in_leaves), key)
        for i, value in written.items():
            own[i][1]._set_jax(value)
        return _rebuild(shape["out"], [NDArray(o, ctx=ctx) for o in outs],
                        [0])

    def _call_impl(self, *args, **kwargs):
        try:
            return self._forward_maybe_v1(*args, **kwargs)
        except DeferredInitializationError:
            self._deferred_init_from(args)
            return self._forward_maybe_v1(*args, **kwargs)

    def _deferred_init_from(self, args) -> None:
        """Finish deferred param init using input shapes (reference:
        HybridBlock._deferred_infer_shape → Parameter._finish_deferred_init)."""
        with _telemetry.phase("initialize"):
            self.infer_shape(*args)
            for param in self._reg_params.values():
                if param._deferred_init is not None:
                    param._finish_deferred_init()

    def infer_shape(self, *args) -> None:
        """Leaf layers with deferred-shape params override this."""
        raise DeferredInitializationError(
            "%s has parameters with unknown shape and does not implement "
            "infer_shape" % type(self).__name__)

    def _forward_maybe_v1(self, *args, **kwargs):
        """Dispatch to forward(); v1.x-era subclasses may define
        hybrid_forward(F, x, **params) instead — inject F=nd + own params."""
        if type(self).forward not in Block._FORWARD_PLACEHOLDERS:
            return self.forward(*args, **kwargs)
        if hasattr(self, "hybrid_forward"):
            ctx = _first_ctx(args) or current_context()
            pkw = {n: p.data(ctx) for n, p in self._reg_params.items()}
            return self.hybrid_forward(_nd_mod, *args, **pkw, **kwargs)
        raise NotImplementedError(
            "%s must implement forward (or hybrid_forward)" % type(self).__name__)

    # set after HybridBlock is defined: {Block.forward, HybridBlock.forward}
    _FORWARD_PLACEHOLDERS: tuple = ()

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def hybridize(self, active: bool = True, **kwargs) -> None:
        """Recursively hybridize children (no-op on plain Blocks)."""
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def summary(self, *inputs):
        """Print a per-block summary table (reference: Block.summary)."""
        rows = []

        def walk(block, name, depth):
            n_params = sum(int(_np.prod(p.shape)) if p.shape else 0
                           for p in block._reg_params.values())
            rows.append(("  " * depth + (name or type(block).__name__),
                         type(block).__name__, n_params))
            for cname, child in block._children.items():
                walk(child, cname, depth + 1)

        walk(self, type(self).__name__, 0)
        total = sum(r[2] for r in rows)
        lines = ["%-40s %-20s %12s" % ("Layer", "Type", "Params"),
                 "-" * 74]
        lines += ["%-40s %-20s %12d" % r for r in rows]
        lines += ["-" * 74, "Total params: %d" % total]
        print("\n".join(lines))

    def __repr__(self):
        body = "\n".join("  (%s): %s" % (k, repr(v).replace("\n", "\n  "))
                         for k, v in self._children.items())
        return "%s(\n%s\n)" % (type(self).__name__, body) if body else \
            "%s()" % type(self).__name__


class _HookHandle:
    _next_id = [0]

    def __init__(self, hooks: OrderedDict, hook: Callable):
        self._hooks = hooks
        self._id = _HookHandle._next_id[0]
        _HookHandle._next_id[0] += 1
        hooks[self._id] = hook

    def detach(self):
        self._hooks.pop(self._id, None)


def _first_ctx(args) -> Optional[Context]:
    for a in args:
        if isinstance(a, NDArray):
            return a.context
        if isinstance(a, (list, tuple)):
            c = _first_ctx(a)
            if c is not None:
                return c
    return None


def _flatten_nds(obj, out: List[NDArray]):
    """Collect NDArray leaves; return a template for rebuilding."""
    if isinstance(obj, NDArray):
        out.append(obj)
        return _LEAF
    if isinstance(obj, (list, tuple)):
        return type(obj)(_flatten_nds(x, out) for x in obj)
    return obj


_LEAF = object()


def _rebuild(template, leaves: List[Any], pos: List[int]):
    if template is _LEAF:
        v = leaves[pos[0]]
        pos[0] += 1
        return v
    if isinstance(template, (list, tuple)):
        return type(template)(_rebuild(t, leaves, pos) for t in template)
    return template


# Single process-wide backward executor: applies a vjp Partial to cotangents.
# The Partial's static structure is fixed per forward-trace, so this jit hits
# its cache every step (one XLA executable per cached graph).  Light-mode
# census (ISSUE 10): keeps jax.jit's C++ dispatch on the per-backward hot
# path while the program registry counts its (re)traces.
def _apply_vjp_body(vjp_fn, cotangents):
    return vjp_fn(cotangents)


def _make_apply_vjp():
    from ..programs import register_program
    return register_program("hybrid.apply_vjp", _apply_vjp_body,
                            mode="light", specializing=True)


_apply_vjp = _make_apply_vjp()


# Hybrid imperative-pass scope (ISSUE 13 retrace chase): while a
# hybridized ANCESTOR runs its imperative fallback pass (deferred
# params — the reference's _build_cache infer pass), nested hybridized
# children must run imperatively too.  Without this, the first resnet18
# step built 30 per-child programs plus 31 per-child backward (vjp)
# programs — ~2.7s of trace+compile and 60+ census "retraces" — all
# dead weight the moment the SECOND step traces the whole net as one
# program (children inline into an enclosing trace via the override
# scope; this scope closes the same hole for the imperative pass).
_imperative_pass = threading.local()


def _in_imperative_pass() -> bool:
    return getattr(_imperative_pass, "depth", 0) > 0


class _ImperativePassScope:
    __slots__ = ()

    def __enter__(self):
        _imperative_pass.depth = getattr(_imperative_pass, "depth", 0) + 1
        return self

    def __exit__(self, *exc):
        _imperative_pass.depth -= 1
        return False


class _CacheEntry:
    """One compiled graph: key = (input avals, param avals, mode)."""
    __slots__ = ("fwd_infer", "fwd_train", "mutated_ids", "out_template",
                 "n_outs")

    def __init__(self):
        self.fwd_infer = None
        self.fwd_train = None
        self.mutated_ids: List[int] = []
        self.out_template = None
        self.n_outs = 0


class HybridBlock(Block):
    """Block that can be compiled into a cached XLA graph.

    Reference: gluon.HybridBlock (hybridize/_build_cache/_call_cached_op,
    export, optimize_for).  Steady state after hybridize():
      inference — one jitted executable;
      training  — fwd executable returning (outs, aux, vjp-Partial) + one
                  shared backward executable; the tape records one node.
    """

    def __init__(self, prefix: Optional[str] = None, params=None):
        super().__init__(prefix, params)
        object.__setattr__(self, "_active", False)
        object.__setattr__(self, "_cache", {})
        object.__setattr__(self, "_flags", {})
        object.__setattr__(self, "_monitor_all", False)

    def hybridize(self, active: bool = True, static_alloc: bool = False,
                  static_shape: bool = False, inline_limit: int = 2,
                  forward_bulk_size: Optional[int] = None,
                  backward_bulk_size: Optional[int] = None) -> None:
        self._active = active
        self._flags = {"static_alloc": static_alloc,
                       "static_shape": static_shape}
        self._cache = {}
        super().hybridize(active,
                          static_alloc=static_alloc, static_shape=static_shape)

    def _clear_cached_op(self):
        self._cache = {}

    # -- the cached-op path -------------------------------------------------
    def _call_impl(self, *args, **kwargs):
        from ..ndarray import ndarray as _ndmod
        # inside an enclosing trace, compose into it imperatively rather
        # than nesting a second jit (reference: CachedOp inlining); same
        # during SYMBOL tracing (export after hybridize+forward): nested
        # blocks must not run their jitted cache or tracers leak into the
        # symbol recorder
        if not self._active or _overrides() is not None \
                or _ndmod._sym_tracer is not None \
                or _in_imperative_pass():
            return super()._call_impl(*args, **kwargs)
        params = list(self.collect_params().items())
        # deferred params: first call runs imperatively (finishes deferred
        # init with real shapes — the reference's _build_cache infer pass).
        # The scope keeps hybridized CHILDREN imperative too: their
        # soon-obsolete per-child programs must not be built for a pass
        # the whole-net trace replaces on the next call.
        if any(p._data is None for _, p in params):
            with _ImperativePassScope():
                return super()._call_impl(*args, **kwargs)
        return self._call_cached(params, args, kwargs)

    def _call_cached(self, params, args, kwargs):
        in_leaves: List[NDArray] = []
        template = _flatten_nds(args, in_leaves)
        in_vals = [x._jax for x in in_leaves]
        ctx = _first_ctx(args) or current_context()

        trainable, frozen = [], []
        for _, p in params:
            (trainable if p.grad_req != "null" else frozen).append(p)
        recording = autograd.is_recording()
        training = autograd.is_training()
        backend = getattr(self, "_backend", None)
        from ..subgraph import get_backend as _get_backend
        from ..ops import attention as _att
        key = (tuple((v.shape, str(v.dtype)) for v in in_vals),
               tuple((p.shape, str(p.dtype)) for _, p in params),
               tuple(sorted(kwargs.items())) if kwargs else (),
               recording, training,
               # lowering identity: the property's cache token AND the
               # process-wide attention default the scoped impl falls back
               # to — changing either must retrace, never reuse a stale
               # executable
               _get_backend(backend).cache_token() if backend else None,
               _att._FORCED_IMPL)
        entry = self._cache.get(key)
        if entry is None:
            entry = self._build_cache(params, trainable, frozen, template,
                                      len(in_vals), kwargs, recording, training)
            self._cache[key] = entry

        t_vals = tuple(p.data(ctx)._jax for p in trainable)
        f_vals = tuple(p.data(ctx)._jax for p in frozen)
        rng = _ops_random.next_key()

        # per-block lowering overrides active for trace AND execution:
        # jax.jit traces lazily on the first call of the jitted fn, so the
        # property scope must wrap the call, not just entry construction
        with self._backend_scope():
            if recording:
                outs, vjp_fn, mutated = entry.fwd_train(t_vals, f_vals, rng,
                                                        tuple(in_vals))
            else:
                outs, mutated = entry.fwd_infer(t_vals, f_vals, rng,
                                                tuple(in_vals))
                vjp_fn = None

        # write mutated aux state (BatchNorm running stats) back into params
        by_id = {id(p): p for _, p in params}
        for pid, new_val in zip(entry.mutated_ids, mutated):
            p = by_id[pid]
            arr = p.data(ctx)
            arr._set_jax(new_val.astype(arr.dtype))

        if vjp_fn is not None:
            def tape_vjp(cotangents):
                g_train, g_ins = _apply_vjp(vjp_fn, cotangents)
                return tuple(g_train) + tuple(g_ins)

            nd_inputs = [p.data(ctx) for p in trainable] + in_leaves
            wrapped = autograd.record_custom(
                tape_vjp, nd_inputs, tuple(outs), ctx,
                name=type(self).__name__)
        else:
            wrapped = [NDArray(o, ctx=ctx) for o in outs]
        return _rebuild(entry.out_template, wrapped, [0])

    def _build_cache(self, params, trainable, frozen, template, n_in,
                     kwargs, recording, training) -> _CacheEntry:
        """Trace forward into a pure jax function and jit it (reference:
        CachedOp::CachedOp + OptimizeGraph — here XLA does the optimizing)."""
        entry = _CacheEntry()
        block = self

        def run(t_vals, f_vals, rng, in_vals):
            # fresh tracer-backed NDArray per param; layers read them through
            # Parameter.data() via the override scope
            entry.mutated_ids = []
            overrides: Dict[int, NDArray] = {}
            tr_nds, fr_nds = [], []
            for p, v in zip(trainable, t_vals):
                nd = NDArray(v, ctx=cpu())
                overrides[id(p)] = nd
                tr_nds.append((p, nd))
            for p, v in zip(frozen, f_vals):
                nd = NDArray(v, ctx=cpu())
                overrides[id(p)] = nd
                fr_nds.append((p, nd))
            in_nds = [NDArray(v, ctx=cpu()) for v in in_vals]
            rebuilt = _rebuild(template, in_nds, [0])
            with _ParamOverrideScope(overrides), \
                    _ops_random.trace_key_scope(rng), \
                    autograd._Scope(False, training):
                out = Block._call_impl(block, *rebuilt, **kwargs)
            out_leaves: List[NDArray] = []
            entry.out_template = _flatten_nds(out, out_leaves)
            entry.n_outs = len(out_leaves)
            # detect aux-state mutation (chunk version bumped during trace)
            mutated_vals = []
            for p, nd in tr_nds + fr_nds:
                if nd._chunk.version > 0:
                    entry.mutated_ids.append(id(p))
                    mutated_vals.append(nd._jax)
            return tuple(o._jax for o in out_leaves), tuple(mutated_vals)

        from ..programs import register_program
        pname = "hybrid.%s" % type(self).__name__
        if recording:
            def fwd_train(t_vals, f_vals, rng, in_vals):
                def f(tv, iv):
                    return run(tv, f_vals, rng, iv)
                outs, vjp_fn, mutated = jax.vjp(f, t_vals, in_vals,
                                                has_aux=True)
                return outs, vjp_fn, mutated

            entry.fwd_train = register_program(pname + ".train",
                                               fwd_train, mode="light",
                                               specializing=True)
        else:
            entry.fwd_infer = register_program(pname + ".infer", run,
                                               mode="light",
                                               specializing=True)
        return entry

    # -- export (symbol.json + params artifact) -----------------------------
    def export(self, path: str, epoch: int = 0, remove_amp_cast: bool = True):
        """Serialize to `path-symbol.json` + `path-%04d.params` (reference:
        HybridBlock.export).  The JSON carries the block config; parameters
        use the MXNet binary dict format."""
        from ..symbol import symbol_json_from_block
        sym_file = "%s-symbol.json" % path
        with open(sym_file, "w") as f:
            f.write(symbol_json_from_block(self))
        params_file = "%s-%04d.params" % (path, epoch)
        arg_dict = {}
        for name, p in self.collect_params().items():
            arg_dict["arg:" + name] = p._reduce()
        _nd_mod.save(params_file, arg_dict)
        return sym_file, params_file

    def optimize_for(self, x, backend=None, clear=True, **kwargs):
        """Reference: HybridBlock.optimize_for(backend) — subgraph-backend
        selection via the backend-property registry (mxnet_tpu.subgraph;
        reference subgraph_property.h SubgraphPropertyRegistry).

        The named property's lowering overrides apply to THIS block only
        (per-block semantics like the reference, not process-wide): its
        scope is entered around every trace/execution of this block's
        cached op, and the cached-op key carries the backend name.
        Built-ins: ``'pallas'`` (force the Pallas flash-attention kernel
        where alignment permits), ``'xla'`` (plain jnp composition),
        ``'amp_bf16'`` / ``'amp_float16'`` (AMP policy lists scoped to the
        block).  ``None`` restores default lowering.  Unknown backends
        warn loudly instead of silently doing nothing."""
        from ..subgraph import get_backend
        if backend is None:
            self._backend = None
        else:
            try:
                get_backend(backend)
                self._backend = backend
            except KeyError as e:
                import warnings
                warnings.warn(
                    "optimize_for: %s; running the default XLA path" % e,
                    stacklevel=2)
                self._backend = None
        if clear:
            self._clear_cached_op()  # retrace under the new lowering config
        self.hybridize(True, **{k: v for k, v in kwargs.items()
                                if k in ("static_alloc", "static_shape")})
        return self(x)

    def _backend_scope(self):
        import contextlib
        backend = getattr(self, "_backend", None)
        if backend is None:
            return contextlib.nullcontext()
        from ..subgraph import get_backend
        return get_backend(backend).scope()

    def forward(self, *args, **kwargs):
        raise NotImplementedError


Block._FORWARD_PLACEHOLDERS = (Block.forward, HybridBlock.forward)


def functionalize(block: Block):
    """Lift a Block into (pure_fn, params) for direct jax use.

    ``pure_fn(param_values, *inputs)`` runs the block's forward with the
    given parameter arrays substituted (the CachedOp trace mechanism made
    public) — the bridge the parallel/ package uses to pjit whole training
    steps over a Mesh, and what __graft_entry__ exposes to the driver.
    Parameters must be initialized; keys are structural names.
    """
    params = list(block.collect_params().items())

    def pure_fn(param_values, *inputs, training=False):
        overrides: Dict[int, NDArray] = {}
        for name, p in params:
            overrides[id(p)] = NDArray(param_values[name], ctx=cpu())
        in_nds = [x if isinstance(x, NDArray) else NDArray(x, ctx=cpu())
                  for x in inputs]
        with _ParamOverrideScope(overrides), autograd._Scope(False, training):
            out = block(*in_nds)
        return jax.tree_util.tree_map(
            lambda o: o._jax if isinstance(o, NDArray) else o, out,
            is_leaf=lambda o: isinstance(o, NDArray))

    param_values = {name: p.data()._jax for name, p in params}
    return pure_fn, param_values


class SymbolBlock(HybridBlock):
    """Runs a network from exported symbol.json + params (reference:
    gluon.SymbolBlock.imports).  Full graph-json execution lands with the
    symbol subsystem; constructing from a live Symbol works now."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__()
        self._outputs = outputs
        self._inputs = inputs

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        """Load an exported artifact; the parameters are registered as
        this block's Parameters on ``ctx`` (default: the current
        context), so ``collect_params``/``functionalize`` see them and
        a jitted forward takes them as operands on that device instead
        of baking host constants into the program."""
        from ..symbol import load as sym_load
        sym = sym_load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        block = SymbolBlock(sym, input_names)
        loaded = {}
        if param_file:
            loaded = _nd_mod.load(param_file)
            if isinstance(loaded, list):
                if loaded:
                    raise MXNetError(
                        "SymbolBlock.imports: %r holds a name-less array "
                        "LIST; parameters need the dict form (arg:/aux: "
                        "keys)" % param_file)
                loaded = {}      # empty save is format-ambiguous
        ctx = ctx or current_context()
        for key, value in loaded.items():
            aux = key.startswith("aux:")
            name = key[4:] if aux or key.startswith("arg:") else key
            param = Parameter(name, shape=value.shape, dtype=value.dtype,
                              grad_req="null" if aux else "write")
            param._deferred_init = (None, [ctx], None)
            param.set_data(value)    # materializes on ctx
            block._reg_params[name] = param
        block._input_names = input_names
        return block

    def forward(self, *args):
        from ..symbol import evaluate as sym_eval
        feeds = dict(zip(self._input_names, args))
        params = {name: p.data() for name, p in self._reg_params.items()}
        return sym_eval(self._outputs, feeds, params)
