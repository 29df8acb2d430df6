"""Imperative autograd: tape recording + reverse-mode backward.

Reference: python/mxnet/autograd.py (record, pause, backward, grad, Function),
src/imperative/imperative.cc (Imperative::RecordOp, Imperative::Backward),
src/nnvm/gradient.cc (Gradient pass).

TPU-native design (SURVEY.md §3.3 TPU mapping): each eagerly-invoked op is
recorded as a tape node carrying the backward closure obtained from
``jax.vjp`` over the op's pure JAX implementation — jax.vjp plays the role of
the per-op FGradient attribute and runs the forward exactly once.
``backward()`` walks the tape in reverse topological order accumulating
cotangents and writes leaf gradients into the arrays attached by
``attach_grad`` honoring grad_req ('write' | 'add' | 'null').  A hybridized
block records ONE node whose vjp is the jit-compiled backward of the whole
cached graph, so the training hot path is two XLA executables, not a Python
loop (SURVEY.md §7.2 item 1).
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .base import MXNetError

__all__ = ["record", "pause", "train_mode", "predict_mode", "is_recording",
           "is_training", "set_recording", "set_training", "ambient_is_train",
           "backward", "grad", "mark_variables", "Function", "VariableNode"]

_state = threading.local()

# Cross-thread mirror of which threads are currently recording/training.
# XLA host callbacks (jax.pure_callback — the Custom-op bridge) execute on
# runtime threads that never entered an autograd scope; ambient_is_train()
# lets them see "is any thread training right now" instead of a fresh
# thread-local default of False.
_ambient_lock = threading.Lock()
_recording_threads: set = set()
_training_threads: set = set()


def _st():
    if not hasattr(_state, "recording"):
        _state.recording = False
        _state.training = False
        _state.explicit = False  # this thread never entered a scope
    return _state


def is_recording() -> bool:
    return _st().recording


def is_training() -> bool:
    return _st().training


def _mirror(which: set, flag: bool) -> None:
    ident = threading.get_ident()
    with _ambient_lock:
        (which.add if flag else which.discard)(ident)


def set_recording(flag: bool) -> bool:
    st = _st()
    old = st.recording
    st.recording = bool(flag)
    st.explicit = True
    _mirror(_recording_threads, st.recording)
    return old


def set_training(flag: bool) -> bool:
    st = _st()
    old = st.training
    st.training = bool(flag)
    st.explicit = True
    _mirror(_training_threads, st.training)
    return old


def ambient_is_train() -> bool:
    """Per-call train flag for code running on a thread that may not own the
    autograd scope (XLA host-callback threads).  Falls back to "any thread is
    currently recording/training" — correct for the single-trainer process;
    a process training and predicting on two threads at once sees train=True
    on both callback paths (documented edge)."""
    st = _st()
    if st.explicit:
        return st.recording or st.training
    with _ambient_lock:
        return bool(_recording_threads or _training_threads)


class _Scope:
    def __init__(self, recording: Optional[bool], training: Optional[bool]):
        self._rec = recording
        self._train = training

    def __enter__(self):
        st = _st()
        self._old = (st.recording, st.training)
        if self._rec is not None:
            set_recording(self._rec)
        if self._train is not None:
            set_training(self._train)
        return self

    def __exit__(self, *exc):
        set_recording(self._old[0])
        set_training(self._old[1])
        return False


def record(train_mode: bool = True) -> _Scope:
    return _Scope(True, train_mode)


def pause(train_mode: bool = False) -> _Scope:
    return _Scope(False, train_mode)


def train_mode() -> _Scope:
    return _Scope(None, True)


def predict_mode() -> _Scope:
    return _Scope(None, False)


# ---------------------------------------------------------------------------
# tape
# ---------------------------------------------------------------------------


class VariableNode:
    """Leaf marker created by NDArray.attach_grad / mark_variables."""
    __slots__ = ("array",)

    def __init__(self, array):
        self.array = array


class OpNode:
    """One recorded op: vjp closure + parent links (≈ nnvm::Node + AGInfo)."""
    __slots__ = ("name", "vjp_fn", "parents", "n_outputs", "rng_offset",
                 "primal_fn", "primal_vals", "primal_refs",
                 "out_structure", "out_avals")

    def __init__(self, name, vjp_fn, parents, n_outputs, rng_offset,
                 out_structure, out_avals, primal_fn=None, primal_vals=None,
                 primal_refs=None):
        self.name = name
        self.vjp_fn = vjp_fn
        self.parents = parents      # per-jax-input: VariableNode|OpNode|None
        self.n_outputs = n_outputs
        self.rng_offset = rng_offset
        self.out_structure = out_structure  # 'one' | 'tuple'
        self.out_avals = out_avals  # [(shape, dtype)] for zero-cotangent fill
        # higher-order support: re-linearization needs the op's pure fn and
        # its primal inputs (the stored vjp closure alone cannot yield
        # d(grad)/d(primal))
        self.primal_fn = primal_fn
        self.primal_vals = primal_vals   # list[jax.Array]
        self.primal_refs = primal_refs   # list[NDArray|None] (tape links)


def record_op(op, params: Dict[str, Any], nd_inputs, jax_in, ctx):
    """Called by ndarray.invoke while recording.  Runs forward via jax.vjp and
    wraps outputs with tape pointers."""
    from .ndarray.ndarray import NDArray

    def pure(*xs):
        return op.fn(*xs, **params)

    outs, vjp_fn = jax.vjp(pure, *jax_in)
    structure = "tuple" if isinstance(outs, tuple) else "one"
    outs_t = outs if structure == "tuple" else (outs,)
    rng_offset = 1 if op.needs_rng else 0

    parents: List[Any] = [None] * rng_offset
    for x in nd_inputs:
        if isinstance(x, NDArray):
            parents.append(x._ag_node)
        else:
            parents.append(None)
    avals = [(o.shape, o.dtype) for o in outs_t]
    refs = [None] * rng_offset + [x if isinstance(x, NDArray) else None
                                  for x in nd_inputs]
    node = OpNode(op.name, vjp_fn, parents, len(outs_t), rng_offset, structure,
                  avals, primal_fn=pure, primal_vals=list(jax_in),
                  primal_refs=refs)
    wrapped = []
    for i, o in enumerate(outs_t):
        nd = NDArray(o, ctx=ctx)
        nd._ag_node = (node, i)
        wrapped.append(nd)
    if structure == "one":
        return wrapped[0]
    return wrapped


def record_custom(vjp_fn, nd_inputs, outs, ctx, name="custom",
                  primal_fn=None):
    """Record a single node with a user/jit-supplied vjp (the CachedOp path).
    Pass primal_fn (pure over the nd_inputs' jax values) to keep the node
    differentiable under create_graph."""
    from .ndarray.ndarray import NDArray
    structure = "tuple" if isinstance(outs, tuple) else "one"
    outs_t = outs if structure == "tuple" else (outs,)
    parents = []
    for x in nd_inputs:
        parents.append(x._ag_node if isinstance(x, NDArray) else None)
    avals = [(o.shape, o.dtype) for o in outs_t]
    pvals = prefs = None
    if primal_fn is not None:
        pvals = [x._jax if isinstance(x, NDArray) else jnp.asarray(x)
                 for x in nd_inputs]
        prefs = [x if isinstance(x, NDArray) else None for x in nd_inputs]
    node = OpNode(name, vjp_fn, parents, len(outs_t), 0, structure, avals,
                  primal_fn=primal_fn, primal_vals=pvals, primal_refs=prefs)
    wrapped = []
    for i, o in enumerate(outs_t):
        nd = NDArray(o, ctx=ctx)
        nd._ag_node = (node, i)
        wrapped.append(nd)
    return wrapped[0] if structure == "one" else wrapped


def mark_variables(variables, gradients, grad_reqs="write") -> None:
    """Reference: autograd.mark_variables."""
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for v, g, req in zip(variables, gradients, grad_reqs):
        v._grad = g
        v._grad_req = req
        v._ag_node = VariableNode(v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _topo_from(heads: Sequence[Tuple[OpNode, int]]) -> List[OpNode]:
    """Iterative post-order DFS from the head nodes (no recursion limit on
    deep tapes).  Post-order emits a node after all its producers, so the
    caller iterates ``reversed(order)`` to run heads-first backward."""
    seen = set()
    order: List[OpNode] = []
    for head, _ in heads:
        if not isinstance(head, OpNode) or id(head) in seen:
            continue
        seen.add(id(head))
        stack = [(head, iter(head.parents))]
        while stack:
            n, it = stack[-1]
            advanced = False
            for p in it:
                pn = p[0] if isinstance(p, tuple) else p
                if isinstance(pn, OpNode) and id(pn) not in seen:
                    seen.add(id(pn))
                    stack.append((pn, iter(pn.parents)))
                    advanced = True
                    break
            if not advanced:
                order.append(n)
                stack.pop()
    return order


def backward(heads, head_grads=None, retain_graph: bool = False,
             train_mode: bool = True) -> None:
    """Compute gradients of heads w.r.t. attached variables (writes .grad)."""
    from . import telemetry as _telemetry
    # step-phase span (ISSUE 8): eager Gluon loops get their backward
    # attributed; dispatch-time only (the tape replay enqueues async
    # XLA work, nothing here syncs it)
    with _telemetry.phase("backward"):
        _run_backward(heads, head_grads, retain_graph, write_leaves=True)


def grad(heads, variables, head_grads=None, retain_graph=None,
         create_graph: bool = False, train_mode: bool = True):
    """Reference: autograd.grad — returns grads instead of writing .grad.

    With ``create_graph=True`` the backward pass itself is RECORDED: every
    tape node's vjp closure is a jax-transformable function, so its
    application becomes a new tape node (via jax.vjp over the vjp),
    making the returned gradients differentiable — grad-of-grad composes
    to any order (reference: Imperative::Backward's create_graph)."""
    from .ndarray.ndarray import NDArray
    variables = list(variables)
    if retain_graph is None:
        retain_graph = create_graph
    if create_graph:
        # the backward computation itself must RECORD (cotangent fan-in
        # accumulation and dtype promotes are ordinary NDArray ops) — force
        # recording even when grad() is called outside a record() scope
        with _Scope(True, train_mode):
            got = _run_backward(heads, head_grads, retain_graph,
                                write_leaves=False, wanted=variables,
                                record_graph=True)
    else:
        got = _run_backward(heads, head_grads, retain_graph,
                            write_leaves=False, wanted=variables)
    out = []
    for v in variables:
        g = got.get(id(v))
        if g is None:
            raise MXNetError("one of the variables does not require gradient "
                             "or is unreachable from heads")
        out.append(g if isinstance(g, NDArray) else NDArray(g, ctx=v.context))
    return out


def _run_backward(heads, head_grads, retain_graph, write_leaves=True,
                  wanted=None, record_graph=False):
    from .ndarray.ndarray import NDArray
    if isinstance(heads, NDArray):
        heads = [heads]
    if head_grads is not None and isinstance(head_grads, NDArray):
        head_grads = [head_grads]

    # cotangent store: id(OpNode) -> list per output slot.  Values are raw
    # jax arrays normally; with record_graph they are NDArrays carrying
    # tape pointers so the backward computation is itself differentiable
    # (NDArray.__add__ in the accumulation below records too).
    cts: Dict[int, List[Optional[Any]]] = {}
    leaf_vals: Dict[int, Any] = {}
    leaf_refs: Dict[int, Any] = {}
    head_nodes: List[Tuple[OpNode, int]] = []

    def add_ct(target, value):
        if target is None:
            return
        if isinstance(target, VariableNode):
            arr = target.array
            prev = leaf_vals.get(id(arr))
            leaf_vals[id(arr)] = value if prev is None else prev + value
            leaf_refs[id(arr)] = arr
            return
        node, idx = target
        slot = cts.setdefault(id(node), [None] * node.n_outputs)
        slot[idx] = value if slot[idx] is None else slot[idx] + value

    for i, h in enumerate(heads):
        if h._ag_node is None:
            raise MXNetError("cannot differentiate a head that was not "
                             "computed while autograd was recording")
        hg = None
        if head_grads is not None and head_grads[i] is not None:
            hg = head_grads[i] if record_graph and \
                isinstance(head_grads[i], NDArray) else (
                head_grads[i]._jax if isinstance(head_grads[i], NDArray)
                else jnp.asarray(head_grads[i]))
        else:
            hg = jnp.ones(h.shape, h.dtype)
            if record_graph:
                hg = NDArray(hg)
        add_ct(h._ag_node, hg)
        if isinstance(h._ag_node, tuple):
            head_nodes.append(h._ag_node)

    order = _topo_from(head_nodes)

    # Incremental leaf finalization (ISSUE 5 overlap scheduling): a leaf's
    # gradient is FINAL the moment every tape node that consumes it has
    # been processed.  Writing it (and firing the grad buffer's overlap
    # hook) right then — instead of after the whole walk — lets a consumer
    # (Trainer fusion-bucket exchange) launch its collective while the
    # rest of backward is still running.  Backward visits heads first, so
    # late-layer leaves finalize earliest — which is exactly the order the
    # reverse-packed buckets close in.
    leaf_edges: Dict[int, int] = {}
    finalized: set = set()
    if write_leaves:
        for node in order:
            for p in node.parents:
                if isinstance(p, VariableNode):
                    k = id(p.array)
                    leaf_edges[k] = leaf_edges.get(k, 0) + 1

    def _write_leaf(arr, val):
        req = arr._grad_req
        if req == "null" or arr.grad is None:   # .grad: allocates a lazy one
            return
        if req == "add":
            arr._grad._set_jax(arr._grad._jax + val.astype(arr._grad.dtype))
        else:
            arr._grad._set_jax(val.astype(arr._grad.dtype))
            hook = getattr(arr._grad, "_grad_hook", None)
            if hook is not None:
                # 'write' only: an accumulating grad ('add') is not final
                # until the caller says so — overlap consumers drain it at
                # step time instead.  The hook runs on WHATEVER thread is
                # executing this backward (incl. XLA host-callback
                # threads), so hook targets may only touch state guarded
                # for cross-thread access — mxlint's concurrency pass
                # models every `._grad_hook = ...` target as a thread
                # root and enforces exactly that
                hook()

    def _note_consumed(node):
        for p in node.parents:
            if not isinstance(p, VariableNode):
                continue
            k = id(p.array)
            leaf_edges[k] -= 1
            if leaf_edges[k] == 0 and k not in finalized:
                finalized.add(k)
                if k in leaf_vals:
                    _write_leaf(leaf_refs[k], leaf_vals[k])

    # order: producers-before-consumers removed by reversal → walk heads first
    for node in reversed(order):
        slot = cts.get(id(node))
        if slot is None:
            # unreached node (pruned branch): its leaf inputs still count
            # this visit, or they would never finalize
            if write_leaves:
                _note_consumed(node)
            continue
        # Cotangents must match each output's dtype; a consumer may have
        # promoted (e.g. the AMP fp32-list casts a bf16 activation up before
        # a loss op), in which case its cotangent arrives wide — cast it
        # back, which is precisely the vjp of the implicit promote.
        cotangents = [
            jnp.zeros(node.out_avals[i][0], node.out_avals[i][1])
            if c is None
            else (c.astype(node.out_avals[i][1])
                  if c.dtype != node.out_avals[i][1] else c)
            for i, c in enumerate(slot)]
        if node.vjp_fn is None:
            raise MXNetError(
                "backward through op %r a second time, but the graph was "
                "freed; pass retain_graph=True to the first backward"
                % node.name)
        if record_graph:
            # higher-order: re-linearize the op from its PURE fn + primals
            # so the backward step is a fresh tape node differentiable in
            # BOTH the incoming cotangent and the primal inputs (the
            # stored vjp closure hides the primal dependency)
            if node.primal_fn is None:
                raise MXNetError(
                    "create_graph through %r: this node (hybridized block /"
                    " custom Function) does not retain its primal function;"
                    " higher-order autograd needs eagerly-recorded ops"
                    % node.name)
            ct_nds = [c if isinstance(c, NDArray) else NDArray(c)
                      for c in cotangents]
            jax_cts = [c._jax for c in ct_nds]
            n_ct = len(jax_cts)
            is_tuple = node.out_structure == "tuple"
            pure = node.primal_fn
            # only expose grads whose parent exists: a dangling grad (e.g.
            # x^y's dy = x^y·ln x at negative x) can be NaN, and even a
            # zero cotangent would propagate 0*NaN through the next vjp
            keep = [i for i, p in enumerate(node.parents) if p is not None]

            def apply(*args, pure=pure, n_ct=n_ct, is_tuple=is_tuple,
                      keep=tuple(keep)):
                cs, prims = args[:n_ct], args[n_ct:]
                _, vjp = jax.vjp(pure, *prims)
                gr = vjp(tuple(cs) if is_tuple else cs[0])
                return tuple(gr[i] for i in keep)

            outs, vjp2 = jax.vjp(apply, *jax_cts, *node.primal_vals)
            rec_inputs = list(ct_nds) + [
                r if r is not None else v
                for r, v in zip(node.primal_refs, node.primal_vals)]
            # primal_fn threads through so grad-of-grad-of-grad composes
            wrapped = record_custom(vjp2, rec_inputs, tuple(outs), None,
                                    name=node.name + "_backward",
                                    primal_fn=apply)
            kept_nd = wrapped if isinstance(wrapped, (list, tuple)) \
                else [wrapped]
            grads = [None] * len(node.parents)
            for i, g in zip(keep, kept_nd):
                grads[i] = g
        else:
            ct_in = tuple(cotangents) if node.out_structure == "tuple" \
                else cotangents[0]
            grads = node.vjp_fn(ct_in)
        if not retain_graph:
            # free BOTH the vjp residuals and the higher-order primal refs
            # — otherwise every op input's device buffer stays pinned via
            # the tape after a plain backward
            node.vjp_fn = None
            node.primal_fn = None
            node.primal_vals = None
            node.primal_refs = None
        for parent, g in zip(node.parents, grads):
            if parent is None or g is None:
                continue
            gj = g._jax if hasattr(g, "_jax") else g
            if getattr(gj, "dtype", None) == jax.dtypes.float0:
                # jax.vjp's "no gradient" marker for integer inputs
                # (index operands of gather/clip/mod): nothing flows
                continue
            add_ct(parent, g)
        if write_leaves:
            # this node's contributions are in: any leaf it was the last
            # consumer of is now final — write it and fire its hook
            _note_consumed(node)

    if write_leaves:
        # sweep leaves the walk could not finalize (heads that ARE leaves,
        # zero-consumer edge cases)
        for key, val in leaf_vals.items():
            if key not in finalized:
                _write_leaf(leaf_refs[key], val)
        return None
    return dict(leaf_vals)


# ---------------------------------------------------------------------------
# custom differentiable Function (reference: autograd.Function)
# ---------------------------------------------------------------------------


class Function:
    """User-defined differentiable function.

    Subclass and implement forward(self, *inputs) and backward(self,
    *output_grads), both over NDArrays.  Mirrors python/mxnet/autograd.py
    (class Function).
    """

    def __init__(self):
        self._saved = None

    def save_for_backward(self, *arrays):
        self._saved = arrays

    @property
    def saved_tensors(self):
        return self._saved

    def forward(self, *inputs):
        raise NotImplementedError

    def backward(self, *output_grads):
        raise NotImplementedError

    def __call__(self, *inputs):
        from .ndarray.ndarray import NDArray
        with pause():
            outs = self.forward(*inputs)
        single = not isinstance(outs, (list, tuple))
        outs_t = (outs,) if single else tuple(outs)
        if not is_recording():
            return outs
        func = self

        def vjp_fn(cotangents):
            cts = (cotangents,) if single else cotangents
            with pause():
                gr = func.backward(*[NDArray(c) for c in cts])
            if not isinstance(gr, (list, tuple)):
                gr = (gr,)
            return tuple(g._jax if isinstance(g, NDArray) else g for g in gr)

        ctx = inputs[0].context if inputs and isinstance(inputs[0], NDArray) \
            else None
        jax_outs = tuple(o._jax for o in outs_t)
        res = record_custom(vjp_fn, list(inputs),
                            jax_outs if not single else jax_outs[0],
                            ctx, name=type(self).__name__)
        return res


def get_symbol(x):
    """Reference: autograd.get_symbol — retrieve the recorded compute
    history of an NDArray as a Symbol.  This rebuild's tape records jax
    vjp closures, not named graph nodes, so the imperative history is
    not reconstructible as a Symbol; the supported route to a symbolic
    graph is HybridBlock.hybridize()+export (or SymbolBlock), which
    trace through the same kernels with full fidelity."""
    raise MXNetError(
        "autograd.get_symbol is not supported on the TPU rebuild: the "
        "autograd tape holds jax vjp closures, not graph nodes.  Use "
        "net.hybridize() + net.export(...) (or gluon.SymbolBlock) to "
        "obtain the symbolic graph of a computation.")
