"""mx.operator: user-defined operators with numpy callbacks (CustomOp).

Reference: python/mxnet/operator.py (CustomOp, CustomOpProp, register),
src/operator/custom/custom.cc (CustomOperator::Push — the engine bridge
that schedules the python callback on its own thread pool).

TPU-native design: the numpy callback crosses the device boundary through
``jax.pure_callback`` so a Custom op remains *traceable* — it works inside
``hybridize()``/``jit`` (XLA inserts the host transfer at the callback
boundary, playing the role of custom.cc's engine thread + DevCopy).  The
gradient is wired with ``jax.custom_vjp`` whose backward is the user's
``CustomOp.backward`` behind a second pure_callback, so autograd works both
on the eager tape and under the whole-graph vjp a CachedOp takes.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as _np
import jax
import jax.numpy as jnp

from .base import MXNetError

__all__ = ["CustomOp", "CustomOpProp", "register", "get_registered_op",
           "Custom"]

_REGISTRY: Dict[str, type] = {}


class CustomOp:
    """Base class for user ops.  Implement ``forward`` and ``backward``
    with numpy semantics (reference: operator.py class CustomOp)."""

    def forward(self, is_train, req, in_data, out_data, aux):
        raise NotImplementedError

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        raise NotImplementedError

    def assign(self, dst, req, src):
        """Helper honoring the grad_req the same way the reference does."""
        if req in ("write", "inplace", 1, 2):
            dst[...] = src
        elif req in ("add", 3):
            dst[...] = dst + src
        # 'null'/0: drop


class CustomOpProp:
    """Op metadata provider (reference: operator.py class CustomOpProp).

    Subclass and override ``list_arguments``/``list_outputs``/
    ``infer_shape``/``infer_type``/``create_operator``."""

    def __init__(self, need_top_grad: bool = True):
        self.need_top_grad_ = need_top_grad

    def list_arguments(self) -> List[str]:
        return ["data"]

    def list_outputs(self) -> List[str]:
        return ["output"]

    def list_auxiliary_states(self) -> List[str]:
        return []

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]], []

    def infer_type(self, in_type):
        return in_type, [in_type[0]] * len(self.list_outputs()), []

    def need_top_grad(self) -> bool:
        return self.need_top_grad_

    def declare_backward_dependency(self, out_grad, in_data, out_data):
        deps = []
        if self.need_top_grad():
            deps.extend(out_grad)
        deps.extend(in_data)
        deps.extend(out_data)
        return deps

    def create_operator(self, ctx, in_shapes, in_dtypes) -> CustomOp:
        raise NotImplementedError


def register(reg_name: str):
    """Decorator: ``@mx.operator.register("my_op")`` on a CustomOpProp
    subclass (reference: operator.py register)."""

    def _reg(prop_cls):
        if not issubclass(prop_cls, CustomOpProp):
            raise MXNetError("register expects a CustomOpProp subclass")
        _REGISTRY[reg_name] = prop_cls
        return prop_cls

    return _reg


def get_registered_op(name: str) -> type:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise MXNetError("custom op %r is not registered" % (name,))


def _writable(arrs: Sequence[_np.ndarray]) -> List[_np.ndarray]:
    # pure_callback hands read-only views; the CustomOp contract is
    # in-place assignment into out_data/in_grad buffers.
    return [_np.array(a) for a in arrs]


def Custom(*inputs, op_type: Optional[str] = None, **kwargs):
    """Invoke a registered custom op: ``mx.nd.Custom(x, op_type='my_op')``
    (reference: the generated nd.Custom wrapper over custom.cc)."""
    from .ndarray.ndarray import NDArray
    from . import autograd
    from .device import current_context

    if op_type is None:
        raise MXNetError("Custom requires op_type=")
    from .ndarray import ndarray as _ndmod
    if _ndmod._sym_tracer is not None:
        raise MXNetError(
            "Custom ops cannot be traced into symbol.json (the numpy "
            "callback has no graph representation — the reference's "
            "exported Custom nodes need the python process too); exclude "
            "the Custom op from the exported subgraph")
    # standard MXNet call kwargs are not prop parameters
    kwargs.pop("name", None)
    kwargs.pop("ctx", None)
    prop = get_registered_op(op_type)(**{k: str(v) for k, v in kwargs.items()})

    nd_in = [x if isinstance(x, NDArray) else NDArray(jnp.asarray(x))
             for x in inputs]
    ctx = nd_in[0].context if nd_in else current_context()
    n_args = len(prop.list_arguments())
    if len(nd_in) != n_args:
        raise MXNetError("custom op %r expects %d inputs (%s), got %d"
                         % (op_type, n_args, prop.list_arguments(),
                            len(nd_in)))

    in_shapes = [tuple(x.shape) for x in nd_in]
    in_dtypes = [_np.dtype(x.dtype) for x in nd_in]
    if prop.list_auxiliary_states():
        raise MXNetError("custom op %r declares auxiliary states, which the "
                         "TPU bridge does not support yet (keep state on the "
                         "CustomOp instance instead)" % (op_type,))
    in_shapes2, out_shapes, _aux = prop.infer_shape(list(in_shapes))
    _, out_dtypes, _ = prop.infer_type(list(in_dtypes))
    n_out = len(prop.list_outputs())
    op = prop.create_operator(ctx, in_shapes2, in_dtypes)

    out_avals = tuple(jax.ShapeDtypeStruct(tuple(s), _np.dtype(t))
                      for s, t in zip(out_shapes, out_dtypes))
    in_avals = tuple(jax.ShapeDtypeStruct(s, t)
                     for s, t in zip(in_shapes, in_dtypes))
    def _fwd_cb(*xs):
        # is_train is re-derived at CALLBACK time, not closed over at trace
        # time: under hybridize the first trace's value would otherwise be
        # frozen into every later call (the reference passes per-call
        # is_train to CustomOp.forward).  ambient_is_train() (not
        # is_training()) because pure_callback may run on an XLA runtime
        # thread whose thread-local autograd state was never set.
        is_train = autograd.ambient_is_train()
        in_data = _writable(xs)
        out_data = [_np.zeros(s, t) for s, t in zip(out_shapes, out_dtypes)]
        op.forward(is_train, ["write"] * n_out, in_data, out_data, [])
        return tuple(out_data)

    def _bwd_cb(*flat):
        og = _writable(flat[:n_out])
        ind = _writable(flat[n_out:n_out + n_args])
        outd = _writable(flat[n_out + n_args:])
        in_grad = [_np.zeros(s, t) for s, t in zip(in_shapes, in_dtypes)]
        op.backward(["write"] * n_args, og, ind, outd, in_grad, [])
        return tuple(in_grad)

    @jax.custom_vjp
    def run(*xs):
        return jax.pure_callback(_fwd_cb, out_avals, *xs)

    def run_fwd(*xs):
        ys = jax.pure_callback(_fwd_cb, out_avals, *xs)
        return ys, (xs, ys)

    def run_bwd(res, cts):
        xs, ys = res
        gs = jax.pure_callback(_bwd_cb, in_avals, *cts, *xs, *ys)
        return tuple(gs)

    run.defvjp(run_fwd, run_bwd)

    jax_in = [x._jax for x in nd_in]
    traced = any(isinstance(v, jax.core.Tracer) for v in jax_in)
    if traced:
        # inside a hybridize/jit trace: stay traceable via pure_callback
        # (XLA host send/recv plays the role of custom.cc's engine thread).
        outs = run(*jax_in)
        return ([NDArray(o, ctx=ctx) for o in outs][0] if n_out == 1
                else [NDArray(o, ctx=ctx) for o in outs])
    # eager: execute the numpy callback directly on host values — no
    # callback primitive, so it works on every backend (the reference's
    # CustomOperator also runs the python callback synchronously on host).
    from .ndarray.ndarray import _put
    host_in = [_np.asarray(v) for v in jax_in]
    host_out = _fwd_cb(*host_in)
    outs = tuple(_put(o, ctx) for o in host_out)
    if autograd.is_recording():
        def tape_vjp(cts):
            gs = _bwd_cb(*[_np.asarray(c) for c in cts], *host_in, *host_out)
            return tuple(jnp.asarray(g) for g in gs)
        wrapped = autograd.record_custom(tape_vjp, nd_in, outs, ctx,
                                         name="Custom:%s" % op_type)
        outs_nd = wrapped if isinstance(wrapped, list) else [wrapped]
    else:
        outs_nd = [NDArray(o, ctx=ctx) for o in outs]
    return outs_nd[0] if n_out == 1 else outs_nd
