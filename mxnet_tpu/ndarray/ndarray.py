"""NDArray: the imperative tensor.

Reference: include/mxnet/ndarray.h (class NDArray), src/ndarray/ndarray.cc
(CopyFromTo, NDArray::Save/Load), python/mxnet/ndarray/ndarray.py
(class NDArray, asnumpy, attach_grad, __getitem__).

TPU-native design
-----------------
The reference NDArray is a ref-counted chunk of device memory plus an engine
variable used for async dependency tracking.  Here the chunk holds a
``jax.Array`` (a PJRT HBM buffer): dispatch is async by construction, the
engine variable's role is played by the buffer's definition event, and
``wait_to_read`` is ``block_until_ready`` (SURVEY.md §3.2 TPU mapping).

Mutability over an immutable substrate: MXNet NDArrays are mutable
(``a[:] = x``, fused optimizer updates write weights in place) and slices are
*views* that write through to their base.  We keep a mutable ``_Chunk`` cell
holding the current jax.Array; in-place writes functionally update the root
array (``data.at[idx].set(v)``) and swap the cell.  Views record their basic
index into the root chunk and read/write through it.  A version counter on the
chunk lets views cache their materialized value.
"""
from __future__ import annotations

import numbers
import struct
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as _np
import jax
import jax.numpy as jnp

from ..base import MXNetError, get_env
from ..device import Context, current_context, cpu
from ..engine import engine
from ..ops.registry import get_op, cached_jit
from .. import profiler as _profiler
from .. import amp as _amp

__all__ = ["NDArray", "invoke", "array", "zeros", "ones", "full", "empty",
           "arange", "zeros_like", "ones_like", "concatenate", "stack_arrays",
           "save", "load", "save_bytes", "load_bytes", "waitall",
           "from_jax", "DTYPE_TO_FLAG", "FLAG_TO_DTYPE"]

# mshadow type flags (3rdparty/mshadow/mshadow/base.h TypeFlag)
DTYPE_TO_FLAG = {
    _np.dtype("float32"): 0, _np.dtype("float64"): 1, _np.dtype("float16"): 2,
    _np.dtype("uint8"): 3, _np.dtype("int32"): 4, _np.dtype("int8"): 5,
    _np.dtype("int64"): 6, _np.dtype("bool"): 7, _np.dtype("int16"): 8,
    _np.dtype("uint16"): 9, _np.dtype("uint32"): 10, _np.dtype("uint64"): 11,
    _np.dtype(jnp.bfloat16): 12,
}
FLAG_TO_DTYPE = {v: k for k, v in DTYPE_TO_FLAG.items()}


def _default_dtype():
    return _np.dtype(get_env("MXNET_DEFAULT_DTYPE", "float32"))


class _Chunk:
    """Mutable cell holding the current root jax.Array + a write version."""
    __slots__ = ("data", "version", "ctx", "__weakref__")

    def __init__(self, data: jax.Array, ctx: Context):
        self.data = data
        self.version = 0
        self.ctx = ctx
        # concrete arrays only — tracers (hybridize/jit trace time) must not
        # leak into the engine's live set
        if isinstance(data, jax.Array) and not isinstance(data, jax.core.Tracer):
            engine.track(self)

    def write(self, new_data: jax.Array) -> None:
        self.data = new_data
        self.version += 1


def _put(value, ctx: Context) -> jax.Array:
    return jax.device_put(value, ctx.jax_device)


class NDArray:
    __slots__ = ("_chunk", "_index", "_vshape", "_cached", "_cached_version",
                 "_grad", "_grad_req", "_ag_node", "_grad_hook",
                 "__weakref__")

    # higher than numpy's so ndarray.__op__(numpy) defers to us
    __array_priority__ = 1000.0

    def __init__(self, data: jax.Array, ctx: Optional[Context] = None,
                 _chunk: Optional[_Chunk] = None, _index=None, _vshape=None):
        if _chunk is not None:
            self._chunk = _chunk
        else:
            ctx = ctx or current_context()
            self._chunk = _Chunk(data, ctx)
        self._index = _index          # basic index into root chunk, or None
        self._vshape = _vshape        # reshape-view target shape, or None
        self._cached = None
        self._cached_version = -1
        self._grad: Optional[NDArray] = None
        self._grad_req: str = "null"
        self._ag_node = None          # autograd tape node that produced this
        # overlap scheduling (ISSUE 5): set on a GRAD buffer, called the
        # moment backward finalizes its value — lets the Trainer launch a
        # fusion bucket's exchange mid-backward
        self._grad_hook = None

    # ------------------------------------------------------------------
    # raw value access
    # ------------------------------------------------------------------
    @property
    def _jax(self) -> jax.Array:
        ch = self._chunk
        if self._index is None and self._vshape is None:
            return ch.data
        if self._cached_version == ch.version and self._cached is not None:
            return self._cached
        val = ch.data
        if self._index is not None:
            val = val[self._index]
        if self._vshape is not None:
            val = val.reshape(self._vshape)
        self._cached = val
        self._cached_version = ch.version
        return val

    def _set_jax(self, value: jax.Array) -> None:
        """Whole-array in-place write (the `a[:] = x` / optimizer path)."""
        ch = self._chunk
        if self._index is None and self._vshape is None:
            ch.write(value)
        elif self._index is not None and self._vshape is None:
            ch.write(ch.data.at[self._index].set(value))
        else:  # reshape view of root
            ch.write(value.reshape(ch.data.shape).astype(ch.data.dtype))
        engine.maybe_sync(ch.data)

    # ------------------------------------------------------------------
    # basic properties
    # ------------------------------------------------------------------
    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self._jax.shape)

    @property
    def dtype(self):
        return _np.dtype(self._jax.dtype)

    @property
    def size(self) -> int:
        return int(self._jax.size)

    @property
    def ndim(self) -> int:
        return self._jax.ndim

    @property
    def context(self) -> Context:
        return self._chunk.ctx

    ctx = context
    device = context

    @property
    def stype(self) -> str:
        return "default"

    @property
    def T(self) -> "NDArray":
        return invoke("transpose", self)

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __bool__(self):
        if self.size != 1:
            raise ValueError("The truth value of an NDArray with multiple "
                             "elements is ambiguous.")
        return bool(self.asscalar())

    def __repr__(self):
        try:
            arr = self.asnumpy()
            body = _np.array2string(arr, precision=4, threshold=20)
        except Exception as e:  # async error surfaces here, like the reference
            body = "<unreadable: %s>" % e
        return "%s\n<NDArray %s @%s>" % (
            body, "x".join(str(d) for d in self.shape), self.context)

    # ------------------------------------------------------------------
    # sync / host transfer
    # ------------------------------------------------------------------
    def wait_to_read(self) -> None:
        engine.wait_for_var(self._jax)

    def wait_to_write(self) -> None:
        engine.wait_for_var(self._chunk.data)

    def asnumpy(self) -> _np.ndarray:
        """Sync point: device→host copy (reference: NDArray.asnumpy)."""
        return _np.asarray(self._jax)

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __index__(self):
        if self.ndim == 0 or self.size == 1:
            return int(self.asscalar())
        raise TypeError("only integer scalar arrays can be converted to index")

    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    # numpy interop protocols (reference: mx.np.ndarray implements
    # __array_ufunc__/__array_function__ so numpy-API code operates on
    # MXNet arrays without a host copy): route numpy ufuncs/functions onto
    # the jnp implementations, returning NDArray — device-resident.
    def __array_ufunc__(self, ufunc, method, *inputs, **kwargs):
        if method != "__call__" or kwargs:
            # reductions / dtype= / where= / casting= have numpy semantics
            # we don't replicate on device: run them on HOST numpy (the
            # pre-protocol __array__ behavior; NotImplemented would raise)
            vals = [x.asnumpy() if isinstance(x, NDArray) else x
                    for x in inputs]
            return getattr(ufunc, method)(*vals, **kwargs)
        from .. import numpy as _mxnp
        # prefer the mx.np implementation: registry-backed ops there go
        # through invoke(), so the call RECORDS on the autograd tape
        impl = getattr(_mxnp, ufunc.__name__, None)
        if impl is not None and callable(impl):
            try:
                return impl(*inputs)
            except (TypeError, MXNetError):
                pass
        jfn = getattr(jnp, ufunc.__name__, None)
        if jfn is None:
            return NotImplemented
        vals = [x._jax if isinstance(x, NDArray) else x for x in inputs]
        try:
            out = jfn(*vals)
        except TypeError:
            return NotImplemented
        if isinstance(out, tuple):
            return tuple(NDArray(o, ctx=self.context) for o in out)
        return NDArray(out, ctx=self.context)

    def __array_function__(self, func, types, args, kwargs):
        from .. import numpy as _mxnp
        impl = getattr(_mxnp, func.__name__, None)
        if impl is not None and callable(impl):
            try:
                return impl(*args, **kwargs)
            except (TypeError, MXNetError):
                pass  # numpy-only kwargs (where=, ...) -> host fallback

        # no device implementation: preserve the pre-protocol behavior by
        # coercing to host numpy (the __array__ fallback numpy used before
        # __array_function__ existed on this type)
        def coerce(x):
            if isinstance(x, NDArray):
                return x.asnumpy()
            if isinstance(x, (list, tuple)):
                return type(x)(coerce(v) for v in x)
            return x
        return func(*[coerce(a) for a in args],
                    **{k: coerce(v) for k, v in kwargs.items()})

    # pickling (reference: NDArray is picklable via its binary serialization;
    # used by Trainer.save_states / kvstore set_optimizer)
    def __reduce__(self):
        return (_unpickle_ndarray, (self.asnumpy(), str(self.dtype)
                                    if self.dtype != jnp.bfloat16 else
                                    "bfloat16"))

    # dlpack bridge (reference: NDArray::ToDLPack / FromDLPack)
    def __dlpack__(self, stream=None):
        return self._jax.__dlpack__()

    def __dlpack_device__(self):
        return self._jax.__dlpack_device__()

    # ------------------------------------------------------------------
    # copies / context movement
    # ------------------------------------------------------------------
    def copy(self) -> "NDArray":
        return NDArray(jnp.copy(self._jax), ctx=self.context)

    def copyto(self, other: Union["NDArray", Context]) -> "NDArray":
        """Reference: CopyFromTo — cross-device copy through the engine."""
        if isinstance(other, Context):
            return NDArray(_put(self._jax, other), ctx=other)
        if not isinstance(other, NDArray):
            raise TypeError("copyto expects NDArray or Context")
        if other.context == self.context:
            # same device: device_put is a no-op and a same-dtype astype
            # returns an Array SHARING this buffer — copyto must produce
            # an independent value (the whole-step compiled lane donates
            # parameter buffers; an alias would be deleted with them)
            val = self._jax.astype(other.dtype) \
                if other.dtype != self.dtype else jnp.copy(self._jax)
        else:
            val = _put(self._jax, other.context).astype(other.dtype)
        other._set_jax(val)
        return other

    def as_in_context(self, ctx: Context) -> "NDArray":
        if ctx == self.context:
            return self
        return self.copyto(ctx)

    as_in_ctx = as_in_context

    def as_nd_ndarray(self):
        return self

    def astype(self, dtype, copy: bool = True) -> "NDArray":
        dtype = _np.dtype(jnp.bfloat16) if dtype in ("bfloat16", jnp.bfloat16) \
            else _np.dtype(dtype)
        if not copy and self.dtype == dtype:
            return self
        return invoke("cast", self, dtype=str(dtype) if dtype != jnp.bfloat16 else "bfloat16")

    def tolist(self):
        return self.asnumpy().tolist()

    # ------------------------------------------------------------------
    # autograd surface (reference: attach_grad / .grad / detach / backward)
    # ------------------------------------------------------------------
    def attach_grad(self, grad_req: str = "write", stype=None,
                    lazy: bool = False) -> None:
        """`lazy`: the gradient buffer is allocated when first asked for
        (`grad`, or a backward pass that writes it) - a Gluon parameter's
        way: a compiled step computes its gradients inside its program and
        never reads the buffer, which is as large as the parameter."""
        from .. import autograd
        self._grad = None if lazy else NDArray(
            jnp.zeros(self.shape, self.dtype), ctx=self.context)
        self._grad_req = grad_req
        self._ag_node = autograd.VariableNode(self)

    @property
    def grad(self) -> Optional["NDArray"]:
        if self._grad is None and self._grad_req != "null":
            self._grad = NDArray(jnp.zeros(self.shape, self.dtype),
                                 ctx=self.context)
        return self._grad

    def detach(self) -> "NDArray":
        out = NDArray(None, _chunk=self._chunk, _index=self._index,
                      _vshape=self._vshape)
        return out

    def backward(self, out_grad: Optional["NDArray"] = None,
                 retain_graph: bool = False, train_mode: bool = True) -> None:
        from .. import autograd
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    # ------------------------------------------------------------------
    # indexing
    # ------------------------------------------------------------------
    @staticmethod
    def _is_basic_index(key) -> bool:
        if isinstance(key, tuple):
            return all(isinstance(k, (slice, numbers.Integral)) or k is None
                       or k is Ellipsis for k in key)
        return isinstance(key, (slice, numbers.Integral)) or key is None \
            or key is Ellipsis

    def _unwrap_key(self, key):
        def conv(k):
            if isinstance(k, NDArray):
                return k._jax
            return k
        if isinstance(key, tuple):
            return tuple(conv(k) for k in key)
        return conv(key)

    def _check_bounds(self, key) -> None:
        """Basic integer indices must bound-check eagerly: JAX clamps, but
        MXNet (and Python's iteration protocol) require IndexError."""
        ks = key if isinstance(key, tuple) else (key,)
        axis = 0
        shape = self.shape
        for k in ks:
            if k is Ellipsis:
                axis = len(shape) - (len([x for x in ks if x is not None]) -
                                     ks.index(k) - 1)
                continue
            if k is None:
                continue
            if isinstance(k, numbers.Integral):
                if axis >= len(shape):
                    raise IndexError("too many indices for array")
                n = shape[axis]
                if not (-n <= int(k) < n):
                    raise IndexError(
                        "index %d is out of bounds for axis %d with size %d"
                        % (k, axis, n))
            axis += 1

    def __getitem__(self, key) -> "NDArray":
        key = self._unwrap_key(key)
        from .. import autograd
        if autograd.is_recording() and self._ag_node is not None and \
                self._is_basic_index(key):
            # recorded copy: keeps the gradient chain (views carry no node)
            self._check_bounds(key)
            return invoke("_internal_getitem", self,
                          key=key if isinstance(key, tuple) else (key,))
        if self._is_basic_index(key) and self._vshape is None:
            self._check_bounds(key)
            # view sharing the chunk: writes through (MXNet slice semantics)
            if self._index is None:
                new_index = key if isinstance(key, tuple) else (key,)
            else:
                # compose: slice the already-sliced region lazily by chaining.
                # We store a chained index as a nested marker.
                new_index = _compose_index(self._chunk.data.shape,
                                           self._index,
                                           key if isinstance(key, tuple) else (key,))
                if new_index is None:   # composition not expressible: copy
                    return NDArray(self._jax[key], ctx=self.context)
            out = NDArray(None, _chunk=self._chunk, _index=new_index)
            # basic indexing with out-of-range -> let jax/numpy semantics apply
            _ = out.shape
            return out
        # advanced indexing returns a copy (same as the reference)
        val = self._jax[key]
        return NDArray(val, ctx=self.context)

    def __setitem__(self, key, value) -> None:
        key = self._unwrap_key(key)
        if isinstance(value, NDArray):
            value = value._jax
        elif isinstance(value, (numbers.Number, _np.ndarray, list, tuple)):
            value = jnp.asarray(value, dtype=self.dtype)
        ch = self._chunk
        full_write = (key == slice(None)) or (
            isinstance(key, tuple) and all(k == slice(None) for k in key))
        if self._index is None and self._vshape is None:
            if full_write:
                ch.write(jnp.broadcast_to(value, self.shape).astype(self.dtype)
                         if getattr(value, "shape", None) != self.shape
                         or value.dtype != self.dtype else value)
            else:
                ch.write(ch.data.at[key].set(value))
        else:
            # view: read-modify-write through the root chunk
            sub = self._jax
            sub = sub.at[key].set(value) if not full_write else \
                jnp.broadcast_to(value, sub.shape).astype(sub.dtype)
            if self._vshape is not None:
                ch.write(sub.reshape(ch.data.shape).astype(ch.data.dtype))
            else:
                ch.write(ch.data.at[self._index].set(sub))
        self._cached = None
        engine.maybe_sync(ch.data)

    # ------------------------------------------------------------------
    # reshape view
    # ------------------------------------------------------------------
    def reshape(self, *shape, **kwargs) -> "NDArray":
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        shape = _infer_reshape(self.shape, shape)
        from .. import autograd
        if (autograd.is_recording() and self._ag_node is not None) \
                or _sym_tracer is not None:
            # recorded op-form reshape: a view would drop the tape node and
            # silently cut the gradient chain (rnn param packing relies on
            # grads flowing through reshape) - and under the symbol tracer
            # it would be no node of the graph: a block that ENDS in a
            # reshape (PixelShuffle2D) exported only while the view
            # happened to reuse the address of a recorded intermediate
            return invoke("reshape", self, shape=shape)
        if self._index is None and self._vshape is None:
            # view of the root chunk: writes through (reference semantics)
            return NDArray(None, _chunk=self._chunk, _vshape=shape)
        return NDArray(self._jax.reshape(shape), ctx=self.context)

    def reshape_like(self, other: "NDArray") -> "NDArray":
        return self.reshape(other.shape)

    # ------------------------------------------------------------------
    # arithmetic operators — all dispatch through the op registry so that
    # autograd records them uniformly
    # ------------------------------------------------------------------
    def _binop(self, name, other, reverse=False):
        if isinstance(other, numbers.Number):
            other = full((), other, ctx=self.context, dtype=self.dtype)
        elif isinstance(other, (_np.ndarray, list, tuple)):
            other = array(other, ctx=self.context)
        if not isinstance(other, NDArray):
            return NotImplemented
        return invoke(name, other, self) if reverse else invoke(name, self, other)

    def __add__(self, o):  return self._binop("broadcast_add", o)
    def __radd__(self, o): return self._binop("broadcast_add", o, True)
    def __sub__(self, o):  return self._binop("broadcast_sub", o)
    def __rsub__(self, o): return self._binop("broadcast_sub", o, True)
    def __mul__(self, o):  return self._binop("broadcast_mul", o)
    def __rmul__(self, o): return self._binop("broadcast_mul", o, True)
    def __truediv__(self, o):  return self._binop("broadcast_div", o)
    def __rtruediv__(self, o): return self._binop("broadcast_div", o, True)
    def __mod__(self, o):  return self._binop("broadcast_mod", o)
    def __rmod__(self, o): return self._binop("broadcast_mod", o, True)
    def __pow__(self, o):  return self._binop("broadcast_power", o)
    def __rpow__(self, o): return self._binop("broadcast_power", o, True)
    def __matmul__(self, o): return invoke("dot", self, o)
    def __neg__(self): return invoke("negative", self)
    def __abs__(self): return invoke("abs", self)

    # comparisons: legacy mx.nd returns float32 0/1; under npx.set_np()
    # they switch to the _npi numpy-semantics ops (bool outputs, so
    # x[x > 0] boolean masking works) — the reference's set_np contract
    @staticmethod
    def _cmp_op(legacy, npi):
        from .. import npx as _npx
        return npi if _npx.is_np_array() else legacy

    def __eq__(self, o):
        if o is None:
            return False
        return self._binop(self._cmp_op("broadcast_equal", "_npi_equal"), o)
    def __ne__(self, o):
        if o is None:
            return True
        return self._binop(self._cmp_op("broadcast_not_equal",
                                        "_npi_not_equal"), o)
    def __gt__(self, o):
        return self._binop(self._cmp_op("broadcast_greater",
                                        "_npi_greater"), o)
    def __ge__(self, o):
        return self._binop(self._cmp_op("broadcast_greater_equal",
                                        "_npi_greater_equal"), o)
    def __lt__(self, o):
        return self._binop(self._cmp_op("broadcast_lesser", "_npi_less"), o)
    def __le__(self, o):
        return self._binop(self._cmp_op("broadcast_lesser_equal",
                                        "_npi_less_equal"), o)

    def __hash__(self):
        return id(self)

    # in-place ops write through the chunk
    def _ibinop(self, name, other):
        res = self._binop(name, other)
        if res is NotImplemented:
            return res
        self._set_jax(res._jax.astype(self.dtype))
        return self

    def __iadd__(self, o): return self._ibinop("broadcast_add", o)
    def __isub__(self, o): return self._ibinop("broadcast_sub", o)
    def __imul__(self, o): return self._ibinop("broadcast_mul", o)
    def __itruediv__(self, o): return self._ibinop("broadcast_div", o)

    # ------------------------------------------------------------------
    # method forms of common ops (generated namespace adds the rest)
    # ------------------------------------------------------------------
    def sum(self, axis=None, keepdims=False, **kw):
        return invoke("sum", self, axis=_norm_axis(axis), keepdims=keepdims)

    def mean(self, axis=None, keepdims=False, **kw):
        return invoke("mean", self, axis=_norm_axis(axis), keepdims=keepdims)

    def max(self, axis=None, keepdims=False, **kw):
        return invoke("max", self, axis=_norm_axis(axis), keepdims=keepdims)

    def min(self, axis=None, keepdims=False, **kw):
        return invoke("min", self, axis=_norm_axis(axis), keepdims=keepdims)

    def argmax(self, axis=None, keepdims=False):
        return invoke("argmax", self, axis=axis, keepdims=keepdims)

    def argmin(self, axis=None, keepdims=False):
        return invoke("argmin", self, axis=axis, keepdims=keepdims)

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (list, tuple)):
            axes = tuple(axes[0])
        return invoke("transpose", self, axes=axes if axes else None)

    def flatten(self):
        return invoke("flatten", self)

    def expand_dims(self, axis):
        return invoke("expand_dims", self, axis=axis)

    def squeeze(self, axis=None):
        return invoke("squeeze", self, axis=axis)

    def broadcast_to(self, shape):
        return invoke("broadcast_to", self, shape=tuple(shape))

    def broadcast_like(self, other):
        return invoke("broadcast_to", self, shape=other.shape)

    def clip(self, a_min=None, a_max=None):
        return invoke("clip", self, a_min=a_min, a_max=a_max)

    def abs(self):
        return invoke("abs", self)

    def sqrt(self):
        return invoke("sqrt", self)

    def exp(self):
        return invoke("exp", self)

    def log(self):
        return invoke("log", self)

    def relu(self):
        return invoke("relu", self)

    def sigmoid(self):
        return invoke("sigmoid", self)

    def tanh(self):
        return invoke("tanh", self)

    def softmax(self, axis=-1):
        return invoke("softmax", self, axis=axis)

    def log_softmax(self, axis=-1):
        return invoke("log_softmax", self, axis=axis)

    def dot(self, other):
        return invoke("dot", self, other)

    def take(self, indices, axis=0, mode="clip"):
        return invoke("take", self, indices, axis=axis, mode=mode)

    def one_hot(self, depth, on_value=1.0, off_value=0.0, dtype="float32"):
        return invoke("one_hot", self, depth=depth, on_value=on_value,
                      off_value=off_value, dtype=dtype)

    def tostype(self, stype):
        if stype != "default":
            from .sparse import cast_storage
            return cast_storage(self, stype)
        return self

    def slice_axis(self, axis, begin, end):
        return invoke("slice_axis", self, axis=axis, begin=begin, end=end)

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        return invoke("split", self, num_outputs=num_outputs, axis=axis,
                      squeeze_axis=squeeze_axis)

    def norm(self, ord=2, axis=None, keepdims=False):
        return invoke("norm", self, ord=ord, axis=_norm_axis(axis), keepdims=keepdims)

    def save(self, fname: str):
        save(fname, self)


# ---------------------------------------------------------------------------
# index composition for chained basic views
# ---------------------------------------------------------------------------

def _expand_index(shape, idx):
    """Expand an index tuple to one entry per axis of `shape` (no newaxis)."""
    idx = list(idx)
    if Ellipsis in idx:
        pos = idx.index(Ellipsis)
        n_missing = len(shape) - (len(idx) - 1 - sum(1 for k in idx if k is None))
        idx[pos:pos + 1] = [slice(None)] * (n_missing)
    while len([k for k in idx if k is not None]) < len(shape):
        idx.append(slice(None))
    return idx


def _compose_index(root_shape, outer, inner):
    """Compose two basic indices: root[outer][inner] == root[composed].
    Returns None when not expressible as a single basic index."""
    if any(k is None for k in list(outer) + list(inner)):
        return None
    outer = _expand_index(root_shape, outer)
    # shape after outer
    inter_axes = []  # (root_axis, slice) for surviving axes
    for ax, k in enumerate(outer):
        if isinstance(k, slice):
            inter_axes.append((ax, k))
    inner = _expand_index(tuple(len(range(*k.indices(root_shape[ax])))
                                for ax, k in inter_axes), inner)
    if len(inner) > len(inter_axes):
        return None
    composed = list(outer)
    for (ax, sl), k in zip(inter_axes, inner):
        start, stop, step = sl.indices(root_shape[ax])
        n = len(range(start, stop, step))
        if isinstance(k, numbers.Integral):
            kk = int(k)
            if kk < 0:
                kk += n
            if not (0 <= kk < n):
                raise IndexError("index %d out of bounds for axis %d with size %d"
                                 % (k, ax, n))
            composed[ax] = start + kk * step
        elif isinstance(k, slice):
            s2, e2, st2 = k.indices(n)
            new_start = start + s2 * step
            new_step = step * st2
            cnt = len(range(s2, e2, st2))
            new_stop = new_start + cnt * new_step
            if new_step < 0 and new_stop < 0:
                new_stop = None
            composed[ax] = slice(new_start, new_stop, new_step)
        else:
            return None
    return tuple(composed)


def _infer_reshape(old_shape, new_shape):
    """MXNet reshape special codes: 0 (keep), -1 (infer), -2.. not supported."""
    out = []
    for i, d in enumerate(new_shape):
        if d == 0:
            out.append(old_shape[i])
        else:
            out.append(int(d))
    if out.count(-1) > 1:
        raise ValueError("can only specify one unknown dimension")
    if -1 in out:
        known = 1
        for d in out:
            if d != -1:
                known *= d
        total = 1
        for d in old_shape:
            total *= d
        out[out.index(-1)] = total // max(known, 1)
    return tuple(out)


def _norm_axis(axis):
    if isinstance(axis, list):
        return tuple(axis)
    return axis


# ---------------------------------------------------------------------------
# eager dispatch (reference: MXImperativeInvokeEx -> Imperative::Invoke)
# ---------------------------------------------------------------------------

# Symbol-trace hook (set by mxnet_tpu.symbol.trace_block): when non-None,
# every invoke() is also recorded as a graph node — the imperative run IS
# the trace (reference: hybrid_forward Symbol-proxy tracing).
_sym_tracer = None
_autograd = None


def invoke(op_name: str, *inputs, out=None, **params):
    # positional-attr extraction happens HERE, before dispatch AND before
    # the symbol tracer records — both must see the canonical call
    inputs = get_op(op_name).split_pos_attrs(inputs, params, NDArray)
    if _profiler.IMPERATIVE:
        with _profiler.op_span(op_name):
            ret = _invoke_impl(op_name, *inputs, out=out, **params)
            if _profiler.want_sync():
                jax.tree_util.tree_map(
                    lambda x: jax.block_until_ready(x._jax)
                    if isinstance(x, NDArray) else x, ret)
    else:
        ret = _invoke_impl(op_name, *inputs, out=out, **params)
    tracer = _sym_tracer
    if tracer is not None:
        tracer.record(op_name,
                      {k: v for k, v in params.items()
                       if k not in ("ctx", "name")},
                      inputs, ret)
    return ret


def _invoke_impl(op_name: str, *inputs, out=None, **params):
    """Invoke a registered op on NDArrays (HOT LOOP 1, SURVEY.md §3.2).

    - unwraps inputs to jax.Arrays (committed to their context's device)
    - if autograd is recording and the op is differentiable, routes through
      the tape (jax.vjp captures the backward closure);
    - otherwise calls the per-(op, params) jit-cached executable.
    """
    op = get_op(op_name)
    engine.count_dispatch()
    # MXNet op calls accept ctx= (output placement) and name= (symbol compat)
    ctx_kw = params.pop("ctx", None)
    params.pop("name", None)
    jax_in: List[jax.Array] = []
    ctx = ctx_kw
    for x in inputs:
        if isinstance(x, NDArray):
            jax_in.append(x._jax)
            if ctx is None:
                ctx = x.context
        elif isinstance(x, (numbers.Number, _np.ndarray, jnp.ndarray)):
            jax_in.append(jnp.asarray(x))
        elif x is None:
            jax_in.append(None)
        elif hasattr(x, "stype") and hasattr(x, "tostype"):
            # sparse input.  no_jit ops (graph/sampling ops) take the sparse
            # object raw; everything else gets the reference's storage
            # FALLBACK semantics — densify with a one-time warning
            # (src/operator/elemwise_op_common.h dispatch-fallback +
            # "storage fallback" LogStorageFallback).
            if op.no_jit:
                jax_in.append(x)
            else:
                _warn_storage_fallback(op_name, x.stype)
                jax_in.append(x.tostype("default")._jax)
            if ctx is None:
                ctx = x.context
        else:
            raise TypeError("invoke(%s): bad input type %s" % (op_name, type(x)))
    ctx = ctx or current_context()
    amp_state = _amp.current_state()
    if amp_state is not None:
        jax_in = amp_state.cast_inputs(op.name, params, jax_in)
    if op.needs_rng:
        from ..ops import random as _rnd
        jax_in.insert(0, _rnd.next_key())

    global _autograd
    if _autograd is None:
        from .. import autograd as _autograd  # lazy: breaks import cycle
    autograd = _autograd
    if autograd.is_recording() and op.differentiable:
        outs = autograd.record_op(op, params, inputs, jax_in, ctx)
    elif op.no_jit:
        # dynamic-output-shape op: eager only, outside the jit cache
        outs = op.fn(*jax_in, **params)
        outs = _wrap_outputs(op, outs, ctx)
    else:
        fn = cached_jit(op.name, params)
        outs = fn(*jax_in)
        if ctx_kw is not None:
            outs = jax.tree_util.tree_map(lambda o: _put(o, ctx_kw), outs)
        outs = _wrap_outputs(op, outs, ctx)
    # aux-state write-back (BatchNorm moving stats ≈ reference aux arrays):
    # designated outputs are stored into their input NDArrays in place and
    # stripped from the visible return
    # aux_writeback may be a callable of the call params for ops with a
    # variable arity (multi_sgd fleets: the output->input map depends on
    # num_weights)
    awb = op.aux_writeback(params) if callable(op.aux_writeback) \
        else op.aux_writeback
    if awb and isinstance(outs, (list, tuple)):
        visible = []
        for i, o in enumerate(outs):
            tgt_idx = awb.get(i)
            if tgt_idx is not None:
                tgt = inputs[tgt_idx]
                if isinstance(tgt, NDArray):
                    tgt._set_jax(o._jax.astype(tgt.dtype))
            else:
                visible.append(o)
        outs = visible[0] if len(visible) == 1 else visible
    # in-place ops write result back through the mutated input's chunk
    if op.mutates_input is not None:
        target = inputs[op.mutates_input]
        res = outs[0] if isinstance(outs, (list, tuple)) else outs
        target._set_jax(res._jax)
        return target
    if out is not None:
        src = outs[0] if isinstance(outs, (list, tuple)) else outs
        out._set_jax(src._jax.astype(out.dtype))
        return out
    return outs


_STORAGE_FALLBACK_WARNED = set()


def _warn_storage_fallback(op_name, stype):
    if (op_name, stype) not in _STORAGE_FALLBACK_WARNED:
        _STORAGE_FALLBACK_WARNED.add((op_name, stype))
        import warnings
        warnings.warn(
            "op %s has no sparse implementation for stype=%r; converting "
            "to dense (reference: MXNet storage-fallback warning)"
            % (op_name, stype))


def _wrap_one(o, ctx):
    # ops may return already-wrapped NDArrays / sparse arrays (no_jit
    # graph ops); pass them through instead of re-wrapping
    if isinstance(o, NDArray) or hasattr(o, "stype"):
        return o
    return NDArray(o, ctx=ctx)


def _wrap_outputs(op, outs, ctx):
    if isinstance(outs, tuple) and op.num_outputs != 1:
        wrapped = [_wrap_one(o, ctx) for o in outs]
        engine.maybe_sync(wrapped[0]._jax
                          if isinstance(wrapped[0], NDArray) else None)
        return wrapped
    if isinstance(outs, (tuple, list)):
        outs = outs[0] if len(outs) == 1 and op.num_outputs == 1 else outs
    if isinstance(outs, (tuple, list)):
        return [_wrap_one(o, ctx) for o in outs]
    o = _wrap_one(outs, ctx)
    if isinstance(o, NDArray):
        engine.maybe_sync(o._jax)
    return o


def _unpickle_ndarray(value: _np.ndarray, dtype: str) -> NDArray:
    dt = jnp.bfloat16 if dtype == "bfloat16" else _np.dtype(dtype)
    return NDArray(jnp.asarray(value, dtype=dt))


def from_jax(value, ctx: Optional[Context] = None) -> NDArray:
    return NDArray(value, ctx=ctx or current_context())


# ---------------------------------------------------------------------------
# creation functions (reference: python/mxnet/ndarray/utils.py + ndarray.py)
# ---------------------------------------------------------------------------

def _creation_dtype(dtype):
    if dtype is None:
        return _default_dtype()
    if dtype in ("bfloat16", jnp.bfloat16):
        return jnp.bfloat16
    return _np.dtype(dtype)


def array(source, ctx: Optional[Context] = None, dtype=None) -> NDArray:
    ctx = ctx or current_context()
    if isinstance(source, NDArray):
        src = source._jax
        if dtype is not None:
            src = src.astype(_creation_dtype(dtype))
        return NDArray(_put(src, ctx), ctx=ctx)
    is_np = isinstance(source, _np.ndarray) or hasattr(source, "__array__")
    arr = _np.asarray(source)
    if dtype is None:
        if not is_np:
            dtype = _default_dtype()   # python lists → float32 (reference)
        elif arr.dtype == _np.float64:
            dtype = _default_dtype()   # no x64 on TPU path: narrow to f32
    if dtype is not None:
        arr = arr.astype(_creation_dtype(dtype))
    return NDArray(_put(arr, ctx), ctx=ctx)


def zeros(shape, ctx=None, dtype=None, **kw) -> NDArray:
    ctx = ctx or current_context()
    shape = (shape,) if isinstance(shape, numbers.Integral) else tuple(shape)
    return NDArray(_put(jnp.zeros(shape, _creation_dtype(dtype)), ctx), ctx=ctx)


def ones(shape, ctx=None, dtype=None, **kw) -> NDArray:
    ctx = ctx or current_context()
    shape = (shape,) if isinstance(shape, numbers.Integral) else tuple(shape)
    return NDArray(_put(jnp.ones(shape, _creation_dtype(dtype)), ctx), ctx=ctx)


def full(shape, val, ctx=None, dtype=None, **kw) -> NDArray:
    ctx = ctx or current_context()
    shape = (shape,) if isinstance(shape, numbers.Integral) else tuple(shape)
    return NDArray(_put(jnp.full(shape, val, _creation_dtype(dtype)), ctx), ctx=ctx)


def empty(shape, ctx=None, dtype=None) -> NDArray:
    return zeros(shape, ctx=ctx, dtype=dtype)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None) -> NDArray:
    ctx = ctx or current_context()
    vals = jnp.arange(start, stop, step, _creation_dtype(dtype))
    if repeat != 1:
        vals = jnp.repeat(vals, repeat)
    return NDArray(_put(vals, ctx), ctx=ctx)


def eye(N, M=0, k=0, ctx=None, dtype=None) -> NDArray:
    """Reference: mx.nd.eye (M=0 means square)."""
    ctx = ctx or current_context()
    vals = jnp.eye(int(N), int(M) or None, int(k), _creation_dtype(dtype))
    return NDArray(_put(vals, ctx), ctx=ctx)


def zeros_like(a: NDArray, **kw) -> NDArray:
    return zeros(a.shape, ctx=a.context, dtype=a.dtype)


def ones_like(a: NDArray, **kw) -> NDArray:
    return ones(a.shape, ctx=a.context, dtype=a.dtype)


def concatenate(arrays: Sequence[NDArray], axis=0) -> NDArray:
    return invoke("concat", *arrays, dim=axis)


def stack_arrays(arrays: Sequence[NDArray], axis=0) -> NDArray:
    return invoke("stack", *arrays, axis=axis)


def waitall() -> None:
    engine.wait_for_all()


# ---------------------------------------------------------------------------
# serialization (reference: src/ndarray/ndarray.cc NDArray::Save/Load and
# src/c_api/c_api.cc MXNDArraySave file-dict format)
#
# Byte layout kept compatible with the reference's dense V2 format:
#   file:   uint64 list_magic=0x112, uint64 reserved,
#           uint64 ndarray_count, [each NDArray],
#           uint64 name_count, [uint64 len + utf8 bytes]
#   array:  uint32 NDARRAY_V2_MAGIC=0xF993FAC9, int32 stype(=0 dense? see
#           note: v2 writes stype only for sparse-capable builds; we always
#           write it, and accept both layouts on load),
#           uint32 ndim + uint32 dims..., int32 devtype + int32 devid,
#           int32 type_flag, raw data bytes
# ---------------------------------------------------------------------------

_LIST_MAGIC = 0x112
_NDARRAY_V1_MAGIC = 0xF993FAC8
_NDARRAY_V2_MAGIC = 0xF993FAC9
_NDARRAY_V3_MAGIC = 0xF993FACA


def _write_dense_payload(buf: bytearray, a: _np.ndarray) -> None:
    buf += struct.pack("<I", a.ndim)
    for d in a.shape:
        buf += struct.pack("<I", d)
    buf += struct.pack("<ii", 1, 0)                   # saved ctx: cpu(0)
    flag = DTYPE_TO_FLAG.get(_np.dtype(a.dtype))
    if flag is None:
        a = a.astype(_np.float32)
        flag = 0
    buf += struct.pack("<i", flag)
    if flag == 12:   # bfloat16: numpy can't memmap it; store via uint16 view
        a16 = _np.asarray(jnp.asarray(a, jnp.bfloat16)).view(_np.uint16)
        buf += a16.tobytes()
    else:
        buf += _np.ascontiguousarray(a).tobytes()


def _save_one(buf: bytearray, arr) -> None:
    # sparse stypes round-trip (reference NDArray::Save handles
    # kRowSparseStorage=1 / kCSRStorage=2 with their aux arrays; byte
    # layout here: stype, logical shape, n_aux, aux payloads..., data —
    # self-consistent, unverifiable against reference bytes offline)
    stype = getattr(arr, "stype", "default")
    if stype == "row_sparse":
        buf += struct.pack("<I", _NDARRAY_V2_MAGIC)
        buf += struct.pack("<i", 1)
        buf += struct.pack("<I", len(arr.shape))
        for d in arr.shape:
            buf += struct.pack("<I", d)
        buf += struct.pack("<I", 1)                   # n aux
        _write_dense_payload(buf, arr.indices.asnumpy().astype(_np.int64))
        _write_dense_payload(buf, arr.data.asnumpy())
        return
    if stype == "csr":
        buf += struct.pack("<I", _NDARRAY_V2_MAGIC)
        buf += struct.pack("<i", 2)
        buf += struct.pack("<I", len(arr.shape))
        for d in arr.shape:
            buf += struct.pack("<I", d)
        buf += struct.pack("<I", 2)                   # n aux
        _write_dense_payload(buf, arr.indptr.asnumpy().astype(_np.int64))
        _write_dense_payload(buf, arr.indices.asnumpy().astype(_np.int64))
        _write_dense_payload(buf, arr.data.asnumpy())
        return
    a = arr.asnumpy()
    buf += struct.pack("<I", _NDARRAY_V2_MAGIC)
    buf += struct.pack("<i", 0)                       # kDefaultStorage
    _write_dense_payload(buf, a)


class _Reader:
    def __init__(self, data: bytes):
        self.data = data
        self.pos = 0

    def take(self, fmt: str):
        vals = struct.unpack_from("<" + fmt, self.data, self.pos)
        self.pos += struct.calcsize("<" + fmt)
        return vals if len(vals) > 1 else vals[0]

    def raw(self, n: int) -> bytes:
        b = self.data[self.pos:self.pos + n]
        self.pos += n
        return b


def _read_dense_payload(r: "_Reader"):
    ndim = r.take("I")
    shape = tuple(int(r.take("I")) for _ in range(ndim))
    r.take("ii")                                      # saved ctx
    flag = r.take("i")
    dtype = FLAG_TO_DTYPE[flag]
    count = 1
    for d in shape:
        count *= d
    if flag == 12:
        raw = r.raw(count * 2)
        a = _np.frombuffer(raw, dtype=_np.uint16).reshape(shape)
        return jnp.asarray(a).view(jnp.bfloat16), True
    a = _np.frombuffer(r.raw(count * dtype.itemsize),
                       dtype=dtype).reshape(shape)
    return a, False


def _load_one(r: _Reader):
    magic = r.take("I")
    stype = 0
    if magic == _NDARRAY_V1_MAGIC:
        ndim = r.take("I")
        shape = tuple(int(r.take("I")) for _ in range(ndim))
        r.take("ii")
        flag = r.take("i")
        dtype = FLAG_TO_DTYPE[flag]
        count = 1
        for d in shape:
            count *= d
        a = _np.frombuffer(r.raw(count * dtype.itemsize),
                           dtype=dtype).reshape(shape)
        return array(a, dtype=a.dtype)
    if magic not in (_NDARRAY_V2_MAGIC, _NDARRAY_V3_MAGIC):
        raise MXNetError("invalid NDArray magic 0x%x" % magic)
    stype = r.take("i")
    if stype == 0:
        val, is_bf16 = _read_dense_payload(r)
        if is_bf16:
            return NDArray(val, ctx=current_context())
        return array(val, dtype=val.dtype)
    # sparse: logical shape, n_aux, aux payloads..., data
    from . import sparse as _sp
    ndim = r.take("I")
    shape = tuple(int(r.take("I")) for _ in range(ndim))
    n_aux = r.take("I")
    aux = [_read_dense_payload(r)[0] for _ in range(n_aux)]
    data, _ = _read_dense_payload(r)
    if stype == 1:                                    # row_sparse
        return _sp.RowSparseNDArray(array(data),
                                    array(_np.asarray(aux[0])), shape)
    if stype == 2:                                    # csr
        return _sp.CSRNDArray(array(data), array(_np.asarray(aux[1])),
                              array(_np.asarray(aux[0])), shape)
    raise MXNetError("unknown storage type %d in file" % stype)


def save_bytes(data) -> bytes:
    """Serialize list/dict of NDArrays to the reference's file format."""
    if isinstance(data, NDArray):
        data = [data]
    names: List[str] = []
    arrays: List[NDArray] = []
    if isinstance(data, dict):
        for k, v in data.items():
            names.append(k)
            arrays.append(v)
    else:
        arrays = list(data)
    buf = bytearray()
    buf += struct.pack("<QQ", _LIST_MAGIC, 0)
    buf += struct.pack("<Q", len(arrays))
    for a in arrays:
        _save_one(buf, a)
    buf += struct.pack("<Q", len(names))
    for n in names:
        nb = n.encode("utf-8")
        buf += struct.pack("<Q", len(nb)) + nb
    return bytes(buf)


def load_bytes(raw: bytes):
    r = _Reader(raw)
    magic, _res = r.take("QQ")
    if magic != _LIST_MAGIC:
        raise MXNetError("invalid NDArray file magic")
    n = r.take("Q")
    arrays = [_load_one(r) for _ in range(n)]
    n_names = r.take("Q")
    if n_names == 0:
        return arrays
    names = []
    for _ in range(n_names):
        ln = r.take("Q")
        names.append(r.raw(ln).decode("utf-8"))
    return dict(zip(names, arrays))


def save(fname: str, data) -> None:
    with open(fname, "wb") as f:
        f.write(save_bytes(data))


def load(fname: str):
    with open(fname, "rb") as f:
        return load_bytes(f.read())
