"""Gluon Parameter / Constant / ParameterDict.

Reference: python/mxnet/gluon/parameter.py (class Parameter — deferred shape
init, grad_req, _check_and_get, load semantics; class Constant;
class ParameterDict [v1.x]).

TPU-native notes: a Parameter's storage is an NDArray whose chunk is a PJRT
HBM buffer.  Replication across contexts (the reference's per-GPU copies made
by Trainer/kvstore) keeps the same dict-of-ctx layout; the pod-scale data
path instead shards/replicates via `mxnet_tpu.parallel` meshes.  During a
hybridize trace (CachedOp), `data()` returns the tracer-backed override so
the same layer code runs imperative and traced (see block.py).
"""
from __future__ import annotations

import threading
import uuid
from collections import OrderedDict
from typing import Dict, List, Optional

import numpy as _np

from ..base import MXNetError
from ..device import Context, current_context, cpu
from ..ndarray import ndarray as _nd_mod
from ..ndarray.ndarray import NDArray
from .. import initializer as init_mod

__all__ = ["DeferredInitializationError", "Parameter", "Constant",
           "ParameterDict"]


class DeferredInitializationError(MXNetError):
    """Parameter accessed before its deferred shape was known."""


# Thread-local override map used while tracing a hybridized block: the trace
# substitutes tracer-backed NDArrays for parameter data (block.py CachedOp).
_trace_state = threading.local()


def _overrides() -> Optional[Dict[int, NDArray]]:
    return getattr(_trace_state, "param_overrides", None)


class _ParamOverrideScope:
    def __init__(self, mapping: Dict[int, NDArray]):
        self._mapping = mapping

    def __enter__(self):
        self._old = _overrides()
        _trace_state.param_overrides = self._mapping
        return self

    def __exit__(self, *exc):
        _trace_state.param_overrides = self._old
        return False


def _norm_dtype(dtype):
    """Normalize to np.dtype; bfloat16 kept as its ml_dtypes dtype."""
    if dtype is None:
        return _np.dtype("float32")
    if str(dtype) == "bfloat16":
        import jax.numpy as jnp
        return _np.dtype(jnp.bfloat16)
    return _np.dtype(dtype)


def _shape_complete(shape) -> bool:
    return shape is not None and all(
        d is not None and int(d) > 0 for d in shape)


def _param_census_arrays(p):
    """One parameter's live device buffers (data + grad, every ctx copy)
    for the buffer census."""
    out = []
    for nds in (p._data.values() if p._data else (),
                p._grad.allocated() if p._grad else ()):
        for nd in nds:
            a = getattr(nd, "_jax", None)
            if a is not None:
                out.append(a)
    return out


class _GradStore:
    """ctx -> gradient NDArray of a parameter's data arrays, read like the
    dict it replaces.  A buffer is allocated when it is first asked for
    (``NDArray.attach_grad(lazy=True)``): the eager path asks on its first
    backward pass, a compiled step never does."""

    def __init__(self, data):
        self._data = data

    def __len__(self):
        return len(self._data)

    def __contains__(self, ctx):
        return ctx in self._data

    def __getitem__(self, ctx):
        return self._data[ctx].grad

    def values(self):
        return [arr.grad for arr in self._data.values()]

    def allocated(self):
        """The buffers that exist, without making the others."""
        return [arr._grad for arr in self._data.values()
                if arr._grad is not None]


class Parameter:
    """A weight/bias/state of a Block (reference: gluon.Parameter).

    Supports deferred initialization: unknown dims are 0/None/-1 and get
    filled by the layer's first forward (Block.infer_shape path), matching
    Parameter._finish_deferred_init in the reference.
    """

    def __init__(self, name: Optional[str] = None, grad_req: str = "write",
                 shape=None, dtype="float32", lr_mult: float = 1.0,
                 wd_mult: float = 1.0, init=None, allow_deferred_init: bool = False,
                 differentiable: bool = True, stype: str = "default",
                 grad_stype: str = "default"):
        self._name = name or ("param_" + uuid.uuid4().hex[:12])
        self._uuid = uuid.uuid4().hex
        if isinstance(shape, int):
            shape = (shape,)
        self._shape = tuple(shape) if shape is not None else None
        self.dtype = _norm_dtype(dtype)
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        if not differentiable:
            grad_req = "null"
        self._grad_req = grad_req
        if stype not in ("default", "row_sparse", "csr"):
            raise ValueError("invalid stype %r" % stype)
        self._stype = stype
        self._grad_stype = grad_stype
        # ctx -> NDArray (reference keeps per-device copies)
        self._data: Optional["OrderedDict[Context, NDArray]"] = None
        self._grad: Optional["OrderedDict[Context, NDArray]"] = None
        self._deferred_init = None    # (init, ctx_list, default_init)
        self._structural_name = None  # set by Block registration walk
        # buffer-census attribution (ISSUE 10): every live param/grad
        # device buffer is claimed by the "params" owner bucket
        from .. import programs as _programs
        _programs.track_buffers("params", self, _param_census_arrays)

    # -- identity ----------------------------------------------------------
    @property
    def name(self) -> str:
        return self._structural_name or self._name

    @name.setter
    def name(self, value):
        self._name = value

    def __repr__(self):
        return "Parameter %s (shape=%s, dtype=%s)" % (
            self.name, self._shape, self.dtype)

    # -- shape (settable while incomplete, like the reference) -------------
    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is None:
            self._shape = tuple(new_shape)
            return
        unknown_ok = all(
            int(s1) in (0, -1) or s1 is None or int(s1) == int(s2)
            for s1, s2 in zip(self._shape, new_shape))
        if len(self._shape) != len(new_shape) or not unknown_ok:
            raise AssertionError(
                "Expected shape %s is incompatible with given shape %s for "
                "Parameter %s" % (str(new_shape), str(self._shape), self.name))
        self._shape = tuple(int(d) for d in new_shape)

    @property
    def grad_req(self) -> str:
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req: str):
        if req not in ("write", "add", "null"):
            raise ValueError("grad_req must be write/add/null, got %r" % req)
        if self._grad_req == req:
            return
        self._grad_req = req
        if req == "null":
            self._grad = None
            if self._data is not None:
                for arr in self._data.values():
                    arr._grad = None
                    arr._grad_req = "null"
        elif self._data is not None:
            self._init_grad()

    @property
    def stype(self):
        return self._stype

    # -- initialization ----------------------------------------------------
    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit: bool = False):
        """Materialize data on ctx(s) (reference: Parameter.initialize)."""
        default_init = default_init or init_mod.Uniform()
        if self._data is not None and not force_reinit:
            return
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        if init is None:
            init = self.init if self.init is not None else default_init
        if not _shape_complete(self._shape):
            if self.allow_deferred_init:
                self._deferred_init = (init, list(ctx), default_init)
                return
            raise ValueError(
                "Cannot initialize Parameter %s because it has invalid shape "
                "%s and deferred init is not allowed" % (self.name, self._shape))
        self._init_impl(init, ctx)

    def _init_impl(self, init, ctx_list):
        host = _np.zeros(self._shape, dtype=_np.float32)
        holder = _nd_mod.array(host, ctx=cpu(),
                               dtype=_np.float32)
        init_fn = init_mod.create(init)
        init_fn(init_mod.InitDesc(self.name), holder)
        value = holder.asnumpy()
        self._data = OrderedDict()
        for c in ctx_list:
            self._data[c] = _nd_mod.array(value, ctx=c, dtype=self.dtype)
        self._deferred_init = None
        if self._grad_req != "null":
            self._init_grad()

    def _init_grad(self):
        for arr in self._data.values():
            arr.attach_grad(self._grad_req, lazy=True)
        self._grad = _GradStore(self._data)

    def _finish_deferred_init(self):
        if self._deferred_init is None:
            raise DeferredInitializationError(
                "Parameter %s was not initialized" % self.name)
        if not _shape_complete(self._shape):
            raise DeferredInitializationError(
                "Parameter %s has unknown shape %s; run a forward pass or "
                "call infer_shape first" % (self.name, self._shape))
        init, ctx, default_init = self._deferred_init
        self._init_impl(init if init is not None else default_init, ctx)

    # -- access ------------------------------------------------------------
    def _check_and_get(self, arr_dict, ctx):
        if arr_dict is None:
            if self._deferred_init is not None:
                raise DeferredInitializationError(
                    "Parameter %s has not been initialized yet because its "
                    "shape is unknown; run a forward pass first" % self.name)
            raise RuntimeError(
                "Parameter %s has not been initialized. You should initialize "
                "parameters with Block.initialize() before use" % self.name)
        if ctx is list:   # sentinel: return all copies (reference idiom)
            return list(arr_dict.values())
        if ctx is None:
            if len(arr_dict) == 1:
                return next(iter(arr_dict.values()))
            ctx = current_context()
        if isinstance(ctx, Context) and ctx in arr_dict:
            return arr_dict[ctx]
        raise RuntimeError(
            "Parameter %s was not initialized on context %s (it lives on %s)"
            % (self.name, ctx, list(arr_dict.keys())))

    def data(self, ctx: Optional[Context] = None) -> NDArray:
        ov = _overrides()
        if ov is not None and id(self) in ov:
            return ov[id(self)]
        return self._check_and_get(self._data, ctx)

    def list_data(self) -> List[NDArray]:
        self._check_and_get(self._data, list)
        return list(self._data.values())

    def grad(self, ctx: Optional[Context] = None) -> NDArray:
        if self._data is not None and self._grad is None:
            raise RuntimeError(
                "Cannot get gradient array for Parameter %s because "
                "grad_req='null'" % self.name)
        return self._check_and_get(self._grad, ctx)

    def list_grad(self) -> List[NDArray]:
        if self._data is not None and self._grad is None:
            raise RuntimeError("grad_req='null' for Parameter %s" % self.name)
        self._check_and_get(self._grad, list)
        return list(self._grad.values())

    def list_ctx(self) -> List[Context]:
        if self._data is None:
            if self._deferred_init is not None:
                return list(self._deferred_init[1])
            raise RuntimeError("Parameter %s has not been initialized"
                               % self.name)
        return list(self._data.keys())

    def set_data(self, data):
        """Set data on all contexts (reference: Parameter.set_data)."""
        self.shape = data.shape  # validates compatibility
        if self._data is None:
            if self._deferred_init is None:
                raise RuntimeError("initialize Parameter %s first" % self.name)
            # materialize directly from the given value
            _, ctx, _ = self._deferred_init
            self._data = OrderedDict()
            value = data.asnumpy() if isinstance(data, NDArray) else _np.asarray(data)
            for c in ctx:
                self._data[c] = _nd_mod.array(value, ctx=c, dtype=self.dtype)
            self._deferred_init = None
            if self._grad_req != "null":
                self._init_grad()
            return
        for arr in self._data.values():
            if isinstance(data, NDArray):
                # copyto, not a raw _set_jax of data's array: on the same
                # device+dtype that would alias data's buffer, and a
                # donated alias (compiled-step lane) dies with the donor
                data.copyto(arr)
            else:
                arr[:] = data

    def zero_grad(self):
        if self._grad is None:
            return
        for g in self._grad.allocated():      # one not yet made is zero
            g[:] = 0

    def reset_ctx(self, ctx):
        """Move parameter to new context(s) (reference: reset_ctx)."""
        if isinstance(ctx, Context):
            ctx = [ctx]
        if self._data is not None:
            value = next(iter(self._data.values())).asnumpy()
            self._data = OrderedDict(
                (c, _nd_mod.array(value, ctx=c, dtype=self.dtype)) for c in ctx)
            if self._grad_req != "null":
                self._init_grad()
        elif self._deferred_init is not None:
            init, _, default_init = self._deferred_init
            self._deferred_init = (init, list(ctx), default_init)
        else:
            raise ValueError("Cannot reset context for uninitialized "
                             "Parameter %s" % self.name)

    def cast(self, dtype):
        self.dtype = _norm_dtype(dtype)
        if self._data is None:
            return
        for c in list(self._data.keys()):
            self._data[c] = self._data[c].astype(dtype)
        if self._grad_req != "null":
            self._init_grad()

    def var(self):
        """Symbol variable for this parameter (symbol API compat)."""
        from ..symbol import Variable
        return Variable(self.name)

    def _reduce(self) -> NDArray:
        """Average over contexts → cpu (reference: Parameter._reduce)."""
        vals = self.list_data()
        out = vals[0].asnumpy().astype(_np.float64)
        for v in vals[1:]:
            out = out + v.asnumpy()
        out /= len(vals)
        return _nd_mod.array(out.astype(self.dtype), ctx=cpu())


class Constant(Parameter):
    """Non-trainable constant (reference: gluon.Constant)."""

    def __init__(self, value, name: Optional[str] = None):
        if isinstance(value, NDArray):
            value = value.asnumpy()
        value = _np.asarray(value)
        self.value = value
        super().__init__(name=name, grad_req="null", shape=value.shape,
                         dtype=value.dtype,
                         init=init_mod.Constant(value.tolist()))


class ParameterDict:
    """Ordered name→Parameter mapping (reference: gluon.ParameterDict; in
    2.x collect_params returns a plain dict — this class supports both
    surfaces: mapping protocol + initialize/zero_grad/save/load helpers)."""

    def __init__(self, prefix: str = "", shared=None):
        self._prefix = prefix
        self._params: "OrderedDict[str, Parameter]" = OrderedDict()
        self._shared = shared

    # -- mapping protocol --------------------------------------------------
    def __getitem__(self, key) -> Parameter:
        return self._params[key]

    def __setitem__(self, key, val):
        self._params[key] = val

    def __contains__(self, key):
        return key in self._params

    def __iter__(self):
        return iter(self._params)

    def __len__(self):
        return len(self._params)

    def __repr__(self):
        body = "\n".join("  %s" % p for p in self._params.values())
        return "ParameterDict(\n%s\n)" % body

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    @property
    def prefix(self):
        return self._prefix

    def get(self, name, **kwargs) -> Parameter:
        """v1.x layer style: fetch-or-create `self.params.get('weight', ...)`."""
        full = self._prefix + name
        if full in self._params:
            param = self._params[full]
            for k, v in kwargs.items():
                if k == "shape" and v is not None:
                    param.shape = v
            return param
        if self._shared is not None and full in self._shared:
            self._params[full] = self._shared[full]
            return self._params[full]
        param = Parameter(full, **kwargs)
        self._params[full] = param
        return param

    def update(self, other):
        if isinstance(other, ParameterDict):
            other = other._params
        for k, v in other.items():
            if k in self._params and self._params[k] is not v:
                raise ValueError("Cannot update: duplicate Parameter name %s"
                                 % k)
            self._params[k] = v

    # -- bulk ops ----------------------------------------------------------
    def initialize(self, init=None, ctx=None, verbose: bool = False,
                   force_reinit: bool = False):
        default = init_mod.create(init) if init is not None else init_mod.Uniform()
        for param in self._params.values():
            param.initialize(None, ctx=ctx, default_init=default,
                             force_reinit=force_reinit)

    def zero_grad(self):
        for p in self._params.values():
            p.zero_grad()

    def reset_ctx(self, ctx):
        for p in self._params.values():
            p.reset_ctx(ctx)

    def setattr(self, name, value):
        for p in self._params.values():
            setattr(p, name, value)

    def save(self, filename, strip_prefix: str = ""):
        arg = {}
        for p in self._params.values():
            weight = p._reduce()
            name = p.name
            if strip_prefix and name.startswith(strip_prefix):
                name = name[len(strip_prefix):]
            arg[name] = weight
        _nd_mod.save(filename, arg)

    def load(self, filename, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix: str = "",
             cast_dtype=False, dtype_source="current"):
        loaded = _nd_mod.load(filename)
        loaded = {(restore_prefix + k[4:]) if k.startswith(("arg:", "aux:"))
                  else restore_prefix + k: v for k, v in loaded.items()}
        if not allow_missing:
            for name in self.keys():
                if name not in loaded:
                    raise AssertionError(
                        "Parameter %s is missing in file %s" % (name, filename))
        for name, value in loaded.items():
            if name not in self._params:
                if not ignore_extra:
                    raise AssertionError(
                        "Parameter %s loaded from %s is not present in this "
                        "ParameterDict" % (name, filename))
                continue
            param = self._params[name]
            if cast_dtype and dtype_source == "saved":
                param.cast(value.dtype)
            elif cast_dtype:
                value = value.astype(param.dtype)
            if param._data is None and param._deferred_init is None and ctx is not None:
                param.initialize(ctx=ctx)
            param.set_data(value)
