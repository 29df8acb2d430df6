"""mx.tpu_kernel: user-authored TPU kernels (the RTC equivalent).

Reference: python/mxnet/rtc.py (CudaModule, CudaKernel — runtime-compiled
CUDA via NVRTC, launched with explicit grid/block dims), src/common/rtc.cc.

TPU-native design: the user writes a *Pallas* kernel body instead of CUDA C
— Mosaic compiles it for the MXU/VPU the way NVRTC compiled CUDA for SMs.
``Kernel`` plays CudaKernel (explicit launch over NDArrays: grid ≙ the
pallas grid, BlockSpecs ≙ block dims + shared-mem tiling); ``register``
additionally installs the kernel as a first-class framework op so it
dispatches like any built-in (usable from nd/gluon, differentiable when a
``grad`` is supplied — the role FGradient plays for built-ins).

On non-TPU backends kernels run in Pallas ``interpret`` mode, the same
"works everywhere, fast on the target" posture the reference's RTC had
(CUDA-only there; here CPU interprets, TPU compiles).
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple, Union

import numpy as _np
import jax
import jax.numpy as jnp

from .base import MXNetError

__all__ = ["Kernel", "kernel", "register"]


def _on_tpu() -> bool:
    try:
        return jax.default_backend() == "tpu"
    except Exception:
        return False


def _as_shape_structs(out_shape, out_dtype):
    """Normalize (shape(s), dtype(s)) into ShapeDtypeStruct(s)."""
    if isinstance(out_shape, jax.ShapeDtypeStruct):
        return out_shape, True
    if (isinstance(out_shape, (list, tuple)) and out_shape
            and isinstance(out_shape[0], (list, tuple, jax.ShapeDtypeStruct))):
        dts = (out_dtype if isinstance(out_dtype, (list, tuple))
               else [out_dtype] * len(out_shape))
        structs = tuple(
            s if isinstance(s, jax.ShapeDtypeStruct)
            else jax.ShapeDtypeStruct(tuple(s), _np.dtype(d or _np.float32))
            for s, d in zip(out_shape, dts))
        return structs, False
    return jax.ShapeDtypeStruct(tuple(out_shape),
                                _np.dtype(out_dtype or _np.float32)), True


class Kernel:
    """A launchable Pallas kernel (reference: rtc.py CudaKernel.launch).

    ``body(*refs)`` receives input Refs then output Refs, Pallas-style.
    ``grid``/``in_specs``/``out_specs`` map onto pallas_call verbatim;
    grid plays the role of CudaKernel.launch's grid_dims and the
    BlockSpecs the role of block_dims + shared memory shaping."""

    def __init__(self, body: Callable, name: Optional[str] = None,
                 grid=None, in_specs=None, out_specs=None,
                 interpret: Optional[bool] = None, **pallas_kwargs):
        self.body = body
        self.name = name or getattr(body, "__name__", "tpu_kernel")
        self.grid = grid
        self.in_specs = in_specs
        self.out_specs = out_specs
        self.interpret = interpret
        self.pallas_kwargs = pallas_kwargs

    def _interpret_for(self, xs) -> bool:
        if self.interpret is not None:
            return self.interpret
        # decide from where the inputs actually live (the global default
        # backend can be TPU while the arrays are committed to host CPU)
        for x in xs:
            try:
                plat = next(iter(x.devices())).platform
                return plat != "tpu"
            except Exception:
                continue  # tracer: no committed device, fall through
        return not _on_tpu()

    def _build(self, structs, interpret: bool) -> Callable:
        import jax.experimental.pallas as pl
        kw = dict(self.pallas_kwargs)
        if self.grid is not None:
            kw["grid"] = self.grid
        if self.in_specs is not None:
            kw["in_specs"] = self.in_specs
        if self.out_specs is not None:
            kw["out_specs"] = self.out_specs
        return pl.pallas_call(self.body, out_shape=structs,
                              interpret=interpret, **kw)

    def _call_jax(self, out_shape, *xs, out_dtype=None):
        structs, single = _as_shape_structs(
            out_shape, out_dtype or (xs[0].dtype if xs else _np.float32))
        return self._build(structs, self._interpret_for(xs))(*xs), single

    def _call_traced(self, structs, *xs):
        """Inside a jit trace the inputs carry no committed device; defer
        the interpret-vs-Mosaic choice to lowering time, per platform."""
        if self.interpret is not None:
            return self._build(structs, self.interpret)(*xs)
        from jax import lax as _lax
        return _lax.platform_dependent(*xs,
                                       cpu=self._build(structs, True),
                                       default=self._build(structs, False))

    def launch(self, args: Sequence, out_shape,
               out_dtype=None) -> Union[Any, Tuple]:
        """Launch over NDArrays; returns NDArray(s) on the args' context."""
        from .ndarray import ndarray as _ndmod
        from .ndarray.ndarray import NDArray
        from .device import current_context
        if _ndmod._sym_tracer is not None:
            raise MXNetError(
                "Kernel.launch bypasses the op registry and cannot be "
                "traced into symbol.json — use tpu_kernel.register() to "
                "make the kernel a named, exportable op")
        nd_in = [a if isinstance(a, NDArray) else NDArray(jnp.asarray(a))
                 for a in args]
        ctx = nd_in[0].context if nd_in else current_context()
        outs, single = self._call_jax(out_shape, *[x._jax for x in nd_in],
                                      out_dtype=out_dtype)
        if single:
            return NDArray(outs, ctx=ctx)
        return tuple(NDArray(o, ctx=ctx) for o in outs)

    def __call__(self, *args, out_shape=None, out_dtype=None):
        if out_shape is None:
            raise MXNetError("Kernel() requires out_shape=")
        return self.launch(list(args), out_shape, out_dtype)


def kernel(name: Optional[str] = None, *, grid=None, in_specs=None,
           out_specs=None, interpret: Optional[bool] = None,
           **pallas_kwargs):
    """Decorator form: ``@mx.tpu_kernel.kernel(grid=...)`` over a Pallas
    body returns a launchable :class:`Kernel`."""

    def _wrap(body: Callable) -> Kernel:
        return Kernel(body, name=name, grid=grid, in_specs=in_specs,
                      out_specs=out_specs, interpret=interpret,
                      **pallas_kwargs)

    return _wrap


def register(name: str, *, out_shape_fn: Callable,
             grad: Optional[Callable] = None, grid=None, in_specs=None,
             out_specs=None, interpret: Optional[bool] = None,
             aliases: Sequence[str] = (), **pallas_kwargs):
    """Register a Pallas kernel as a framework op: after

        @mx.tpu_kernel.register("my_op", out_shape_fn=lambda *xs: xs[0])

    ``mx.nd.my_op(...)`` dispatches it like a built-in (jit-cached,
    tape-recorded).  ``out_shape_fn(*avals) -> ShapeDtypeStruct(s)``
    computes output shapes from input avals (the FInferShape role).
    ``grad(cotangents, *inputs) -> input-cotangent tuple`` supplies the
    backward (FGradient); without it the op is marked non-differentiable.
    """
    from .ops import registry as _registry

    def _wrap(body: Callable):
        k = Kernel(body, name=name, grid=grid, in_specs=in_specs,
                   out_specs=out_specs, interpret=interpret, **pallas_kwargs)

        def impl(*xs):
            avals = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in xs]
            structs, _ = _as_shape_structs(
                out_shape_fn(*avals), xs[0].dtype if xs else _np.float32)
            return k._call_traced(structs, *xs)

        if grad is not None:
            fwd_impl = jax.custom_vjp(impl)

            def _f(*xs):
                return impl(*xs), xs

            def _b(res, cts):
                # single-output vjp hands the cotangent bare; the user grad
                # contract is always a tuple (like out_grad lists in FGradient)
                cts_t = cts if isinstance(cts, (tuple, list)) else (cts,)
                return tuple(grad(cts_t, *res))

            fwd_impl.defvjp(_f, _b)
            fn = fwd_impl
        else:
            fn = impl
        fn.__name__ = name
        fn.__doc__ = body.__doc__ or ("user tpu_kernel %s" % name)
        _registry.register(name, fn, differentiable=grad is not None,
                           aliases=aliases, replace=True)
        # surface on the live mx.nd namespace like generated op wrappers
        import sys
        ndmod = sys.modules.get("mxnet_tpu.ndarray")
        if ndmod is not None and not hasattr(ndmod, name):
            setattr(ndmod, name, ndmod._make_op_func(name))
        return k

    return _wrap
