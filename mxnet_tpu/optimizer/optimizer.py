"""Optimizers.

Reference: python/mxnet/optimizer/optimizer.py [v1.x] / per-file [2.x]
(class Optimizer — registry, lr/wd mults, num_update bookkeeping,
create_state, update_multi_precision; SGD, NAG, Adam, RMSProp, AdaGrad,
AdaDelta, Ftrl, LAMB, LARS, Signum, DCASGD, Test; get_updater for the
kvstore server path).

TPU-native: every update dispatches one fused jitted op from
ops/optimizer.py (the reference's hand-written CUDA kernels in
src/operator/optimizer_op.cc become XLA-fused elementwise chains).
Multi-precision keeps an fp32 master copy when the weight is bf16/fp16
(reference: MP_SGD kernels; SURVEY.md AMP row).
"""
from __future__ import annotations

import math
import pickle
import warnings
from typing import Any, Dict, Optional

import numpy as _np

from ..base import MXNetError
from ..ndarray.ndarray import NDArray, invoke
from .. import ndarray as nd
from .. import telemetry as _telemetry
from ..lr_scheduler import LRScheduler

__all__ = ["Optimizer", "Updater", "get_updater", "register", "create"]


def _aggregate_default(n):
    """Default aggregate_num for fused-capable optimizers.  The
    MX_OPTIMIZER_AGGREGATE env knob overrides: 0 opts out (per-param
    loop, the pre-fusion behavior), any other integer caps the number of
    (weight, grad, state) triples fused into one jitted pytree dispatch."""
    from ..base import get_env
    v = get_env("MX_OPTIMIZER_AGGREGATE", None, int)
    # unset reads back as the catalog's "" default: keep the class default
    if not isinstance(v, int) or v < 0:
        return n
    return v


def _chunks(seq, n):
    if n <= 0 or n >= len(seq):
        yield seq
        return
    for i in range(0, len(seq), n):
        yield seq[i:i + n]


class Optimizer:
    """Base optimizer (reference: class Optimizer)."""

    opt_registry: Dict[str, type] = {}

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=None, lr_scheduler=None,
                 sym=None, begin_num_update=0, multi_precision=False,
                 param_dict=None, aggregate_num=0, use_fused_step=True):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate if learning_rate is not None else 0.01
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None and learning_rate is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.lr_mult = {}
        self.wd_mult = {}
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._all_index_update_counts = {0: {}}
        self._index_update_count = self._all_index_update_counts[0]
        self.clip_gradient = clip_gradient
        self.multi_precision = multi_precision
        self.aggregate_num = aggregate_num
        if param_idx2name is None:
            param_idx2name = {}
        assert isinstance(param_idx2name, dict), \
            "param_idx2name should be a dict of param indexes to names."
        self.idx2name = param_idx2name.copy()
        self.sym_info = ()
        self.param_dict = param_dict if param_dict else {}

    # -- registry ----------------------------------------------------------
    @staticmethod
    def register(klass):
        name = klass.__name__.lower()
        Optimizer.opt_registry[name] = klass
        return klass

    @staticmethod
    def create_optimizer(name, **kwargs):
        if name.lower() in Optimizer.opt_registry:
            return Optimizer.opt_registry[name.lower()](**kwargs)
        raise ValueError("Cannot find optimizer %s" % name)

    # -- state -------------------------------------------------------------
    def create_state(self, index, weight):
        return None

    def create_state_multi_precision(self, index, weight):
        if self.multi_precision and weight.dtype in (_np.float16,
                                                     _np.dtype("bfloat16")):
            weight_master_copy = weight.astype(_np.float32)
            return (self.create_state(index, weight_master_copy),
                    weight_master_copy)
        return self.create_state(index, weight)

    # -- update ------------------------------------------------------------
    def update(self, index, weight, grad, state):
        raise NotImplementedError()

    def update_multi_precision(self, index, weight, grad, state):
        if self.multi_precision and isinstance(state, tuple) and \
                len(state) == 2 and isinstance(state[1], NDArray) and \
                state[1].dtype == _np.float32 and weight.dtype != _np.float32:
            inner_state, weight32 = state
            grad32 = grad.astype(_np.float32)
            self.update(index, weight32, grad32, inner_state)
            weight._set_jax(weight32._jax.astype(weight.dtype))
        else:
            self.update(index, weight, grad, state)

    # list-form dispatch (2.x update signature takes lists)
    def _normalize(self, indices, weights, grads, states):
        if isinstance(weights, NDArray):
            return [indices], [weights], [grads], [states]
        return indices, weights, grads, states

    # -- fused multi-tensor apply (ISSUE 3 tentpole a) ---------------------
    # The reference reaches one-kernel-per-group via multi_sgd_update /
    # multi_adamw fleets gated on aggregate_num; here fused-capable
    # optimizers apply the whole (weight, grad, state) batch as ONE jitted
    # pytree update (ops/optimizer.py tree kernels), with lr_mult/wd_mult/
    # num_update bookkeeping folded in as per-leaf scalars.

    def fused_update(self, indices, weights, grads, states):
        """Apply the whole batch in O(1) jitted dispatches (O(#chunks)
        when aggregate_num caps the group).  Returns False when this
        optimizer has no tree kernel — callers then fall back to the
        per-param update loop."""
        return False

    # -- whole-step compiled lane (ISSUE 7) --------------------------------
    def _compiled_spec(self):
        """Functional description of this optimizer's update for the
        whole-step compiled lane (mxnet_tpu.step.CompiledStep / the
        Module compiled fit step): a dict with

          ``kind``      — ops.optimizer tree-body name,
          ``static``    — trace-static kwargs (momentum, betas, ...),
          ``unpack``    — ``(state, mp) -> (inner_state_tuple, w32)``,
                          the same layout split _fused_apply uses,
          ``n_state``   — number of inner state columns,
          ``lr_fn``     — optional ``(index, lr) -> effective lr`` (host,
                          per step; bias correction folds in here so the
                          compiled trace sees lr as a traced scalar),
          ``decay_fn``  — optional ``(index, lr, wd) -> decoupled decay``.

        Returns None when the optimizer has no pure tree kernel — the
        compiled lane then falls back to the eager pipeline."""
        return None

    def _is_mp_state(self, weight, state):
        """Same predicate update_multi_precision routes on: a (inner,
        fp32-master) state pair for a low-precision weight."""
        return (self.multi_precision and isinstance(state, tuple) and
                len(state) == 2 and isinstance(state[1], NDArray) and
                state[1].dtype == _np.float32 and
                weight.dtype != _np.float32)

    def _fused_apply(self, kind, indices, weights, grads, states, unpack,
                     lr_fn=None, decay_fn=None, **static):
        """Shared fused-apply skeleton: num_update bookkeeping, per-leaf
        lr/wd, multi-precision grouping, aggregate_num chunking, ONE
        tree_apply dispatch per chunk, in-place write-back.

        ``unpack(state, mp) -> (inner_state_tuple, weight32_or_None)``
        flattens this optimizer's state layout; ``lr_fn(pos)`` /
        ``decay_fn(pos)`` (pos indexes into `indices`) let Adam-family
        classes fold bias correction / decoupled decay into the per-leaf
        scalars exactly as their per-param update does.
        """
        from ..ops.optimizer import tree_apply
        self._update_count(indices)
        lrs = self._get_lrs(indices)
        wds = self._get_wds(indices)
        groups: Dict[Any, list] = {}
        for pos in range(len(indices)):
            mp = self._is_mp_state(weights[pos], states[pos])
            # one jitted program spans one device: group2ctx model
            # parallelism puts params on different devices — each gets its
            # own fused dispatch (still O(#devices), not O(#params))
            dev = (weights[pos].context.jax_device,
                   grads[pos].context.jax_device)
            groups.setdefault((mp, dev), []).append(pos)
        for (mp, _dev), poss in groups.items():
            for chunk in _chunks(poss, self.aggregate_num):
                ws = [weights[p] for p in chunk]
                inners, w32s = [], []
                for p in chunk:
                    inner, w32 = unpack(states[p], mp)
                    inners.append(inner)
                    w32s.append(w32)
                state_cols = [[inn[j] for inn in inners]
                              for j in range(len(inners[0]))]
                arrays = [[w._jax for w in ws],
                          [grads[p]._jax for p in chunk]]
                arrays += [[s._jax for s in col] for col in state_cols]
                arrays.append([s._jax for s in w32s] if mp else None)
                eff_lrs = [lr_fn(p, lrs[p]) if lr_fn else lrs[p]
                           for p in chunk]
                decays = [decay_fn(p, lrs[p], wds[p]) for p in chunk] \
                    if decay_fn else None
                out_w, out_states, out_w32 = tree_apply(
                    kind, arrays, eff_lrs, decays,
                    wds=tuple(wds[p] for p in chunk),
                    rescale_grad=self.rescale_grad,
                    clip_gradient=_clip(self.clip_gradient),
                    mp=mp, **static)
                for j, w in enumerate(ws):
                    w._set_jax(out_w[j])
                if out_states:
                    for col, outs in zip(state_cols, out_states):
                        for j, s in enumerate(col):
                            s._set_jax(outs[j])
                if mp and out_w32 is not None:
                    for j, s in enumerate(w32s):
                        s._set_jax(out_w32[j])
        return True

    # -- lr / wd plumbing --------------------------------------------------
    @property
    def learning_rate(self):
        """Current base lr — scheduler value without per-param multipliers
        (reference: Optimizer.learning_rate property)."""
        if self.lr_scheduler is not None:
            return self.lr_scheduler(self.num_update)
        return self.lr

    def set_learning_rate(self, lr):
        if self.lr_scheduler is not None:
            raise UserWarning("LRScheduler of the optimizer has already been "
                              "defined. Note that set_learning_rate can mutate "
                              "the value of the learning rate of the optimizer "
                              "only when the LRScheduler of the optimizer is "
                              "undefined.")
        self.lr = lr

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = args_lr_mult.copy()

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = {}
        for n in self.idx2name.values():
            is_weight = n.endswith("_weight") or n.endswith(".weight")
            if not is_weight:
                self.wd_mult[n] = 0.0
        self.wd_mult.update(args_wd_mult)

    def _set_current_context(self, device_id):
        if device_id not in self._all_index_update_counts:
            self._all_index_update_counts[device_id] = {}
        self._index_update_count = self._all_index_update_counts[device_id]

    def _update_count(self, index):
        if not isinstance(index, (list, tuple)):
            index = [index]
        for idx in index:
            if idx not in self._index_update_count:
                self._index_update_count[idx] = self.begin_num_update
            self._index_update_count[idx] += 1
            self.num_update = max(self._index_update_count[idx],
                                  self.num_update)

    def _get_lrs(self, indices):
        if self.lr_scheduler is not None:
            lr = self.lr_scheduler(self.num_update)
        else:
            lr = self.lr
        lrs = [lr for _ in indices]
        for i, index in enumerate(indices):
            if index in self.param_dict:
                lrs[i] *= self.param_dict[index].lr_mult
            elif index in self.lr_mult:
                lrs[i] *= self.lr_mult[index]
            elif index in self.idx2name:
                lrs[i] *= self.lr_mult.get(self.idx2name[index], 1.0)
        return lrs

    def _get_lr(self, index):
        return self._get_lrs([index])[0]

    def _get_wds(self, indices):
        wds = [self.wd for _ in indices]
        for i, index in enumerate(indices):
            if index in self.param_dict:
                wds[i] *= self.param_dict[index].wd_mult
            elif index in self.wd_mult:
                wds[i] *= self.wd_mult[index]
            elif index in self.idx2name:
                wds[i] *= self.wd_mult.get(self.idx2name[index], 1.0)
        return wds

    def _get_wd(self, index):
        return self._get_wds([index])[0]

    def __getstate__(self):
        ret = self.__dict__.copy()
        return ret

    def __setstate__(self, state):
        self.__dict__.update(state)


register = Optimizer.register


def create(name, **kwargs):
    """Reference: mx.optimizer.create."""
    if isinstance(name, Optimizer):
        return name
    return Optimizer.create_optimizer(name, **kwargs)


def _clip(value):
    return -1.0 if value is None else value


@register
class SGD(Optimizer):
    """SGD with momentum (reference: optimizer.SGD → sgd_update /
    sgd_mom_update / mp_* fused kernels)."""

    def __init__(self, learning_rate=0.01, momentum=0.0, lazy_update=True,
                 **kwargs):
        kwargs.setdefault("aggregate_num", _aggregate_default(64))
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return nd.zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)

    def fused_update(self, indices, weights, grads, states):
        has_mom = self.momentum != 0.0

        def unpack(state, mp):
            inner = state[0] if mp else state
            return ((inner,) if has_mom else ()), (state[1] if mp else None)

        extra = {"momentum": self.momentum} if has_mom else {}
        return self._fused_apply("sgd_mom" if has_mom else "sgd", indices,
                                 weights, grads, states, unpack, **extra)

    def _compiled_spec(self):
        has_mom = self.momentum != 0.0

        def unpack(state, mp):
            inner = state[0] if mp else state
            return ((inner,) if has_mom else ()), (state[1] if mp else None)

        return {"kind": "sgd_mom" if has_mom else "sgd",
                "static": {"momentum": self.momentum} if has_mom else {},
                "unpack": unpack, "n_state": 1 if has_mom else 0}

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        kw = dict(lr=lr, wd=wd, rescale_grad=self.rescale_grad,
                  clip_gradient=_clip(self.clip_gradient))
        if getattr(grad, "stype", "default") == "row_sparse":
            if self.lazy_update:
                # reference SGDUpdateRspImpl: only gradient rows are touched
                if state is not None:
                    invoke("_sparse_sgd_mom_update", weight, grad.data,
                           grad.indices, state, momentum=self.momentum, **kw)
                else:
                    invoke("_sparse_sgd_update", weight, grad.data,
                           grad.indices, **kw)
                return
            grad = grad.tostype("default")
        if state is not None:
            invoke("sgd_mom_update", weight, grad, state,
                   momentum=self.momentum, **kw)
        else:
            invoke("sgd_update", weight, grad, **kw)


@register
class NAG(Optimizer):
    """Nesterov accelerated SGD (reference: optimizer.NAG)."""

    def __init__(self, learning_rate=0.1, momentum=0.0, **kwargs):
        kwargs.setdefault("aggregate_num", _aggregate_default(64))
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return nd.zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        kw = dict(lr=lr, wd=wd, rescale_grad=self.rescale_grad,
                  clip_gradient=_clip(self.clip_gradient))
        if state is not None:
            invoke("nag_mom_update", weight, grad, state,
                   momentum=self.momentum, **kw)
        else:
            invoke("sgd_update", weight, grad, **kw)

    def fused_update(self, indices, weights, grads, states):
        has_mom = self.momentum != 0.0

        def unpack(state, mp):
            inner = state[0] if mp else state
            return ((inner,) if has_mom else ()), (state[1] if mp else None)

        extra = {"momentum": self.momentum} if has_mom else {}
        return self._fused_apply("nag_mom" if has_mom else "sgd", indices,
                                 weights, grads, states, unpack, **extra)

    def _compiled_spec(self):
        has_mom = self.momentum != 0.0

        def unpack(state, mp):
            inner = state[0] if mp else state
            return ((inner,) if has_mom else ()), (state[1] if mp else None)

        return {"kind": "nag_mom" if has_mom else "sgd",
                "static": {"momentum": self.momentum} if has_mom else {},
                "unpack": unpack, "n_state": 1 if has_mom else 0}


@register
class Adam(Optimizer):
    """Reference: optimizer.Adam → adam_update fused kernel, with the
    bias-correction folded into lr like the reference."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_update=True, **kwargs):
        kwargs.setdefault("aggregate_num", _aggregate_default(64))
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),
                nd.zeros(weight.shape, ctx=weight.context, dtype=weight.dtype))

    def fused_update(self, indices, weights, grads, states):
        def unpack(state, mp):
            mean, var = state[0] if mp else state
            return (mean, var), (state[1] if mp else None)

        def lr_fn(pos, lr):
            # bias correction folded into lr on host in float64 (t is a
            # host int after _update_count), exactly like update()
            t = self._index_update_count[indices[pos]]
            return lr * math.sqrt(1.0 - self.beta2 ** t) / \
                (1.0 - self.beta1 ** t)

        return self._fused_apply("adam", indices, weights, grads, states,
                                 unpack, lr_fn=lr_fn, beta1=self.beta1,
                                 beta2=self.beta2, epsilon=self.epsilon)

    def _compiled_spec(self):
        def unpack(state, mp):
            mean, var = state[0] if mp else state
            return (mean, var), (state[1] if mp else None)

        def lr_fn(index, lr):
            t = self._index_update_count[index]
            return lr * math.sqrt(1.0 - self.beta2 ** t) / \
                (1.0 - self.beta1 ** t)

        return {"kind": "adam",
                "static": {"beta1": self.beta1, "beta2": self.beta2,
                           "epsilon": self.epsilon},
                "unpack": unpack, "n_state": 2, "lr_fn": lr_fn}

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        t = self._index_update_count[index]
        coef1 = 1.0 - self.beta1 ** t
        coef2 = 1.0 - self.beta2 ** t
        lr *= math.sqrt(coef2) / coef1
        mean, var = state
        if getattr(grad, "stype", "default") == "row_sparse":
            if self.lazy_update:
                # reference AdamUpdateRspImpl: moments decay only on rows
                # the batch touched
                invoke("_sparse_adam_update", weight, grad.data, grad.indices,
                       mean, var, lr=lr, wd=wd, beta1=self.beta1,
                       beta2=self.beta2, epsilon=self.epsilon,
                       rescale_grad=self.rescale_grad,
                       clip_gradient=_clip(self.clip_gradient))
                return
            grad = grad.tostype("default")
        invoke("adam_update", weight, grad, mean, var, lr=lr, wd=wd,
               beta1=self.beta1, beta2=self.beta2, epsilon=self.epsilon,
               rescale_grad=self.rescale_grad,
               clip_gradient=_clip(self.clip_gradient))


@register
class AdamW(Optimizer):
    """Decoupled weight decay Adam (reference: contrib adamw_update;
    2.x optimizer.AdamW)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, correct_bias=True, **kwargs):
        kwargs.setdefault("aggregate_num", _aggregate_default(64))
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.correct_bias = correct_bias

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),
                nd.zeros(weight.shape, ctx=weight.context, dtype=weight.dtype))

    def fused_update(self, indices, weights, grads, states):
        def unpack(state, mp):
            mean, var = state[0] if mp else state
            return (mean, var), (state[1] if mp else None)

        def lr_fn(pos, lr):
            if not self.correct_bias:
                return lr
            t = self._index_update_count[indices[pos]]
            return lr * math.sqrt(1.0 - self.beta2 ** t) / \
                (1.0 - self.beta1 ** t)

        def decay_fn(pos, lr, wd):
            # DECOUPLED decay at the RAW lr (see update() below)
            return lr * wd

        return self._fused_apply("adamw", indices, weights, grads, states,
                                 unpack, lr_fn=lr_fn, decay_fn=decay_fn,
                                 beta1=self.beta1, beta2=self.beta2,
                                 epsilon=self.epsilon)

    def _compiled_spec(self):
        def unpack(state, mp):
            mean, var = state[0] if mp else state
            return (mean, var), (state[1] if mp else None)

        def lr_fn(index, lr):
            if not self.correct_bias:
                return lr
            t = self._index_update_count[index]
            return lr * math.sqrt(1.0 - self.beta2 ** t) / \
                (1.0 - self.beta1 ** t)

        return {"kind": "adamw",
                "static": {"beta1": self.beta1, "beta2": self.beta2,
                           "epsilon": self.epsilon},
                "unpack": unpack, "n_state": 2, "lr_fn": lr_fn,
                "decay_fn": lambda index, lr, wd: lr * wd}

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        t = self._index_update_count[index]
        step_lr = lr
        if self.correct_bias:
            coef1 = 1.0 - self.beta1 ** t
            coef2 = 1.0 - self.beta2 ** t
            step_lr = lr * math.sqrt(coef2) / coef1
        mean, var = state
        # DECOUPLED decay at the RAW lr (the reference class follows the
        # huggingface formulation: only the adam step carries the
        # bias-correction factor; coupling wd with it shrinks the decay
        # ~3x at t=1)
        invoke("adamw_update", weight, grad, mean, var, lr=step_lr,
               wd=0.0, eta=1.0, beta1=self.beta1, beta2=self.beta2,
               epsilon=self.epsilon, rescale_grad=self.rescale_grad,
               clip_gradient=_clip(self.clip_gradient))
        if wd:
            weight -= lr * wd * weight


@register
class RMSProp(Optimizer):
    """Reference: optimizer.RMSProp (centered=True → rmspropalex_update)."""

    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1 = gamma1
        self.gamma2 = gamma2
        self.centered = centered
        self.epsilon = epsilon
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        if self.centered:
            return (nd.zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),
                    nd.zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),
                    nd.zeros(weight.shape, ctx=weight.context, dtype=weight.dtype))
        return nd.zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        kw = dict(lr=lr, wd=wd, rescale_grad=self.rescale_grad,
                  gamma1=self.gamma1, epsilon=self.epsilon,
                  clip_gradient=_clip(self.clip_gradient),
                  clip_weights=_clip(self.clip_weights))
        if self.centered:
            n, g, delta = state
            invoke("rmspropalex_update", weight, grad, n, g, delta,
                   gamma2=self.gamma2, **kw)
        else:
            invoke("rmsprop_update", weight, grad, state, **kw)


@register
class AdaGrad(Optimizer):
    """Reference: optimizer.AdaGrad (history += g^2; w -= lr*g/sqrt(h+eps))."""

    def __init__(self, learning_rate=0.01, eps=1e-7, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return nd.zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = grad.clip(-self.clip_gradient, self.clip_gradient)
        # reference formula: history accumulates raw g^2; wd applied outside
        # the adaptive denominator (optimizer.AdaGrad)
        state += grad * grad
        div = grad / (state + self.float_stable_eps).sqrt()
        weight -= lr * (div + wd * weight)


@register
class AdaDelta(Optimizer):
    """Reference: optimizer.AdaDelta."""

    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho = rho
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, ctx=weight.context),
                nd.zeros(weight.shape, ctx=weight.context))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        wd = self._get_wd(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = grad.clip(-self.clip_gradient, self.clip_gradient)
        grad = grad + wd * weight
        acc_g, acc_delta = state
        acc_g[:] = self.rho * acc_g + (1.0 - self.rho) * grad * grad
        current_delta = ((acc_delta + self.epsilon).sqrt() /
                         (acc_g + self.epsilon).sqrt()) * grad
        acc_delta[:] = self.rho * acc_delta + \
            (1.0 - self.rho) * current_delta * current_delta
        weight -= current_delta


@register
class Ftrl(Optimizer):
    """Reference: optimizer.Ftrl → ftrl_update."""

    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1 = lamda1
        self.beta = beta

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, ctx=weight.context),   # z
                nd.zeros(weight.shape, ctx=weight.context))   # n

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        z, n = state
        invoke("ftrl_update", weight, grad, z, n, lr=lr, lamda1=self.lamda1,
               beta=self.beta, wd=wd, rescale_grad=self.rescale_grad,
               clip_gradient=_clip(self.clip_gradient))


@register
class LAMB(Optimizer):
    """Layer-wise adaptive moments for large-batch BERT (reference:
    optimizer.LAMB → lamb_update_phase1/2; SURVEY.md M6)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-6, lower_bound=None, upper_bound=None,
                 bias_correction=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.lower_bound = lower_bound
        self.upper_bound = upper_bound
        self.bias_correction = bias_correction

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),
                nd.zeros(weight.shape, ctx=weight.context, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        t = self._index_update_count[index]
        mean, var = state
        g_update = invoke("lamb_update_phase1", grad, weight, mean, var,
                          beta1=self.beta1, beta2=self.beta2,
                          epsilon=self.epsilon, t=t,
                          bias_correction=self.bias_correction, wd=wd,
                          rescale_grad=self.rescale_grad,
                          clip_gradient=_clip(self.clip_gradient))
        invoke("lamb_update_phase2", weight, g_update, lr=lr,
               lower_bound=_clip(self.lower_bound),
               upper_bound=_clip(self.upper_bound))


@register
class LARS(Optimizer):
    """Layer-wise adaptive rate scaling (reference: optimizer.LARS)."""

    def __init__(self, learning_rate=0.1, momentum=0.0, eta=0.001,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.eta = eta
        self.epsilon = epsilon

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return nd.zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        w_norm = float(weight.norm().asscalar())
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = g.clip(-self.clip_gradient, self.clip_gradient)
        g_norm = float(g.norm().asscalar())
        if w_norm > 0 and g_norm > 0:
            lars_ratio = self.eta * w_norm / \
                (g_norm + wd * w_norm + self.epsilon)
            lr = lr * lars_ratio
        kw = dict(lr=lr, wd=wd, rescale_grad=self.rescale_grad,
                  clip_gradient=_clip(self.clip_gradient))
        if state is not None:
            invoke("sgd_mom_update", weight, grad, state,
                   momentum=self.momentum, **kw)
        else:
            invoke("sgd_update", weight, grad, **kw)


@register
class SignSGD(Optimizer):
    def __init__(self, learning_rate=0.01, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        invoke("signsgd_update", weight, grad, lr=self._get_lr(index),
               wd=self._get_wd(index), rescale_grad=self.rescale_grad,
               clip_gradient=_clip(self.clip_gradient))


@register
class Signum(Optimizer):
    """Reference: optimizer.Signum → signum_update."""

    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return nd.zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        if state is not None:
            invoke("signum_update", weight, grad, state, lr=lr, wd=wd,
                   momentum=self.momentum, wd_lh=self.wd_lh,
                   rescale_grad=self.rescale_grad,
                   clip_gradient=_clip(self.clip_gradient))
        else:
            invoke("signsgd_update", weight, grad, lr=lr, wd=wd,
                   rescale_grad=self.rescale_grad,
                   clip_gradient=_clip(self.clip_gradient))


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (reference: optimizer.DCASGD)."""

    def __init__(self, learning_rate=0.01, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.weight_previous = {}
        self.lamda = lamda

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return (None, weight.copy())
        return (nd.zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),
                weight.copy())

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = grad.clip(-self.clip_gradient, self.clip_gradient)
        mom, previous_weight = state
        delta = -lr * (grad + wd * weight + self.lamda *
                       grad * grad * (weight - previous_weight))
        if mom is not None:
            mom[:] = self.momentum * mom + delta
            delta = mom
        previous_weight[:] = weight
        weight += delta


@register
class Test(Optimizer):
    """Reference: optimizer.Test — used by test_optimizer comparisons."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)

    def create_state(self, index, weight):
        return nd.zeros(weight.shape, ctx=weight.context)

    def update(self, index, weight, grad, state):
        weight += grad * self.rescale_grad
        state[:] = weight


def _updater_census_arrays(u):
    """One updater's live slot-state device buffers for the census."""
    import jax as _jax
    out = []
    for st in u.states.values():
        for leaf in _jax.tree_util.tree_leaves(st):
            a = getattr(leaf, "_jax", leaf)
            if hasattr(a, "nbytes"):
                out.append(a)
    return out


class Updater:
    """Apply an optimizer to (index, grad, weight) triples — the kvstore
    server-side hook (reference: get_updater / class Updater).

    Called with LISTS (Trainer._update, Module.update and KVStore.push all
    batch their params into one call), an aggregate-enabled optimizer
    applies the whole group as one fused pytree dispatch instead of N —
    the reference's multi_sgd_update path, finally wired up (the old
    ``aggregate_updates`` flag was computed and then ignored)."""

    def __init__(self, optimizer: Optimizer):
        self.optimizer = optimizer
        self.states: Dict[Any, Any] = {}
        self.states_synced: Dict[Any, bool] = {}
        # buffer-census attribution (ISSUE 10): slot state (momenta,
        # adam moments, fp32 masters) lands in "optimizer_state"
        from .. import programs as _programs
        _programs.track_buffers("optimizer_state", self,
                                _updater_census_arrays)

    @property
    def aggregate_updates(self):
        return self.optimizer.aggregate_num > 0

    def __call__(self, index, grad, weight):
        if not isinstance(index, (list, tuple)):
            index = [index]
            grad = [grad]
            weight = [weight]
        # per-device update counts (reference: Updater.__call__ →
        # _set_current_context): the Trainer runs one Updater per device
        # over the SAME optimizer object, so without switching the count
        # table each device copy would advance num_update — Adam-family
        # bias correction then sees t jump by #devices per step AND
        # differ across copies, silently desynchronizing the replicas
        ctx = getattr(weight[0], "context", None)
        if ctx is not None:
            self.optimizer._set_current_context(
                (ctx.canonical_type, ctx.device_id))
        for i, w in zip(index, weight):
            if i not in self.states:
                with _telemetry.phase("initialize"):
                    self.states[i] = \
                        self.optimizer.create_state_multi_precision(i, w)
                self.states_synced[i] = True
        todo = list(zip(index, grad, weight))
        if self.aggregate_updates and len(todo) > 1:
            # sparse grads/weights are excluded from fusion: their update
            # is a per-key gather/scatter keyed on nnz, not a dense pytree
            fusable = [(i, g, w) for i, g, w in todo
                       if getattr(g, "stype", "default") == "default"
                       and getattr(w, "stype", "default") == "default"]
            if len(fusable) > 1 and self.optimizer.fused_update(
                    [i for i, _, _ in fusable],
                    [w for _, _, w in fusable],
                    [g for _, g, _ in fusable],
                    [self.states[i] for i, _, _ in fusable]):
                fused = {i for i, _, _ in fusable}
                todo = [t for t in todo if t[0] not in fused]
        for i, g, w in todo:
            self.optimizer.update_multi_precision(i, w, g, self.states[i])

    def get_states(self, dump_optimizer=False):
        if dump_optimizer:
            return pickle.dumps((self.states, self.optimizer))
        return pickle.dumps(self.states)

    def set_states(self, states):
        loaded = pickle.loads(states)
        if isinstance(loaded, tuple) and len(loaded) == 2 and \
                isinstance(loaded[1], Optimizer):
            self.states, self.optimizer = loaded
        else:
            self.states = loaded
        self.states_synced = dict.fromkeys(self.states.keys(), False)


def get_updater(optimizer: Optimizer) -> Updater:
    return Updater(optimizer)


@register
class FTML(Optimizer):
    """Follow the Moving Leader (reference: optimizer.FTML →
    ftml_update fused kernel)."""

    def __init__(self, learning_rate=0.0025, beta1=0.6, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon

    def create_state(self, index, weight):
        z = nd.zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)
        return (nd.zeros_like(z), nd.zeros_like(z), z)   # d, v, z

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        d, v, z = state
        invoke("ftml_update", weight, grad, d, v, z,
               lr=self._get_lr(index), beta1=self.beta1, beta2=self.beta2,
               epsilon=self.epsilon, t=t, wd=self._get_wd(index),
               rescale_grad=self.rescale_grad,
               clip_grad=_clip(self.clip_gradient))


@register
class Adamax(Optimizer):
    """Infinity-norm Adam variant (reference: optimizer.Adamax — python
    update over nd ops, no fused kernel in the reference either)."""

    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999,
                 **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, ctx=weight.context,
                         dtype=weight.dtype),
                nd.zeros(weight.shape, ctx=weight.context,
                         dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr = self._get_lr(index) / (1.0 - self.beta1 ** t)
        wd = self._get_wd(index)
        grad = grad * self.rescale_grad + wd * weight
        if self.clip_gradient is not None:
            grad = invoke("clip", grad, a_min=-self.clip_gradient,
                          a_max=self.clip_gradient)
        m, u = state
        m[:] = self.beta1 * m + (1.0 - self.beta1) * grad
        u[:] = invoke("maximum", self.beta2 * u, invoke("abs", grad))
        weight[:] = weight - lr * m / (u + 1e-8)


@register
class Nadam(Optimizer):
    """Nesterov Adam (reference: optimizer.Nadam — momentum-schedule
    python update)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.schedule_decay = schedule_decay
        self.m_schedule = 1.0

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, ctx=weight.context,
                         dtype=weight.dtype),
                nd.zeros(weight.shape, ctx=weight.context,
                         dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        grad = grad * self.rescale_grad + wd * weight
        if self.clip_gradient is not None:
            grad = invoke("clip", grad, a_min=-self.clip_gradient,
                          a_max=self.clip_gradient)
        mu_t = self.beta1 * (1.0 - 0.5 * 0.96 ** (t * self.schedule_decay))
        mu_tp1 = self.beta1 * (1.0 - 0.5 * 0.96 ** ((t + 1)
                                                    * self.schedule_decay))
        self.m_schedule = self.m_schedule * mu_t
        m_schedule_next = self.m_schedule * mu_tp1
        m, v = state
        m[:] = self.beta1 * m + (1.0 - self.beta1) * grad
        v[:] = self.beta2 * v + (1.0 - self.beta2) * grad * grad
        g_prime = grad / (1.0 - self.m_schedule)
        m_prime = m / (1.0 - m_schedule_next)
        v_prime = v / (1.0 - self.beta2 ** t)
        m_bar = (1.0 - mu_t) * g_prime + mu_tp1 * m_prime
        weight[:] = weight - lr * m_bar / (invoke("sqrt", v_prime)
                                           + self.epsilon)


@register
class SGLD(Optimizer):
    """Stochastic Gradient Langevin Dynamics (reference: optimizer.SGLD
    — posterior sampling: half-lr gradient step + sqrt(lr) gaussian
    noise)."""

    def __init__(self, learning_rate=0.01, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)

    def create_state(self, index, weight):
        return None

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        # reference order: clip the RESCALED gradient, then add the full
        # (unclipped) weight-decay force
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = invoke("clip", grad, a_min=-self.clip_gradient,
                          a_max=self.clip_gradient)
        grad = grad + wd * weight
        noise = nd.random.normal(0.0, math.sqrt(lr), shape=weight.shape,
                                 ctx=weight.context)
        weight[:] = weight - lr / 2.0 * grad + noise
