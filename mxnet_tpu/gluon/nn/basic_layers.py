"""Basic neural-network layers.

Reference: python/mxnet/gluon/nn/basic_layers.py (Dense, Dropout, BatchNorm,
LayerNorm, GroupNorm, InstanceNorm, Embedding, Flatten, Sequential,
HybridSequential, Lambda, HybridLambda, Identity) and activations.py.

All layers run the same code imperatively and under the hybridize trace
(ops dispatch through the registry; XLA fuses the norm/activation chains
into neighbouring matmuls — SURVEY.md §2.1 "Dense op kernels" row).
"""
from __future__ import annotations

from typing import Optional

import numpy as _np

from ...base import MXNetError
from ... import ndarray as nd
from ... import telemetry
from ...ndarray.ndarray import NDArray, invoke
from ... import initializer as init
from ..block import Block, HybridBlock
from ..parameter import Parameter

__all__ = ["Sequential", "HybridSequential", "Dense", "Dropout", "BatchNorm",
           "SyncBatchNorm", "LayerNorm", "GroupNorm", "InstanceNorm",
           "Embedding", "Flatten", "Lambda", "HybridLambda", "Identity",
           "Activation", "LeakyReLU", "PReLU", "ELU", "SELU", "GELU", "Swish",
           "SiLU", "Concatenate", "HybridConcatenate"]


class Sequential(Block):
    """Stack of blocks (reference: nn.Sequential)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)

    def add(self, *blocks):
        for b in blocks:
            self.register_child(b)
        return self

    def forward(self, x, *args):
        for block in self._children.values():
            x = block(x, *args)
            args = ()
        return x

    def __getitem__(self, key):
        layers = list(self._children.values())
        if isinstance(key, slice):
            net = type(self)()
            net.add(*layers[key])
            return net
        return layers[key]

    def __len__(self):
        return len(self._children)

    def __iter__(self):
        return iter(self._children.values())


class HybridSequential(HybridBlock):
    """Hybridizable stack (reference: nn.HybridSequential)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)

    def add(self, *blocks):
        for b in blocks:
            self.register_child(b)
        return self

    def forward(self, x, *args):
        for block in self._children.values():
            x = block(x, *args)
            args = ()
        return x

    def __getitem__(self, key):
        layers = list(self._children.values())
        if isinstance(key, slice):
            net = type(self)()
            net.add(*layers[key])
            return net
        return layers[key]

    def __len__(self):
        return len(self._children)

    def __iter__(self):
        return iter(self._children.values())


class Dense(HybridBlock):
    """Fully-connected layer, weight layout (units, in_units) like the
    reference (src/operator/nn/fully_connected.cc row-major cuBLAS layout —
    here a jnp matmul the MXU tiles directly)."""

    def __init__(self, units, activation=None, use_bias=True, flatten=True,
                 dtype="float32", weight_initializer=None,
                 bias_initializer="zeros", in_units=0, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._flatten = flatten
        self._act = activation
        self.weight = Parameter("weight", shape=(units, in_units), dtype=dtype,
                                init=weight_initializer,
                                allow_deferred_init=True)
        if use_bias:
            self.bias = Parameter("bias", shape=(units,), dtype=dtype,
                                  init=init.create(bias_initializer)
                                  if isinstance(bias_initializer, str)
                                  else bias_initializer,
                                  allow_deferred_init=True)
        else:
            self.bias = None

    def infer_shape(self, x):
        in_units = int(_np.prod(x.shape[1:])) if self._flatten else x.shape[-1]
        self.weight.shape = (self._units, in_units)
        if self.bias is not None:
            self.bias.shape = (self._units,)

    def forward(self, x):
        out = invoke("FullyConnected", x, self.weight.data(x.context),
                     None if self.bias is None else self.bias.data(x.context),
                     num_hidden=self._units, no_bias=self.bias is None,
                     flatten=self._flatten)
        if self._act:
            out = invoke("Activation", out, act_type=self._act)
        return out

    def __repr__(self):
        shape = self.weight.shape
        return "Dense(%s -> %s, %s)" % (
            shape[1] if shape and len(shape) > 1 else None, self._units,
            self._act or "linear")


class Dropout(HybridBlock):
    """Reference: nn.Dropout — active only in train mode (autograd
    train_mode / is_training), scaled by 1/(1-p)."""

    def __init__(self, rate, axes=(), **kwargs):
        super().__init__(**kwargs)
        self._rate = rate
        self._axes = axes

    def forward(self, x):
        from ... import autograd
        if not autograd.is_training() or self._rate == 0:
            return x
        return invoke("Dropout", x, p=self._rate, axes=tuple(self._axes),
                      mode="training")

    def __repr__(self):
        return "Dropout(p = %s, axes=%s)" % (self._rate, self._axes)


def count_batch_norm(stats):
    """The counter ``batch_norm_calls{stats}``: a BatchNorm layer's calls
    by the statistics they normalise with, ``batch`` (training: the
    batch's, the moving ones updated) or ``moving`` (``use_global_stats``
    and inference).  Counted where the layer is called, as
    ``cross_entropy_calls``: a compiled step counts each of its layers
    once, when it is traced, however often it runs (the operator itself
    is traced once a shape)."""
    telemetry.registry.counter(
        "batch_norm_calls", "calls of a BatchNorm layer by the statistics "
        "they normalise with: the batch's, or the moving ones",
        {"stats": stats}).inc()


class BatchNorm(HybridBlock):
    """Reference: nn.BatchNorm over axis=1 (channels) with moving stats as
    aux states (running_mean/running_var mutated in train mode — the rebuild's
    aux_writeback path)."""

    def __init__(self, axis=1, momentum=0.9, epsilon=1e-5, center=True,
                 scale=True, use_global_stats=False, beta_initializer="zeros",
                 gamma_initializer="ones", running_mean_initializer="zeros",
                 running_variance_initializer="ones", in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._momentum = momentum
        self._eps = epsilon
        self._center = center
        self._scale = scale
        self._use_global_stats = use_global_stats
        ch = in_channels
        self.gamma = Parameter("gamma", shape=(ch,),
                               init=init.create(gamma_initializer),
                               grad_req="write" if scale else "null",
                               allow_deferred_init=True)
        self.beta = Parameter("beta", shape=(ch,),
                              init=init.create(beta_initializer),
                              grad_req="write" if center else "null",
                              allow_deferred_init=True)
        self.running_mean = Parameter(
            "running_mean", shape=(ch,),
            init=init.create(running_mean_initializer), grad_req="null",
            allow_deferred_init=True)
        self.running_var = Parameter(
            "running_var", shape=(ch,),
            init=init.create(running_variance_initializer), grad_req="null",
            allow_deferred_init=True)

    def infer_shape(self, x):
        ch = x.shape[self._axis]
        for p in (self.gamma, self.beta, self.running_mean, self.running_var):
            p.shape = (ch,)

    def forward(self, x):
        from ... import autograd
        use_global = self._use_global_stats or not autograd.is_training()
        count_batch_norm("moving" if use_global else "batch")
        ctx = x.context
        return invoke("BatchNorm", x, self.gamma.data(ctx),
                      self.beta.data(ctx), self.running_mean.data(ctx),
                      self.running_var.data(ctx), eps=self._eps,
                      momentum=self._momentum, axis=self._axis,
                      fix_gamma=not self._scale,
                      use_global_stats=use_global)

    def __repr__(self):
        return "BatchNorm(axis=%s, eps=%s, momentum=%s, in_channels=%s)" % (
            self._axis, self._eps, self._momentum,
            self.gamma.shape[0] if self.gamma.shape else None)


class SyncBatchNorm(BatchNorm):
    """Cross-device BatchNorm (reference: contrib.nn.SyncBatchNorm).  On the
    mesh data path batch stats are reduced with psum inside the sharded step
    (parallel/); single-device semantics equal BatchNorm."""

    def __init__(self, in_channels=0, num_devices=None, **kwargs):
        super().__init__(in_channels=in_channels, **kwargs)
        self._num_devices = num_devices


class LayerNorm(HybridBlock):
    """Reference: nn.LayerNorm (src/operator/nn/layer_norm.cc)."""

    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._eps = epsilon
        self.gamma = Parameter("gamma", shape=(in_channels,),
                               init=init.create(gamma_initializer),
                               grad_req="write" if scale else "null",
                               allow_deferred_init=True)
        self.beta = Parameter("beta", shape=(in_channels,),
                              init=init.create(beta_initializer),
                              grad_req="write" if center else "null",
                              allow_deferred_init=True)

    def infer_shape(self, x):
        ch = x.shape[self._axis]
        self.gamma.shape = (ch,)
        self.beta.shape = (ch,)

    def forward(self, x):
        ctx = x.context
        return invoke("LayerNorm", x, self.gamma.data(ctx),
                      self.beta.data(ctx), axis=self._axis, eps=self._eps)


class GroupNorm(HybridBlock):
    """Reference: nn.GroupNorm."""

    def __init__(self, num_groups=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._groups = num_groups
        self._eps = epsilon
        self.gamma = Parameter("gamma", shape=(in_channels,),
                               init=init.create(gamma_initializer),
                               grad_req="write" if scale else "null",
                               allow_deferred_init=True)
        self.beta = Parameter("beta", shape=(in_channels,),
                              init=init.create(beta_initializer),
                              grad_req="write" if center else "null",
                              allow_deferred_init=True)

    def infer_shape(self, x):
        ch = x.shape[1]
        self.gamma.shape = (ch,)
        self.beta.shape = (ch,)

    def forward(self, x):
        ctx = x.context
        return invoke("GroupNorm", x, self.gamma.data(ctx),
                      self.beta.data(ctx), num_groups=self._groups,
                      eps=self._eps)


class InstanceNorm(HybridBlock):
    """Reference: nn.InstanceNorm."""

    def __init__(self, axis=1, epsilon=1e-5, center=True, scale=False,
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._eps = epsilon
        self.gamma = Parameter("gamma", shape=(in_channels,), init=init.One(),
                               grad_req="write" if scale else "null",
                               allow_deferred_init=True)
        self.beta = Parameter("beta", shape=(in_channels,), init=init.Zero(),
                              grad_req="write" if center else "null",
                              allow_deferred_init=True)

    def infer_shape(self, x):
        ch = x.shape[1]
        self.gamma.shape = (ch,)
        self.beta.shape = (ch,)

    def forward(self, x):
        ctx = x.context
        return invoke("InstanceNorm", x, self.gamma.data(ctx),
                      self.beta.data(ctx), eps=self._eps)


class Embedding(HybridBlock):
    """Reference: nn.Embedding (src/operator/tensor/indexing_op.cc Embedding).
    Rowsparse gradient becomes a scatter-add on TPU (SURVEY.md sparse row)."""

    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, sparse_grad=False, **kwargs):
        super().__init__(**kwargs)
        self._input_dim = input_dim
        self._output_dim = output_dim
        # sparse_grad: the trainer converts the (dense, mostly-zero-row)
        # tape gradient to row_sparse so the optimizer's lazy path touches
        # only rows the batch used (reference: Embedding sparse_grad)
        self.weight = Parameter("weight", shape=(input_dim, output_dim),
                                dtype=dtype, init=weight_initializer,
                                grad_stype="row_sparse" if sparse_grad
                                else "default")

    def forward(self, x):
        return invoke("Embedding", x, self.weight.data(x.context),
                      input_dim=self._input_dim, output_dim=self._output_dim)

    def __repr__(self):
        return "Embedding(%s -> %s)" % (self._input_dim, self._output_dim)


class Flatten(HybridBlock):
    def forward(self, x):
        return invoke("flatten", x)

    def __repr__(self):
        return "Flatten"


class Identity(HybridBlock):
    def forward(self, x):
        return x


class Lambda(Block):
    """Wrap a function as a Block (reference: nn.Lambda)."""

    def __init__(self, function, **kwargs):
        super().__init__(**kwargs)
        if isinstance(function, str):
            self._func = getattr(nd, function)
            self._name = function
        else:
            self._func = function
            self._name = getattr(function, "__name__", "lambda")

    def forward(self, *args):
        return self._func(*args)

    def __repr__(self):
        return "Lambda(%s)" % self._name


class HybridLambda(HybridBlock):
    def __init__(self, function, **kwargs):
        super().__init__(**kwargs)
        if isinstance(function, str):
            self._func = getattr(nd, function)
            self._name = function
        else:
            self._func = function
            self._name = getattr(function, "__name__", "lambda")

    def forward(self, *args):
        return self._func(*args)

    def __repr__(self):
        return "HybridLambda(%s)" % self._name


class Concatenate(Sequential):
    """Run children on the same input, concat outputs (reference 2.x:
    nn.Concatenate)."""

    def __init__(self, axis=-1, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis

    def forward(self, x):
        outs = [block(x) for block in self._children.values()]
        return nd.concat(*outs, dim=self._axis)


class HybridConcatenate(HybridSequential):
    def __init__(self, axis=-1, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis

    def forward(self, x):
        outs = [block(x) for block in self._children.values()]
        return nd.concat(*outs, dim=self._axis)


# ---------------------------------------------------------------------------
# activation layers (reference: gluon/nn/activations.py)
# ---------------------------------------------------------------------------


class Activation(HybridBlock):
    def __init__(self, activation, **kwargs):
        super().__init__(**kwargs)
        self._act = activation

    def forward(self, x):
        return invoke("Activation", x, act_type=self._act)

    def __repr__(self):
        return "Activation(%s)" % self._act


class LeakyReLU(HybridBlock):
    def __init__(self, alpha=0.01, **kwargs):
        super().__init__(**kwargs)
        self._alpha = alpha

    def forward(self, x):
        return invoke("LeakyReLU", x, act_type="leaky", slope=self._alpha)


class PReLU(HybridBlock):
    """Reference: nn.PReLU — learnable slope."""

    def __init__(self, alpha_initializer=init.Constant(0.25), in_channels=1,
                 **kwargs):
        super().__init__(**kwargs)
        self.alpha = Parameter("alpha", shape=(in_channels,),
                               init=alpha_initializer)

    def forward(self, x):
        return invoke("LeakyReLU", x, self.alpha.data(x.context),
                      act_type="prelu")


class ELU(HybridBlock):
    def __init__(self, alpha=1.0, **kwargs):
        super().__init__(**kwargs)
        self._alpha = alpha

    def forward(self, x):
        return invoke("LeakyReLU", x, act_type="elu", slope=self._alpha)


class SELU(HybridBlock):
    def forward(self, x):
        return invoke("LeakyReLU", x, act_type="selu")


class GELU(HybridBlock):
    def __init__(self, approximation="erf", **kwargs):
        super().__init__(**kwargs)
        self._approx = approximation

    def forward(self, x):
        return invoke("LeakyReLU", x, act_type="gelu")


class Swish(HybridBlock):
    def __init__(self, beta=1.0, **kwargs):
        super().__init__(**kwargs)
        self._beta = beta

    def forward(self, x):
        return x * invoke("sigmoid", x * self._beta)


SiLU = Swish
