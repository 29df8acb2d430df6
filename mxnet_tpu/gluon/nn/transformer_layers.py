"""Layers of today's decoder-only language models.

No reference counterpart (the reference stops at GluonNLP's post-LN
encoder): ``RMSNorm``, ``RotaryEmbedding``, the gated ``SwiGLU`` MLP and
``TokenChoiceMoE``, the mixture-of-experts layer as an expert-parallel
deployment runs it on one chip.  ``model_zoo.glm_moe_lite`` builds its
decoder from them.  Every layer names its input width, so nothing is
deferred and a net built from them hybridizes on its first call.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as _np

from ... import autograd
from ... import initializer as init
from ... import telemetry as _telemetry
from ...ndarray.ndarray import invoke
from ..block import HybridBlock
from ..parameter import Parameter
from .basic_layers import Dense

__all__ = ["RMSNorm", "RotaryEmbedding", "SwiGLU", "TokenChoiceMoE"]


class RMSNorm(HybridBlock):
    """``x / sqrt(mean(x^2) + eps) * gamma`` over the last axis, the mean
    in float32 (the ``RMSNorm`` operator)."""

    def __init__(self, in_channels, epsilon=1e-6, gamma_initializer="ones",
                 **kwargs):
        super().__init__(**kwargs)
        self._eps = epsilon
        self.gamma = Parameter("gamma", shape=(in_channels,),
                               init=init.create(gamma_initializer))

    def forward(self, x):
        return invoke("RMSNorm", x, self.gamma.data(x.context), eps=self._eps)

    def __repr__(self):
        return "RMSNorm(%s, eps=%s)" % (self.gamma.shape[0], self._eps)


class RotaryEmbedding(HybridBlock):
    """Rotary positions for the packed ``(B, T, num_heads * D)`` tensor a
    projection produces (the ``rotary_embedding`` operator): the last
    `rotary_dim` lanes of every head turn by the position's angle
    (``theta``, half-split pairs), the lanes before them pass through."""

    def __init__(self, num_heads, rotary_dim=None, theta=10000.0, **kwargs):
        super().__init__(**kwargs)
        self._heads, self._dim, self._theta = num_heads, rotary_dim, theta

    def forward(self, x):
        return invoke("rotary_embedding", x, num_heads=self._heads,
                      rotary_dim=self._dim, theta=float(self._theta))

    def __repr__(self):
        return "RotaryEmbedding(heads=%s, dim=%s, theta=%g)" % (
            self._heads, self._dim, self._theta)


class SwiGLU(HybridBlock):
    """The gated MLP ``down(silu(gate(x)) * up(x))``, no biases.  Gate and
    up share one ``(2 * hidden, units)`` weight (`gate_up_proj`: the gate's
    rows first), so the block is two matrix products."""

    def __init__(self, units, hidden_size, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self.gate_up_proj = Dense(2 * hidden_size, use_bias=False,
                                  flatten=False, in_units=units, dtype=dtype)
        self.down_proj = Dense(units, use_bias=False, flatten=False,
                               in_units=hidden_size, dtype=dtype)

    def forward(self, x):
        gate, up = self.gate_up_proj(x).split(num_outputs=2, axis=-1)
        return self.down_proj(invoke("silu", gate) * up)


class TokenChoiceMoE(HybridBlock):
    """Mixture of experts with token-choice top-k routing, as one chip of
    an expert-parallel deployment runs it (``parallel/moe.py``).

    The layer is TOLD which experts it holds: `held` lists their global
    ids (default: all `num_experts`).  It scores every token over all
    `num_experts` (sigmoid, float32), picks the `top_k` by score +
    `router_correction` (the ``noaux_tc`` selection bias: it picks, it
    does not weigh, it has no gradient; the name keeps clear of the
    initializers' ``*bias`` -> zeros rule), weighs them ``scale * s / sum of the chosen s`` (`norm_topk_prob`)
    and returns

        shared(x) + sum over chosen AND held experts of w_e * expert_e(x)

    with every expert a SwiGLU of width `hidden_size` and `num_shared`
    shared experts fused into one SwiGLU of width ``num_shared *
    hidden_size``.  The held experts' products are grouped (assignments
    sorted by expert, one ragged product a projection); no token is
    dropped whatever the imbalance; nothing stands in for the experts held
    elsewhere or for the exchange a deployment over several chips has.

    Counters: in training mode the float32 aux buffers `assignments`
    (one a held expert) and `elsewhere` grow inside the step; the
    telemetry registry reads them on demand as ``moe_assignments{layer,
    expert}`` and ``moe_assignments_elsewhere{layer}`` (`layer` labels
    them; without it the layer is not registered).
    """

    def __init__(self, units, hidden_size, num_experts, top_k,
                 held: Optional[Sequence[int]] = None, num_shared=0,
                 scale=1.0, norm_topk_prob=True,
                 correction_initializer="zeros", layer=None,
                 dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self._held = tuple(range(num_experts)) if held is None \
            else tuple(int(e) for e in held)
        if not self._held or len(set(self._held)) != len(self._held) or \
                not all(0 <= e < num_experts for e in self._held):
            raise ValueError("held=%r: distinct expert ids below %d"
                             % (held, num_experts))
        self._top_k, self._scale = int(top_k), float(scale)
        self._norm = bool(norm_topk_prob)
        n = len(self._held)
        # the router decides near-ties: float32 whatever the net is cast to
        self.router_weight = Parameter("router_weight", dtype="float32",
                                       shape=(num_experts, units))
        self.router_correction = Parameter(
            "router_correction", shape=(num_experts,), dtype="float32",
            grad_req="null", init=init.create(correction_initializer))
        self.gate_up_weight = Parameter(
            "gate_up_weight", shape=(n, units, 2 * hidden_size), dtype=dtype)
        self.down_weight = Parameter(
            "down_weight", shape=(n, hidden_size, units), dtype=dtype)
        self.assignments = Parameter("assignments", shape=(n,),
                                     grad_req="null", init=init.Zero())
        self.elsewhere = Parameter("elsewhere", shape=(1,), grad_req="null",
                                   init=init.Zero())
        self.shared = SwiGLU(units, num_shared * hidden_size, dtype=dtype) \
            if num_shared else None
        if layer is not None:
            self._register_counters(str(layer))

    @property
    def held(self):
        return self._held

    def _register_counters(self, layer):
        # the readers hold the two small buffers' Parameters, not the
        # block: the counts outlive the net (a benchmark reads them after
        # its driver has let go of the model) and pin a few bytes
        def read(param, at):
            def value():
                if param._data is None:
                    return None
                return float(_np.asarray(param.data()._jax)[at])
            return value

        for at, expert in enumerate(self._held):
            _telemetry.registry.read_counter(
                "moe_assignments", read(self.assignments, at),
                "assignments (token, choice) routed to a held expert",
                {"layer": layer, "expert": str(expert)})
        _telemetry.registry.read_counter(
            "moe_assignments_elsewhere", read(self.elsewhere, 0),
            "assignments routed to experts held on other chips",
            {"layer": layer})

    def cast(self, dtype):
        """The router (weight, bias) and the counters stay float32."""
        if self.shared is not None:
            self.shared.cast(dtype)
        self.gate_up_weight.cast(dtype)
        self.down_weight.cast(dtype)

    def _router(self, x):
        ctx = x.context
        return self.router_weight.data(ctx), self.router_correction.data(ctx)

    def forward(self, x):
        ctx = x.context
        weight, bias = self._router(x)
        y = invoke("moe_token_choice", x, weight, bias,
                   self.gate_up_weight.data(ctx), self.down_weight.data(ctx),
                   self.assignments.data(ctx), self.elsewhere.data(ctx),
                   held=self._held, top_k=self._top_k, scale=self._scale,
                   norm_topk_prob=self._norm, count=autograd.is_training())
        return y if self.shared is None else self.shared(x) + y

    def choose(self, x):
        """The experts (global ids, ``(..., top_k)`` int32) `forward`
        picks for `x`."""
        weight, bias = self._router(x)
        return invoke("moe_topk_choice", x, weight, bias, top_k=self._top_k)

    def __repr__(self):
        return "TokenChoiceMoE(%d held of %d, top %d, shared=%s)" % (
            len(self._held), self.router_weight.shape[0], self._top_k,
            self.shared is not None)
