"""Layers of today's decoder-only language models.

No reference counterpart (the reference stops at GluonNLP's post-LN
encoder): ``RMSNorm``, ``RotaryEmbedding``, the gated ``SwiGLU`` and the
ungated ``SquaredReLUMLP``, ``GroupedQueryAttention`` (query heads that
share key/value heads), ``Mamba2Mixer`` (a state-space layer:
``ops/ssm.py``) and ``TokenChoiceMoE``, the mixture-of-experts layer as an
expert-parallel deployment runs it on one chip.  ``model_zoo.glm_moe_lite``
and ``model_zoo.nemotron_h`` build their decoders from them.  Every layer
names its input width, so nothing is deferred and a net built from them
hybridizes on its first call.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as _np

from ... import autograd
from ... import initializer as init
from ... import telemetry as _telemetry
from ...ndarray.ndarray import invoke
from ..block import HybridBlock, trace_scope
from ..parameter import Parameter
from .basic_layers import Dense

__all__ = ["RMSNorm", "RotaryEmbedding", "SwiGLU", "SquaredReLUMLP",
           "GroupedQueryAttention", "Mamba2Mixer", "TokenChoiceMoE"]


class RMSNorm(HybridBlock):
    """``x / sqrt(mean(x^2) + eps) * gamma`` over the last axis, the mean
    in float32 (the ``RMSNorm`` operator).  The result has `x`'s dtype
    whatever dtype the gain is kept in: a float32 `gamma` beside bfloat16
    data is applied in float32 and the product rounded once, so a
    parameter left float32 under `cast` does not promote the net's
    activations."""

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
    (``theta``, half-split pairs), the lanes before them pass through -
    or, with `first`, the first `rotary_dim` lanes turn and the rest
    pass.  `yarn` = ``(factor, original positions, beta_fast,
    beta_slow)`` blends the frequencies as YaRN does and
    `attention_factor` scales cos and sin; the defaults are the plain
    rotary call.  What runs follows from the shapes
    (``ops/rotary.py:rotary_rule``): heads of whole 128-lane blocks on a
    TPU turn where they lie, in one Pallas kernel forward and backward
    that keeps nothing of its input; any other call is the operator's
    composition."""

    def __init__(self, num_heads, rotary_dim=None, theta=10000.0,
                 first=False, yarn=None, attention_factor=1.0, **kwargs):
        super().__init__(**kwargs)
        self._heads, self._dim, self._theta = num_heads, rotary_dim, theta
        self._more = {}
        if first or yarn is not None or attention_factor != 1.0:
            self._more = dict(
                first=bool(first),
                yarn=None if yarn is None else tuple(float(y) for y in yarn),
                attention_factor=float(attention_factor))

    def forward(self, x, num_heads=None):
        """`num_heads`: of `x`, where it is not the block's own (keys on
        fewer heads than the queries)."""
        return invoke("rotary_embedding", x,
                      num_heads=num_heads or self._heads,
                      rotary_dim=self._dim, theta=float(self._theta),
                      **self._more)

    def __repr__(self):
        return "RotaryEmbedding(heads=%s, dim=%s, theta=%g%s)" % (
            self._heads, self._dim, self._theta,
            "".join(", %s=%s" % kv for kv in self._more.items()))


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


class SquaredReLUMLP(HybridBlock):
    """The ungated MLP ``down(relu(up(x))^2)``, no biases."""

    def __init__(self, units, hidden_size, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self.up_proj = Dense(hidden_size, use_bias=False, flatten=False,
                             in_units=units, dtype=dtype)
        self.down_proj = Dense(units, use_bias=False, flatten=False,
                               in_units=hidden_size, dtype=dtype)

    def forward(self, x):
        return self.down_proj(invoke("square",
                                     invoke("relu", self.up_proj(x))))


class GroupedQueryAttention(HybridBlock):
    """Causal self-attention of `num_heads` query heads on `num_kv_heads`
    key/value heads of `head_dim` lanes (``num_heads / num_kv_heads``
    query heads read one key/value head), no biases:
    ``o(softmax(q k^T / sqrt(head_dim)) v)``.  The core is
    ``multi_head_attention`` on the packed ``(B, T, heads * head_dim)``
    tensors the projections produce, so ``ops/attention.py``'s rule
    decides between the flash kernels - which read the one key/value
    head for each of its query heads - and the composition.

    The defaults are the layer with no position signal, every earlier
    key visible and no gate.  `window`: a query sees the `window` keys
    that end with its own (``multi_head_attention``'s band).  `rotary`:
    keywords of `RotaryEmbedding` (``rotary_dim``, ``theta``, ``first``,
    ``yarn``, ``attention_factor``) for the positions q and k take
    (scope ``rotary``).  `head_gate`: a sigmoid gate a query head on the
    attention output, ``o_h <- sigmoid(x W_g)_h o_h`` before `o_proj`,
    from the layer's own input (`g_proj`, ``units x num_heads``; scope
    ``head_gate``)."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 dtype="float32", window=None, rotary=None, head_gate=False,
                 **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise ValueError("GroupedQueryAttention: %d query heads on %d "
                             "key/value heads" % (num_heads, num_kv_heads))
        self._heads, self._kv_heads = num_heads, num_kv_heads
        self._band = {} if window is None else {"window": int(window)}

        def dense(out, inp):
            return Dense(out, use_bias=False, flatten=False, in_units=inp,
                         dtype=dtype)

        self.q_proj = dense(num_heads * head_dim, units)
        self.k_proj = dense(num_kv_heads * head_dim, units)
        self.v_proj = dense(num_kv_heads * head_dim, units)
        self.o_proj = dense(units, num_heads * head_dim)
        self.rotary = None if rotary is None \
            else RotaryEmbedding(num_heads, **rotary)
        self.g_proj = dense(num_heads, units) if head_gate else None

    def forward(self, x):
        q, k = self.q_proj(x), self.k_proj(x)
        if self.rotary is not None:
            q, k = self.rotary(q), self.rotary(k, self._kv_heads)
        out = invoke("multi_head_attention", q, k, self.v_proj(x), None,
                     num_heads=self._heads, scaled=True, causal=True,
                     **self._band)
        if self.g_proj is not None:
            with trace_scope("head_gate"):
                b, t, _ = out.shape
                gate = invoke("sigmoid", self.g_proj(x))
                out = (out.reshape((b, t, self._heads, -1))
                       * gate.reshape((b, t, self._heads, 1))) \
                    .reshape((b, t, -1))
        return self.o_proj(out)


class _Mamba2Draw(init.Initializer):
    """Mamba-2's own draws, whatever the parameter's name ends in:
    ``log U(low, high)`` (A_log), or with `step` the ``b`` whose
    ``softplus(b)`` is ``exp U(log low, log high)`` floored at `floor`
    (dt_bias: step sizes spread evenly over the orders of magnitude)."""

    def __init__(self, low, high, step=False, floor=0.0):
        super().__init__(low=low, high=high, step=step, floor=floor)
        self._low, self._high = float(low), float(high)
        self._step, self._floor = bool(step), float(floor)

    def __call__(self, desc, arr):
        if self._step:
            drawn = _np.exp(_np.asarray(init._draw_uniform(
                _np.log(self._low), _np.log(self._high), arr.shape)))
            drawn = _np.maximum(drawn, self._floor)
            arr[:] = drawn + _np.log(-_np.expm1(-drawn))
        else:
            arr[:] = _np.log(_np.asarray(init._draw_uniform(
                self._low, self._high, arr.shape)))


class Mamba2Mixer(HybridBlock):
    """Mamba-2's mixer (Dao & Gu 2024; ``ops/ssm.py`` has the scan):

        [z | xBC | dt] = in_proj(u)                      no bias
        xBC = silu(causal depthwise conv_K(xBC) + b)
        [x | B | C] = xBC        x: heads * head_dim, B, C: groups * N
        dt = softplus(dt + dt_bias),  A = -exp(A_log)     a head
        h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t,  y_t = h_t C_t + D x_t
        out = out_proj(GroupRMSNorm(y * silu(z)))         `groups` groups

    `num_heads` heads of `head_dim` in `num_groups` groups (a head reads
    its group's B and C; the norm's groups are the same), state size
    `state_size`, computed in chunks of `chunk_size` positions.  A_log
    ``log U(1, 16)``, dt_bias the inverse softplus of a log-uniform step
    in ``[dt_min, dt_max]`` (floored), D ones, the convolution ``U(+-
    K^-1/2)``: the published initialisation, whatever initializer the net
    is given.  Under `cast` the first three and the norm's gain stay
    float32 (the scan reads them in float32; the gain is applied in
    float32); the mixer returns its input's dtype all the same - the scan
    and the norm each return their data's.
    Scopes: the convolution ``conv``, the scan with its skip and gate
    ``scan``."""

    def __init__(self, units, num_heads, head_dim, state_size, num_groups=1,
                 conv_kernel=4, chunk_size=128, epsilon=1e-5, dt_min=0.001,
                 dt_max=0.1, dt_floor=1e-4, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_groups:
            raise ValueError("Mamba2Mixer: %d heads in %d groups"
                             % (num_heads, num_groups))
        self._heads, self._groups = num_heads, num_groups
        self._inner = num_heads * head_dim
        self._bc = num_groups * state_size
        self._chunk, self._eps = chunk_size, epsilon
        conv = self._inner + 2 * self._bc
        self.in_proj = Dense(self._inner + conv + num_heads, use_bias=False,
                             flatten=False, in_units=units, dtype=dtype)
        self.conv_weight = Parameter(
            "conv_weight", shape=(conv, conv_kernel), dtype=dtype,
            init=init.Uniform(conv_kernel ** -0.5))
        self.conv_bias = Parameter("conv_bias", shape=(conv,), dtype=dtype,
                                   init=init.Zero())
        self.dt_bias = Parameter(
            "dt_bias", shape=(num_heads,), dtype="float32",
            init=_Mamba2Draw(dt_min, dt_max, True, dt_floor))
        self.A_log = Parameter("A_log", shape=(num_heads,), dtype="float32",
                               init=_Mamba2Draw(1.0, 16.0))
        self.D = Parameter("D", shape=(num_heads,), dtype="float32",
                           init=init.One())
        self.norm_gamma = Parameter("norm_gamma", shape=(self._inner,),
                                    dtype="float32", init=init.One())
        self.out_proj = Dense(units, use_bias=False, flatten=False,
                              in_units=self._inner, dtype=dtype)

    def cast(self, dtype):
        """dt_bias, A_log, D and the norm's gain stay float32; the mixer
        returns its input's dtype."""
        self.in_proj.cast(dtype)
        self.out_proj.cast(dtype)
        self.conv_weight.cast(dtype)
        self.conv_bias.cast(dtype)

    def forward(self, u):
        ctx = u.context
        inner, bc = self._inner, self._bc
        mixed = self.in_proj(u)

        def part(x, begin, end):
            return invoke("slice_axis", x, axis=-1, begin=begin, end=end)

        z = part(mixed, 0, inner)
        dt = part(mixed, 2 * inner + 2 * bc, None)
        xbc = invoke("causal_conv1d", part(mixed, inner, 2 * inner + 2 * bc),
                     self.conv_weight.data(ctx), self.conv_bias.data(ctx))
        y = invoke("ssm_scan", part(xbc, 0, inner),
                   part(xbc, inner, inner + bc), part(xbc, inner + bc, None),
                   dt, z, self.dt_bias.data(ctx), self.A_log.data(ctx),
                   self.D.data(ctx), num_heads=self._heads,
                   num_groups=self._groups, chunk=self._chunk)
        b, t, _ = y.shape
        y = invoke("RMSNorm", y.reshape((b, t, self._groups, -1)),
                   self.norm_gamma.data(ctx).reshape((self._groups, -1)),
                   eps=self._eps)
        return self.out_proj(y.reshape((b, t, inner)))

    def __repr__(self):
        return "Mamba2Mixer(%d heads of %d in %d groups, chunk %d)" % (
            self._heads, self._inner // self._heads, self._groups,
            self._chunk)


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

    An expert is what `activation` says, of width `hidden_size`:
    ``"swiglu"``, the gated ``(silu(x Wg) * (x Wu)) Wd`` (first matrix
    `gate_up_weight`), or ``"relu2"``, the ungated ``relu(x W1)^2 Wd``
    (first matrix `up_weight`).  With `latent_size` the routed experts
    live in a latent: ``latent_up(sum ... expert_e(latent_down(x)))``, the
    two projections shared by the layer's experts (scope ``latent``), the
    router still reading `x`.  The shared expert reads `x` itself: one MLP
    of the experts' kind and of width `shared_hidden_size` (default
    ``num_shared * hidden_size``: `num_shared` experts fused).  The held
    experts' products are grouped (assignments sorted by expert, one
    grouped product a projection) over a buffer sized to the load the
    device counts: twice an even router's share where the held experts'
    assignments fit that, else ``tokens * min(top_k, held)`` rows - the
    most that can land here, so no token is dropped whatever the
    imbalance (``parallel.moe.held_expert_ffn``; small layers have the
    second size alone); nothing stands in for the experts held elsewhere
    or for the exchange a deployment over several chips has.

    Counters: in training mode the float32 aux buffers `assignments`
    (one a held expert), `elsewhere` and `buffer_calls` grow inside the
    step; the telemetry registry reads them on demand as
    ``moe_assignments{layer, expert}``, ``moe_assignments_elsewhere{layer}``,
    ``moe_exact_buffer_calls{layer}`` (calls whose load passed the short
    buffer: 0 where the layer has one size) and ``moe_layer_calls{layer}``
    (`layer` labels them; without it the layer is not registered).
    """

    def __init__(self, units, hidden_size, num_experts, top_k,
                 held: Optional[Sequence[int]] = None, num_shared=0,
                 scale=1.0, norm_topk_prob=True,
                 correction_initializer="zeros", layer=None,
                 dtype="float32", activation="swiglu", latent_size=None,
                 shared_hidden_size=None, **kwargs):
        super().__init__(**kwargs)
        if activation not in ("swiglu", "relu2"):
            raise ValueError("TokenChoiceMoE: activation %r" % (activation,))
        self._held = tuple(range(num_experts)) if held is None \
            else tuple(int(e) for e in held)
        if not self._held or len(set(self._held)) != len(self._held) or \
                not all(0 <= e < num_experts for e in self._held):
            raise ValueError("held=%r: distinct expert ids below %d"
                             % (held, num_experts))
        self._top_k, self._scale = int(top_k), float(scale)
        self._norm = bool(norm_topk_prob)
        self._activation = activation
        gated = activation == "swiglu"
        inner = units if latent_size is None else latent_size
        n = len(self._held)
        # the router decides near-ties: float32 whatever the net is cast to
        self.router_weight = Parameter("router_weight", dtype="float32",
                                       shape=(num_experts, units))
        self.router_correction = Parameter(
            "router_correction", shape=(num_experts,), dtype="float32",
            grad_req="null", init=init.create(correction_initializer))
        if gated:
            self.gate_up_weight = Parameter(
                "gate_up_weight", shape=(n, inner, 2 * hidden_size),
                dtype=dtype)
        else:
            self.up_weight = Parameter(
                "up_weight", shape=(n, inner, hidden_size), dtype=dtype)
        self.down_weight = Parameter(
            "down_weight", shape=(n, hidden_size, inner), dtype=dtype)
        self.latent_down = self.latent_up = None
        if latent_size is not None:
            self.latent_down = Dense(latent_size, use_bias=False,
                                     flatten=False, in_units=units,
                                     dtype=dtype)
            self.latent_up = Dense(units, use_bias=False, flatten=False,
                                   in_units=latent_size, dtype=dtype)
        self.assignments = Parameter("assignments", shape=(n,),
                                     grad_req="null", init=init.Zero())
        self.elsewhere = Parameter("elsewhere", shape=(1,), grad_req="null",
                                   init=init.Zero())
        # (calls that took the exact no-drop buffer, calls)
        self.buffer_calls = Parameter("buffer_calls", shape=(2,),
                                      grad_req="null", init=init.Zero())
        shared_width = num_shared * hidden_size \
            if shared_hidden_size is None else shared_hidden_size
        self.shared = (SwiGLU if gated else SquaredReLUMLP)(
            units, shared_width, dtype=dtype) if num_shared else None
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
        _telemetry.registry.read_counter(
            "moe_exact_buffer_calls", read(self.buffer_calls, 0),
            "calls whose load passed the short buffer: the exact no-drop "
            "buffer ran", {"layer": layer})
        _telemetry.registry.read_counter(
            "moe_layer_calls", read(self.buffer_calls, 1),
            "calls of the expert layer in training mode", {"layer": layer})

    def cast(self, dtype):
        """The router (weight, bias) and the counters stay float32."""
        for block in (self.shared, self.latent_down, self.latent_up):
            if block is not None:
                block.cast(dtype)
        self._first_weight.cast(dtype)
        self.down_weight.cast(dtype)

    @property
    def _first_weight(self):
        return self.gate_up_weight if self._activation == "swiglu" \
            else self.up_weight

    def _router(self, x):
        ctx = x.context
        return self.router_weight.data(ctx), self.router_correction.data(ctx)

    def forward(self, x):
        ctx = x.context
        weight, bias = self._router(x)
        latent = ()
        if self.latent_down is not None:
            with trace_scope("latent"):
                latent = (self.latent_down(x),)
        y = invoke("moe_token_choice", x, weight, bias,
                   self._first_weight.data(ctx), self.down_weight.data(ctx),
                   self.assignments.data(ctx), self.elsewhere.data(ctx),
                   self.buffer_calls.data(ctx), *latent, held=self._held,
                   top_k=self._top_k, scale=self._scale,
                   norm_topk_prob=self._norm,
                   count=autograd.is_training(),
                   activation=self._activation)
        if latent:
            with trace_scope("latent"):
                y = self.latent_up(y)
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
