"""Nemotron-H's hybrid decoder family (``model_type`` ``nemotron_h``).

No reference counterpart.  Source: the public ``config.json`` of
nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16 (88 layers, hidden 4096,
each layer ONE mixer by ``hybrid_override_pattern``: ``M`` Mamba-2, ``E``
a latent mixture of experts, ``*`` attention; 512 routed experts, 22 a
token, 1 shared; one multi-token-prediction module with the pattern
``*E``).  The equations, with ``N`` RMSNorm (eps 1e-5):

* **Every layer**: ``x <- x + mixer(N(x))``; after the last layer one
  final norm, then the head (scope ``lm_head``).
* **M** (`gluon.nn.Mamba2Mixer`, scope ``ssm``; ``ops/ssm.py``):
  ``[z | xBC | dt] = W_in u`` (no bias); ``xBC <- silu(causal depthwise
  conv1d_4(xBC) + b)`` (scope ``ssm/conv``); ``[x | B | C] = xBC`` with x
  of heads x 64 lanes and B, C of groups x 128; ``dt <- softplus(dt +
  dt_bias)``, ``A = -exp(A_log)`` a head; a head with its group's B, C:
  ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = h_t C_t + D
  x_t`` (scope ``ssm/scan``, computed in the chunked dual form at chunks
  of 128: inside a chunk ``(L o C B^T) X`` with L the decay mask from the
  running sum of ``dt A``, across chunks the carried state, both float32,
  forward and backward written); ``y <- GroupRMSNorm(y * silu(z))`` over
  the groups; ``out = W_out y``.
* ``*`` (`gluon.nn.GroupedQueryAttention`): ``q, k, v = W_q u, W_k u,
  W_v u`` (no bias), query heads of 128 lanes on fewer key/value heads,
  causal, scale 128^-1/2, ``o = W_o concat``; NO rotary and no other
  position signal (the family's published model applies none in its
  attention layers; the config's ``rope_theta`` is unread).  The core is
  ``multi_head_attention``: ``ops/attention.py``'s rule sends it to the
  flash kernels with grouped key/value heads (scope ``attention_core``).
* **E** (`gluon.nn.TokenChoiceMoE` with ``activation="relu2"`` and a
  latent, scope ``moe``): scores ``s = sigmoid(W_r u)`` float32 over ALL
  experts; the top-k of ``s + correction`` (``noaux_tc``, one group);
  weights ``scale * s_e / sum of the chosen s``; ``l = W_down u`` (scope
  ``moe/latent``); expert e: ``W2_e relu(W1_e l)^2``, no gate; ``out =
  W_up (sum over chosen AND held e of w_e expert_e(l)) + shared(u)``,
  shared ``V2 relu(V1 u)^2`` on the model width (scopes ``moe/route``,
  ``/dispatch``, ``/experts``, ``/combine``, ``/shared``).
* **MTP module** (`glm_moe_lite.MTPModule`, scope ``mtp``; depth 1): ``h'
  = W_eh [N_e(Emb(t_{i+1})) ; N_h(h_i)]`` with ``h_i`` the last layer's
  output before the final norm, the layers of ``mtp_pattern`` (``*E``) on
  ``h'``, then the SHARED final norm, embedding and head: logits for
  ``t_{i+2}``.
* **Loss** (`glm_moe_lite.NextTokenLoss`): ``CE(main, t_{i+1}) + w *
  CE(MTP, t_{i+2})``.

A net is told its SHARE of a deployment: `held` routed experts of
`num_experts`, and the heads, groups, shared-expert columns and ids that
one tensor-parallel rank holds are simply the counts it is built with
(`mamba_heads`, `mamba_groups`, `num_heads`, `num_kv_heads`,
`shared_hidden_size`, `vocab_size`); every width is the published one.
Every layer, the module's two included, carries the recompute mark
(``Block.recompute``).
"""
from __future__ import annotations

from ...ndarray.ndarray import invoke
from .. import nn
from ..block import HybridBlock
from .glm_moe_lite import MTPModule, NextTokenLoss, _Head

__all__ = ["NemotronH", "MixerBlock", "NextTokenLoss",
           "nemotron_3_super"]

MIXERS = {"M": "ssm", "*": "attention", "E": "moe"}


class MixerBlock(HybridBlock):
    """One layer: ``x + mixer(N(x))``, the mixer held under the attribute
    its kind names (``ssm``, ``attention``, ``moe``: the scope a device
    trace reads).  With `output_routing` an ``E`` layer also returns the
    experts its router chose, ``(B, T, top_k)`` int32."""

    def __init__(self, units, kind, mixer, epsilon=1e-5,
                 output_routing=False, **kwargs):
        super().__init__(**kwargs)
        self.kind = kind
        self._routing = output_routing and kind == "E"
        self.norm = nn.RMSNorm(units, epsilon)
        setattr(self, MIXERS[kind], mixer)

    def forward(self, x):
        mixer = getattr(self, MIXERS[self.kind])
        h = self.norm(x)
        out = x + mixer(h)
        return (out, mixer.choose(h)) if self._routing else out


class _Layers(HybridBlock):
    """Layers one after another; returns the last one's output and, where
    layers give their routers' choices, those in order."""

    def __init__(self, layers, **kwargs):
        super().__init__(**kwargs)
        self.layers = nn.HybridSequential()
        for layer in layers:
            self.layers.add(layer)

    def forward(self, x):
        routing = []
        for layer in self.layers:
            x = layer(x)
            if isinstance(x, tuple):
                routing.append(x[1])
                x = x[0]
        return (x,) + tuple(routing) if routing else x


class NemotronH(HybridBlock):
    """The decoder: embedding, one `MixerBlock` a letter of `pattern`,
    final norm and untied head, `num_mtp` (0 or 1) MTP module of
    `mtp_pattern`.

    ``forward(ids)`` with ``(B, T)`` int32 ids returns ``(logits,
    mtp_logits)`` - `mtp_logits` at position i are for token i + 2 - or
    `logits` alone without the module; with `output_routing` a last
    element ``(expert layers, B, T, top_k)`` int32, the trunk's layers
    first, the module's last.
    """

    def __init__(self, vocab_size, units=4096, pattern="MEMEMEMEM*E",
                 mamba_heads=128, mamba_head_dim=64, state_size=128,
                 mamba_groups=8, conv_kernel=4, chunk_size=128,
                 num_heads=32, num_kv_heads=2, head_dim=128,
                 moe_hidden_size=2688, moe_latent_size=1024,
                 shared_hidden_size=5376, num_experts=512, top_k=22,
                 routed_scale=5.0, norm_topk_prob=True, held=None,
                 epsilon=1e-5, num_mtp=1, mtp_pattern="*E",
                 router_correction_initializer="zeros", recompute=True,
                 output_routing=False, **kwargs):
        super().__init__(**kwargs)
        if num_mtp not in (0, 1):
            raise ValueError("num_mtp=%r: 0 or 1 module" % (num_mtp,))
        if set(pattern + mtp_pattern) - set(MIXERS):
            raise ValueError("pattern %r / %r: letters of %r"
                             % (pattern, mtp_pattern, "".join(MIXERS)))
        self._routing = output_routing

        def layer(kind, label):
            if kind == "M":
                mixer = nn.Mamba2Mixer(
                    units, mamba_heads, mamba_head_dim, state_size,
                    mamba_groups, conv_kernel, chunk_size, epsilon)
            elif kind == "*":
                mixer = nn.GroupedQueryAttention(units, num_heads,
                                                 num_kv_heads, head_dim)
            else:
                mixer = nn.TokenChoiceMoE(
                    units, moe_hidden_size, num_experts, top_k, held=held,
                    num_shared=1, scale=routed_scale,
                    norm_topk_prob=norm_topk_prob,
                    correction_initializer=router_correction_initializer,
                    layer=label, activation="relu2",
                    latent_size=moe_latent_size,
                    shared_hidden_size=shared_hidden_size)
            return MixerBlock(units, kind, mixer, epsilon,
                              output_routing).recompute(recompute)

        self.embed = nn.Embedding(vocab_size, units)
        self.blocks = nn.HybridSequential()
        for i, kind in enumerate(pattern):
            self.blocks.add(layer(kind, i))
        self.lm_head = _Head(units, vocab_size, epsilon)
        # an mtp_pattern with more than one E would need a label a layer
        self.mtp = MTPModule(
            units, _Layers([layer(kind, "mtp") for kind in mtp_pattern]),
            epsilon) if num_mtp else None

    def forward(self, ids):
        routing = []

        def hidden(out):
            """A layer's output without its router's choices."""
            if isinstance(out, tuple):
                routing.extend(out[1:])
                return out[0]
            return out

        x = self.embed(ids)
        for block in self.blocks:
            x = hidden(block(x))
        outs = [self.lm_head(x)]
        if self.mtp is not None:
            # position i takes token i + 1; the row's last position wraps
            # to its first token and feeds no position that has a label
            following = self.embed(invoke("roll", ids, shift=-1, axis=1))
            outs.append(self.lm_head(hidden(self.mtp(following, x))))
        if self._routing and routing:
            outs.append(invoke("stack", *routing, axis=0))
        return outs[0] if len(outs) == 1 else tuple(outs)


def nemotron_3_super(**kwargs):
    """Nemotron-3-Super at its published sizes (the class's defaults: one
    period of the 88-layer pattern) over the whole vocabulary; `held`,
    `vocab_size`, `pattern` and the head counts cut it to one chip's
    share of a deployment."""
    kwargs.setdefault("vocab_size", 131072)
    return NemotronH(**kwargs)
