"""Laguna's decoder family (``model_type`` ``laguna``).

No reference counterpart.  Source: the public ``config.json`` of
poolside/Laguna-XS.2 (40 layers, hidden 2048, heads of 128 lanes on 8
key/value heads; ``layer_types`` one full-attention layer then three
sliding-window ones, ten times, with 48 query heads on the full layers
and 64 on the sliding ones (``num_attention_heads_per_layer``), window
512; two rotary parametrisations; ``mlp_layer_types`` one dense layer of
width 8192, then 39 sparse ones: 256 routed experts of width 512, 8 a
token, one shared expert of width 512, scaling 2.5; untied embedding and
head).  The equations, with ``N`` RMSNorm (eps 1e-6, with gain) and no
bias anywhere:

* **Attention, layer l** (`gluon.nn.GroupedQueryAttention`, held under
  the attribute - and so the scope - ``attention_full`` or
  ``attention_window``): ``h = N(x)``; ``q = h W_q`` (``H_l`` heads of
  128), ``k = h W_k``, ``v = h W_v`` (8 heads); ``q, k <- R_l(q),
  R_l(k)`` (scope ``rotary``); scores ``q k^T / sqrt(128)`` with key j
  visible to query i iff ``j <= i`` (full) or ``i - window < j <= i``
  (sliding: `window` keys with the query's own); softmax in float32;
  ``o_h = p v`` (scope ``attention_core``: ``ops/attention.py``'s rule
  sends it to the flash kernels, with their band on the sliding layers);
  ``o_h <- sigmoid(h W_g)_h o_h`` (`gating`: one gate a query head,
  scope ``head_gate``); ``x += concat_h(o_h) W_o``.
* **Rotary** ``R_l`` (the ``rotary_embedding`` operator): half-split
  pairs on the FIRST ``d = partial_rotary_factor x 128`` lanes of each
  head, the rest pass through.  ``rope_type`` ``default``: ``inv_freq_i =
  theta^(-2i/d)``.  ``yarn``: ``f_i = theta^(-2i/d)`` blended with ``f_i
  / factor`` by the ramp between the lanes that turn `beta_fast` and
  `beta_slow` times over the original context
  (``ops.nn.yarn_inv_freq``), cos and sin times `attention_factor`.
  Angles in float32.
* **Dense block**: ``x += Attn(x)``; ``x += SwiGLU(N(x))``.
* **Sparse block**: ``x += Attn(x)``; ``x += TokenChoiceMoE(N(x))``
  (`gluon.nn.TokenChoiceMoE`, scope ``moe``): sigmoid scores in float32
  over ALL experts, the top-k of score + selection correction
  (``noaux_tc``), weights the chosen scores normalised to sum 1 times
  the scaling factor, one shared expert, and only the experts `held`
  here computed: one chip's share of an expert-parallel deployment.
* **Head and loss**: ``logits = N(x) W_head`` (scope ``lm_head``);
  `NextTokenLoss` with no MTP term: ``CE(logits_i, t_{i+1})`` in float32,
  the mean over the T - 1 positions that have a label.

What the config leaves open - what `gating` gates, the router's scoring
and selection, no q/k norm and no attention sinks - is read as above; a
configuration that runs this net states each reading under `assumed`.

A net is told its SHARE of a deployment: `held` routed experts of
`num_experts`, and `vocab_size` the slice of the vocabulary it holds.
Every block carries the recompute mark (``Block.recompute``).
"""
from __future__ import annotations

from ...ndarray.ndarray import invoke
from .. import nn
from ..block import HybridBlock
from .glm_moe_lite import NextTokenLoss, _Head

__all__ = ["Laguna", "DecoderBlock", "NextTokenLoss", "rotary_keywords",
           "laguna_xs_2"]

ATTENTION = {"full_attention": "attention_full",
             "sliding_attention": "attention_window"}

# Laguna-XS.2's `rope_parameters`, by the kind of layer
ROPE_XS_2 = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1},
}


def rotary_keywords(rope, head_dim):
    """`gluon.nn.RotaryEmbedding`'s keywords for one entry of the
    config's ``rope_parameters``."""
    kind = rope.get("rope_type", "default")
    if kind not in ("default", "yarn"):
        raise ValueError("rope_type %r: default or yarn" % (kind,))
    dim = int(head_dim * rope.get("partial_rotary_factor", 1))
    out = dict(rotary_dim=dim, theta=float(rope["rope_theta"]),
               first=dim < head_dim)
    if kind == "yarn":
        out.update(
            yarn=(rope["factor"], rope["original_max_position_embeddings"],
                  rope.get("beta_fast", 32), rope.get("beta_slow", 1)),
            attention_factor=rope.get("attention_factor", 1.0))
    return out


class DecoderBlock(HybridBlock):
    """Pre-norm decoder block: attention of `kind` (a key of
    ``ATTENTION``; the attribute that holds it is the scope a device
    trace reads), then a dense SwiGLU MLP (`moe` None) or a
    `TokenChoiceMoE` built from the `moe` keywords.  With
    `output_routing` a sparse block also returns the experts its router
    chose (``(B, T, top_k)`` int32)."""

    def __init__(self, units, kind, attention, hidden_size=None, moe=None,
                 epsilon=1e-6, output_routing=False, **kwargs):
        super().__init__(**kwargs)
        self.kind = kind
        self._routing = output_routing and moe is not None
        self.input_norm = nn.RMSNorm(units, epsilon)
        setattr(self, ATTENTION[kind],
                nn.GroupedQueryAttention(units, **attention))
        self.post_norm = nn.RMSNorm(units, epsilon)
        if moe is None:
            self.mlp, self.moe = nn.SwiGLU(units, hidden_size), None
        else:
            self.mlp, self.moe = None, nn.TokenChoiceMoE(units, **moe)

    def forward(self, x):
        x = x + getattr(self, ATTENTION[self.kind])(self.input_norm(x))
        h = self.post_norm(x)
        if self.moe is None:
            return x + self.mlp(h)
        out = x + self.moe(h)
        return (out, self.moe.choose(h)) if self._routing else out


class Laguna(HybridBlock):
    """The decoder: embedding, one `DecoderBlock` a layer - its attention
    by ``layer_types[l]`` with ``num_heads_per_layer[l]`` query heads,
    its MLP by ``mlp_layer_types[l]`` - final norm and untied head.  The
    first `num_layers` entries of the three lists are built (default: as
    many as `layer_types` has).

    `held` lists the routed experts this chip holds (default: all) and
    `vocab_size` is the slice of the vocabulary it holds: ids, logits and
    the loss are over the slice.  ``forward(ids)`` with ``(B, T)`` int32
    ids returns `logits`; with `output_routing` ``(logits, routing)``,
    `routing` ``(sparse layers, B, T, top_k)`` int32.
    """

    def __init__(self, vocab_size, units=2048, num_layers=None,
                 layer_types=("full_attention",) + ("sliding_attention",) * 3,
                 mlp_layer_types=("dense", "sparse", "sparse", "sparse"),
                 num_heads_per_layer=(48, 64, 64, 64), num_kv_heads=8,
                 head_dim=128, sliding_window=512, rope_parameters=None,
                 gating=True, hidden_size=8192, moe_hidden_size=512,
                 shared_hidden_size=512, num_experts=256, top_k=8,
                 routed_scale=2.5, norm_topk_prob=True, held=None,
                 epsilon=1e-6, router_correction_initializer="zeros",
                 recompute=True, output_routing=False, **kwargs):
        super().__init__(**kwargs)
        num_layers = len(layer_types) if num_layers is None else num_layers
        if not (num_layers <= len(layer_types)
                and num_layers <= len(mlp_layer_types)
                and num_layers <= len(num_heads_per_layer)):
            raise ValueError("%d layers of %d layer_types, %d "
                             "mlp_layer_types, %d head counts"
                             % (num_layers, len(layer_types),
                                len(mlp_layer_types),
                                len(num_heads_per_layer)))
        rope = ROPE_XS_2 if rope_parameters is None else rope_parameters
        self._routing = output_routing

        def block(layer):
            kind, mlp = layer_types[layer], mlp_layer_types[layer]
            if kind not in ATTENTION or mlp not in ("dense", "sparse"):
                raise ValueError("layer %d: %r attention, %r MLP"
                                 % (layer, kind, mlp))
            attention = dict(
                num_heads=num_heads_per_layer[layer],
                num_kv_heads=num_kv_heads, head_dim=head_dim,
                window=sliding_window if kind == "sliding_attention"
                else None,
                rotary=rotary_keywords(rope[kind], head_dim),
                head_gate=gating)
            moe = None if mlp == "dense" else dict(
                hidden_size=moe_hidden_size, num_experts=num_experts,
                top_k=top_k, held=held, num_shared=1, scale=routed_scale,
                norm_topk_prob=norm_topk_prob,
                correction_initializer=router_correction_initializer,
                layer=layer, shared_hidden_size=shared_hidden_size)
            return DecoderBlock(units, kind, attention, hidden_size, moe,
                                epsilon, output_routing).recompute(recompute)

        self.embed = nn.Embedding(vocab_size, units)
        self.blocks = nn.HybridSequential()
        for layer in range(num_layers):
            self.blocks.add(block(layer))
        self.lm_head = _Head(units, vocab_size, epsilon)

    def forward(self, ids):
        routing = []
        x = self.embed(ids)
        for block in self.blocks:
            x = block(x)
            if isinstance(x, tuple):
                routing.append(x[1])
                x = x[0]
        logits = self.lm_head(x)
        if self._routing and routing:
            return logits, invoke("stack", *routing, axis=0)
        return logits


def laguna_xs_2(**kwargs):
    """Laguna-XS.2 at its published sizes over the whole vocabulary: 40
    layers, one full-attention layer of 48 heads then three window-512
    ones of 64, the first MLP dense; `held`, `vocab_size` and
    `num_layers` cut it to one chip's share of a deployment."""
    kwargs.setdefault("vocab_size", 100352)
    kwargs.setdefault("layer_types", (
        ("full_attention",) + ("sliding_attention",) * 3) * 10)
    kwargs.setdefault("mlp_layer_types", ("dense",) + ("sparse",) * 39)
    kwargs.setdefault("num_heads_per_layer", (48, 64, 64, 64) * 10)
    return Laguna(**kwargs)
