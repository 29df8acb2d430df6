"""GLM-4.7-Flash's decoder family (``model_type`` ``glm4_moe_lite``).

No reference counterpart.  Source: the public ``config.json`` of
zai-org/GLM-4.7-Flash (47 layers, hidden 2048, 20 heads; multi-head latent
attention with query rank 768 and key/value rank 512, heads of 192 + 64
query/key lanes and 256 value lanes; one leading dense block of width
10240; 64 routed experts of width 1536, 4 a token by ``noaux_tc``, 1
shared expert, scaling 1.8; one multi-token-prediction module).  The
equations, with ``N`` RMSNorm and ``R`` rotary positions:

* **MLA** (`MLAttention`, scope ``mla``): ``c_q = N(N(x) W_qa)``;
  ``[q_nope | q_rope] = c_q W_qb`` a head; ``[c_kv | k_r] = N(x) W_kva``;
  ``[k_nope | v] = N(c_kv) W_kvb`` a head; ``q = [q_nope | R(q_rope)]``,
  ``k = [k_nope | R(k_r)]`` with the ONE ``R(k_r)`` shared by all heads;
  causal softmax of ``q k^T / sqrt(256)``; ``concat_h(p v) W_o``.  No
  biases.  The core is ``multi_head_attention(causal=True)`` on the packed
  ``(B, T, heads * 256)`` tensors, so ``ops/attention.py``'s rule decides
  between the flash kernels and the composition, as for BERT.
* **Dense block**: ``x += MLA(x)``; ``x += SwiGLU(N(x))``.
* **Expert block**: ``x += MLA(x)``; ``x += TokenChoiceMoE(N(x))``
  (``gluon.nn.TokenChoiceMoE``, scope ``moe``): sigmoid scores over all
  experts, top-k of score + bias, weights renormalised and scaled, one
  shared expert, and only the experts `held` here computed: one chip's
  share of an expert-parallel deployment.
* **MTP module** (`MTPModule`, scope ``mtp``; depth 1, DeepSeek-V3
  section 2.2): ``h' = W_eh [N_e(Emb(t_{i+1})) ; N_h(h_i)]`` with ``h_i``
  the last block's output before the final norm, one expert block on
  ``h'``, then the SHARED final norm and head (scope ``lm_head``): logits
  for ``t_{i+2}``.  Embedding and head are the main model's.
* **Loss** (`NextTokenLoss`): ``CE(main, t_{i+1}) + w * CE(MTP,
  t_{i+2})``, float32, mean over the positions that have a label.

Every decoder block and the MTP module's block carry the recompute mark
(``Block.recompute``): a compiled step keeps their inputs only and runs
their forward again in its backward pass.
"""
from __future__ import annotations

from ...ndarray.ndarray import invoke
from .. import nn
from ..block import HybridBlock, trace_scope
from ..loss import Loss, count_cross_entropy

__all__ = ["GLMMoeLite", "DecoderBlock", "MLAttention", "MTPModule",
           "NextTokenLoss", "glm_4_7_flash"]


class MLAttention(HybridBlock):
    """Multi-head latent attention with its pre-norm (module docstring)."""

    def __init__(self, units, num_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 rope_theta=10000.0, epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        if qk_nope_head_dim + qk_rope_head_dim != v_head_dim:
            raise ValueError(
                "MLAttention hands q, k and v to multi_head_attention as "
                "heads of one size: %d + %d query/key lanes against %d "
                "value lanes" % (qk_nope_head_dim, qk_rope_head_dim,
                                 v_head_dim))
        self._heads = num_heads
        self._nope, self._rope, self._v = (qk_nope_head_dim,
                                           qk_rope_head_dim, v_head_dim)
        self._kv_rank = kv_lora_rank
        qk = qk_nope_head_dim + qk_rope_head_dim

        def dense(out, inp):
            return nn.Dense(out, use_bias=False, flatten=False, in_units=inp)

        self.input_norm = nn.RMSNorm(units, epsilon)
        self.q_a_proj = dense(q_lora_rank, units)
        self.q_a_norm = nn.RMSNorm(q_lora_rank, epsilon)
        self.q_b_proj = dense(num_heads * qk, q_lora_rank)
        self.kv_a_proj = dense(kv_lora_rank + qk_rope_head_dim, units)
        self.kv_a_norm = nn.RMSNorm(kv_lora_rank, epsilon)
        self.kv_b_proj = dense(num_heads * (qk_nope_head_dim + v_head_dim),
                               kv_lora_rank)
        self.o_proj = dense(units, num_heads * v_head_dim)
        self.rotary_q = nn.RotaryEmbedding(num_heads, qk_rope_head_dim,
                                           rope_theta)
        self.rotary_k = nn.RotaryEmbedding(1, qk_rope_head_dim, rope_theta)

    def forward(self, x):
        b, t, _ = x.shape
        h, nope, rope, v = self._heads, self._nope, self._rope, self._v
        x = self.input_norm(x)
        q = self.rotary_q(self.q_b_proj(self.q_a_norm(self.q_a_proj(x))))
        latent = self.kv_a_proj(x)
        c_kv = invoke("slice_axis", latent, axis=-1, begin=0,
                      end=self._kv_rank)
        k_rope = self.rotary_k(invoke("slice_axis", latent, axis=-1,
                                      begin=self._kv_rank, end=None))
        kv = self.kv_b_proj(self.kv_a_norm(c_kv)).reshape((b, t, h, nope + v))
        k_rope = invoke("broadcast_to", k_rope.reshape((b, t, 1, rope)),
                        shape=(b, t, h, rope))
        k = invoke("concat",
                   invoke("slice_axis", kv, axis=-1, begin=0, end=nope),
                   k_rope, dim=-1).reshape((b, t, h * (nope + rope)))
        value = invoke("slice_axis", kv, axis=-1, begin=nope, end=None) \
            .reshape((b, t, h * v))
        out = invoke("multi_head_attention", q, k, value, None, num_heads=h,
                     scaled=True, causal=True)
        return self.o_proj(out)


class DecoderBlock(HybridBlock):
    """Pre-norm decoder block: MLA, then a dense SwiGLU MLP (`moe` None)
    or a `TokenChoiceMoE` built from the `moe` keywords.  With
    `output_routing` the block also returns the experts its router chose
    (``(B, T, top_k)`` int32; a dense block returns none)."""

    def __init__(self, units, attention, hidden_size=None, moe=None,
                 epsilon=1e-6, output_routing=False, **kwargs):
        super().__init__(**kwargs)
        self._routing = output_routing and moe is not None
        self.mla = MLAttention(units, epsilon=epsilon, **attention)
        self.post_norm = nn.RMSNorm(units, epsilon)
        if moe is None:
            self.mlp, self.moe = nn.SwiGLU(units, hidden_size), None
        else:
            self.mlp, self.moe = None, nn.TokenChoiceMoE(units, **moe)

    def forward(self, x):
        x = x + self.mla(x)
        h = self.post_norm(x)
        if self.moe is None:
            return x + self.mlp(h)
        out = x + self.moe(h)
        return (out, self.moe.choose(h)) if self._routing else out


class MTPModule(HybridBlock):
    """One multi-token-prediction module (depth 1): the next token's
    embedding and the trunk's last hidden state, each normed, projected
    from 2 * units to units, through one expert block."""

    def __init__(self, units, block, epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        self.enorm = nn.RMSNorm(units, epsilon)
        self.hnorm = nn.RMSNorm(units, epsilon)
        self.eh_proj = nn.Dense(units, use_bias=False, flatten=False,
                                in_units=2 * units)
        self.block = block

    def forward(self, next_embedding, hidden):
        return self.block(self.eh_proj(invoke(
            "concat", self.enorm(next_embedding), self.hnorm(hidden),
            dim=-1)))


class _Head(HybridBlock):
    """Final norm and the untied output layer, shared by both heads."""

    def __init__(self, units, vocab_size, epsilon, **kwargs):
        super().__init__(**kwargs)
        self.norm = nn.RMSNorm(units, epsilon)
        self.proj = nn.Dense(vocab_size, use_bias=False, flatten=False,
                             in_units=units)

    def forward(self, x):
        return self.proj(self.norm(x))


class GLMMoeLite(HybridBlock):
    """The decoder: embedding, `first_dense` dense blocks, expert blocks
    up to `num_layers`, final norm and untied head, `num_mtp` (0 or 1)
    MTP module.

    `held` lists the routed experts this chip holds (default: all) and
    `vocab_size` is the slice of the vocabulary it holds: ids, logits and
    the loss are over the slice.  ``forward(ids)`` with ``(B, T)`` int32
    ids returns ``(logits, mtp_logits)`` - `mtp_logits` at position i are
    for token i + 2 - or `logits` alone without the module; with
    `output_routing` a last element ``(expert layers, B, T, top_k)``
    int32, the trunk's layers first, the module's last.
    """

    def __init__(self, vocab_size, units=2048, num_layers=47, first_dense=1,
                 num_heads=20, q_lora_rank=768, kv_lora_rank=512,
                 qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
                 hidden_size=10240, moe_hidden_size=1536, num_experts=64,
                 top_k=4, num_shared=1, routed_scale=1.8, norm_topk_prob=True,
                 held=None, rope_theta=1e6, epsilon=1e-5, num_mtp=1,
                 router_correction_initializer="zeros", recompute=True,
                 output_routing=False, **kwargs):
        super().__init__(**kwargs)
        if num_mtp not in (0, 1):
            raise ValueError("num_mtp=%r: 0 or 1 module" % (num_mtp,))
        self._routing = output_routing
        attention = dict(num_heads=num_heads, q_lora_rank=q_lora_rank,
                         kv_lora_rank=kv_lora_rank,
                         qk_nope_head_dim=qk_nope_head_dim,
                         qk_rope_head_dim=qk_rope_head_dim,
                         v_head_dim=v_head_dim, rope_theta=rope_theta)

        def block(layer):
            moe = None if layer != "mtp" and layer < first_dense else dict(
                hidden_size=moe_hidden_size, num_experts=num_experts,
                top_k=top_k, held=held, num_shared=num_shared,
                scale=routed_scale, norm_topk_prob=norm_topk_prob,
                correction_initializer=router_correction_initializer, layer=layer)
            return DecoderBlock(units, attention, hidden_size, moe, epsilon,
                                output_routing).recompute(recompute)

        self.embed = nn.Embedding(vocab_size, units)
        self.blocks = nn.HybridSequential()
        for layer in range(num_layers):
            self.blocks.add(block(layer))
        self.lm_head = _Head(units, vocab_size, epsilon)
        self.mtp = MTPModule(units, block("mtp"), epsilon) if num_mtp \
            else None

    def forward(self, ids):
        routing = []

        def hidden(out):
            """A block's output without its router's choices."""
            if isinstance(out, tuple):
                routing.append(out[1])
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


class NextTokenLoss(Loss):
    """``CE(logits, t_{i+1}) + mtp_weight * CE(mtp_logits, t_{i+2})`` of
    `GLMMoeLite`'s outputs against the row's own ids, in float32, each
    term the mean over the positions that have a label (T - 1 and T - 2
    of a row); one value a row."""

    def __init__(self, mtp_weight=0.3, weight=None, batch_axis=0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._mtp_weight = mtp_weight

    @staticmethod
    def _term(logits, ids, ahead):
        """Mean over positions 0 .. T - ahead - 1 of -log p(t_{i+ahead})."""
        count_cross_entropy("fused")
        nll = invoke("sparse_softmax_cross_entropy", logits,
                     invoke("roll", ids, shift=-ahead, axis=1), axis=-1)
        return invoke("slice_axis", nll, axis=1, begin=0,
                      end=ids.shape[1] - ahead).mean(axis=1)

    def forward(self, outs, ids):
        outs = outs if isinstance(outs, (tuple, list)) else (outs,)
        with trace_scope("lm_head"):
            loss = self._term(outs[0], ids, 1)
            if len(outs) > 1 and self._mtp_weight:
                loss = loss + self._mtp_weight * self._term(outs[1], ids, 2)
        return loss if self._weight is None else loss * self._weight


def glm_4_7_flash(**kwargs):
    """GLM-4.7-Flash at its published sizes (the class's defaults) over
    the whole vocabulary; `held`, `vocab_size` and `num_layers` cut it to
    one chip's share of a deployment."""
    kwargs.setdefault("vocab_size", 154880)
    return GLMMoeLite(**kwargs)
