"""GLM-4.7-Flash for the benchmark, on ONE CHIP'S SHARE of an expert- and
vocabulary-parallel deployment: the net through the repo's public API
(``gluon.model_zoo.glm_moe_lite``), the plain float32 reference given the
same share, and the operations and bytes of one train step worked out
from the shapes.  Every size comes from the configuration file: the
published widths, the `experts_held` of `n_routed_experts_published`
routed experts, the slice `vocab_size` of the vocabulary.
"""
import json

import numpy as np

def _sizes(config):
    return dict(
        vocab_size=config["vocab_size"], units=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        first_dense=config["first_k_dense_replace"],
        num_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        hidden_size=config["intermediate_size"],
        moe_hidden_size=config["moe_intermediate_size"],
        num_experts=config["n_routed_experts_published"],
        top_k=config["num_experts_per_tok"],
        num_shared=config["n_shared_experts"],
        routed_scale=config["routed_scaling_factor"],
        norm_topk_prob=config["norm_topk_prob"],
        held=tuple(config["experts_held"]),
        rope_theta=config["rope_theta"], epsilon=config["rms_norm_eps"],
        num_mtp=config["num_nextn_predict_layers"])


# -- the system under test --------------------------------------------------

def build(config, ctx, seed):
    """The zoo's decoder on `ctx`, cast and hybridized.  Every layer names
    its input width, so nothing is deferred and no forward is needed
    before the first compiled step.  The router's selection bias is drawn
    (a trained model's is not zero) and then held: nothing updates it."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import glm_moe_lite
    mx.random.seed(seed)
    net = glm_moe_lite.GLMMoeLite(
        router_correction_initializer=mx.init.Normal(config["router_correction_std"]),
        output_routing=True, **_sizes(config))
    net.initialize(mx.init.Normal(config["initializer_std"]), ctx=ctx)
    net.cast(config["dtype"])
    net.hybridize()
    return net


def loss_fn():
    from mxnet_tpu.gluon.model_zoo import glm_moe_lite
    return glm_moe_lite.NextTokenLoss(mtp_weight=MTP_WEIGHT)


MTP_WEIGHT = 0.3        # the configuration's `assumed.mtp_loss_weight`


def _rows(config, rows, seq, rng):
    return rng.randint(0, config["vocab_size"], (rows, seq)).astype(np.int32)


def batches(config, traffic, seed):
    """The pool of host batches: ((ids,), ids) - a row is its own label,
    shifted by one (next token) and by two (the MTP module) in the loss."""
    rng = np.random.RandomState(seed % (2 ** 32))
    pool = []
    for _ in range(traffic["pool"]):
        ids = _rows(config, traffic["batch"], traffic["seq"], rng)
        pool.append(((ids,), ids))
    return pool


def units_per_row(traffic):
    """Tokens in one row of a batch (the throughput's unit)."""
    return traffic["seq"]


def _expert_layers(config):
    return config["num_hidden_layers"] - config["first_k_dense_replace"] \
        + config["num_nextn_predict_layers"]


def check_inputs(config, traffic, seed):
    """Rows at the TIMED sizes: `batch` rows of `seq` ids from the slice,
    and a slot (expert layers, B, T, k) for the experts the net's routers
    choose on them: `logits` fills it, `reference` follows it (see there).
    -1: no choice given."""
    rng = np.random.RandomState((seed + 1) % (2 ** 32))
    ids = _rows(config, traffic["batch"], traffic["seq"], rng)
    return (ids, np.full((_expert_layers(config),) + ids.shape
                         + (config["num_experts_per_tok"],), -1, np.int32))


def logits(net, inputs, ctx):
    """Both heads' logits of the net that is then trained, (2, B, T,
    vocab) float32: the main head's, then the MTP module's.  The experts
    its routers chose go into `inputs`' slot."""
    import jax.numpy as jnp
    from mxnet_tpu import nd
    outs = net(nd.array(inputs[0], ctx=ctx, dtype="int32"))
    if len(inputs) > 1 and len(outs) > 2:
        inputs[1][...] = np.asarray(outs[2]._jax)
    return jnp.stack([outs[0]._jax.astype("float32"),
                      outs[1]._jax.astype("float32")])


# -- the plain reference ----------------------------------------------------

QUERY_BLOCK = 512       # attention in query blocks: T x T never stands whole


def _note_routing(differs, gap):
    """Host side of `reference`: one ``benchmark:`` line on the router
    choices it was given against its own."""
    differs, gap = np.asarray(differs), np.asarray(gap)
    print("benchmark: " + json.dumps(
        {"routing_choices_differ_share": float(differs.mean()),
         "routing_choices_differ_by_layer":
             [float(d.mean()) for d in differs],
         "routing_worst_gap": float(gap.max()),
         "routing_choices": int(differs.size)}, sort_keys=True), flush=True)


class _Equations:
    """The published equations on the configuration's share, in float32
    ``jax.numpy``: no Gluon, no kernel, no grouping.  `params` maps the
    net's parameter names to arrays.  `operand`, when given, rounds every
    matrix product's operands to that dtype first (how a lower precision
    than the stated one would compute: the tests and PERF.md use it to
    place the tolerance); the router's scores are never rounded."""

    def __init__(self, params, config, operand=None):
        self.params, self.config, self.operand = params, config, operand
        self.heads = config["num_attention_heads"]
        self.nope = config["qk_nope_head_dim"]
        self.rope = config["qk_rope_head_dim"]
        self.eps = config["rms_norm_eps"]

    def p(self, name):
        import jax.numpy as jnp
        return jnp.asarray(self.params[name], jnp.float32)

    def dot(self, a, b):
        import jax.numpy as jnp
        if self.operand is not None:
            a, b = (x.astype(self.operand).astype(jnp.float32)
                    for x in (a, b))
        return jnp.matmul(a, b)

    def dense(self, x, name):
        return self.dot(x, self.p(name + ".weight").T)

    def norm(self, x, name):
        import jax
        import jax.numpy as jnp
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + self.eps) * self.p(name + ".gamma")

    def rotary(self, x):
        """x: (..., T, rope) -> rotated; pairs (i, i + rope/2)."""
        import jax.numpy as jnp
        t, half = x.shape[-2], self.rope // 2
        inv = float(self.config["rope_theta"]) ** (
            -jnp.arange(half, dtype=jnp.float32) * 2.0 / self.rope)
        angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def swiglu(self, x, gate_up, down):
        """gate_up: (d, 2f) with the gate's columns first; down: (f, d)."""
        import jax
        h = self.dot(x, gate_up)
        f = h.shape[-1] // 2
        return self.dot(jax.nn.silu(h[..., :f]) * h[..., f:], down)

    def attention(self, x, at):
        import jax
        import jax.numpy as jnp
        heads, nope, rope = self.heads, self.nope, self.rope
        vdim, kv_rank = self.config["v_head_dim"], self.config["kv_lora_rank"]
        n, t, _ = x.shape
        x = self.norm(x, at + "input_norm")
        q = self.dense(self.norm(self.dense(x, at + "q_a_proj"),
                                 at + "q_a_norm"), at + "q_b_proj")
        q = q.reshape(n, t, heads, nope + rope).transpose(0, 2, 1, 3)
        q = jnp.concatenate([q[..., :nope], self.rotary(q[..., nope:])], -1)
        latent = self.dense(x, at + "kv_a_proj")
        k_rope = self.rotary(latent[..., kv_rank:])[:, None]   # all heads'
        kv = self.dense(self.norm(latent[..., :kv_rank], at + "kv_a_norm"),
                        at + "kv_b_proj")
        kv = kv.reshape(n, t, heads, nope + vdim).transpose(0, 2, 1, 3)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_rope, (n, heads, t, rope))], -1)
        v = kv[..., nope:]
        block = min(QUERY_BLOCK, t)

        def rows(start):
            qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=2)
            s = self.dot(qb, k.transpose(0, 1, 3, 2)) / np.sqrt(nope + rope)
            seen = (start + jnp.arange(block))[:, None] \
                >= jnp.arange(t)[None, :]
            w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return self.dot(w, v)                         # (n, h, block, v)

        out = jax.lax.map(rows, jnp.arange(0, t, block))  # (blocks, n, h, ..)
        out = out.transpose(1, 0, 3, 2, 4).reshape(n, t, heads * vdim)
        return self.dense(out, at + "o_proj")

    def route(self, x, at, given=None):
        """(idx (..., k), weights (..., k), differs (...), gap (...)) over
        ALL published experts.  `given` (..., k): experts to follow
        instead of the own top-k where >= 0 - the weights are still the
        own float32 scores' - with `differs` whether the two sets differ
        and `gap` how far the worst given expert's selection score lies
        under the own k-th (0 where they agree)."""
        import jax
        import jax.numpy as jnp
        s = jax.nn.sigmoid(jnp.matmul(x, self.p(at + "router_weight").T))
        select = s + self.p(at + "router_correction")
        kth, idx = jax.lax.top_k(select, self.config["num_experts_per_tok"])
        differs = jnp.zeros(idx.shape[:-1], bool)
        gap = jnp.zeros(idx.shape[:-1], jnp.float32)
        if given is not None:
            follow = (given >= 0).all(-1, keepdims=True)
            differs = follow[..., 0] & (jnp.sort(given, -1)
                                        != jnp.sort(idx, -1)).any(-1)
            idx = jnp.where(follow, given, idx)
            gap = jnp.maximum(kth[..., -1] - jnp.take_along_axis(
                select, idx, axis=-1).min(-1), 0.0)
        chosen = jnp.take_along_axis(s, idx, axis=-1)
        if self.config["norm_topk_prob"]:
            chosen = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
        return idx, chosen * self.config["routed_scaling_factor"], \
            differs, gap

    def experts(self, x, at, held=None, shared=True, given=None):
        """(shared(x) + the `held` experts' part, idx, differs, gap);
        `held` defaults to the configuration's share, whose weights
        `gate_up_weight` / `down_weight` hold in that order."""
        held = list(self.config["experts_held"]) if held is None else held
        idx, weight, differs, gap = self.route(x, at, given)
        y = self.swiglu(x, self.p(at + "shared.gate_up_proj.weight").T,
                        self.p(at + "shared.down_proj.weight").T) \
            if shared else 0.0
        gate_up, down = self.p(at + "gate_up_weight"), \
            self.p(at + "down_weight")
        for local, expert in enumerate(held):                # a plain loop
            w_e = (weight * (idx == expert)).sum(-1, keepdims=True)
            y = y + w_e * self.swiglu(x, gate_up[local], down[local])
        return y, idx, differs, gap

    def block(self, x, at, given=None):
        """A decoder block; `given` None: the dense one.  Returns (x,
        (idx, differs, gap) of an expert block's router)."""
        x = x + self.attention(x, at + "mla.")
        h = self.norm(x, at + "post_norm")
        if given is None:
            return x + self.swiglu(
                h, self.p(at + "mlp.gate_up_proj.weight").T,
                self.p(at + "mlp.down_proj.weight").T), None
        y, idx, differs, gap = self.experts(h, at + "moe.", given=given)
        return x + y, (idx, differs, gap)

    def head(self, x):
        return self.dense(self.norm(x, "lm_head.norm"), "lm_head.proj")

    def forward(self, ids, given=None):
        """(main logits, MTP logits, routing (expert layers, B, T, k),
        differs and gap (expert layers, B, T)); `given` as `routing`."""
        import jax.numpy as jnp
        config = self.config
        dense = config["first_k_dense_replace"]
        layers = config["num_hidden_layers"]
        if given is None:
            given = jnp.full((layers - dense + 1,) + ids.shape
                             + (config["num_experts_per_tok"],), -1)
        embed = self.p("embed.weight")
        x = embed[ids]
        routed = []
        for i in range(layers):
            x, r = self.block(x, "blocks.%d." % i,
                              None if i < dense else given[i - dense])
            if r is not None:
                routed.append(r)
        main = self.head(x)
        following = embed[jnp.roll(ids, -1, axis=1)]
        joined = jnp.concatenate([self.norm(following, "mtp.enorm"),
                                  self.norm(x, "mtp.hnorm")], -1)
        h, r = self.block(self.dense(joined, "mtp.eh_proj"), "mtp.block.",
                          given[-1])
        routed.append(r)
        return (main, self.head(h)) + tuple(
            jnp.stack([r[i] for r in routed]) for i in range(3))


def _forward(params, inputs, config, operand=None):
    """`_Equations.forward` of `inputs` = (ids[, given routing])."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        return _Equations(params, config, operand).forward(
            *(jnp.asarray(a) for a in inputs[:2]))


def reference_expert_layer(params, x, config, held=None, shared=True):
    """One expert layer of the reference on `x` (..., d): `params` holds
    the layer's own names (``router_weight``, ``gate_up_weight``, ...);
    `held` the experts whose part is wanted (their weights stacked in that
    order), default the configuration's share; `shared` whether the shared
    expert is counted.  What the add-up test sums over all shares."""
    import jax
    with jax.default_matmul_precision("highest"):
        return _Equations(params, config).experts(x, "", held, shared)[0]


def reference(params, inputs, config):
    """Both heads' logits, (2, B, T, vocab) float32, of the published
    equations on the same share (module docstring of the zoo file; each
    departure is a line under `assumed` in the configuration file).
    `params` maps the net's parameter names to arrays.

    Top-k is discontinuous: where the 4th and 5th selection scores of a
    token nearly tie, the bf16 residual the net's router reads can pick
    the other one, and that token's logits then differ by a whole
    expert's output - as far as a float8 net's differ everywhere.  So the
    reference FOLLOWS the choices in `inputs`' slot (the net's; -1: its
    own), weighs them by its own float32 scores, and holds the net to
    them in another way: a followed expert whose selection score lies
    more than ``check_routing_gap`` under the reference's own k-th is no
    near-tie but a wrong router (a missing bias, a rounded score), and
    that token's logits come back NaN, which fails the comparison
    whatever its tolerance.  One ``benchmark:`` line gives the share of
    (token, layer) choices that differ and the worst gap."""
    import jax
    import jax.numpy as jnp
    main, mtp, _, differs, gap = _forward(params, inputs, config)
    jax.debug.callback(_note_routing, differs, gap)
    fair = (gap <= config["check_routing_gap"]).all(0)[..., None]
    return jnp.stack([jnp.where(fair, main, jnp.nan),
                      jnp.where(fair, mtp, jnp.nan)])


def reference_loss(params, inputs, config, operand=None):
    """The training loss of the reference's logits on the rows themselves:
    CE(main, t+1) + MTP_WEIGHT * CE(MTP, t+2), each the mean over the
    positions that have a label, mean over rows; router choices in
    `inputs`' slot are followed as in `reference`.  ``jax.grad`` of it by
    `params` is what the tests hold the net's gradients to."""
    import jax
    import jax.numpy as jnp
    ids = jnp.asarray(inputs[0])
    main, mtp = _forward(params, inputs, config, operand)[:2]

    def term(logits_, ahead):
        logp = jax.nn.log_softmax(logits_[:, :-ahead], axis=-1)
        return -jnp.take_along_axis(logp, ids[:, ahead:, None],
                                    axis=-1)[..., 0].mean(axis=1)

    return (term(main, 1) + MTP_WEIGHT * term(mtp, 2)).mean()


# -- operations and bytes of one train step, from the shapes ----------------

def ops_and_bytes(config, traffic):
    """Required floating-point operations and least HBM bytes of ONE train
    step of the batch on this chip's share.

    Operations: matrix products only, 2 a multiply-add, forward once and
    backward twice; causal attention counted at half the square; the
    routed experts at their EXPECTED load (every token picks k of the
    published experts, so `held`/`published` of the assignments land
    here); nothing counted twice for being recomputed.  Norms, rotary,
    softmax, the router's sort and the gathers count 0.  Bytes: the batch
    in, every parameter with its float32 master copy and AdamW's two
    float32 moments (14 B a parameter) read once and written once."""
    b, t = traffic["batch"], traffic["seq"]
    d, heads = config["hidden_size"], config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    vd = config["v_head_dim"]
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
    f, fe = config["intermediate_size"], config["moe_intermediate_size"]
    v = config["vocab_size"]
    published = config["n_routed_experts_published"]
    held, k = len(config["experts_held"]), config["num_experts_per_tok"]
    shared = config["n_shared_experts"]
    layers, dense = config["num_hidden_layers"], \
        config["first_k_dense_replace"]
    mtp = config["num_nextn_predict_layers"]
    tokens = b * t
    attention_params = d * rq + rq * heads * qk \
        + d * (rkv + config["qk_rope_head_dim"]) \
        + rkv * heads * (config["qk_nope_head_dim"] + vd) + heads * vd * d
    expert_params = 3 * d * fe
    blocks = layers + mtp                   # every block has attention
    expert_layers = layers - dense + mtp
    forward = {
        "mla_projections": blocks * 2 * tokens * attention_params,
        # causal: half the square; q.k over 256 lanes, p.v over 256
        "mla_core": blocks * b * heads * (qk + vd) * t * t,
        "dense_mlp": dense * 2 * tokens * 3 * d * f,
        "moe_shared": expert_layers * 2 * tokens * shared * expert_params,
        "moe_routed": expert_layers * 2 * tokens * expert_params
        * k * held / published,
        "moe_router": expert_layers * 2 * tokens * d * published,
        "mtp_eh_proj": mtp * 2 * tokens * 2 * d * d,
        "lm_head": (1 + mtp) * 2 * tokens * d * v,
    }
    norms = blocks * (2 * d + rq + rkv) + d + mtp * 2 * d
    n_params = blocks * attention_params + dense * 3 * d * f \
        + expert_layers * ((shared + held) * expert_params + d * published) \
        + mtp * 2 * d * d + 2 * v * d + norms
    state_bytes = n_params * (2 + 4 + 4 + 4)
    return {"flops": 3 * sum(forward.values()),
            "forward_flops": sum(forward.values()),
            "bytes": 2 * state_bytes + tokens * (4 + 4),
            "n_params": n_params,
            "detail": {"forward": forward,
                       "held_expert_weight_bytes":
                           expert_layers * held * expert_params * 2,
                       "expected_assignments_per_expert":
                           tokens * k / published}}
