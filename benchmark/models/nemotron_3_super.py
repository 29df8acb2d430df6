"""Nemotron-3-Super for the benchmark, on ONE CHIP'S SHARE of a deployment
in which 64 chips share each layer (expert parallel 64 x tensor parallel 8
inside each of 8 data-parallel groups): the net through the repo's public
API (``gluon.model_zoo.nemotron_h``), the plain float32 reference given
the same share, and the operations and bytes of one train step worked out
from the shapes.  Every size comes from the configuration file: the
published widths, the `experts_held` of `n_routed_experts_published`
routed experts, the heads, groups, shared-expert columns and ids of one
tensor-parallel rank.
"""
import json

import numpy as np


def _sizes(config):
    return dict(
        vocab_size=config["vocab_size"], units=config["hidden_size"],
        pattern=config["pattern_held"],
        mamba_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"],
        state_size=config["ssm_state_size"],
        mamba_groups=config["n_groups"],
        conv_kernel=config["conv_kernel"], chunk_size=config["chunk_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        moe_hidden_size=config["moe_intermediate_size"],
        moe_latent_size=config["moe_latent_size"],
        shared_hidden_size=config["shared_expert_columns_held"],
        num_experts=config["n_routed_experts_published"],
        top_k=config["num_experts_per_tok"],
        routed_scale=config["routed_scaling_factor"],
        norm_topk_prob=config["norm_topk_prob"],
        held=tuple(config["experts_held"]), epsilon=config["norm_eps"],
        num_mtp=config["num_nextn_predict_layers"],
        mtp_pattern=config["mtp_hybrid_override_pattern"])


# -- the system under test --------------------------------------------------

def build(config, ctx, seed):
    """The zoo's decoder on `ctx`, cast and hybridized.  Every layer names
    its input width, so nothing is deferred and no forward is needed
    before the first compiled step.  The router's selection bias is drawn
    (a trained model's is not zero), balanced as its own rule balances it
    where the configuration says so (`balance_routers`), and then held:
    nothing updates it in the window."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import nemotron_h
    mx.random.seed(seed)
    net = nemotron_h.NemotronH(
        router_correction_initializer=mx.init.Normal(
            config["router_correction_std"]),
        output_routing=True, **_sizes(config))
    net.initialize(mx.init.Normal(config["initializer_std"]), ctx=ctx)
    net.cast(config["dtype"])
    net.hybridize()
    if config.get("router_balance"):
        balance_routers(net, config, ctx, seed)
    return net


def expert_loads(routing, experts):
    """Assignments an expert, (layers, experts), of the routers' choices
    `routing` (layers, ..., k)."""
    routing = np.asarray(routing)
    return np.stack([np.bincount(layer.reshape(-1), minlength=experts)
                     for layer in routing])


def balance_routers(net, config, ctx, seed):
    """The selection corrections as ``noaux_tc`` leaves them: BALANCED.

    A trained model's correction is no noise: the rule that makes it
    (``b_e += rate * sign(mean load - load_e)`` after every step) holds
    every expert's load near the mean, and a deployment's step time
    rests on that - a freshly drawn router sends a layer's tokens to the
    few experts its weights happen to favour, the held experts' load
    (and the grouped products' time) then differs from seed to seed by
    what nothing in a deployment differs by.  So the rule itself runs
    here, in set-up, on one seeded row of the cell's length, `steps`
    times with the rate falling from `rate` to `rate_last`, every expert
    layer at once; then the corrections are held as before.  Prints one
    ``benchmark:`` line: the worst layer's max / mean load before and
    after."""
    from mxnet_tpu import nd
    spec = config["router_balance"]
    experts = config["n_routed_experts_published"]
    rng = np.random.RandomState((seed + 2) % (2 ** 32))
    ids = nd.array(_rows(config, 1, spec["tokens"], rng), ctx=ctx,
                   dtype="int32")
    corrections = [p for name, p in net.collect_params().items()
                   if name.endswith("router_correction")]
    rates = np.geomspace(spec["rate"], spec["rate_last"], spec["steps"])
    worst = []
    for rate in list(rates) + [None]:
        loads = expert_loads(net(ids)[2]._jax, experts)
        worst.append(float((loads.max(1) / loads.mean(1)).max()))
        if rate is None:
            break
        for p, load in zip(corrections, loads):
            p.set_data(nd.array(
                p.data().asnumpy() + rate * np.sign(load.mean() - load),
                ctx=ctx, dtype="float32"))
    print("benchmark: " + json.dumps(
        {"router_balance_steps": spec["steps"],
         "expert_load_max_over_mean_before": worst[0],
         "expert_load_max_over_mean_after": worst[-1]}), flush=True)


def loss_fn():
    from mxnet_tpu.gluon.model_zoo import nemotron_h
    return nemotron_h.NextTokenLoss(mtp_weight=MTP_WEIGHT)


MTP_WEIGHT = 0.1        # the configuration's `assumed.mtp_loss_weight`


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


def _count(config, kind):
    """Layers of `kind` ("M", "E", "*") in (the trunk, the MTP module)."""
    mtp = config["mtp_hybrid_override_pattern"] \
        * config["num_nextn_predict_layers"]
    return config["pattern_held"].count(kind), mtp.count(kind)


def _expert_layers(config):
    return sum(_count(config, "E"))


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
    ``jax.numpy``: no Gluon, no kernel, no chunk, no grouping.  `params`
    maps the net's parameter names to arrays.  `operand`, when given,
    rounds every matrix product's operands (and what the scan reads) to
    that dtype first; `state`, when given, is the dtype the scan's state
    is kept in between positions (how a lower precision than the stated
    one would compute: the tests and PERF.md use both to place the
    tolerance); the router's scores are never rounded."""

    def __init__(self, params, config, operand=None, state=None):
        self.params, self.config = params, config
        self.operand, self.state = operand, state
        self.eps = config["norm_eps"]

    def p(self, name):
        import jax.numpy as jnp
        return jnp.asarray(self.params[name], jnp.float32)

    def rounded(self, x):
        import jax.numpy as jnp
        return x if self.operand is None \
            else x.astype(self.operand).astype(jnp.float32)

    def dot(self, a, b):
        import jax.numpy as jnp
        return jnp.matmul(self.rounded(a), self.rounded(b))

    def dense(self, x, name):
        return self.dot(x, self.p(name + ".weight").T)

    def norm(self, x, gamma):
        import jax
        import jax.numpy as jnp
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + self.eps) * gamma

    def mlp(self, x, up, down):
        """relu(x up)^2 down, no gate; up (d, f), down (f, d)."""
        import jax
        import jax.numpy as jnp
        return self.dot(jnp.square(jax.nn.relu(self.dot(x, up))), down)

    def mamba(self, u, at, heads=None, groups=None):
        """The Mamba-2 mixer as the recurrence over positions, one state
        (P, N) a head; `heads` and `groups` default to the share's."""
        import jax
        import jax.numpy as jnp
        config = self.config
        heads = heads or config["mamba_num_heads"]
        groups = groups or config["n_groups"]
        hd, n = config["mamba_head_dim"], config["ssm_state_size"]
        inner, bc = heads * hd, groups * n
        b_, t, _ = u.shape
        mixed = self.dense(u, at + "in_proj")
        z, xbc, dt = (mixed[..., :inner], mixed[..., inner:2 * inner + 2 * bc],
                      mixed[..., 2 * inner + 2 * bc:])
        w, bias = self.p(at + "conv_weight"), self.p(at + "conv_bias")
        k = w.shape[1]
        padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
        xbc = jax.nn.silu(bias + sum(padded[:, i:i + t] * w[:, i]
                                     for i in range(k)))
        xbc = self.rounded(xbc)
        x = xbc[..., :inner].reshape(b_, t, heads, hd)
        per = heads // groups
        bm = jnp.repeat(xbc[..., inner:inner + bc].reshape(b_, t, groups, n),
                        per, axis=2)
        cm = jnp.repeat(xbc[..., inner + bc:].reshape(b_, t, groups, n),
                        per, axis=2)
        dt = jax.nn.softplus(dt + self.p(at + "dt_bias"))
        a = -jnp.exp(self.p(at + "A_log"))
        kept = self.state or jnp.float32

        def position(h, inputs):
            x_t, b_t, c_t, dt_t = inputs    # (B,H,P), (B,H,N), (B,H,N), (B,H)
            h = jnp.exp(dt_t * a)[..., None, None] * h.astype(jnp.float32) \
                + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
            h = h.astype(kept)
            return h, (h.astype(jnp.float32) * c_t[..., None, :]).sum(-1)

        first = jnp.zeros((b_, heads, hd, n), kept)
        _, y = jax.lax.scan(position, first, tuple(
            jnp.moveaxis(v, 1, 0) for v in (x, bm, cm, dt)))
        y = jnp.moveaxis(y, 0, 1) + self.p(at + "D")[:, None] * x
        y = y.reshape(b_, t, inner) * jax.nn.silu(z)
        y = self.norm(y.reshape(b_, t, groups, -1),
                      self.p(at + "norm_gamma").reshape(groups, -1))
        return self.dense(y.reshape(b_, t, inner), at + "out_proj")

    def attention(self, u, at, heads=None, kv_heads=None):
        """Plain causal softmax, a key/value head repeated for its query
        heads, in query blocks."""
        import jax
        import jax.numpy as jnp
        config = self.config
        heads = heads or config["num_attention_heads"]
        kv_heads = kv_heads or config["num_key_value_heads"]
        d = config["head_dim"]
        n, t, _ = u.shape

        def split(x, h):
            return x.reshape(n, t, h, d).transpose(0, 2, 1, 3)

        q = split(self.dense(u, at + "q_proj"), heads)
        k = jnp.repeat(split(self.dense(u, at + "k_proj"), kv_heads),
                       heads // kv_heads, axis=1)
        v = jnp.repeat(split(self.dense(u, at + "v_proj"), kv_heads),
                       heads // kv_heads, axis=1)
        block = min(QUERY_BLOCK, t)

        def rows(start):
            qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=2)
            s = self.dot(qb, k.transpose(0, 1, 3, 2)) / np.sqrt(d)
            seen = (start + jnp.arange(block))[:, None] \
                >= jnp.arange(t)[None, :]
            w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return self.dot(w, v)                         # (n, h, block, d)

        out = jax.lax.map(rows, jnp.arange(0, t, block))  # (blocks, n, h, ..)
        out = out.transpose(1, 0, 3, 2, 4).reshape(n, t, heads * d)
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
        """(shared(x) + up(the `held` experts' part of down(x)), idx,
        differs, gap); `held` defaults to the configuration's share, whose
        weights `up_weight` / `down_weight` hold in that order."""
        held = list(self.config["experts_held"]) if held is None else held
        idx, weight, differs, gap = self.route(x, at, given)
        latent = self.dense(x, at + "latent_down")
        first, second = self.p(at + "up_weight"), self.p(at + "down_weight")
        routed = 0.0
        for local, expert in enumerate(held):                # a plain loop
            w_e = (weight * (idx == expert)).sum(-1, keepdims=True)
            routed = routed + w_e * self.mlp(latent, first[local],
                                             second[local])
        y = self.dense(routed, at + "latent_up") if held else 0.0
        if shared:
            y = y + self.mlp(x, self.p(at + "shared.up_proj.weight").T,
                             self.p(at + "shared.down_proj.weight").T)
        return y, idx, differs, gap

    def layer(self, x, kind, at, given=None):
        """One layer ``x + mixer(N(x))``; returns (x, (idx, differs, gap)
        of an E layer's router, else None)."""
        h = self.norm(x, self.p(at + "norm.gamma"))
        if kind == "M":
            return x + self.mamba(h, at + "ssm."), None
        if kind == "*":
            return x + self.attention(h, at + "attention."), None
        y, idx, differs, gap = self.experts(h, at + "moe.", given=given)
        return x + y, (idx, differs, gap)

    def head(self, x):
        return self.dense(self.norm(x, self.p("lm_head.norm.gamma")),
                          "lm_head.proj")

    def forward(self, ids, given=None):
        """(main logits, MTP logits, routing (expert layers, B, T, k),
        differs and gap (expert layers, B, T)); `given` as `routing`."""
        import jax.numpy as jnp
        config = self.config
        if given is None:
            given = jnp.full((_expert_layers(config),) + ids.shape
                             + (config["num_experts_per_tok"],), -1)
        embed = self.p("embed.weight")
        x = embed[ids]
        routed = []

        def run(x, pattern, prefix):
            for i, kind in enumerate(pattern):
                x, r = self.layer(x, kind, prefix % i,
                                  given[len(routed)] if kind == "E" else None)
                if r is not None:
                    routed.append(r)
            return x

        x = run(x, config["pattern_held"], "blocks.%d.")
        main = self.head(x)
        following = embed[jnp.roll(ids, -1, axis=1)]
        joined = jnp.concatenate(
            [self.norm(following, self.p("mtp.enorm.gamma")),
             self.norm(x, self.p("mtp.hnorm.gamma"))], -1)
        h = run(self.dense(joined, "mtp.eh_proj"),
                config["mtp_hybrid_override_pattern"],
                "mtp.block.layers.%d.")
        return (main, self.head(h)) + tuple(
            jnp.stack([r[i] for r in routed]) for i in range(3))


def _forward(params, inputs, config, operand=None, state=None):
    """`_Equations.forward` of `inputs` = (ids[, given routing])."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        return _Equations(params, config, operand, state).forward(
            *(jnp.asarray(a) for a in inputs[:2]))


def reference_expert_layer(params, x, config, held=None, shared=True):
    """One expert layer of the reference on `x` (..., d): `params` holds
    the layer's own names (``router_weight``, ``up_weight``, ...); `held`
    the experts whose part is wanted (their weights stacked in that
    order), default the configuration's share; `shared` whether the shared
    expert is counted.  What the add-up test sums over all shares."""
    import jax
    with jax.default_matmul_precision("highest"):
        return _Equations(params, config).experts(x, "", held, shared)[0]


def reference_mixer(kind, params, x, config, **share):
    """A Mamba-2 (`kind` "M": `heads`, `groups`) or attention ("*":
    `heads`, `kv_heads`) mixer of the reference on `x` (B, T, d), without
    the layer's norm and residual; `params` holds the mixer's own names.
    What the add-up test sums over the tensor-parallel ranks."""
    import jax
    with jax.default_matmul_precision("highest"):
        eq = _Equations(params, config)
        return eq.mamba(x, "", **share) if kind == "M" \
            else eq.attention(x, "", **share)


def reference(params, inputs, config):
    """Both heads' logits, (2, B, T, vocab) float32, of the published
    equations on the same share (module docstring of the zoo file; each
    departure is a line under `assumed` in the configuration file).
    `params` maps the net's parameter names to arrays.

    Top-k is discontinuous: where the 22nd and 23rd selection scores of a
    token nearly tie - with 512 scores a token that is the rule - the
    bf16 residual the net's router reads picks the other one, and that
    token's logits then differ by a whole expert's output.  So the
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
    backward twice; causal attention counted at half the square; the scan
    at the products of its chunked dual form at the published chunk
    (`ssm_scan`: a chunk's C B^T a group, the masked (chunk x chunk)
    matrix times the chunk's x, the chunk's own state and what the state
    before it adds, a head); the routed experts at their EXPECTED load
    (every token picks k of the published experts, so `held`/`published`
    of the assignments land here); nothing counted twice for being
    recomputed.  Norms, the convolution, decays, softmax, the router's
    sort and the gathers count 0.  Bytes: the batch in, every parameter
    with its float32 master copy and AdamW's two float32 moments (14 B a
    parameter) read once and written once.  ``detail["ssm_scan_bytes"]``:
    what one forward pass of the scans reads and writes once - x, B, C,
    dt and z in, y out, in the net's dtype."""
    b, t = traffic["batch"], traffic["seq"]
    d = config["hidden_size"]
    heads, kv_heads = config["num_attention_heads"], \
        config["num_key_value_heads"]
    hd = config["head_dim"]
    mh, mp = config["mamba_num_heads"], config["mamba_head_dim"]
    n, groups = config["ssm_state_size"], config["n_groups"]
    chunk, kernel = config["chunk_size"], config["conv_kernel"]
    inner, bc = mh * mp, groups * n
    latent, fe = config["moe_latent_size"], config["moe_intermediate_size"]
    fs = config["shared_expert_columns_held"]
    v = config["vocab_size"]
    published = config["n_routed_experts_published"]
    held, k = len(config["experts_held"]), config["num_experts_per_tok"]
    mtp = config["num_nextn_predict_layers"]
    tokens = b * t
    n_m, n_e, n_a = (sum(_count(config, kind)) for kind in "ME*")
    ssm_in = d * (2 * inner + 2 * bc + mh)
    ssm_params = ssm_in + inner * d + (inner + 2 * bc) * (kernel + 1) \
        + 3 * mh + inner
    attention_params = 2 * d * heads * hd + 2 * d * kv_heads * hd
    expert_params = 2 * latent * fe
    moe_params = published * d + 2 * d * latent + 2 * d * fs \
        + held * expert_params
    forward = {
        "ssm_projections": n_m * 2 * tokens * (ssm_in + inner * d),
        "ssm_scan": n_m * 2 * tokens * (chunk * n * groups
                                        + chunk * mp * mh + 2 * n * mp * mh),
        "attention_projections": n_a * 2 * tokens * attention_params,
        # causal: half the square; q.k over 128 lanes, p.v over 128
        "attention_core": n_a * b * heads * 2 * hd * t * t,
        "moe_router": n_e * 2 * tokens * d * published,
        "moe_latent": n_e * 2 * tokens * 2 * d * latent,
        "moe_shared": n_e * 2 * tokens * 2 * d * fs,
        "moe_routed": n_e * 2 * tokens * expert_params * k * held
        / published,
        "mtp_eh_proj": mtp * 2 * tokens * 2 * d * d,
        "lm_head": (1 + mtp) * 2 * tokens * d * v,
    }
    n_params = n_m * ssm_params + n_a * attention_params \
        + n_e * moe_params + (n_m + n_a + n_e) * d \
        + mtp * (2 * d * d + 2 * d) + d + 2 * v * d
    state_bytes = n_params * (2 + 4 + 4 + 4)
    item = 2                                    # the net's dtype: bf16
    return {"flops": 3 * sum(forward.values()),
            "forward_flops": sum(forward.values()),
            "bytes": 2 * state_bytes + tokens * (4 + 4),
            "n_params": n_params,
            "detail": {"forward": forward,
                       "ssm_scan_bytes":
                           n_m * tokens * item * (3 * inner + 2 * bc + mh),
                       "held_expert_weight_bytes":
                           n_e * held * expert_params * 2,
                       "expected_assignments_per_expert":
                           tokens * k / published}}
