"""Laguna-XS.2 for the benchmark, on ONE CHIP'S SHARE of a deployment in
which 8 chips share each layer (expert parallel 8 x vocabulary parallel
8): the net through the repo's public API (``gluon.model_zoo.laguna``),
the plain float32 reference given the same share, and the operations and
bytes of one train step worked out from the shapes.  Every size comes
from the configuration file: the published widths, the `experts_held` of
`num_experts_published` routed experts, the slice `vocab_size` of the
vocabulary, the first `num_hidden_layers` entries of the three published
lists a layer (`layer_types`, `mlp_layer_types`,
`num_attention_heads_per_layer`).
"""
import json

import numpy as np


def _sizes(config):
    return dict(
        vocab_size=config["vocab_size"], units=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        layer_types=tuple(config["layer_types"]),
        mlp_layer_types=tuple(config["mlp_layer_types"]),
        num_heads_per_layer=tuple(config["num_attention_heads_per_layer"]),
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        sliding_window=config["sliding_window"],
        rope_parameters=config["rope_parameters"],
        gating=config["gating"],
        hidden_size=config["intermediate_size"],
        moe_hidden_size=config["moe_intermediate_size"],
        shared_hidden_size=config["shared_expert_intermediate_size"],
        num_experts=config["num_experts_published"],
        top_k=config["num_experts_per_tok"],
        routed_scale=config["moe_routed_scaling_factor"],
        held=tuple(config["experts_held"]),
        epsilon=config["rms_norm_eps"])


# -- the system under test --------------------------------------------------

def build(config, ctx, seed):
    """The zoo's decoder on `ctx`, cast and hybridized.  Every layer names
    its input width, so nothing is deferred and no forward is needed
    before the first compiled step.  The router's selection correction is
    drawn (a trained model's is not zero), balanced as its own rule
    balances it where the configuration says so (`balance_routers`), and
    then held: nothing updates it in the window."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import laguna
    mx.random.seed(seed)
    net = laguna.Laguna(
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
    times with the rate falling from `rate` to `rate_last`, every sparse
    layer at once; then the corrections are held as before.  Prints one
    ``benchmark:`` line: the worst layer's max / mean load before and
    after."""
    from mxnet_tpu import nd
    spec = config["router_balance"]
    experts = config["num_experts_published"]
    rng = np.random.RandomState((seed + 2) % (2 ** 32))
    ids = nd.array(_rows(config, 1, spec["tokens"], rng), ctx=ctx,
                   dtype="int32")
    corrections = [p for name, p in net.collect_params().items()
                   if name.endswith("router_correction")]
    rates = np.geomspace(spec["rate"], spec["rate_last"], spec["steps"])
    worst = []
    for rate in list(rates) + [None]:
        loads = expert_loads(net(ids)[1]._jax, experts)
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
    """Next-token cross-entropy alone: the family has no MTP module."""
    from mxnet_tpu.gluon.model_zoo import laguna
    return laguna.NextTokenLoss(mtp_weight=0.0)


def _rows(config, rows, seq, rng):
    return rng.randint(0, config["vocab_size"], (rows, seq)).astype(np.int32)


def batches(config, traffic, seed):
    """The pool of host batches: ((ids,), ids) - a row is its own label,
    shifted by one (next token) in the loss."""
    rng = np.random.RandomState(seed % (2 ** 32))
    pool = []
    for _ in range(traffic["pool"]):
        ids = _rows(config, traffic["batch"], traffic["seq"], rng)
        pool.append(((ids,), ids))
    return pool


def units_per_row(traffic):
    """Tokens in one row of a batch (the throughput's unit)."""
    return traffic["seq"]


def _layers(config):
    """[(attention kind, query heads, MLP kind)] of the layers held."""
    n = config["num_hidden_layers"]
    return list(zip(config["layer_types"][:n],
                    config["num_attention_heads_per_layer"][:n],
                    config["mlp_layer_types"][:n]))


def _sparse_layers(config):
    return sum(1 for _, _, mlp in _layers(config) if mlp == "sparse")


def check_inputs(config, traffic, seed):
    """Rows at the TIMED sizes: `batch` rows of `seq` ids from the slice,
    and a slot (sparse layers, B, T, k) for the experts the net's routers
    choose on them: `logits` fills it, `reference` follows it (see there).
    -1: no choice given."""
    rng = np.random.RandomState((seed + 1) % (2 ** 32))
    ids = _rows(config, traffic["batch"], traffic["seq"], rng)
    return (ids, np.full((_sparse_layers(config),) + ids.shape
                         + (config["num_experts_per_tok"],), -1, np.int32))


def logits(net, inputs, ctx):
    """The logits of the net that is then trained, (B, T, vocab) float32.
    The experts its routers chose go into `inputs`' slot."""
    from mxnet_tpu import nd
    outs = net(nd.array(inputs[0], ctx=ctx, dtype="int32"))
    if len(inputs) > 1:
        inputs[1][...] = np.asarray(outs[1]._jax)
    return outs[0]._jax.astype("float32")


# -- the plain reference ----------------------------------------------------

QUERY_BLOCK = 512       # attention in query blocks: T x T never stands whole
ATTENTION = {"full_attention": "attention_full.",
             "sliding_attention": "attention_window."}


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


def rotary_tables(rope, head_dim, positions):
    """(cos, sin), each (positions, d / 2) float32, and d, the lanes that
    turn, of one entry of the config's ``rope_parameters`` - from the
    formulas, in numpy float64 up to the angle's float32 product:

    default: ``inv_freq_i = theta^(-2i/d)``.  yarn: ``f_i = theta^(-2i/d)``;
    ``dim(n) = d ln(original / (2 pi n)) / (2 ln theta)``; ``low =
    max(floor(dim(beta_fast)), 0)``, ``high = min(ceil(dim(beta_slow)), d -
    1)``; ``ramp_i = clip((i - low) / (high - low), 0, 1)``; ``inv_freq_i =
    (1 - ramp_i) f_i + ramp_i f_i / factor``; cos and sin both times
    `attention_factor`."""
    d = int(head_dim * rope.get("partial_rotary_factor", 1))
    theta = float(rope["rope_theta"])
    i = np.arange(d // 2, dtype=np.float64)
    inv = theta ** (-2.0 * i / d)
    factor = 1.0
    if rope.get("rope_type", "default") == "yarn":
        original = rope["original_max_position_embeddings"]

        def dim(turns):
            return d * np.log(original / (2 * np.pi * turns)) \
                / (2 * np.log(theta))

        low = max(np.floor(dim(rope["beta_fast"])), 0)
        high = min(np.ceil(dim(rope["beta_slow"])), d - 1)
        ramp = np.clip((i - low) / (high - low), 0, 1)
        inv = (1 - ramp) * inv + ramp * inv / rope["factor"]
        factor = rope["attention_factor"]
    angle = np.arange(positions, dtype=np.float32)[:, None] \
        * inv.astype(np.float32)
    return (np.cos(angle) * np.float32(factor),
            np.sin(angle) * np.float32(factor), d)


class _Equations:
    """The published equations on the configuration's share, in float32
    ``jax.numpy``: no Gluon, no kernel, no grouping.  `params` maps the
    net's parameter names to arrays.  `operand`, when given, rounds every
    matrix product's operands to that dtype first (how a lower precision
    than the stated one would compute); `without` names what a WRONG
    implementation leaves out - ``"band"`` (the sliding layers see every
    earlier key), ``"yarn"`` (the full layers turn by plain theta),
    ``"correction"`` (the router selects by its scores alone): the tests
    and PERF.md use them to place the limits; the router's scores are
    never rounded."""

    def __init__(self, params, config, operand=None, without=()):
        self.params, self.config, self.operand = params, config, operand
        self.without = tuple(without)
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

    def rotary(self, x, kind):
        """x: (..., T, D) -> the first d lanes turned, pairs (i, i + d/2),
        the rest as they are."""
        import jax.numpy as jnp
        rope = dict(self.config["rope_parameters"][kind])
        if "yarn" in self.without:
            rope["rope_type"] = "default"
        cos, sin, d = rotary_tables(rope, x.shape[-1], x.shape[-2])
        a, b = x[..., :d // 2], x[..., d // 2:d]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                                x[..., d:]], -1)

    def swiglu(self, x, gate_up, down):
        """gate_up: (d, 2f) with the gate's columns first; down: (f, d)."""
        import jax
        h = self.dot(x, gate_up)
        f = h.shape[-1] // 2
        return self.dot(jax.nn.silu(h[..., :f]) * h[..., f:], down)

    def attention(self, h, at, kind, heads):
        """The attention of one layer on its normed input `h`: `heads`
        query heads on the configuration's key/value heads, positions by
        `kind`'s rotary, the band on a sliding layer, one sigmoid gate a
        query head."""
        import jax
        import jax.numpy as jnp
        config = self.config
        kv_heads, d = config["num_key_value_heads"], config["head_dim"]
        window = config["sliding_window"] \
            if kind == "sliding_attention" and "band" not in self.without \
            else None
        n, t, _ = h.shape

        def split(x, count):
            return x.reshape(n, t, count, d).transpose(0, 2, 1, 3)

        q = self.rotary(split(self.dense(h, at + "q_proj"), heads), kind)
        k = self.rotary(split(self.dense(h, at + "k_proj"), kv_heads), kind)
        v = split(self.dense(h, at + "v_proj"), kv_heads)
        k, v = (jnp.repeat(x, heads // kv_heads, axis=1) for x in (k, v))
        block = min(QUERY_BLOCK, t)

        def rows(start):
            qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=2)
            s = self.dot(qb, k.transpose(0, 1, 3, 2)) / np.sqrt(d)
            i = (start + jnp.arange(block))[:, None]
            j = jnp.arange(t)[None, :]
            seen = j <= i
            if window is not None:
                seen &= j > i - window
            w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return self.dot(w, v)                         # (n, h, block, d)

        out = jax.lax.map(rows, jnp.arange(0, t, block))  # (blocks, n, h, ..)
        out = out.transpose(1, 0, 3, 2, 4).reshape(n, t, heads, d)
        if config["gating"]:
            out = out * jax.nn.sigmoid(
                self.dense(h, at + "g_proj"))[..., None]
        return self.dense(out.reshape(n, t, heads * d), at + "o_proj")

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
        select = s if "correction" in self.without \
            else s + self.p(at + "router_correction")
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
        chosen = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
        return idx, chosen * self.config["moe_routed_scaling_factor"], \
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

    def block(self, x, at, kind, heads, given=None):
        """A decoder block; `given` None: the dense one.  Returns (x,
        (idx, differs, gap) of a sparse block's router)."""
        x = x + self.attention(self.norm(x, at + "input_norm"),
                               at + ATTENTION[kind], kind, heads)
        h = self.norm(x, at + "post_norm")
        if given is None:
            return x + self.swiglu(
                h, self.p(at + "mlp.gate_up_proj.weight").T,
                self.p(at + "mlp.down_proj.weight").T), None
        y, idx, differs, gap = self.experts(h, at + "moe.", given=given)
        return x + y, (idx, differs, gap)

    def forward(self, ids, given=None):
        """(logits, routing (sparse layers, B, T, k), differs and gap
        (sparse layers, B, T)); `given` as `routing`."""
        import jax.numpy as jnp
        config = self.config
        if given is None:
            given = jnp.full((_sparse_layers(config),) + ids.shape
                             + (config["num_experts_per_tok"],), -1)
        x = self.p("embed.weight")[ids]
        routed = []
        for i, (kind, heads, mlp) in enumerate(_layers(config)):
            x, r = self.block(x, "blocks.%d." % i, kind, heads,
                              given[len(routed)] if mlp == "sparse"
                              else None)
            if r is not None:
                routed.append(r)
        logits_ = self.dense(self.norm(x, "lm_head.norm"), "lm_head.proj")
        return (logits_,) + tuple(
            jnp.stack([r[i] for r in routed]) for i in range(3))


def _forward(params, inputs, config, operand=None, without=()):
    """`_Equations.forward` of `inputs` = (ids[, given routing])."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        return _Equations(params, config, operand, without).forward(
            *(jnp.asarray(a) for a in inputs[:2]))


def reference_expert_layer(params, x, config, held=None, shared=True):
    """One sparse layer's MLP of the reference on `x` (..., d): `params`
    holds the layer's own names (``router_weight``, ``gate_up_weight``,
    ...); `held` the experts whose part is wanted (their weights stacked
    in that order), default the configuration's share; `shared` whether
    the shared expert is counted.  What the add-up test sums over all
    shares."""
    import jax
    with jax.default_matmul_precision("highest"):
        return _Equations(params, config).experts(x, "", held, shared)[0]


def reference(params, inputs, config):
    """The logits, (B, T, vocab) float32, of the published equations on
    the same share (module docstring of the zoo file; each reading the
    config leaves open is a line under `assumed` in the configuration
    file).  `params` maps the net's parameter names to arrays.

    Top-k is discontinuous: where the 8th and 9th selection scores of a
    token nearly tie - with 256 scores a token that is common - the bf16
    residual the net's router reads picks the other one, and that
    token's logits then differ by a whole expert's output.  So the
    reference FOLLOWS the choices in `inputs`' slot (the net's; -1: its
    own), weighs them by its own float32 scores, and holds the net to
    them in another way: a followed expert whose selection score lies
    more than ``check_routing_gap`` under the reference's own k-th is no
    near-tie but a wrong router (a missing correction, a rounded score),
    and that token's logits come back NaN, which fails the comparison
    whatever its tolerance.  One ``benchmark:`` line gives the share of
    (token, layer) choices that differ and the worst gap."""
    import jax
    import jax.numpy as jnp
    out, _, differs, gap = _forward(params, inputs, config)
    jax.debug.callback(_note_routing, differs, gap)
    fair = (gap <= config["check_routing_gap"]).all(0)[..., None]
    return jnp.where(fair, out, jnp.nan)


def reference_loss(params, inputs, config, operand=None):
    """The training loss of the reference's logits on the rows themselves:
    CE(logits_i, t_{i+1}), the mean over the T - 1 positions that have a
    label, mean over rows; router choices in `inputs`' slot are followed
    as in `reference`.  ``jax.grad`` of it by `params` is what the tests
    hold the net's gradients to."""
    import jax
    import jax.numpy as jnp
    ids = jnp.asarray(inputs[0])
    out = _forward(params, inputs, config, operand)[0]
    logp = jax.nn.log_softmax(out[:, :-1], axis=-1)
    return -jnp.take_along_axis(logp, ids[:, 1:, None],
                                axis=-1)[..., 0].mean(axis=1).mean()


# -- operations and bytes of one train step, from the shapes ----------------

def band_pairs(t, window=None):
    """(query, key) pairs a head's causal attention holds over `t`
    positions: every key up to the query's own, or with `window` the
    `window` keys that end with it."""
    w = t if window is None else min(window, t)
    return t * w - w * (w - 1) // 2


def ops_and_bytes(config, traffic):
    """Required floating-point operations and least HBM bytes of ONE train
    step of the batch on this chip's share.

    Operations: matrix products only, 2 a multiply-add, forward once and
    backward twice; the attention cores counted at the pairs INSIDE the
    band (`band_pairs`: the causal triangle on a full layer, ``T W - W (W
    - 1) / 2`` on a sliding one), 4 x head_dim FLOP a pair forward (q.k
    and p.v); the routed experts at their EXPECTED load (every token picks
    k of the published experts, so `held`/`published` of the assignments
    land here); nothing counted twice for being recomputed.  Norms,
    rotary, softmax, the gates' products with the heads, the router's sort
    and the gathers count 0.  Bytes: the batch in, every parameter with
    its float32 master copy and AdamW's two float32 moments (14 B a
    parameter) read once and written once."""
    b, t = traffic["batch"], traffic["seq"]
    d, kv_heads = config["hidden_size"], config["num_key_value_heads"]
    hd, window = config["head_dim"], config["sliding_window"]
    f, fe = config["intermediate_size"], config["moe_intermediate_size"]
    fs = config["shared_expert_intermediate_size"]
    v = config["vocab_size"]
    published = config["num_experts_published"]
    held, k = len(config["experts_held"]), config["num_experts_per_tok"]
    gate = 1 if config["gating"] else 0
    tokens = b * t
    layers = _layers(config)
    dense = sum(1 for _, _, mlp in layers if mlp == "dense")
    sparse = len(layers) - dense
    expert_params = 3 * d * fe
    attention_params = sum(2 * d * heads * hd + 2 * d * kv_heads * hd
                           + gate * d * heads for _, heads, _ in layers)
    forward = {
        "attention_projections": 2 * tokens * attention_params,
        "attention_core_full": sum(
            b * heads * 4 * hd * band_pairs(t) for kind, heads, _ in layers
            if kind == "full_attention"),
        "attention_core_window": sum(
            b * heads * 4 * hd * band_pairs(t, window)
            for kind, heads, _ in layers if kind == "sliding_attention"),
        "dense_mlp": dense * 2 * tokens * 3 * d * f,
        "moe_shared": sparse * 2 * tokens * 3 * d * fs,
        "moe_routed": sparse * 2 * tokens * expert_params * k * held
        / published,
        "moe_router": sparse * 2 * tokens * d * published,
        "lm_head": 2 * tokens * d * v,
    }
    n_params = attention_params + len(layers) * 2 * d + dense * 3 * d * f \
        + sparse * (d * published + 3 * d * fs + held * expert_params) \
        + d + 2 * v * d
    state_bytes = n_params * (2 + 4 + 4 + 4)
    return {"flops": 3 * sum(forward.values()),
            "forward_flops": sum(forward.values()),
            "bytes": 2 * state_bytes + tokens * (4 + 4),
            "n_params": n_params,
            "detail": {"forward": forward,
                       "held_expert_weight_bytes":
                           sparse * held * expert_params * 2,
                       "expected_assignments_per_expert":
                           tokens * k / published}}
