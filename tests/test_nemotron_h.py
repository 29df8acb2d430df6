"""Nemotron-H's hybrid decoder (gluon.model_zoo.nemotron_h) against the
plain float32 reference the benchmark keeps
(benchmark/models/nemotron_3_super.py), at a small size on the CPU: logits
of both heads, loss and gradients; the chunked scan against the recurrence
over positions, forward and backward; grouped key/value heads in the flash
kernels against the composition; the latent squared-ReLU expert layer
against a dense loop and its buffer under the worst imbalance; the shares
of all three mixer kinds adding up to the whole; the compiled step."""
import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, programs, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import attention, ssm
from mxnet_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _model_file():
    spec = importlib.util.spec_from_file_location(
        "_nemotron_3_super", os.path.join(REPO, "benchmark", "models",
                                          "nemotron_3_super.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


MODEL = _model_file()

# hidden 64; Mamba: 4 heads of 8 in 2 groups, state 16, chunks of 16;
# attention: 4 query heads on 1 key/value head of 16; 16 experts top-5 of
# which 3 are held, latent 32, width 24, shared 40; M E M * E + MTP's * E
CONFIG = {
    "hidden_size": 64, "pattern_held": "MEM*E",
    "mtp_hybrid_override_pattern": "*E", "mamba_num_heads": 4,
    "mamba_head_dim": 8, "ssm_state_size": 16, "n_groups": 2,
    "conv_kernel": 4, "chunk_size": 16, "num_attention_heads": 4,
    "num_key_value_heads": 1, "head_dim": 16, "moe_intermediate_size": 24,
    "moe_latent_size": 32, "shared_expert_columns_held": 40,
    "n_routed_experts_published": 16, "num_experts_per_tok": 5,
    "routed_scaling_factor": 5.0, "norm_topk_prob": True,
    "experts_held": [2, 3, 4], "norm_eps": 1e-5,
    "num_nextn_predict_layers": 1, "vocab_size": 96,
    "router_correction_std": 0.1, "initializer_std": 0.05,
    "dtype": "float32", "check_routing_gap": 0.02,
}
CTX = mx.cpu()


def _net(dtype="float32", seed=11, **over):
    config = dict(CONFIG, dtype=dtype, **over)
    return MODEL.build(config, CTX, seed), config


def _ids(rows=2, seq=48, seed=0):
    return np.random.RandomState(seed).randint(
        0, CONFIG["vocab_size"], (rows, seq)).astype(np.int32)


def _params(block):
    return {name: p.data()._jax
            for name, p in block.collect_params().items()}


def _kind(name):
    """A parameter's kind: its name without the layer numbers."""
    return ".".join(p for p in name.split(".") if not p.isdigit())


# -- logits, loss and gradients against the reference ------------------------

# float32: the same arithmetic in another order of summation - the chunked
# scan against the recurrence included.  bfloat16: every product's
# operands carry 8 bits, through 7 layers; the reference follows the net's
# router choices and holds them to a gap.  float8_e4m3 operands, a scan
# state kept in bfloat16 and a skipped term must each fail at least one of
# the bfloat16 limits (asserted below).  Read on seeds 11, 14, 17, 21
# since PR 38, when the cast net's residual stream became bfloat16 (until
# then the mixer's float32 gain had made every layer after the first
# float32, on the host the scans' operands too): bf16 logits 0.9-1.2e-2
# (were 0.7-1.0e-2), loss under 1.5e-4, gap 0.3-1.6e-3 (0.2-0.8e-3),
# gradients 1.8-3.9e-2 by kind (1.1-1.7e-2), worst D and dt_bias - but
# for A_log, four numbers a layer here, each a sum of differences that
# nearly cancel (rows of the decay matrix against its columns): 0.6-2.6e-2
# on seeds 11, 14, 21 and 2.1e-1 on seed 17 (5.5e-2 before), a seed the
# cases below do not run.
TOLERANCE = {"float32": {"logits": 2e-5, "loss": 1e-5, "grads": 2e-4,
                         "gap": 1e-5},
             "bfloat16": {"logits": 3e-2, "loss": 3e-3, "grads": 1e-1,
                          "gap": 2e-2}}


def _net_readings(net, ids):
    """(logits (2,B,T,V), routing, loss, {name: grad}) of the Gluon net
    through the tape."""
    loss_fn = MODEL.loss_fn()
    x = nd.array(ids, ctx=CTX, dtype="int32")
    with autograd.record():
        outs = net(x)
        loss = loss_fn(outs[:2], x).mean()
    loss.backward()
    grads = {name: np.asarray(p.grad()._jax, np.float32)
             for name, p in net.collect_params().items()
             if p.grad_req != "null"}
    logits = np.stack([np.asarray(o._jax, np.float32) for o in outs[:2]])
    return logits, np.asarray(outs[2]._jax), \
        float(np.asarray(loss._jax, np.float32).mean()), grads


def _reference_readings(params, ids, config, operand=None, given=None):
    inputs = (ids,) if given is None else (ids, given)
    main, mtp, routing, _, gap = MODEL._forward(params, inputs, config,
                                                operand)
    loss, grads = jax.value_and_grad(
        lambda ps: MODEL.reference_loss(ps, inputs, config, operand))(params)
    return (np.stack([np.asarray(main), np.asarray(mtp)]),
            np.asarray(routing), float(loss),
            {k: np.asarray(v, np.float32) for k, v in grads.items()}), \
        float(np.asarray(gap).max())


def _errors(got, want):
    g_logits, _, g_loss, g_grads = got
    w_logits, _, w_loss, w_grads = want
    kinds = {}
    for name, g in g_grads.items():
        err = np.linalg.norm(g - w_grads[name])
        scale = np.linalg.norm(w_grads[name]) + 1e-12
        kinds[_kind(name)] = max(kinds.get(_kind(name), 0.0), err / scale)
    return (np.abs(g_logits - w_logits).max() / np.abs(w_logits).max(),
            abs(g_loss - w_loss) / abs(w_loss), kinds)


def _fails(tol, errors, gap):
    logits, loss, kinds = errors
    return bool(logits > tol["logits"] or loss > tol["loss"]
                or max(kinds.values()) > tol["grads"] or gap > tol["gap"])


@pytest.mark.parametrize("dtype,seed", [("float32", 11), ("bfloat16", 11),
                                        ("bfloat16", 14)])
def test_logits_loss_and_gradients_match_the_reference(dtype, seed):
    net, config = _net(dtype, seed=seed)
    ids = _ids()
    params = _params(net)
    got = _net_readings(net, ids)
    want, gap = _reference_readings(params, ids, config, given=got[1])
    logits, loss, kinds = _errors(got, want)
    tol = TOLERANCE[dtype]
    assert gap <= tol["gap"], gap
    assert logits <= tol["logits"], logits
    assert loss <= tol["loss"], loss
    assert len(kinds) >= 35            # every parameter kind has a gradient
    worst = max(kinds, key=kinds.get)
    assert kinds[worst] <= tol["grads"], (worst, kinds[worst])
    own, _ = _reference_readings(params, ids, config)
    differ = (np.sort(got[1], -1) != np.sort(own[1], -1)).any(-1).mean()
    assert (differ == 0) if dtype == "float32" else (differ < 0.2)


def test_the_limits_refuse_lower_precision_and_a_skipped_term():
    """What the bfloat16 limits are FOR.  Held to the float32 reference as
    a net is (its choices followed): the reference with float8_e4m3
    operands, and with one term left out - the selection bias, the shared
    expert, the scan's skip, the routed scaling - must each fail at least
    one of them."""
    net, config = _net("float32")
    ids = _ids()
    params = _params(net)
    tol = TOLERANCE["bfloat16"]

    def fails(other_params=None, operand=None, **other_config):
        reading, _ = _reference_readings(other_params or params, ids,
                                         dict(config, **other_config),
                                         operand)
        want, gap = _reference_readings(params, ids, config,
                                        given=reading[1])
        return _fails(tol, _errors(reading, want), gap)

    def without(suffix):
        return {k: (jnp.zeros_like(v) if k.endswith(suffix) else v)
                for k, v in params.items()}

    assert not fails()
    assert fails(operand=jnp.float8_e4m3fn)
    assert fails(without("router_correction"))
    assert fails(without("shared.down_proj.weight"))
    assert fails(without("ssm.D"))
    assert fails(routed_scaling_factor=1.0)


def test_a_scan_state_kept_in_bfloat16_is_told_from_float32():
    """One mixer, 256 positions: the recurrence with its state rounded to
    bfloat16 after every position drifts from the float32 recurrence a
    hundred times further than the chunked scan in float32 does.  (At the
    logits of a freshly initialised net the drift is small beside the
    skip ``D x``: PERF.md has the chip's reading.)"""
    mx.random.seed(13)
    mixer = nn.Mamba2Mixer(32, 4, 8, 16, 2, chunk_size=16)
    mixer.initialize(mx.init.Normal(0.3), ctx=CTX)
    params = _params(mixer)
    x = jnp.asarray(np.random.RandomState(14).randn(2, 256, 32), jnp.float32)
    config = dict(CONFIG, chunk_size=16)
    with jax.default_matmul_precision("highest"):
        want = MODEL._Equations(params, config).mamba(x, "")
        low = MODEL._Equations(params, config,
                               state=jnp.bfloat16).mamba(x, "")
    got = mixer(nd.array(np.asarray(x), ctx=CTX))._jax
    scale = float(jnp.abs(want).max())
    err_low = float(jnp.abs(low - want).max()) / scale
    err_net = float(jnp.abs(got - want).max()) / scale
    assert err_net < 1e-5 and err_low > 100 * err_net, (err_net, err_low)


def test_the_selection_correction_is_balanced_by_its_own_rule(capsys):
    """`router_balance` in a configuration runs noaux_tc's update in
    set-up: every expert layer's worst load falls from ~3 x the mean to
    within a tenth of it, through the corrections alone."""
    spec = {"tokens": 256, "steps": 40, "rate": 0.03, "rate_last": 0.001}
    plain, config = _net("float32", seed=3)
    balanced, _ = _net("float32", seed=3, router_balance=spec)
    said = json.loads(capsys.readouterr().out.split("benchmark: ")[-1])
    assert said["expert_load_max_over_mean_before"] > 2.0
    assert said["expert_load_max_over_mean_after"] < 1.1
    ids = nd.array(MODEL._rows(config, 1, 256, np.random.RandomState(5)),
                   ctx=CTX, dtype="int32")
    loads = MODEL.expert_loads(balanced(ids)[2]._jax, 16)
    assert loads.shape == (3, 16) and (loads.sum(1) == 256 * 5).all()
    assert (loads.max(1) / loads.mean(1)).max() < 1.25     # another row
    for (name, p), q in zip(plain.collect_params().items(),
                            balanced.collect_params().values()):
        same = np.array_equal(np.asarray(p.data()._jax),
                              np.asarray(q.data()._jax))
        assert same != name.endswith("router_correction"), name


def test_the_reference_follows_near_ties_and_refuses_a_wrong_router():
    net, config = _net("float32")
    ids = _ids()
    params = _params(net)
    _, _, own, _, _ = MODEL._forward(params, (ids,), config)
    slot = MODEL.check_inputs(config, {"batch": 2, "seq": 48}, 0)[1]
    assert slot.shape == own.shape == (3, 2, 48, 5) and (slot == -1).all()
    free = np.asarray(MODEL.reference(params, (ids, slot), config))
    same = np.asarray(MODEL.reference(params, (ids, np.asarray(own)),
                                      config))
    np.testing.assert_array_equal(free, same)
    # an expert far from the top-k at one token of the first expert layer:
    # that token's logits are refused
    wrong = np.array(own)
    taken = set(wrong[0, 1, 5].tolist())
    wrong[0, 1, 5, 0] = next(e for e in range(16) if e not in taken)
    out = np.asarray(MODEL.reference(params, (ids, wrong), config))
    _, _, _, _, gap = MODEL._forward(params, (ids, wrong), config)
    if float(np.asarray(gap).max()) > 0.02:
        assert np.isnan(out[:, 1, 5]).all()
        assert np.isfinite(out[:, 0]).all()
    else:                               # a near-tie after all: followed
        assert np.isfinite(out).all()


# -- the chunked scan against the recurrence over positions ------------------

def _recurrence(x, dt, a, b, c):
    """h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x) b_t; y_t = h_t c_t: one
    state a head, position by position, float32."""
    x, dt, b, c = (jnp.asarray(v, jnp.float32) for v in (x, dt, b, c))
    per = x.shape[2] // b.shape[2]
    b, c = (jnp.repeat(v, per, axis=2) for v in (b, c))

    def position(h, inputs):
        x_t, dt_t, b_t, c_t = inputs
        h = jnp.exp(dt_t * a)[..., None, None] * h \
            + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        return h, (h * c_t[..., None, :]).sum(-1)

    first = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], jnp.float32)
    _, y = jax.lax.scan(position, first, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


def _scan_inputs(shape, dtype, seed=0):
    B, T, H, P, G, N = shape
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(B, T, H, P), dtype)
    # steps from 1e-3 to 0.5 and A from -16 to -1: a chunk's decay runs
    # from nearly 1 down to e^-100 and beyond
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.5),
                                        (B, T, H))), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, (H,)), jnp.float32)
    b = jnp.asarray(rng.randn(B, T, G, N), dtype)
    c = jnp.asarray(rng.randn(B, T, G, N), dtype)
    return x, dt, a, b, c


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 3e-2)])
@pytest.mark.parametrize("shape,chunk", [
    ((2, 16, 4, 8, 2, 16), 16),         # T = one chunk
    ((2, 64, 4, 8, 2, 16), 16),         # four chunks: the state is carried
    ((1, 96, 4, 4, 4, 8), 32),          # a group a head
    ((1, 128, 2, 8, 1, 8), 8),          # sixteen chunks, one group
])
def test_the_chunked_scan_is_the_recurrence_forward_and_backward(
        shape, chunk, dtype, tol):
    x, dt, a, b, c = _scan_inputs(shape, dtype)
    weight = jnp.asarray(np.random.RandomState(1).randn(*x.shape),
                         jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = ssm.ssd_scan(x, dt, a, b, c, chunk)
        want = _recurrence(x, dt, a, b, c)
        assert got.dtype == x.dtype and got.shape == x.shape
        scale = float(jnp.abs(want).max())
        assert float(jnp.abs(got.astype(jnp.float32) - want).max()) \
            <= tol * scale

        def loss(fn):
            return lambda *args: (fn(*args).astype(jnp.float32)
                                  * weight).sum()

        mine = jax.grad(loss(lambda *v: ssm.ssd_scan(*v, chunk)),
                        argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c)
        theirs = jax.grad(loss(_recurrence),
                          argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c)
    for name, g, w in zip("x dt a b c".split(), mine, theirs):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        err = float(jnp.abs(g.astype(jnp.float32)
                            - w.astype(jnp.float32)).max())
        assert err <= tol * float(jnp.abs(w.astype(jnp.float32)).max()), \
            (name, err)


def test_the_scan_carries_its_state_across_chunk_boundaries():
    """A change to position 3 reaches position 60 only through the states
    carried across three chunk boundaries; one chunk of the whole gives
    the same output as four."""
    x, dt, a, b, c = _scan_inputs((1, 64, 2, 4, 1, 8), "float32", seed=3)
    dt = dt * 0.05                      # slow decay: the past matters
    with jax.default_matmul_precision("highest"):
        base = ssm.ssd_scan(x, dt, a, b, c, 16)
        moved = ssm.ssd_scan(x.at[0, 3].add(1.0), dt, a, b, c, 16)
        whole = ssm.ssd_scan(x, dt, a, b, c, 64)
    assert float(jnp.abs(moved - base)[0, :3].max()) == 0.0
    assert float(jnp.abs(moved - base)[0, 60].max()) > 1e-6
    np.testing.assert_allclose(np.asarray(base), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError):
        ssm.ssd_scan(x, dt, a, b, c, 48)


def test_the_causal_convolution_looks_back_and_never_ahead():
    rng = np.random.RandomState(2)
    x = rng.randn(2, 10, 6).astype(np.float32)
    w = rng.randn(6, 4).astype(np.float32)
    bias = rng.randn(6).astype(np.float32)
    got = np.asarray(ssm.causal_conv1d(jnp.asarray(x), jnp.asarray(w),
                                       jnp.asarray(bias)))
    want = np.zeros_like(x) + bias
    for t in range(10):
        for k in range(4):
            if t - 3 + k >= 0:
                want[:, t] += x[:, t - 3 + k] * w[:, k]
    np.testing.assert_allclose(got, want / (1 + np.exp(-want)), rtol=1e-5,
                               atol=1e-5)


# -- grouped key/value heads in the flash kernels ----------------------------

@pytest.mark.parametrize("heads,kv_heads,seq,causal", [
    (4, 1, 512, True),                  # the cell's share: 4 on 1
    (32, 2, 256, True),                 # the published layer: 32 on 2
    (8, 2, 256, False),
])
def test_grouped_kv_flash_is_the_composition(heads, kv_heads, seq, causal):
    d = 128
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(1, seq, heads * d) * 0.5, jnp.float32)
    k = jnp.asarray(rng.randn(1, seq, kv_heads * d) * 0.5, jnp.float32)
    v = jnp.asarray(rng.randn(1, seq, kv_heads * d), jnp.float32)
    weight = jnp.asarray(rng.randn(1, seq, heads * d), jnp.float32)
    assert attention.flash_rule(seq, seq, d, causal, None, q.dtype,
                                heads // kv_heads)

    def readings(impl):
        def call(q, k, v):
            with attention.attention_impl_scope(impl):
                return attention.attention_heads(q, k, v, heads,
                                                 causal=causal)
        grads = jax.grad(lambda *args: (call(*args) * weight).sum(),
                         argnums=(0, 1, 2))(q, k, v)
        return (call(q, k, v),) + grads

    for name, got, want in zip(("out", "dq", "dk", "dv"),
                               readings("pallas"), readings("xla")):
        assert got.shape == want.shape, name
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5 * float(
                                       jnp.abs(want).max()), err_msg=name)


def test_grouped_heads_of_64_lanes_take_the_composition():
    """Two 64-lane heads share a 128-lane block: a shared key/value head
    has no block of its own, and the rule sends the call to the
    composition (which repeats the head)."""
    assert attention.flash_rule(256, 256, 64)
    assert not attention.flash_rule(256, 256, 64, group=2)
    assert attention.flash_rule(256, 256, 128, group=4)
    rng = np.random.RandomState(6)
    q = jnp.asarray(rng.randn(1, 256, 4 * 64), jnp.float32)
    k = jnp.asarray(rng.randn(1, 256, 2 * 64), jnp.float32)
    with attention.attention_impl_scope("pallas"):
        got = attention.attention_heads(q, k, k, 4, causal=True)
    repeated = jnp.repeat(k.reshape(1, 256, 2, 64), 2, axis=2) \
        .reshape(1, 256, 256)
    with attention.attention_impl_scope("xla"):
        want = attention.attention_heads(q, repeated, repeated, 4,
                                         causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6)
    with pytest.raises(ValueError):
        attention.attention_heads(q, k[..., :64 * 3 // 2], k, 4)


# -- the latent squared-ReLU expert layer -------------------------------------

LAYER = dict(units=32, hidden_size=24, num_experts=16, top_k=5, num_shared=1,
             scale=5.0, activation="relu2", latent_size=16,
             shared_hidden_size=40)
LAYER_CONFIG = {"experts_held": list(range(16)), "num_experts_per_tok": 5,
                "norm_topk_prob": True, "routed_scaling_factor": 5.0,
                "norm_eps": 1e-5}


def _layer(held, seed=5, **over):
    mx.random.seed(seed)
    layer = nn.TokenChoiceMoE(
        held=held, correction_initializer=mx.init.Normal(0.05),
        **dict(LAYER, **over))
    layer.initialize(mx.init.Normal(0.2), ctx=CTX)
    return layer


def test_latent_squared_relu_experts_are_the_dense_loop():
    """Forward and gradients of `token_choice_moe` with ungated experts
    in a latent against jax.grad of the reference's loop over experts."""
    held = (4, 5, 6)
    layer = _layer(held)
    params = _params(layer)
    x = jnp.asarray(np.random.RandomState(6).randn(96, 32), jnp.float32)
    names = ("router_weight", "up_weight", "down_weight")

    def mine(x, latent, *ws):
        return (moe.token_choice_moe(
            x, ws[0], params["router_correction"], ws[1], ws[2], held=held,
            top_k=5, scale=5.0, activation="relu2",
            expert_input=latent)[0] ** 2).sum()

    def theirs(x, latent, *ws):
        eq = MODEL._Equations(dict(params, **dict(zip(names, ws))),
                              dict(LAYER_CONFIG, experts_held=list(held)))
        idx, weight, _, _ = eq.route(x, "")
        y = 0.0
        for local, expert in enumerate(held):
            w_e = (weight * (idx == expert)).sum(-1, keepdims=True)
            y = y + w_e * eq.mlp(latent, ws[1][local], ws[2][local])
        return (y ** 2).sum()

    latent = x @ params["latent_down.weight"].T
    ws = [params[n] for n in names]
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(mine, argnums=(0, 1, 2, 3, 4))(x, latent,
                                                                *ws)
        want = jax.value_and_grad(theirs, argnums=(0, 1, 2, 3, 4))(
            x, latent, *ws)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-3,
                                   atol=1e-4 * float(jnp.abs(w).max()))
    # the whole block (latent projections, shared expert) against the
    # reference's layer
    with autograd.train_mode():
        y = layer(nd.array(np.asarray(x), ctx=CTX))
    whole = MODEL.reference_expert_layer(
        params, x, dict(LAYER_CONFIG, experts_held=list(held)))
    np.testing.assert_allclose(np.asarray(y._jax), np.asarray(whole),
                               rtol=2e-4, atol=2e-5)


def _ragged_rows(fn, *args):
    """Rows of every grouped product's left operand in `fn`'s program,
    whatever implements it: a ragged product, or a call of the grouped
    kernels (ops/grouped.py: the operands after the scalar-prefetched
    maps; rows x lanes is the first of them)."""
    rows = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name.startswith("ragged_dot"):
                rows.append(eqn.invars[0].aval.shape[0])
            elif eqn.primitive.name == "pallas_call" \
                    and eqn.params["name"].startswith("grouped_product"):
                rows.append(next(v.aval.shape[0] for v in eqn.invars
                                 if v.aval.ndim == 2))
            for value in eqn.params.values():
                # a cond's branches come as a tuple
                for sub in (value if isinstance(value, (tuple, list))
                            else (value,)):
                    inner = getattr(sub, "jaxpr", None)
                    if inner is not None:
                        walk(getattr(inner, "jaxpr", inner))

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return rows


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["ragged-dot", "grouped-kernels"])
@pytest.mark.parametrize("top_k,held,buffers", [
    (5, (3, 4), {1024, 2048}), (2, (3, 4, 5, 6), {512, 2048}),
    (2, tuple(range(32)), {2048})])
def test_every_product_runs_over_the_sized_buffers(monkeypatch, kernels,
                                                   top_k, held, buffers):
    """1024 tokens of 128 lanes choose of 32 experts: forward and
    backward, every grouped product's rows are the short buffer's or the
    exact bound's (a layer that holds all its experts has one size) -
    with ``lax.ragged_dot`` as on the CPU, and with the grouped kernels
    in its place as on the chip (their rule holds at this width)."""
    from mxnet_tpu.ops import grouped
    if kernels:
        monkeypatch.setattr(grouped, "grouped_product", grouped._product)
    n, d, f, h = SIZED_TOKENS, 128, 256, len(held)
    assert grouped.product_rule(min(buffers), d, f, jnp.float32,
                                jnp.float32)
    rng = np.random.RandomState(5)
    args = [jnp.asarray(rng.randn(*shape), jnp.float32) * 0.1 for shape in
            ((n, d), (SIZED_EXPERTS, d), (h, d, f), (h, f, d))]

    def loss(x, router, up, down):
        return moe.token_choice_moe(
            x, router, jnp.zeros((SIZED_EXPERTS,)), up, down, held=held,
            top_k=top_k, activation="relu2")[0].sum()

    rows = _ragged_rows(jax.grad(loss, argnums=(0, 2, 3)), *args)
    # 2 products forward, 2 + 2 backward, in each size's branch
    assert set(rows) == buffers and len(rows) >= 6 * len(buffers)


@pytest.mark.parametrize("top_k,held", [(5, (1, 2, 3)), (2, (0, 1, 2, 3))])
def test_the_buffer_is_the_no_drop_bound_under_the_worst_imbalance(top_k,
                                                                   held):
    """Every token on ALL its held experts (a bias that lifts them over
    the rest): tokens * min(k, held) assignments land here, the buffer has
    exactly that many rows - not tokens * k - and nothing is dropped: the
    result is the dense loop's."""
    n, h = 64, len(held)
    layer = _layer(held, top_k=top_k)
    bias = layer.router_correction.data().asnumpy().copy()
    bias[list(held)] = 50.0 + np.arange(h)
    layer.router_correction.set_data(nd.array(bias, ctx=CTX))
    params = _params(layer)
    x = np.random.RandomState(4).randn(n, 32).astype(np.float32)
    with autograd.train_mode():
        y = layer(nd.array(x, ctx=CTX))
    counts = layer.assignments.data().asnumpy()
    most = min(top_k, h)
    assert counts.sum() == n * most
    assert counts.sum() + layer.elsewhere.data().asnumpy()[0] == n * top_k
    want = MODEL.reference_expert_layer(
        params, jnp.asarray(x),
        dict(LAYER_CONFIG, experts_held=list(held),
             num_experts_per_tok=top_k))
    np.testing.assert_allclose(np.asarray(y._jax), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    latent = jnp.asarray(x) @ params["latent_down.weight"].T
    rows = _ragged_rows(
        lambda x, l: moe.token_choice_moe(
            x, params["router_weight"], params["router_correction"],
            params["up_weight"], params["down_weight"], held=held,
            top_k=top_k, scale=5.0, activation="relu2", expert_input=l)[0],
        jnp.asarray(x), latent)
    assert rows and set(rows) == {n * most}


# 1024 tokens choose of 32 experts.  k > H: top-5, 2 held - twice an even
# router's share is 640 -> 1024 rows of the exact bound's 2048; k < H:
# top-2, 4 held - 512 of 2048.
SIZED = {"k>H": dict(top_k=5, held=(3, 4)),
         "k<H": dict(top_k=2, held=(3, 4, 5, 6))}
SIZED_TOKENS, SIZED_EXPERTS = 1024, 32


def _value_and_grads(fn, *args):
    # a new function a call: jit and grad remember a function's trace, and
    # what the caller patches in `moe` is not among its arguments
    return jax.jit(jax.value_and_grad(lambda *a: fn(*a), argnums=tuple(
        range(len(args))), has_aux=True))(*args)


@pytest.mark.parametrize("case", sorted(SIZED))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_short_buffer_gives_what_the_exact_one_gives(monkeypatch, dtype,
                                                        case):
    """Ungated experts in a latent, a load inside twice the even share:
    over the short buffer the result and the gradients by the
    tokens, the latent, the router and both expert matrices are the exact
    buffer's (the same layer held to one path: float32 to 1e-6, bfloat16
    to an ulp of the largest sum), and both are the dense loop's."""
    top_k, held = SIZED[case]["top_k"], SIZED[case]["held"]
    n = SIZED_TOKENS
    short = moe.short_rows(n, top_k, len(held), SIZED_EXPERTS)
    assert short is not None and 2 * short <= n * min(top_k, len(held))
    layer = _layer(held, num_experts=SIZED_EXPERTS, top_k=top_k)
    params = _params(layer)
    names = ("router_weight", "up_weight", "down_weight")
    x = jnp.asarray(np.random.RandomState(6).randn(n, 32), dtype)
    latent = (x.astype(jnp.float32)
              @ params["latent_down.weight"].T).astype(dtype)
    ws = [params[names[0]]] + [params[k].astype(dtype) for k in names[1:]]

    def mine(x, latent, *ws):
        y, counts, _, exact = moe.token_choice_moe(
            x, ws[0], params["router_correction"], ws[1], ws[2], held=held,
            top_k=top_k, scale=5.0, activation="relu2", expert_input=latent)
        return (y.astype(jnp.float32) ** 2).sum(), (y, counts.sum(), exact)

    def theirs(x, latent, *ws):
        eq = MODEL._Equations(
            dict(params, **dict(zip(names, ws))),
            dict(LAYER_CONFIG, experts_held=list(held),
                 num_experts_per_tok=top_k))
        idx, weight, _, _ = eq.route(x, "")
        y = 0.0
        for local, expert in enumerate(held):
            w_e = (weight * (idx == expert)).sum(-1, keepdims=True)
            y = y + w_e * eq.mlp(latent, ws[1][local], ws[2][local])
        return (y ** 2).sum(), y

    (_, (y, here, exact)), grads = _value_and_grads(mine, x, latent, *ws)
    assert 0 < float(here) <= short and float(exact) == 0.0
    monkeypatch.setattr(moe, "_SHORT_OVER_EVEN", 0)     # one path
    assert moe.short_rows(n, top_k, len(held), SIZED_EXPERTS) is None
    (_, (y_exact, _, _)), grads_exact = _value_and_grads(mine, x, latent,
                                                         *ws)
    rel = 1e-6 if dtype == "float32" else 2.0 ** -8
    for name, g, w in zip(("y", "x", "latent") + names, (y,) + grads,
                          (y_exact,) + grads_exact):
        g, w = (np.asarray(v, np.float32) for v in (g, w))
        np.testing.assert_allclose(g, w, rtol=10 * rel,
                                   atol=rel * float(np.abs(w).max()),
                                   err_msg=name)
    with jax.default_matmul_precision("highest"):
        (_, y_loop), grads_loop = _value_and_grads(
            theirs, *(v.astype(jnp.float32) for v in (x, latent, *ws)))
    loose = {"float32": dict(rtol=2e-3, atol=1e-4),
             "bfloat16": dict(rtol=6e-2, atol=3e-2)}[dtype]
    for name, g, w in zip(("y", "x", "latent") + names, (y,) + grads,
                          (y_loop,) + grads_loop):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w), rtol=loose["rtol"],
            atol=loose["atol"] * float(jnp.abs(w).max()), err_msg=name)


@pytest.mark.parametrize("lifted", [0, 2], ids=["even", "all"])
def test_a_load_past_the_short_buffer_runs_the_exact_one_and_is_counted(
        lifted):
    """Top-5 with 2 held of 32 (k > H): both held experts lifted over the
    rest, every token chooses both - 2048 assignments, the whole exact
    bound, against a short buffer of 1024.  The exact buffer runs, nothing
    is dropped, the result is the dense loop's, and
    `moe_exact_buffer_calls` grows by one a call - and stays 0 while the
    load fits."""
    top_k, held = SIZED["k>H"]["top_k"], SIZED["k>H"]["held"]
    n, label = SIZED_TOKENS, "sized-relu2-%d" % lifted
    layer = _layer(held, num_experts=SIZED_EXPERTS, top_k=top_k, layer=label)
    bias = layer.router_correction.data().asnumpy().copy()
    bias[list(held[:lifted])] = 50.0
    layer.router_correction.set_data(nd.array(bias, ctx=CTX))
    x = np.random.RandomState(4).randn(n, 32).astype(np.float32)
    calls = 2
    for _ in range(calls):
        with autograd.train_mode():
            y = layer(nd.array(x, ctx=CTX))
    here = layer.assignments.data().asnumpy().sum() / calls
    assert here + layer.elsewhere.data().asnumpy()[0] / calls == n * top_k
    assert here == n * 2 if lifted else 0 < here <= 1024
    snapshot = telemetry.registry.snapshot()
    assert snapshot["moe_layer_calls{layer=%s}" % label]["value"] == calls
    assert snapshot["moe_exact_buffer_calls{layer=%s}" % label]["value"] \
        == (calls if lifted else 0)
    want = MODEL.reference_expert_layer(
        _params(layer), jnp.asarray(x),
        dict(LAYER_CONFIG, experts_held=list(held),
             num_experts_per_tok=top_k))
    np.testing.assert_allclose(np.asarray(y._jax), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


# -- the add-up tests: every share of a layer sums to the uncut layer ---------

def _set(block, values):
    for name, p in block.collect_params().items():
        if name in values:
            p.set_data(nd.array(np.asarray(values[name]), ctx=CTX))


def test_the_tensor_parallel_ranks_of_a_mamba_mixer_add_up():
    """8 ranks, each with an 8th of the heads and ONE of the 8 B/C/norm
    groups: the sum of their outputs is the uncut reference's
    mixer (the model's n_groups is what makes the split exact)."""
    ranks, heads, hd, n = 8, 16, 4, 8
    config = dict(CONFIG, mamba_num_heads=heads, mamba_head_dim=hd,
                  ssm_state_size=n, n_groups=ranks, chunk_size=8)
    mx.random.seed(7)
    whole = nn.Mamba2Mixer(32, heads, hd, n, ranks, chunk_size=8)
    whole.initialize(mx.init.Normal(0.2), ctx=CTX)
    params = _params(whole)
    x = np.random.RandomState(8).randn(2, 24, 32).astype(np.float32)
    want = np.asarray(MODEL.reference_mixer("M", params, jnp.asarray(x),
                                            config))
    np.testing.assert_allclose(
        np.asarray(whole(nd.array(x, ctx=CTX))._jax), want, rtol=2e-4,
        atol=2e-5)
    inner, per = heads * hd, heads // ranks
    total = 0.0
    for r in range(ranks):
        lanes = np.arange(r * per * hd, (r + 1) * per * hd)
        state = np.arange(r * n, (r + 1) * n)
        head = np.arange(r * per, (r + 1) * per)
        rows = np.concatenate([lanes, inner + lanes, 2 * inner + state,
                               2 * inner + ranks * n + state,
                               2 * inner + 2 * ranks * n + head])
        conv = np.concatenate([lanes, inner + state,
                               inner + ranks * n + state])
        part = nn.Mamba2Mixer(32, per, hd, n, 1, chunk_size=8)
        part.initialize(ctx=CTX)
        _set(part, {
            "in_proj.weight": params["in_proj.weight"][rows],
            "conv_weight": params["conv_weight"][conv],
            "conv_bias": params["conv_bias"][conv],
            "dt_bias": params["dt_bias"][head],
            "A_log": params["A_log"][head], "D": params["D"][head],
            "norm_gamma": params["norm_gamma"][lanes],
            "out_proj.weight": params["out_proj.weight"][:, lanes]})
        total = total + np.asarray(part(nd.array(x, ctx=CTX))._jax)
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


def test_the_tensor_parallel_ranks_of_an_attention_mixer_add_up():
    """32 query heads on 2 key/value heads over 8 ranks: a rank holds 4
    query heads and the ONE key/value head they read (ranks 0-3 head 0,
    ranks 4-7 head 1)."""
    ranks, heads, kv_heads, d = 8, 32, 2, 8
    config = dict(CONFIG, num_attention_heads=heads,
                  num_key_value_heads=kv_heads, head_dim=d)
    mx.random.seed(9)
    whole = nn.GroupedQueryAttention(32, heads, kv_heads, d)
    whole.initialize(mx.init.Normal(0.2), ctx=CTX)
    params = _params(whole)
    x = np.random.RandomState(10).randn(2, 24, 32).astype(np.float32)
    want = np.asarray(MODEL.reference_mixer("*", params, jnp.asarray(x),
                                            config))
    per = heads // ranks
    total = 0.0
    for r in range(ranks):
        lanes = np.arange(r * per * d, (r + 1) * per * d)
        shared = r * per // (heads // kv_heads)
        kv = np.arange(shared * d, (shared + 1) * d)
        part = nn.GroupedQueryAttention(32, per, 1, d)
        part.initialize(ctx=CTX)
        _set(part, {"q_proj.weight": params["q_proj.weight"][lanes],
                    "k_proj.weight": params["k_proj.weight"][kv],
                    "v_proj.weight": params["v_proj.weight"][kv],
                    "o_proj.weight": params["o_proj.weight"][:, lanes]})
        total = total + np.asarray(part(nd.array(x, ctx=CTX))._jax)
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


def test_the_shares_of_an_expert_layer_add_up_to_the_whole():
    """64 expert shares (one expert each of 64) and 8 slices of the shared
    expert's columns: the routed parts through each share's own copy of
    the latent projections, the shared slices, sum to what the UNCUT
    reference gives for the layer; every assignment is counted once."""
    experts, top_k, slices, width = 64, 6, 8, 40
    whole = _layer(None, num_experts=experts, top_k=top_k)
    params = _params(whole)
    config = dict(LAYER_CONFIG, experts_held=list(range(experts)),
                  num_experts_per_tok=top_k)
    x = np.random.RandomState(3).randn(2, 20, 32).astype(np.float32)
    want = np.asarray(MODEL.reference_expert_layer(params, jnp.asarray(x),
                                                   config))
    total, seen = 0.0, 0.0
    for share in range(experts):
        part = _layer((share,), num_experts=experts, top_k=top_k,
                      num_shared=0)
        _set(part, dict(params, **{
            "up_weight": params["up_weight"][share:share + 1],
            "down_weight": params["down_weight"][share:share + 1],
            "assignments": np.zeros(1), "elsewhere": np.zeros(1)}))
        with autograd.train_mode():
            total = total + np.asarray(part(nd.array(x, ctx=CTX))._jax)
        seen += float(part.assignments.data().asnumpy().sum())
    per = width // slices
    for s in range(slices):
        columns = np.arange(s * per, (s + 1) * per)
        piece = nn.SquaredReLUMLP(32, per)
        piece.initialize(ctx=CTX)
        _set(piece, {
            "up_proj.weight": params["shared.up_proj.weight"][columns],
            "down_proj.weight":
                params["shared.down_proj.weight"][:, columns]})
        total = total + np.asarray(piece(nd.array(x, ctx=CTX))._jax)
    np.testing.assert_allclose(total, want, rtol=3e-4, atol=3e-5)
    assert seen == 2 * 20 * top_k          # every assignment, exactly once


def test_the_layers_refuse_shares_they_cannot_hold():
    with pytest.raises(ValueError):
        nn.TokenChoiceMoE(8, 8, 4, 2, activation="gelu")
    with pytest.raises(ValueError):
        nn.Mamba2Mixer(8, 6, 4, 4, num_groups=4)
    with pytest.raises(ValueError):
        nn.GroupedQueryAttention(8, 6, 4, 4)
    with pytest.raises(ValueError):
        moe.held_expert_ffn(jnp.zeros((4, 8)), jnp.zeros((4, 1), jnp.int32),
                            jnp.ones((4, 1)), jnp.zeros((1, 8, 8)),
                            jnp.zeros((1, 8, 8)), (0,), 2, activation="x")


def test_mamba_parameters_take_the_published_initialisation():
    mx.random.seed(12)
    mixer = nn.Mamba2Mixer(32, 64, 4, 8, 8)
    mixer.initialize(mx.init.Normal(0.02), ctx=CTX)
    mixer.cast("bfloat16")
    params = _params(mixer)
    step = np.log1p(np.exp(np.asarray(params["dt_bias"])))    # softplus
    assert 0.001 * 0.99 <= step.min() and step.max() <= 0.1 * 1.01
    a = np.exp(np.asarray(params["A_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0 and a.std() > 1.0
    assert (np.asarray(params["D"]) == 1).all()
    assert abs(np.asarray(params["conv_weight"], np.float32)).max() <= 0.5
    assert (np.asarray(params["conv_bias"], np.float32) == 0).all()
    for name in ("dt_bias", "A_log", "D", "norm_gamma"):
        assert params[name].dtype == np.float32, name
    assert params["in_proj.weight"].dtype == jnp.bfloat16
    assert params["conv_weight"].dtype == jnp.bfloat16


# -- recomputation and the compiled step ---------------------------------------

def _layers(net):
    return list(net.blocks) + list(net.mtp.block.layers)


def _one_sgd_step(recompute, seq, **over):
    net, config = _net("float32", seed=21, **over)
    if not recompute:
        for block in _layers(net):
            block.recompute(False)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 1.0})
    step = trainer.make_compiled_step(net, MODEL.loss_fn())
    ids = nd.array(_ids(2, seq, seed=9), ctx=CTX, dtype="int32")
    loss = step.step((ids,), ids)
    assert step.compiled, step.fallback_reason
    record = programs.find_record("step.step")
    text = record.executable.as_text()
    return {name: np.asarray(p.data()._jax)
            for name, p in net.collect_params().items()}, \
        np.asarray(loss._jax), record.snapshot(), text


def test_a_recomputed_layer_keeps_what_the_scan_made():
    """The marked net's SGD step is the unmarked net's (to the last bits:
    XLA fuses the second run's float32 sums otherwise), and the census
    counts what the recomputed layers keep from the shapes: a scan's
    output and the states it carried into each chunk, an expert layer's
    choices and sort order."""
    rows, seq = 2, 64
    marked, loss_m, kept, text_m = _one_sgd_step(True, seq)
    plain, loss_p, unmarked, text_p = _one_sgd_step(False, seq)
    np.testing.assert_array_equal(loss_m, loss_p)
    for name in marked:
        np.testing.assert_allclose(marked[name], plain[name], rtol=1e-5,
                                   atol=1e-6, err_msg=name)
    c = CONFIG
    heads, hd, n = c["mamba_num_heads"], c["mamba_head_dim"], \
        c["ssm_state_size"]
    chunks = seq // c["chunk_size"]
    scan = (rows * seq * heads * hd + rows * chunks * heads * hd * n) * 4
    tokens, k, held = rows * seq, c["num_experts_per_tok"], 3
    # idx (twice: the benchmark's net also returns its routing), the chosen
    # scores, order, position, sizes
    experts = (2 * tokens * k + tokens * k + 2 * tokens * k + held + 1) * 4
    assert kept["recompute_kept_values"] == 2 * 2 + 3 * 6
    assert kept["recompute_kept_bytes"] == 2 * scan + 3 * experts
    assert unmarked["recompute_kept_values"] == 0
    assert "rematted_computation" in text_m
    assert "rematted_computation" not in text_p


def test_an_eager_call_ignores_the_recompute_mark():
    net, _ = _net("float32")
    net.hybridize(False)
    ids = nd.array(_ids(), ctx=CTX, dtype="int32")
    marked = np.asarray(net(ids)[0]._jax)
    for block in _layers(net):
        assert block._recompute
        block.recompute(False)
    np.testing.assert_array_equal(marked, np.asarray(net(ids)[0]._jax))


def test_the_compiled_step_compiles_once_trains_and_counts():
    net, config = _net("bfloat16", seed=31)
    trainer = gluon.Trainer(net.collect_params(), "adamw",
                            {"learning_rate": 3e-3, "multi_precision": True})
    step = trainer.make_compiled_step(net, MODEL.loss_fn())
    pool = MODEL.batches(config, {"batch": 2, "seq": 32, "pool": 4}, 5)
    record = programs.find_record("step.step")
    compiles0 = record.compiles if record is not None else 0
    losses = []
    for i in range(24):
        (ids,), label = pool[i % len(pool)]
        losses.append(float(step.step(
            (nd.array(ids, ctx=CTX, dtype="int32"),),
            nd.array(label, ctx=CTX, dtype="int32")).asnumpy().mean()))
    assert step.compiled, step.fallback_reason
    assert programs.find_record("step.step").compiles - compiles0 == 1
    assert np.isfinite(losses).all()
    assert np.mean(losses[-8:]) < np.mean(losses[:8])
    assert abs(losses[0] - 1.1 * np.log(CONFIG["vocab_size"])) < 0.3
    params = net.collect_params()
    assert params["blocks.1.moe.up_weight"].dtype == jnp.bfloat16
    assert params["blocks.1.moe.latent_down.weight"].dtype == jnp.bfloat16
    assert params["blocks.1.moe.router_weight"].dtype == np.float32
    assert params["blocks.0.ssm.A_log"].dtype == np.float32
    assert params["blocks.0.ssm.in_proj.weight"].dtype == jnp.bfloat16
    # the counters advanced inside the step: 24 steps x 64 tokens x top-5
    snapshot = telemetry.registry.snapshot()
    held = 0.0
    for layer in ("1", "4", "mtp"):
        here = sum(snapshot["moe_assignments{expert=%d,layer=%s}"
                            % (e, layer)]["value"] for e in (2, 3, 4))
        away = snapshot["moe_assignments_elsewhere{layer=%s}"
                        % layer]["value"]
        assert here + away == 24 * 64 * 5
        held += here
    assert 0 < held < 3 * 24 * 64 * 5       # some here, most elsewhere


def test_ops_and_bytes_of_the_published_configuration():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "nemotron_3_super.json")) as f:
        config = json.load(f)
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "clm-s8192-b1.json")) as f:
        traffic = json.load(f)
    ops = MODEL.ops_and_bytes(config, traffic)
    tokens = 8192
    assert round(ops["n_params"] / 1e6, 1) == 607.0
    assert round(ops["bytes"] / 2 / 1e9, 2) == 8.50
    assert round(ops["forward_flops"] / tokens / 1e6, 1) == 729.0
    assert ops["flops"] == 3 * ops["forward_flops"]
    detail, forward = ops["detail"], ops["detail"]["forward"]
    assert detail["expected_assignments_per_expert"] == 352
    # a scan a token and a layer: C B^T a group, the masked matrix times
    # x, the chunk's state and the state's part, a head
    assert forward["ssm_scan"] == 5 * tokens * 2 * (
        128 * 128 + 128 * 64 * 16 + 2 * 128 * 64 * 16)
    # x, z and y of 1024 lanes, B and C of 128, dt of 16, in bf16
    assert detail["ssm_scan_bytes"] == 5 * tokens * 2 * (3 * 1024 + 256 + 16)
    assert forward["attention_core"] == 2 * 1 * 4 * 256 * 8192 * 8192
    assert round(forward["lm_head"] / ops["forward_flops"], 2) == 0.37
    assert detail["held_expert_weight_bytes"] == 6 * 8 * 2 * 1024 * 2688 * 2
