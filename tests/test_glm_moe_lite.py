"""GLM-4.7-Flash's decoder (gluon.model_zoo.glm_moe_lite) against the
plain float32 reference the benchmark keeps
(benchmark/models/glm_4_7_flash.py), at a small size on the CPU: logits of
both heads, loss and gradients; the expert layer's shares adding up to the
whole; no dropped token under a skewed router; recomputation; the compiled
step."""
import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, programs, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import moe

import decoder_checks as checks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = checks.model_file("glm_4_7_flash")

# hidden 64, 4 heads of 24 + 8 | 32, 8 experts top-2 of which 2 are held,
# 1 dense + 2 expert blocks + the MTP module, 96 ids
CONFIG = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 48,
    "norm_topk_prob": True, "num_attention_heads": 4,
    "n_routed_experts": 2, "n_routed_experts_published": 8,
    "experts_held": [2, 3], "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 2,
    "first_k_dense_replace": 1, "num_hidden_layers": 3,
    "num_nextn_predict_layers": 1, "rms_norm_eps": 1e-5,
    "rope_theta": 10000, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 24, "qk_rope_head_dim": 8, "v_head_dim": 32,
    "vocab_size": 96, "router_correction_std": 0.3, "initializer_std": 0.05,
    "dtype": "float32",
}
CTX = mx.cpu()


def _net(dtype="float32", seed=11, **over):
    config = dict(CONFIG, dtype=dtype, **over)
    net = MODEL.build(config, CTX, seed)
    return net, config


def _ids(rows=2, seq=32, seed=0):
    return np.random.RandomState(seed).randint(
        0, CONFIG["vocab_size"], (rows, seq)).astype(np.int32)


def _params(net):
    return {name: p.data()._jax for name, p in net.collect_params().items()}


@functools.lru_cache(maxsize=None)
def _reading_net(dtype, seed):
    """`_net(dtype, seed)` and its parameters as built, once a module: the
    tests that hold the reference to it change neither."""
    net, config = _net(dtype, seed=seed)
    return net, config, _params(net)


# -- logits, loss and gradients against the reference ------------------------

# float32: the same arithmetic in another order of summation.
# bfloat16: every product's operands carry 8 bits (2^-9 relative), through
# 4 blocks of ~8 products; the reference follows the net's router choices
# (near-ties flip under a bf16 residual: benchmark/models/glm_4_7_flash.py,
# `reference`) and holds them to a gap instead, so no discontinuity hides
# in these limits.  float8_e4m3 operands (3 bits) and a skipped term must
# fail at least one of them (asserted below).  Read on these seeds: bf16
# logits 0.7-2.2e-2, gradients 1.8-2.3e-2.
TOLERANCE = {"float32": {"logits": 2e-5, "loss": 1e-5, "grads": 2e-4,
                         "gap": 1e-5},
             "bfloat16": {"logits": 3e-2, "loss": 3e-3, "grads": 6e-2,
                          "gap": 2e-2}}


@pytest.mark.parametrize("dtype,seed", [("float32", 11), ("bfloat16", 11),
                                        ("bfloat16", 14)])
def test_logits_loss_and_gradients_match_the_reference(dtype, seed):
    """Seeds 11 and 14 are ones on which the bf16 net's routers pick
    another expert than the float32 reference's in 1-2 % of the choices:
    followed, and inside the gap.  They are also what
    ``base.RECOMPUTE_KEEP`` is for: the net's blocks are recomputed in
    the backward pass, and before the router's choice was kept from the
    forward pass the second run took seed 14's near-tie the other way -
    gradients 37 % off (2 % with the blocks unmarked) under logits that
    agreed to 0.7 %."""
    net, config, params = _reading_net(dtype, seed)
    ids = _ids()
    got = checks.net_readings(net, ids, MODEL.loss_fn(), heads=2)
    want, gap = checks.reference_readings(MODEL, params, ids, config,
                                          given=got[1])
    logits, loss, kinds = checks.errors(got, want)
    tol = TOLERANCE[dtype]
    assert gap <= tol["gap"], gap
    assert logits <= tol["logits"], logits
    assert loss <= tol["loss"], loss
    assert len(kinds) >= 20            # every parameter kind has a gradient
    worst = max(kinds, key=kinds.get)
    assert kinds[worst] <= tol["grads"], (worst, kinds[worst])
    own, _ = checks.reference_readings(MODEL, params, ids, config)
    differ = (np.sort(got[1], -1) != np.sort(own[1], -1)).any(-1).mean()
    assert (differ == 0) if dtype == "float32" else (0 < differ < 0.05)


def test_the_limits_refuse_float8_and_a_skipped_term():
    """What the bfloat16 limits are FOR.  Held to the float32 reference as
    a net is (its choices followed): the reference with float8_e4m3
    operands, and with one term left out - the selection bias, the shared
    expert, the routed scaling - must each fail at least one of them."""
    _, config, params = _reading_net("float32", 11)
    ids = _ids()
    tol = TOLERANCE["bfloat16"]

    def fails(other_params=None, operand=None, **other_config):
        reading, _ = checks.reference_readings(
            MODEL, other_params or params, ids, dict(config, **other_config),
            operand)
        want, gap = checks.reference_readings(MODEL, params, ids, config,
                                              given=reading[1])
        return checks.fails(tol, checks.errors(reading, want), gap)

    assert not fails()
    assert fails(operand=jnp.float8_e4m3fn)
    assert fails({k: (jnp.zeros_like(v) if k.endswith("router_correction") else v)
                  for k, v in params.items()})
    assert fails({k: (jnp.zeros_like(v) if "shared.down_proj" in k else v)
                  for k, v in params.items()})
    assert fails(routed_scaling_factor=1.0)


def test_the_reference_follows_near_ties_and_refuses_a_wrong_router():
    _, config, params = _reading_net("float32", 11)
    config = dict(config, check_routing_gap=0.02)
    ids = _ids()
    forward = checks.jitted(MODEL._forward, config)
    reference = checks.jitted(MODEL.reference, config)
    _, _, own, _, _ = forward(params, ids)
    slot = MODEL.check_inputs(config, {"batch": 2, "seq": 32}, 0)[1]
    assert slot.shape == own.shape and (slot == -1).all()
    free = np.asarray(reference(params, ids, slot))
    same = np.asarray(reference(params, ids, np.asarray(own)))
    np.testing.assert_array_equal(free, same)
    # an expert far from the top-k at one token of the first expert layer:
    # that token's logits (and the row's later MTP positions) are refused
    wrong = np.array(own)
    taken = set(wrong[0, 1, 5].tolist())
    wrong[0, 1, 5, 0] = next(e for e in range(8) if e not in taken)
    out = np.asarray(reference(params, ids, wrong))
    _, _, _, _, gap = forward(params, ids, wrong)
    if float(np.asarray(gap).max()) > 0.02:
        assert np.isnan(out[:, 1, 5]).all()
        assert np.isfinite(out[:, 0]).all()
    else:                               # a near-tie after all: followed
        assert np.isfinite(out).all()


# -- the expert layer: shares add up, nothing is dropped ---------------------

def _layer(held, seed=5, units=32, hidden=24, experts=8, top_k=2, **kwargs):
    mx.random.seed(seed)
    layer = nn.TokenChoiceMoE(units, hidden, experts, top_k, held=held,
                              num_shared=1, scale=1.8,
                              correction_initializer=mx.init.Normal(0.05), **kwargs)
    layer.initialize(mx.init.Normal(0.2), ctx=CTX)
    return layer


def _layer_params(layer):
    return {name: p.data()._jax
            for name, p in layer.collect_params().items()}


LAYER_CONFIG = {"experts_held": list(range(8)), "num_experts_per_tok": 2,
                "norm_topk_prob": True, "routed_scaling_factor": 1.8,
                "num_attention_heads": 1, "qk_nope_head_dim": 0,
                "qk_rope_head_dim": 0, "rms_norm_eps": 1e-5}


def test_the_shares_of_an_expert_layer_add_up_to_the_whole():
    """Over every share (two experts each of eight), the parts of one
    layer's result, the shared expert counted once, sum to what the UNCUT
    reference gives for the whole layer."""
    whole = _layer(None)                    # holds all eight
    params = _layer_params(whole)
    x = np.random.RandomState(3).randn(2, 40, 32).astype(np.float32)
    want = np.asarray(MODEL.reference_expert_layer(
        params, jnp.asarray(x), LAYER_CONFIG))
    shared = np.asarray(whole.shared(nd.array(x, ctx=CTX))._jax)
    total, seen = shared.copy(), 0.0
    for share in range(4):
        held = (2 * share, 2 * share + 1)
        part = _layer(held)
        for name, p in part.collect_params().items():
            value = params[name]
            if name in ("gate_up_weight", "down_weight"):
                value = value[jnp.asarray(held)]
            elif name in ("assignments", "elsewhere"):
                continue
            p.set_data(nd.array(np.asarray(value), ctx=CTX))
        with autograd.train_mode():
            y = part(nd.array(x, ctx=CTX))
        total += np.asarray(y._jax) - shared
        seen += float(part.assignments.data().asnumpy().sum())
        # a share's own part is what the reference gives for that share
        own = np.asarray(MODEL.reference_expert_layer(
            {**params, "gate_up_weight": params["gate_up_weight"][
                jnp.asarray(held)], "down_weight": params["down_weight"][
                jnp.asarray(held)]}, jnp.asarray(x), LAYER_CONFIG,
            held=list(held)))
        np.testing.assert_allclose(np.asarray(y._jax), own, rtol=2e-4,
                                   atol=2e-5)
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)
    assert seen == 2 * 40 * 2               # every assignment, exactly once


@pytest.mark.parametrize("tokens", [64, 1024])
def test_no_token_is_dropped_when_one_held_expert_takes_every_assignment(
        tokens):
    """A bias that sends every token to held expert 2: it receives all
    `tokens` assignments - four times an even router's share - and the
    result is the reference's."""
    layer = _layer((2, 3))
    bias = layer.router_correction.data().asnumpy().copy()
    bias[2] = 50.0
    layer.router_correction.set_data(nd.array(bias, ctx=CTX))
    x = np.random.RandomState(4).randn(tokens, 32).astype(np.float32)
    with autograd.train_mode():
        y = layer(nd.array(x, ctx=CTX))
    counts = layer.assignments.data().asnumpy()
    assert counts[0] == tokens
    assert counts.sum() + layer.elsewhere.data().asnumpy()[0] == 2 * tokens
    want = MODEL.reference_expert_layer(
        _layer_params(layer), jnp.asarray(x),
        dict(LAYER_CONFIG, experts_held=[2, 3]))
    np.testing.assert_allclose(np.asarray(y._jax), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_the_grouped_products_differentiate_like_the_dense_loop():
    """parallel.moe.token_choice_moe's gradients (gathers written as each
    other's transposes, the ragged products, the cond) against jax.grad
    of the reference's loop over experts."""
    layer = _layer((4, 5, 6))
    params = _layer_params(layer)
    x = jnp.asarray(np.random.RandomState(6).randn(96, 32), jnp.float32)
    names = ("router_weight", "gate_up_weight", "down_weight")

    def mine(x, *ws):
        return (moe.token_choice_moe(
            x, ws[0], params["router_correction"], ws[1], ws[2], held=(4, 5, 6),
            top_k=2, scale=1.8)[0] ** 2).sum()

    def theirs(x, *ws):
        return (MODEL.reference_expert_layer(
            dict(params, **dict(zip(names, ws))), x,
            dict(LAYER_CONFIG, experts_held=[4, 5, 6]), shared=False)
            ** 2).sum()

    ws = [params[n] for n in names]
    # each side under one jax.jit: eagerly every primitive compiles alone
    got = jax.jit(jax.grad(mine, argnums=(0, 1, 2, 3)))(x, *ws)
    want = jax.jit(jax.grad(theirs, argnums=(0, 1, 2, 3)))(x, *ws)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-3,
                                   atol=1e-4 * float(jnp.abs(w).max()))


# -- the buffer sized to the load ---------------------------------------------

# 1024 tokens choose of 32 experts.  k < H: top-2, 4 held - twice an even
# router's share is 512 rows of the exact bound's 2048; k > H: top-5, 2
# held - 640 -> 1024 of 2048.
SIZED = {"k<H": dict(top_k=2, held=(3, 4, 5, 6)),
         "k>H": dict(top_k=5, held=(3, 4))}
SIZED_TOKENS, SIZED_EXPERTS = 1024, 32


def _value_and_grads(fn, *args):
    # a new function a call: jit and grad remember a function's trace, and
    # what the caller patches in `moe` is not among its arguments
    return jax.jit(jax.value_and_grad(lambda *a: fn(*a), argnums=tuple(
        range(len(args))), has_aux=True))(*args)


def _close(got, want, dtype, what):
    """float32: the same sums in another order; bfloat16: an ulp of the
    largest sum."""
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    scale = float(np.abs(want).max())
    rel = 1e-6 if dtype == "float32" else 2.0 ** -8
    np.testing.assert_allclose(got, want, rtol=10 * rel, atol=rel * scale,
                               err_msg=what)


@pytest.mark.parametrize("case", sorted(SIZED))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_short_buffer_gives_what_the_exact_one_gives(monkeypatch, dtype,
                                                        case):
    """A load inside twice the even share runs over the short buffer:
    the result and the gradients by the tokens, the router and both
    expert matrices are the exact buffer's (the same layer held to one
    path), and both are the dense loop's."""
    top_k, held = SIZED[case]["top_k"], SIZED[case]["held"]
    n, most = SIZED_TOKENS, min(top_k, len(held))
    short = moe.short_rows(n, top_k, len(held), SIZED_EXPERTS)
    assert short is not None and 2 * short <= n * most
    layer = _layer(held, experts=SIZED_EXPERTS, top_k=top_k)
    params = _layer_params(layer)
    names = ("router_weight", "gate_up_weight", "down_weight")
    cast = {"router_weight": jnp.float32}
    x = jnp.asarray(np.random.RandomState(6).randn(n, 32), dtype)
    ws = [params[k].astype(cast.get(k, dtype)) for k in names]

    def mine(x, *ws):
        y, counts, _, exact = moe.token_choice_moe(
            x, ws[0], params["router_correction"], ws[1], ws[2], held=held,
            top_k=top_k, scale=1.8)
        return (y.astype(jnp.float32) ** 2).sum(), (y, counts.sum(), exact)

    def theirs(x, *ws):
        y = MODEL.reference_expert_layer(
            dict(params, **dict(zip(names, ws))), x,
            dict(LAYER_CONFIG, experts_held=list(held),
                 num_experts_per_tok=top_k), shared=False)
        return (y ** 2).sum(), y

    (_, (y, here, exact)), grads = _value_and_grads(mine, x, *ws)
    assert 0 < float(here) <= short and float(exact) == 0.0
    monkeypatch.setattr(moe, "_SHORT_OVER_EVEN", 0)     # one path
    assert moe.short_rows(n, top_k, len(held), SIZED_EXPERTS) is None
    (_, (y_exact, _, ran)), grads_exact = _value_and_grads(mine, x, *ws)
    assert float(ran) == 0.0                # no second size: nothing to count
    _close(y, y_exact, dtype, "y")
    for name, g, w in zip(("x",) + names, grads, grads_exact):
        _close(g, w, dtype, name)
    (_, y_loop), grads_loop = _value_and_grads(
        theirs, *(v.astype(jnp.float32) for v in (x, *ws)))
    loose = {"float32": dict(rtol=2e-3, atol=1e-4),
             "bfloat16": dict(rtol=6e-2, atol=3e-2)}[dtype]
    for name, g, w in zip(("y", "x") + names, (y,) + grads,
                          (y_loop,) + grads_loop):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w), rtol=loose["rtol"],
            atol=loose["atol"] * float(jnp.abs(w).max()), err_msg=name)


def test_the_short_size_follows_from_the_shapes():
    """`short_rows` at the two cells' layers: GLM (8192 tokens top-4, 8
    of 64) 8192 rows of 32768; Nemotron (top-22, 8 of 512) 5632 of 65536.
    One size where the short one would not halve the bound."""
    assert moe.short_rows(8192, 4, 8, 64) == 8192
    assert moe.short_rows(8192, 22, 8, 512) == 5632
    assert moe.short_rows(64, 2, 3, 8) is None          # 512 rows of 128
    assert moe.short_rows(8192, 4, 64, 64) is None      # holds them all
    assert moe.short_rows(1024, 2, 1, 8) == 512         # half: a branch


@pytest.mark.parametrize("lifted", [0, 1, 4], ids=["even", "one", "all"])
def test_a_load_past_the_short_buffer_runs_the_exact_one_and_is_counted(
        lifted):
    """A bias that lifts `lifted` of the 4 held experts over the rest
    sends every token there: 1024 or 2048 assignments against a short
    buffer of 512.  The exact buffer runs, nothing is dropped, the result
    is the dense loop's, and `moe_exact_buffer_calls` grows by one a call
    - and stays 0 while the load fits (`moe_layer_calls` counts both)."""
    top_k, held = SIZED["k<H"]["top_k"], SIZED["k<H"]["held"]
    n, label = SIZED_TOKENS, "sized-%d" % lifted
    layer = _layer(held, experts=SIZED_EXPERTS, top_k=top_k, layer=label)
    bias = layer.router_correction.data().asnumpy().copy()
    bias[list(held[:lifted])] = 50.0
    layer.router_correction.set_data(nd.array(bias, ctx=CTX))
    x = np.random.RandomState(4).randn(n, 32).astype(np.float32)
    calls = 3
    for _ in range(calls):
        with autograd.train_mode():
            y = layer(nd.array(x, ctx=CTX))
    layer(nd.array(x, ctx=CTX))             # not training: not counted
    here = layer.assignments.data().asnumpy().sum() / calls
    assert here + layer.elsewhere.data().asnumpy()[0] / calls == n * top_k
    assert here >= n * min(lifted, top_k)
    assert (here > 512) == bool(lifted)
    snapshot = telemetry.registry.snapshot()
    assert snapshot["moe_layer_calls{layer=%s}" % label]["value"] == calls
    assert snapshot["moe_exact_buffer_calls{layer=%s}" % label]["value"] \
        == (calls if lifted else 0)
    want = MODEL.reference_expert_layer(
        _layer_params(layer), jnp.asarray(x),
        dict(LAYER_CONFIG, experts_held=list(held)))
    np.testing.assert_allclose(np.asarray(y._jax), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_the_layer_refuses_a_share_it_cannot_hold():
    with pytest.raises(ValueError):
        nn.TokenChoiceMoE(8, 8, 4, 2, held=(1, 1))
    with pytest.raises(ValueError):
        nn.TokenChoiceMoE(8, 8, 4, 2, held=(4,))


# -- rotary, RMSNorm, SwiGLU blocks ------------------------------------------

def test_rotary_turns_the_last_lanes_of_every_head_and_keeps_the_rest():
    heads, d, r = 3, 16, 8
    x = np.random.RandomState(7).randn(2, 5, heads * d).astype(np.float32)
    out = np.asarray(nn.RotaryEmbedding(heads, r, theta=100.0)(
        nd.array(x, ctx=CTX))._jax).reshape(2, 5, heads, d)
    xh = x.reshape(2, 5, heads, d)
    np.testing.assert_array_equal(out[..., :d - r], xh[..., :d - r])
    inv = 100.0 ** (-np.arange(r // 2) * 2.0 / r)
    angle = np.arange(5)[:, None] * inv
    a, b = xh[..., d - r:d - r // 2], xh[..., d - r // 2:]
    cos, sin = np.cos(angle)[None, :, None], np.sin(angle)[None, :, None]
    np.testing.assert_allclose(out[..., d - r:d - r // 2],
                               a * cos - b * sin, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(out[..., d - r // 2:], b * cos + a * sin,
                               rtol=1e-5, atol=1e-6)
    # position 0 is not turned; norms are kept
    np.testing.assert_allclose(out[:, 0], xh[:, 0], rtol=1e-6)
    np.testing.assert_allclose(np.linalg.norm(out, axis=-1),
                               np.linalg.norm(xh, axis=-1), rtol=1e-5)


def test_rms_norm_and_swiglu_blocks():
    x = np.random.RandomState(8).randn(4, 6, 16).astype(np.float32)
    norm = nn.RMSNorm(16, epsilon=1e-5)
    norm.initialize(ctx=CTX)
    got = np.asarray(norm(nd.array(x, ctx=CTX))._jax)
    np.testing.assert_allclose(
        got, x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5),
        rtol=1e-5)
    mlp = nn.SwiGLU(16, 24)
    mlp.initialize(mx.init.Normal(0.3), ctx=CTX)
    w = mlp.gate_up_proj.weight.data().asnumpy()
    h = x @ w.T
    gate, up = h[..., :24], h[..., 24:]
    want = (gate / (1 + np.exp(-gate)) * up) \
        @ mlp.down_proj.weight.data().asnumpy().T
    np.testing.assert_allclose(np.asarray(mlp(nd.array(x, ctx=CTX))._jax),
                               want, rtol=2e-4, atol=1e-5)


# -- recomputation ------------------------------------------------------------

def _one_sgd_step(recompute, seq, prepare=None, **over):
    net, config = _net("float32", seed=21, **over)
    if prepare is not None:
        prepare(net)
    if not recompute:
        for block in list(net.blocks) + [net.mtp.block]:
            block.recompute(False)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 1.0})
    step = trainer.make_compiled_step(net, MODEL.loss_fn())
    ids = nd.array(_ids(2, seq, seed=9), ctx=CTX, dtype="int32")
    loss = step.step((ids,), ids)
    assert step.compiled, step.fallback_reason
    record = programs.find_record("step.step")
    temp = record.executable.memory_analysis().temp_size_in_bytes
    text = record.executable.as_text()
    return {name: np.asarray(p.data()._jax)
            for name, p in net.collect_params().items()}, \
        np.asarray(loss._jax), temp, text


def test_recomputation_changes_no_gradient_and_frees_memory():
    """The marked net against the same net unmarked, one SGD step at
    learning rate 1 (new weight = weight - gradient): every parameter
    bit for bit, in float32; the step program's temp bytes fall and its
    text carries the recomputed forward."""
    marked, loss_m, temp_m, text_m = _one_sgd_step(True, 128)
    plain, loss_p, temp_p, text_p = _one_sgd_step(False, 128)
    np.testing.assert_array_equal(loss_m, loss_p)
    for name in marked:
        np.testing.assert_array_equal(marked[name], plain[name],
                                      err_msg=name)
    assert temp_m < temp_p, (temp_m, temp_p)
    assert "rematted_computation" in text_m
    assert "rematted_computation" not in text_p


def test_a_recomputed_block_keeps_what_its_kernels_made():
    """Heads of 64 lanes at T = 256 with the flash kernels forced
    (interpreted here): the marked net's SGD step equals the unmarked
    net's bit for bit, and the census counts what the recomputed blocks
    keep from the shapes (that the second run holds no forward kernel is
    tests/test_aot_compile.py's: interpreted kernels have no name)."""
    from mxnet_tpu.ops.attention import attention_impl_scope
    heads = {"qk_nope_head_dim": 48, "qk_rope_head_dim": 16,
             "v_head_dim": 64}
    rows, seq, h, d = 2, 256, CONFIG["num_attention_heads"], 64
    with attention_impl_scope("pallas"):
        marked, loss_m, _, text_m = _one_sgd_step(True, seq, **heads)
        kept = programs.find_record("step.step").snapshot()
        summary = programs.program_summary()
        plain, loss_p, _, text_p = _one_sgd_step(False, seq, **heads)
        unmarked = programs.find_record("step.step").snapshot()
    np.testing.assert_array_equal(loss_m, loss_p)
    for name in marked:
        np.testing.assert_array_equal(marked[name], plain[name],
                                      err_msg=name)
    # every MLA block (1 dense + 2 expert + the MTP module's) keeps the
    # kernel's out and logsumexp; every expert layer the router's idx
    # (twice: the benchmark's net also returns its routing), the chosen
    # scores and the dispatch's order, position and sizes
    n, k, held = rows * seq, CONFIG["num_experts_per_tok"], 2
    attention = 4 * (rows * seq * h * d + rows * h * seq) * 4
    experts = 3 * (2 * n * k + n * k + 2 * n * k + held + 1) * 4
    assert kept["recompute_kept_values"] == 4 * 2 + 3 * 6
    assert kept["recompute_kept_bytes"] == attention + experts
    assert summary["recompute_kept_bytes"] >= attention + experts
    assert unmarked["recompute_kept_values"] == 0
    assert unmarked["recompute_kept_bytes"] == 0
    assert "rematted_computation" in text_m
    assert "rematted_computation" not in text_p


@pytest.mark.parametrize("buffer", ["short", "exact"])
def test_a_recomputed_block_takes_the_buffer_its_forward_took(buffer):
    """Expert layers with two sizes (1024 tokens, top-2 of 16, 2 held: 512
    rows of 2048): the marked net's SGD step equals the unmarked net's bit
    for bit in float32, whether the load fits the short buffer or - the
    held experts lifted over the rest - takes the exact one; the recomputed
    run and the backward pass read the forward's kept sizes, and the
    counters say which buffer every call of the three layers took."""
    def prepare(net):
        # the drawn corrections decide a fresh router's choices: none,
        # the scores alone choose; the held experts' lifted, every token
        # chooses both
        for name, p in net.collect_params().items():
            if name.endswith("router_correction"):
                bias = np.zeros(p.shape, np.float32)
                if buffer == "exact":
                    bias[CONFIG["experts_held"]] = 50.0
                p.set_data(nd.array(bias, ctx=CTX))

    over = {"n_routed_experts_published": 16}
    assert moe.short_rows(1024, 2, 2, 16) == 512
    marked, loss_m, _, text_m = _one_sgd_step(True, 512, prepare, **over)
    plain, loss_p, _, text_p = _one_sgd_step(False, 512, prepare, **over)
    loads = [v.sum() for name, v in marked.items()
             if name.endswith("moe.assignments")]
    assert len(loads) == 3 and all(
        0 < v <= 512 if buffer == "short" else v == 2048 for v in loads)
    np.testing.assert_array_equal(loss_m, loss_p)
    for name in marked:
        np.testing.assert_array_equal(marked[name], plain[name],
                                      err_msg=name)
    calls = [v for name, v in marked.items() if name.endswith("buffer_calls")]
    assert len(calls) == 3
    for exact, every in calls:
        assert every == 1 and exact == (buffer == "exact")
    assert "rematted_computation" in text_m
    assert "rematted_computation" not in text_p
    assert " conditional(" in text_m and " conditional(" in text_p


def test_an_eager_call_ignores_the_recompute_mark():
    net, _ = _net("float32")
    net.hybridize(False)
    ids = nd.array(_ids(), ctx=CTX, dtype="int32")
    marked = np.asarray(net(ids)[0]._jax)
    for block in list(net.blocks) + [net.mtp.block]:
        assert block._recompute
        block.recompute(False)
    np.testing.assert_array_equal(marked, np.asarray(net(ids)[0]._jax))


def test_recompute_carries_aux_state_and_random_keys_out_of_the_checkpoint():
    """A marked block with BatchNorm (aux state written inside) and
    Dropout (a key drawn inside) through a compiled step: the running
    statistics move, nothing leaks a tracer, and the loss is finite."""
    class Body(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.dense = nn.Dense(8, in_units=8)
            self.bn = nn.BatchNorm(in_channels=8)
            self.drop = nn.Dropout(0.5)

        def forward(self, x):
            return self.drop(self.bn(self.dense(x)))

    class Net(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.body = Body().recompute()
            self.out = nn.Dense(1, in_units=8)

        def forward(self, x):
            return self.out(self.body(x))

    mx.random.seed(2)
    net = Net()
    net.initialize(ctx=CTX)
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    step = trainer.make_compiled_step(net, gluon.loss.L2Loss())
    x = nd.array(np.random.RandomState(1).randn(16, 8) + 3.0, ctx=CTX)
    y = nd.array(np.zeros((16, 1), np.float32), ctx=CTX)
    before = net.body.bn.running_mean.data().asnumpy().copy()
    losses = [float(step.step((x,), y).asnumpy().mean()) for _ in range(3)]
    assert step.compiled, step.fallback_reason
    assert np.isfinite(losses).all()
    assert not np.allclose(net.body.bn.running_mean.data().asnumpy(), before)


# -- the compiled step at the tiny size ---------------------------------------

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
    assert abs(losses[0] - 1.3 * np.log(CONFIG["vocab_size"])) < 0.3
    # state: bf16 weights with float32 masters; the router stays float32
    params = net.collect_params()
    assert params["blocks.1.moe.gate_up_weight"].dtype == jnp.bfloat16
    assert params["blocks.1.moe.router_weight"].dtype == np.float32
    assert params["blocks.1.moe.assignments"].dtype == np.float32
    for p in params.values():
        assert {d.platform for d in p.data()._jax.devices()} == {"cpu"}
    # the counters advanced inside the step: 24 steps x 64 tokens x top-2
    snapshot = telemetry.registry.snapshot()
    held = 0.0
    for layer in ("1", "2", "mtp"):
        here = sum(snapshot["moe_assignments{expert=%d,layer=%s}"
                            % (e, layer)]["value"] for e in (2, 3))
        away = snapshot["moe_assignments_elsewhere{layer=%s}"
                        % layer]["value"]
        assert here + away == 24 * 64 * 2
        held += here
    assert 0 < held < 3 * 24 * 64 * 2       # some here, most elsewhere


def test_parameter_gradient_buffers_are_allocated_on_first_use():
    """A compiled step never reads Parameter.grad(): the buffers (as large
    as the parameters) are not allocated until the eager path asks."""
    net, _ = _net("float32")
    weight = net.collect_params()["lm_head.proj.weight"]
    assert weight.data()._grad is None
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    step = trainer.make_compiled_step(net, MODEL.loss_fn())
    ids = nd.array(_ids(), ctx=CTX, dtype="int32")
    step.step((ids,), ids)
    assert step.compiled and weight.data()._grad is None
    assert weight.grad().shape == weight.shape          # asked: made, zero
    assert float(abs(weight.grad()).sum().asscalar()) == 0.0
    with autograd.record():
        loss = MODEL.loss_fn()(net(ids)[:2], ids)
    loss.backward()
    assert float(abs(weight.grad()).sum().asscalar()) > 0.0


def test_ops_and_bytes_of_the_published_configuration():
    import json
    with open(os.path.join(REPO, "benchmark", "configs",
                           "glm_4_7_flash.json")) as f:
        config = json.load(f)
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "clm-s4096-b2.json")) as f:
        traffic = json.load(f)
    ops = MODEL.ops_and_bytes(config, traffic)
    assert round(ops["n_params"] / 1e6, 1) == 706.5
    assert round(ops["forward_flops"] / 8192 / 1e6, 1) == 956.8
    assert ops["flops"] == 3 * ops["forward_flops"]
    assert ops["detail"]["expected_assignments_per_expert"] == 512
    assert round(ops["bytes"] / 2 / 1e9, 2) == 9.89
    forward = ops["detail"]["forward"]
    assert round(forward["mla_core"] / 8192 / 1e6 / 6, 1) == 41.9
    assert round(forward["moe_routed"] / forward["moe_shared"], 3) == 0.5
