"""Laguna's decoder (gluon.model_zoo.laguna) against the plain float32
reference the benchmark keeps (benchmark/models/laguna_xs_2.py), at a small
size on the CPU: logits, loss and gradients by parameter kind; the windowed
flash kernels (forward, fused backward, two-kernel backward) in interpret
mode against the banded composition; `_visits` with a window against a
brute count of the visible pairs; YaRN's frequencies at the published
numbers; the partial rotary part; the shares of a sparse layer adding up to
the whole; the compiled step."""
import functools
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, programs, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo import laguna
from mxnet_tpu.ops import attention
from mxnet_tpu.ops import nn as ops_nn

import decoder_checks as checks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = checks.model_file("laguna_xs_2")
PUBLISHED_ROPE = laguna.ROPE_XS_2

# hidden 48; heads of 16 lanes, 6 (full) and 8 (sliding) query heads on 2
# key/value heads: groups of 3 and 4; window 8; YaRN over 8 of the 16
# lanes on the full layers (original context 16, so the ramp lies inside
# the 4 pairs); dense MLP 96; 16 experts of width 24, top-4, 4 held, one
# shared; layers dense/full, then sliding x 3 and full, all sparse
CONFIG = {
    "hidden_size": 48, "intermediate_size": 96, "num_hidden_layers": 5,
    "num_key_value_heads": 2, "head_dim": 16, "rms_norm_eps": 1e-6,
    "num_experts_published": 16, "experts_held": [4, 5, 6, 7],
    "num_experts_per_tok": 4, "moe_intermediate_size": 24,
    "shared_expert_intermediate_size": 24, "moe_routed_scaling_factor": 2.5,
    "gating": True, "sliding_window": 8,
    "rope_parameters": {
        "full_attention": dict(PUBLISHED_ROPE["full_attention"],
                               original_max_position_embeddings=16,
                               beta_fast=4),
        "sliding_attention": PUBLISHED_ROPE["sliding_attention"]},
    "layer_types": ["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"],
    "mlp_layer_types": ["dense"] + ["sparse"] * 4,
    "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
    "vocab_size": 96, "router_correction_std": 0.1, "initializer_std": 0.05,
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


@functools.lru_cache(maxsize=None)
def _reading_net(dtype, seed):
    """`_net(dtype, seed)` and its parameters as built, once a module: the
    tests that hold the reference to it change neither."""
    net, config = _net(dtype, seed=seed)
    return net, config, _params(net)


# -- logits, loss and gradients against the reference ------------------------

# float32: the same arithmetic in another order of summation.  bfloat16:
# every product's operands carry 8 bits, through 5 layers; the reference
# follows the net's router choices and holds them to a gap.  float8_e4m3
# operands, a sliding layer without its band, a full layer without YaRN
# and a router without its correction must each fail at least one of the
# bfloat16 limits (asserted below).  Read on seeds 11, 14, 17, 21: bf16
# logits 0.6-0.9e-2, gradients 1.1-2.4e-2 by kind.
TOLERANCE = {"float32": {"logits": 2e-5, "loss": 1e-5, "grads": 2e-4,
                         "gap": 1e-5},
             "bfloat16": {"logits": 3e-2, "loss": 3e-3, "grads": 1e-1,
                          "gap": 2e-2}}


@pytest.mark.parametrize("dtype,seed", [("float32", 11), ("bfloat16", 11),
                                        ("bfloat16", 14)])
def test_logits_loss_and_gradients_match_the_reference(dtype, seed):
    net, config, params = _reading_net(dtype, seed)
    ids = _ids()
    got = checks.net_readings(net, ids, MODEL.loss_fn(), heads=1)
    want, gap = checks.reference_readings(MODEL, params, ids, config,
                                          given=got[1])
    logits, loss, kinds = checks.errors(got, want)
    tol = TOLERANCE[dtype]
    assert gap <= tol["gap"], gap
    assert logits <= tol["logits"], logits
    assert loss <= tol["loss"], loss
    # both kinds of attention (5 matrices each), both kinds of MLP, the
    # router, the norms, embedding and head: every kind has a gradient
    assert len(kinds) >= 22, sorted(kinds)
    assert "blocks.attention_window.g_proj.weight" in kinds
    assert "blocks.attention_full.g_proj.weight" in kinds
    worst = max(kinds, key=kinds.get)
    assert kinds[worst] <= tol["grads"], (worst, kinds[worst])
    own, _ = checks.reference_readings(MODEL, params, ids, config)
    differ = (np.sort(got[1], -1) != np.sort(own[1], -1)).any(-1).mean()
    assert (differ == 0) if dtype == "float32" else (differ < 0.2)


@pytest.mark.parametrize("wrong", ["float8", "band", "yarn", "correction"])
def test_the_limits_refuse_lower_precision_and_a_wrong_reading(wrong):
    """What the bfloat16 limits are FOR.  Held to the float32 reference as
    a net is (its choices followed): the reference with float8_e4m3
    operands, with the band left out of the sliding layers, with YaRN
    left out of the full layers, and with the selection correction left
    out of the router must each fail at least one of them."""
    _, config, params = _reading_net("float32", 11)
    ids = _ids()
    tol = TOLERANCE["bfloat16"]

    def fails(operand=None, without=()):
        reading, _ = checks.reference_readings(
            MODEL, params, ids, config, operand, without=without,
            gradients=False)
        want, gap = checks.reference_readings(
            MODEL, params, ids, config, given=reading[1], gradients=False)
        return checks.fails(tol, checks.errors(reading, want), gap)

    assert not fails()
    if wrong == "float8":
        assert fails(operand=jnp.float8_e4m3fn)
    else:
        assert fails(without=(wrong,))


def test_the_reference_refuses_a_wrong_router_with_nan():
    """A followed choice more than `check_routing_gap` under the
    reference's own 8th selection score: that token's logits are NaN."""
    net, config, params = _reading_net("float32", 11)
    ids = _ids()
    inputs = (ids, np.asarray(net(nd.array(ids, ctx=CTX,
                                           dtype="int32"))[1]._jax))
    reference = checks.jitted(MODEL.reference, config)
    assert not np.isnan(np.asarray(reference(params, *inputs))).any()
    flat = {k: (jnp.zeros_like(v) if k.endswith("router_correction") else v)
            for k, v in params.items()}
    assert np.isnan(np.asarray(reference(flat, *inputs))).any()


# -- the windowed kernels against the banded composition ----------------------

def _band_attention(q, k, v, scale, window):
    """numpy: softmax over the keys j with i - window < j <= i."""
    group = q.shape[1] // k.shape[1]
    k, v = (np.repeat(x, group, axis=1) for x in (k, v))
    s = np.einsum("bhqd,bhkd->bhqk", q, k) * scale
    i, j = np.arange(s.shape[-2])[:, None], np.arange(s.shape[-1])[None, :]
    s = np.where((j <= i) & (j > i - window), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True), v)


def _window_case(T, heads, kv_heads, seed, dtype="float32", D=128):
    rng = np.random.RandomState(seed)
    q, g = (jnp.asarray(rng.randn(1, T, heads * D), dtype)
            for _ in range(2))
    k, v = (jnp.asarray(rng.randn(1, T, kv_heads * D), dtype)
            for _ in range(2))
    return q, k, v, g


WINDOW_CASES = [
    # every (T, W) at one head; the groups of the two kinds of layer (6
    # and 8 query heads a key/value head) where the band has an inside
    pytest.param(T, W, group, two, id="t%d-w%d-g%d-%s" % (
        T, W, group, "two-kernels" if two else "fused"))
    for T, W in ((512, 256), (1024, 256), (2048, 256), (1024, 512),
                 (2048, 512))
    for group in (1, 6, 8)
    for two in (False, True)
    if group == 1 or (T, W, two) in ((1024, 256, False), (1024, 512, False),
                                     (2048, 512, False), (1024, 512, True))]


@pytest.mark.parametrize("T,W,group,two_kernels", WINDOW_CASES)
def test_windowed_flash_is_the_banded_composition(monkeypatch, T, W, group,
                                                  two_kernels):
    """Forward and backward of the kernels with a window, interpreted,
    against the composition's band mask (and the forward against a numpy
    band); with the fast memory taken away the backward is the dk/dv
    kernel and the dq kernel."""
    if two_kernels:
        monkeypatch.setattr(attention, "_FAST_MEMORY", 0)
    q, k, v, g = _window_case(T, group, 1, seed=T + W + group)
    if two_kernels:
        geo = attention._Geometry(q, k, group)
        block_q, _, block_kv = geo.blocks(True, W)
        assert geo.fused_backward(block_kv, block_q) is None

    def run(impl):
        def loss(q, k, v):
            with attention.attention_impl_scope(impl):
                out = attention.attention_heads(q, k, v, group, causal=True,
                                                window=W)
            return jnp.sum(out * g), out
        return jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    got, out = run("pallas")
    want, composed = run("xla")

    def split(x, h):
        return np.asarray(x).reshape(1, T, h, 128).transpose(0, 2, 1, 3)

    band = _band_attention(split(q, group), split(k, 1), split(v, 1),
                           1.0 / math.sqrt(128), W)
    assert np.abs(split(out, group) - band).max() < 2e-5
    assert np.abs(np.asarray(out) - np.asarray(composed)).max() < 2e-5
    for a, b, name in zip(got, want, ("dq", "dk", "dv")):
        rel = np.abs(np.asarray(a) - np.asarray(b)).max() \
            / np.abs(np.asarray(b)).max()
        assert rel < 2e-5, (name, rel)


def test_windowed_flash_in_bfloat16_and_as_attention_core():
    """bf16 operands (the training dtype) through `attention_core`'s
    (B, H, T, D) layout, 8 query heads on 2 key/value heads."""
    rng = np.random.RandomState(2)
    q, g = (jnp.asarray(rng.randn(1, 8, 1024, 128), jnp.bfloat16)
            for _ in range(2))
    k, v = (jnp.asarray(rng.randn(1, 2, 1024, 128), jnp.bfloat16)
            for _ in range(2))

    def run(impl):
        def loss(q, k, v):
            with attention.attention_impl_scope(impl):
                out = attention.attention_core(q, k, v, causal=True,
                                               window=512)
            return jnp.sum(out.astype(jnp.float32)
                           * g.astype(jnp.float32)), out
        return jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    got, out = run("pallas")
    want, composed = run("xla")
    assert out.dtype == jnp.bfloat16
    for a, b in zip(got + (out,), want + (composed,)):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        assert np.abs(a - b).max() / np.abs(b).max() < 0.05


def test_a_window_that_holds_every_key_is_the_causal_call_bit_for_bit():
    q, k, v, _ = _window_case(512, 6, 1, seed=9)

    def call(window):
        def f(q, k, v):
            with attention.attention_impl_scope("pallas"):
                return attention.attention_heads(q, k, v, 6, causal=True,
                                                 window=window)
        return f

    plain = jax.jit(call(None))
    for window in (512, 640, 4096):
        assert jax.jit(call(window)).lower(q, k, v).as_text() \
            == plain.lower(q, k, v).as_text()
        np.testing.assert_array_equal(np.asarray(call(window)(q, k, v)),
                                      np.asarray(plain(q, k, v)))
    # ... and a true window is another program
    assert jax.jit(call(256)).lower(q, k, v).as_text() \
        != plain.lower(q, k, v).as_text()


def test_the_rule_admits_a_causal_window_of_whole_units_alone():
    rule = attention.flash_rule
    assert rule(8192, 8192, 128, causal=True, group=8, window=512)
    assert rule(1024, 1024, 128, causal=True, window=256)
    assert rule(1024, 1024, 128, causal=True, window=768)
    assert not rule(1024, 1024, 128, causal=True, window=128)
    assert not rule(1024, 1024, 128, causal=True, window=300)
    assert not rule(1024, 1024, 128, causal=False, window=256)
    assert not rule(1024, 1024, 128, causal=True, window=0)
    # everything else of the rule is as it was
    assert rule(1024, 1024, 128, causal=True) and rule(512, 512, 64)
    assert not rule(512, 512, 64, causal=True, group=2)
    x = jnp.zeros((1, 64, 32), jnp.float32)
    with pytest.raises(ValueError, match="causal"):
        attention.attention_heads(x, x, x, 2, causal=False, window=8)
    with pytest.raises(ValueError, match="at least one key"):
        attention.attention_heads(x, x, x, 2, causal=True, window=0)


def test_the_composition_applies_the_band_off_the_rule():
    """The CPU's small sizes: `multi_head_attention(window=)` is the band
    mask on the composition, HF's ``kv_idx > q_idx - sliding_window``."""
    rng = np.random.RandomState(4)
    q = rng.randn(2, 40, 6 * 16).astype(np.float32)
    k, v = (rng.randn(2, 40, 2 * 16).astype(np.float32) for _ in range(2))
    out = nd.multi_head_attention(nd.array(q), nd.array(k), nd.array(v),
                                  None, num_heads=6, causal=True, window=8)

    def split(x, h):
        return x.reshape(2, 40, h, 16).transpose(0, 2, 1, 3)

    want = _band_attention(split(q, 6), split(k, 2), split(v, 2), 0.25, 8)
    got = split(out.asnumpy(), 6)
    assert np.abs(got - want).max() < 1e-5
    # position 20 of head 0 reads keys 13..20 alone
    v2 = v.copy()
    v2[:, :13] += 100.0
    v2[:, 21:] += 100.0
    out2 = nd.multi_head_attention(nd.array(q), nd.array(k), nd.array(v2),
                                   None, num_heads=6, causal=True, window=8)
    np.testing.assert_allclose(out2.asnumpy()[:, 20], out.asnumpy()[:, 20],
                               rtol=1e-5, atol=1e-5)


# -- `_visits` with a window ----------------------------------------------------

@pytest.mark.parametrize("T,W,held,stream", [
    (2048, 512, 512, 512), (2048, 512, 256, 256), (2048, 256, 256, 256),
    (2048, 768, 256, 256), (2048, 1024, 512, 512), (2048, 512, 512, 256),
    (2048, 512, 256, 512), (1024, 512, 512, 512)])
@pytest.mark.parametrize("held_is_query", [True, False])
def test_visits_with_a_window_lists_the_blocks_that_hold_a_visible_pair(
        T, W, held, stream, held_is_query):
    """Every block `_visits` names holds a visible pair, no block it
    leaves out does; a block named unmasked holds visible pairs alone, one
    named causal lies on the diagonal, one named "window" on the band's
    lower edge; no block is named twice.  Brute force over positions."""
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    visible = (j <= i) & (j > i - W)
    for start in range(0, T, held):
        named = {}
        for first, count, rows, masked in attention._visits(
                True, start, held, stream, T, held_is_query, W):
            for block in range(first, first + max(count, 0)):
                key = (block * rows, rows)
                assert key not in named
                named[key] = masked
        covered = np.zeros(T, bool)
        for (at, rows), masked in named.items():
            assert not covered[at:at + rows].any()
            covered[at:at + rows] = True
            pairs = visible[start:start + held, at:at + rows] \
                if held_is_query else visible[at:at + rows,
                                              start:start + held]
            assert pairs.any(), (start, at)
            if masked is False:
                assert pairs.all(), (start, at)
            elif masked is True:
                assert at < start + held and at + rows > start
            else:
                assert masked == "window" and not pairs.all()
        # what was left out holds no visible pair
        other = visible[start:start + held] if held_is_query \
            else visible[:, start:start + held].T
        assert not other[:, ~covered].any(), start


def test_visits_without_a_window_are_what_they_were():
    """The causal and the full segments of the calls the benchmark's other
    cells make are untouched by the window."""
    assert attention._visits(False, 512, 512, 512, 2048, True) \
        == [(0, 4, 512, False)]
    assert attention._visits(True, 1024, 512, 512, 2048, True) \
        == [(0, 2, 512, False), (2, 1, 512, True)]
    assert attention._visits(True, 512, 512, 512, 2048, False) \
        == [(1, 1, 512, True), (2, 2, 512, False)]
    assert attention._visits(True, 512, 256, 512, 2048, True) \
        == [(0, 1, 512, False), (2, 0, 256, False), (2, 1, 256, True)]
    with pytest.raises(ValueError, match="window"):
        attention._visits(True, 0, 512, 512, 2048, True, 256)
    x = jnp.zeros((1, 2048, 128), jnp.bfloat16)
    geo = attention._Geometry(x, x, 1)
    assert geo.blocks(True) == geo.blocks(True, None) == (512, 512, 512)
    assert geo.blocks(True, 512) == geo.blocks(True, 1024) == (512,) * 3
    assert geo.blocks(True, 256) == geo.blocks(True, 768) == (256,) * 3


def test_the_pair_counters_say_what_the_blocks_cost():
    """`attention_pairs_needed{kind}` / `attention_pairs_visited{kind}`
    grow when a causal kernel call is traced: the band's pairs, and the
    pairs of the blocks the forward visits - two 512-row blocks a query
    block at a 512-key band, three 256-row blocks at the same band."""
    def value(name, kind):
        return telemetry.registry.value(name, {"kind": kind})

    def traced(T, heads, window):
        before = [value(n, k) for n in ("attention_pairs_needed",
                                        "attention_pairs_visited")
                  for k in ("window", "full")]
        q = jnp.zeros((2, T, heads * 128), jnp.bfloat16)
        with attention.attention_impl_scope("pallas"):
            jax.eval_shape(lambda q: attention.attention_heads(
                q, q, q, heads, causal=True, window=window), q)
        after = [value(n, k) for n in ("attention_pairs_needed",
                                       "attention_pairs_visited")
                 for k in ("window", "full")]
        return [a - b for a, b in zip(after, before)]

    T = 2048
    needed, _, visited, _ = traced(T, 3, 512)
    assert needed == 2 * 3 * MODEL.band_pairs(T, 512) \
        == 2 * 3 * (T * 512 - 512 * 511 // 2)
    assert visited == 2 * 3 * (512 * 512 * (2 * (T // 512) - 1))
    needed, _, visited, _ = traced(T, 1, 768)           # 256-row blocks
    assert needed == 2 * MODEL.band_pairs(T, 768)
    blocks = sum(min(b + 1, 4) for b in range(T // 256))
    assert visited == 2 * 256 * 256 * blocks
    _, needed, _, visited = traced(T, 1, None)
    assert needed == 2 * T * (T + 1) // 2
    assert visited == 2 * 512 * 512 * sum(range(1, T // 512 + 1))
    # the composition counts nothing
    with attention.attention_impl_scope("xla"):
        before = value("attention_pairs_needed", "window")
        jax.eval_shape(lambda q: attention.attention_heads(
            q, q, q, 1, causal=True, window=512),
            jnp.zeros((1, T, 128), jnp.bfloat16))
        assert value("attention_pairs_needed", "window") == before


# -- rotary: YaRN and the partial part ------------------------------------------

def test_yarn_frequencies_and_factor_at_the_published_numbers():
    """d 64, theta 500000, factor 64, original 4096, beta_fast 64,
    beta_slow 1: the blended inverse frequencies and the attention factor
    against the formulas, written out here."""
    rope = PUBLISHED_ROPE["full_attention"]
    d, theta = 64, 500000.0
    assert rope["partial_rotary_factor"] * 128 == d
    assert rope["attention_factor"] == pytest.approx(0.1 * math.log(64) + 1,
                                                     abs=1e-15)

    def dim(turns):
        return d * math.log(4096 / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    low, high = math.floor(dim(64)), math.ceil(dim(1))
    assert (low, high) == (5, 16)
    want = []
    for i in range(d // 2):
        f = theta ** (-2.0 * i / d)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append((1 - ramp) * f + ramp * f / 64)
    got = ops_nn.yarn_inv_freq(d, theta, 64, 4096, 64, 1)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    # lanes that turn often keep their frequency, slow lanes are stretched
    assert got[0] == 1.0 and got[5] == theta ** (-10.0 / d)
    assert got[16] == pytest.approx(theta ** (-32.0 / d) / 64)
    assert got[31] == pytest.approx(theta ** (-62.0 / d) / 64)
    # the reference's tables are the same numbers, made on their own
    cos, sin, lanes = MODEL.rotary_tables(rope, 128, 8192)
    assert lanes == d and cos.shape == (8192, 32)
    angle = np.arange(8192, dtype=np.float32)[:, None] \
        * np.asarray(want, np.float32)
    np.testing.assert_allclose(cos, np.cos(angle) * rope["attention_factor"],
                               rtol=1e-6, atol=1e-6)
    # what the zoo hands the operator
    assert laguna.rotary_keywords(rope, 128) == dict(
        rotary_dim=64, theta=500000.0, first=True, yarn=(64, 4096, 64, 1),
        attention_factor=1.4158883083359672)
    assert laguna.rotary_keywords(PUBLISHED_ROPE["sliding_attention"], 128) \
        == dict(rotary_dim=128, theta=10000.0, first=False)


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_the_rotary_operator_is_the_reference_formula(kind):
    """Partial rotary turns the FIRST lanes and leaves lanes 64-127 as
    they are; the operator against the reference's tables, both kinds."""
    rope = PUBLISHED_ROPE[kind]
    x = np.random.RandomState(6).randn(2, 96, 3 * 128).astype(np.float32)
    got = nd.rotary_embedding(nd.array(x), num_heads=3,
                              **laguna.rotary_keywords(rope, 128)).asnumpy()
    heads = x.reshape(2, 96, 3, 128).transpose(0, 2, 1, 3)
    want = np.asarray(MODEL._Equations({}, {"rope_parameters":
                                            PUBLISHED_ROPE,
                                            "rms_norm_eps": 0})
                      .rotary(jnp.asarray(heads), kind))
    want = want.transpose(0, 2, 1, 3).reshape(x.shape)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    got, x = (a.reshape(2, 96, 3, 128) for a in (got, x))
    if kind == "full_attention":
        np.testing.assert_array_equal(got[..., 64:], x[..., 64:])
        # position 0 turns by no angle: scaled by the attention factor
        np.testing.assert_allclose(got[:, 0, :, :64],
                                   x[:, 0, :, :64] * 1.4158883083359672,
                                   rtol=1e-6)
    assert np.abs(got[:, 1:, :, :64] - x[:, 1:, :, :64]).max() > 0.1


def test_the_operators_defaults_are_the_calls_they_were():
    """`window=None` and the rotary defaults trace what a call without the
    keywords traces: the other models' programs do not change."""
    x = jnp.asarray(np.random.RandomState(3).randn(2, 256, 256), jnp.float32)

    def text(f):
        return jax.jit(f).lower(x).as_text()

    for impl in ("pallas", "xla"):
        with attention.attention_impl_scope(impl):
            for causal in (False, True):
                assert text(lambda q: ops_nn._mha(
                    q, q, q, num_heads=2, causal=causal, window=None)) \
                    == text(lambda q: ops_nn._mha(q, q, q, num_heads=2,
                                                  causal=causal))
    assert text(lambda q: ops_nn._rotary_embedding(
        q, num_heads=2, rotary_dim=64, theta=1e6, first=False, yarn=None,
        attention_factor=1.0)) \
        == text(lambda q: ops_nn._rotary_embedding(q, num_heads=2,
                                                   rotary_dim=64, theta=1e6))
    plain = nn.RotaryEmbedding(2, 64, 1e6)
    assert plain._more == {} and "first" not in repr(plain)


def test_grouped_query_attention_by_default_is_the_layer_it_was():
    """No window, no rotary, no gate: four matrices and the causal core;
    each keyword adds its own part alone."""
    mx.random.seed(2)
    plain = nn.GroupedQueryAttention(32, 4, 2, 16)
    plain.initialize(mx.init.Normal(0.3), ctx=CTX)
    assert sorted(_params(plain)) == ["k_proj.weight", "o_proj.weight",
                                      "q_proj.weight", "v_proj.weight"]
    x = np.random.RandomState(8).randn(2, 24, 32).astype(np.float32)
    params = {k: np.asarray(v) for k, v in _params(plain).items()}

    def split(y, h):
        return y.reshape(2, 24, h, 16).transpose(0, 2, 1, 3)

    q, k, v = (x @ params[n + "_proj.weight"].T for n in "qkv")
    want = _band_attention(split(q, 4), split(k, 2), split(v, 2), 0.25, 24)
    want = want.transpose(0, 2, 1, 3).reshape(2, 24, 64) \
        @ params["o_proj.weight"].T
    np.testing.assert_allclose(plain(nd.array(x)).asnumpy(), want,
                               rtol=2e-4, atol=2e-5)
    gated = nn.GroupedQueryAttention(32, 4, 2, 16, window=8, head_gate=True,
                                     rotary=dict(rotary_dim=8, first=True))
    gated.initialize(ctx=CTX)
    assert sorted(_params(gated)) == ["g_proj.weight", "k_proj.weight",
                                      "o_proj.weight", "q_proj.weight",
                                      "v_proj.weight"]
    assert gated.g_proj.weight.shape == (4, 32)


# -- the shares of a sparse layer add up ---------------------------------------

def test_the_eight_shares_of_a_sparse_layer_add_up_to_the_whole():
    """The 8 shares' routed parts (experts 0-31, ..., 224-255 of 256, top-8)
    plus the shared expert ONCE equal the uncut reference's whole layer;
    every assignment is counted exactly once."""
    experts, top_k, shares, units, width = 256, 8, 8, 32, 16
    per = experts // shares

    def layer(held, shared):
        mx.random.seed(5)
        block = nn.TokenChoiceMoE(
            units, width, experts, top_k, held=held, num_shared=shared,
            scale=2.5, layer=None, shared_hidden_size=width,
            correction_initializer=mx.init.Normal(0.1))
        block.initialize(mx.init.Normal(0.3), ctx=CTX)
        return block

    whole = layer(None, 1)
    params = _params(whole)
    config = dict(CONFIG, experts_held=list(range(experts)),
                  num_experts_published=experts, num_experts_per_tok=top_k)
    x = np.random.RandomState(3).randn(2, 24, units).astype(np.float32)
    want = np.asarray(MODEL.reference_expert_layer(params, jnp.asarray(x),
                                                   config))
    total, seen = 0.0, 0.0
    for share in range(shares):
        held = tuple(range(share * per, (share + 1) * per))
        part = layer(held, 1 if share == 0 else 0)      # the shared: once
        for name, p in part.collect_params().items():
            value = np.asarray(params[name])
            if name in ("gate_up_weight", "down_weight"):
                value = value[list(held)]
            elif name in ("assignments", "elsewhere", "buffer_calls"):
                value = np.zeros(p.shape, np.float32)
            p.set_data(nd.array(value, ctx=CTX))
        with autograd.train_mode():
            total = total + np.asarray(part(nd.array(x, ctx=CTX))._jax)
        seen += float(part.assignments.data().asnumpy().sum())
        # ... and one share alone is what the reference gives that share
        if share == 3:
            alone = MODEL.reference_expert_layer(
                {k: (np.asarray(v)[list(held)]
                     if k in ("gate_up_weight", "down_weight") else v)
                 for k, v in params.items()},
                jnp.asarray(x), config, held=list(held), shared=False)
            with autograd.train_mode():
                np.testing.assert_allclose(
                    np.asarray(part(nd.array(x, ctx=CTX))._jax),
                    np.asarray(alone), rtol=3e-4, atol=3e-5)
    np.testing.assert_allclose(total, want, rtol=3e-4, atol=3e-5)
    assert seen == 2 * 24 * top_k          # every assignment, exactly once


# -- the net -----------------------------------------------------------------

def test_the_net_is_built_from_the_configs_keys():
    net, config = _net()
    params = net.collect_params()
    assert params["blocks.0.attention_full.q_proj.weight"].shape == (96, 48)
    assert params["blocks.1.attention_window.q_proj.weight"].shape \
        == (128, 48)
    assert params["blocks.1.attention_window.o_proj.weight"].shape \
        == (48, 128)
    assert params["blocks.4.attention_full.g_proj.weight"].shape == (6, 48)
    assert params["blocks.2.attention_window.k_proj.weight"].shape == (32, 48)
    assert params["blocks.0.mlp.gate_up_proj.weight"].shape == (192, 48)
    assert params["blocks.3.moe.gate_up_weight"].shape == (4, 48, 48)
    assert params["blocks.3.moe.router_weight"].shape == (16, 48)
    assert "blocks.0.moe.router_weight" not in params
    assert [b.kind for b in net.blocks] == config["layer_types"]
    for block in net.blocks:
        assert block._recompute
    kinds = [getattr(b, laguna.ATTENTION[b.kind]) for b in net.blocks]
    assert [a._band for a in kinds] \
        == [{}, {"window": 8}, {"window": 8}, {"window": 8}, {}]
    assert "yarn" in repr(kinds[0].rotary) and "yarn" not in \
        repr(kinds[1].rotary)
    with pytest.raises(ValueError, match="layers"):
        laguna.Laguna(96, num_layers=5)
    with pytest.raises(ValueError, match="rope_type"):
        laguna.rotary_keywords({"rope_type": "linear", "rope_theta": 1.0}, 8)
    # the published net's description, without building 33 B parameters
    doc = laguna.laguna_xs_2.__doc__
    assert "40" in doc and "48" in doc and "512" in doc


def test_the_selection_correction_is_balanced_by_its_own_rule(capsys):
    spec = {"tokens": 256, "steps": 40, "rate": 0.03, "rate_last": 0.001}
    _net("float32", seed=3, router_balance=spec)
    said = json.loads(capsys.readouterr().out.split("benchmark: ")[-1])
    assert said["expert_load_max_over_mean_before"] > 1.5
    assert said["expert_load_max_over_mean_after"] < 1.15


def test_an_eager_call_ignores_the_recompute_mark():
    net, _ = _net("float32")
    net.hybridize(False)
    ids = nd.array(_ids(), ctx=CTX, dtype="int32")
    marked = np.asarray(net(ids)[0]._jax)
    for block in net.blocks:
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
    assert abs(losses[0] - np.log(CONFIG["vocab_size"])) < 0.3
    params = net.collect_params()
    assert params["blocks.1.moe.gate_up_weight"].dtype == jnp.bfloat16
    assert params["blocks.1.attention_window.g_proj.weight"].dtype \
        == jnp.bfloat16
    assert params["blocks.1.moe.router_weight"].dtype == np.float32
    # the scopes a device trace reads the layers by
    text = programs.program_scopes("step.step")
    paths = {w["scope"] for w in text["instructions"].values()}
    for scope in ("attention_full", "attention_window", "attention_core",
                  "rotary", "head_gate", "moe", "lm_head"):
        assert any("/%s/" % scope in "/%s/" % p for p in paths), scope
    # the counters advanced inside the step: 24 steps x 64 tokens x top-4
    snapshot = telemetry.registry.snapshot()
    held = 0.0
    for layer in ("1", "2", "3", "4"):
        here = sum(snapshot["moe_assignments{expert=%d,layer=%s}"
                            % (e, layer)]["value"] for e in (4, 5, 6, 7))
        away = snapshot["moe_assignments_elsewhere{layer=%s}"
                        % layer]["value"]
        assert here + away == 24 * 64 * 4
        held += here
    assert 0 < held < 4 * 24 * 64 * 4       # some here, most elsewhere


def test_ops_and_bytes_of_the_published_configuration():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "laguna_xs_2.json")) as f:
        config = json.load(f)
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "clm-s8192-b1-ep8.json")) as f:
        traffic = json.load(f)
    ops = MODEL.ops_and_bytes(config, traffic)
    T, W = 8192, 512
    assert round(ops["n_params"] / 1e6, 1) == 691.6
    assert round(ops["bytes"] / 2 / 1e9, 2) == 9.68
    assert round(ops["forward_flops"] / 1e12, 2) == 6.57
    assert ops["flops"] == 3 * ops["forward_flops"]
    detail, forward = ops["detail"], ops["detail"]["forward"]
    assert detail["expected_assignments_per_expert"] == 256
    # the pairs inside the band by brute count, 4 x 128 FLOP a pair
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    band = int(((j <= i) & (j > i - W)).sum())
    assert band == MODEL.band_pairs(T, W) == T * W - W * (W - 1) // 2
    assert forward["attention_core_window"] == 3 * 64 * 4 * 128 * band
    assert MODEL.band_pairs(T) == int((j <= i).sum())
    assert forward["attention_core_full"] \
        == 2 * 48 * 4 * 128 * (T * (T + 1) // 2)
    # under a plain causal kernel the sliding layers' cores would be half
    # again the whole step
    assert 8 < forward["attention_core_full"] / 2 * 3 * 64 / 48 \
        / forward["attention_core_window"] < 8.5
    share = {k: v / ops["forward_flops"] for k, v in forward.items()}
    assert round(share["attention_projections"], 2) == 0.43
    assert round(share["attention_core_full"], 2) == 0.25
    assert round(share["attention_core_window"], 2) == 0.06
    assert round(share["moe_routed"] + share["moe_shared"]
                 + share["moe_router"], 2) == 0.07
    assert detail["held_expert_weight_bytes"] == 4 * 32 * 3 * 2048 * 512 * 2
