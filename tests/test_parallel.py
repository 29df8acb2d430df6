"""Parallel-layer tests on the fake 8-device CPU mesh.

Reference patterns: tests/nightly/dist_sync_kvstore.py (exact-integer
payload reduces), SURVEY.md §4.5 (xla_force_host_platform_device_count
fake-mesh testing of kvstore='ici'/shard_map logic).

Key invariant exercised throughout: sharding annotations NEVER change
semantics — a dp- or dp×tp-sharded TrainStep must produce the same loss
trajectory as the single-device step (XLA inserts collectives to preserve
the math; placement only affects performance).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import (make_mesh, shard_params_tp, batch_sharded,
                                TrainStep)
from jax.sharding import PartitionSpec as P


def _devices(n=8):
    devs = jax.devices("cpu")
    if len(devs) < n:
        pytest.skip("needs %d fake devices" % n)
    return devs[:n]


def _make_net(seed=0, dense_sizes=(16, 10), conv=False):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = nn.HybridSequential()
    if conv:
        net.add(nn.Conv2D(4, 3, padding=1, activation="relu"),
                nn.MaxPool2D(), nn.Flatten())
    for k in dense_sizes[:-1]:
        net.add(nn.Dense(k, activation="relu"))
    net.add(nn.Dense(dense_sizes[-1]))
    net.initialize(mx.init.Xavier())
    return net


def _loss_fn(logits, labels):
    import jax.numpy as jnp
    logp = jax.nn.log_softmax(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logp.dtype)
    return -jnp.mean(jnp.sum(logp * onehot, axis=-1))


def _batch(seed=1, n=16, feat=(8,), classes=10):
    rng = np.random.RandomState(seed)
    import jax.numpy as jnp
    x = jnp.asarray(rng.randn(n, *feat).astype(np.float32))
    y = jnp.asarray(rng.randint(0, classes, n).astype(np.int32))
    return x, y


def _run_steps(net_seed, mesh, steps=3, conv=False, tp_rules=None):
    feat = (3, 8, 8) if conv else (8,)
    net = _make_net(net_seed, conv=conv)
    net(nd.zeros((1,) + feat))      # finalize deferred shapes
    step = TrainStep(net, _loss_fn, mesh, learning_rate=0.1,
                     momentum=0.9, tp_rules=tp_rules)
    x, y = _batch(net_seed + 1, feat=feat)
    return [float(step(x, y)) for _ in range(steps)]


def test_make_mesh_shapes():
    devs = _devices()
    m = make_mesh(axes=("dp",), devices=devs)
    assert dict(m.shape) == {"dp": 8}
    m = make_mesh(axes=("dp", "tp"), shape=(-1, 2), devices=devs)
    assert dict(m.shape) == {"dp": 4, "tp": 2}
    m = make_mesh(axes=("dp", "tp"), shape=(2, 4), devices=devs)
    assert dict(m.shape) == {"dp": 2, "tp": 4}


def test_dp_matches_single_device():
    devs = _devices()
    losses_1 = _run_steps(0, make_mesh(axes=("dp",), devices=devs[:1]))
    losses_8 = _run_steps(0, make_mesh(axes=("dp",), devices=devs))
    np.testing.assert_allclose(losses_1, losses_8, rtol=2e-4)
    assert losses_8[-1] < losses_8[0]    # and it actually descends


def test_dp_tp_matches_dp_only():
    devs = _devices()
    losses_dp = _run_steps(0, make_mesh(axes=("dp",), devices=devs))
    losses_tp = _run_steps(0, make_mesh(axes=("dp", "tp"), shape=(-1, 2),
                                        devices=devs))
    np.testing.assert_allclose(losses_dp, losses_tp, rtol=2e-4)


def test_tp_non_alternating_architecture_correct():
    """3 Dense + conv: col/row alternation is a placement heuristic only —
    results must equal the single-device run regardless of layer layout."""
    devs = _devices()
    losses_1 = _run_steps(0, make_mesh(axes=("dp",), devices=devs[:1]),
                          conv=True)
    losses_tp = _run_steps(0, make_mesh(axes=("dp", "tp"), shape=(2, 4),
                                        devices=devs), conv=True)
    np.testing.assert_allclose(losses_1, losses_tp, rtol=2e-4)


def test_shard_params_tp_explicit_rules():
    devs = _devices()
    mesh = make_mesh(axes=("dp", "tp"), shape=(4, 2), devices=devs)
    import jax.numpy as jnp
    params = {"a.weight": jnp.zeros((8, 4)), "a.bias": jnp.zeros((8,)),
              "emb.weight": jnp.zeros((16, 8))}
    out = shard_params_tp(params, mesh, rules={"a.weight": P("tp", None)})
    spec_a = out["a.weight"].sharding.spec
    assert tuple(spec_a) == ("tp", None)
    # un-matched names replicate under explicit rules
    assert tuple(out["emb.weight"].sharding.spec) in ((), (None, None))


def test_shard_params_tp_default_alternation():
    devs = _devices()
    mesh = make_mesh(axes=("dp", "tp"), shape=(4, 2), devices=devs)
    import jax.numpy as jnp
    params = {"0.weight": jnp.zeros((8, 4)), "0.bias": jnp.zeros((8,)),
              "1.weight": jnp.zeros((4, 8))}
    out = shard_params_tp(params, mesh)
    assert tuple(out["0.weight"].sharding.spec) == ("tp", None)   # column
    assert tuple(out["1.weight"].sharding.spec) == (None, "tp")   # row
    assert tuple(out["0.bias"].sharding.spec) in ((), (None,))    # replicated


def test_batch_sharded_placement():
    devs = _devices()
    mesh = make_mesh(axes=("dp",), devices=devs)
    import jax.numpy as jnp
    x = jax.device_put(jnp.zeros((16, 4)), batch_sharded(mesh))
    assert len(x.sharding.device_set) == 8
    assert tuple(x.sharding.spec) == ("dp",)


def test_kvstore_ici_exact_integer_reduce():
    """dist_sync_kvstore pattern: push known integer payloads from every
    'worker' (device), pull the exact sum."""
    kv = mx.kvstore.create("ici")
    shape = (4, 4)
    kv.init("w", nd.zeros(shape))
    n = kv.num_devices if hasattr(kv, "num_devices") else 8
    vals = [nd.array(np.full(shape, i + 1, np.float32)) for i in range(4)]
    kv.push("w", vals)
    out = nd.zeros(shape)
    kv.pull("w", out=out)
    np.testing.assert_array_equal(out.asnumpy(),
                                  np.full(shape, 1 + 2 + 3 + 4, np.float32))


def test_trainstep_write_back():
    devs = _devices()
    mesh = make_mesh(axes=("dp",), devices=devs)
    net = _make_net(3)
    net(nd.zeros((1, 8)))
    before = {k: p.data().asnumpy().copy()
              for k, p in net.collect_params().items()}
    step = TrainStep(net, _loss_fn, mesh, learning_rate=0.1)
    x, y = _batch(4)
    step(x, y)
    step.write_back(net)
    after = {k: p.data().asnumpy() for k, p in net.collect_params().items()}
    changed = [k for k in before if not np.allclose(before[k], after[k])]
    assert changed, "write_back did not update any parameter"


# -- sequence/context parallelism (ring + ulysses) ----------------------------

def _ref_attention(q, k, v, causal):
    scale = q.shape[-1] ** -0.5
    s = np.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        L = q.shape[1]
        mask = np.tril(np.ones((L, L), bool))
        s = np.where(mask[None, None], s, -np.inf)
    s = s - s.max(axis=-1, keepdims=True)
    p = np.exp(s)
    p = p / p.sum(axis=-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("method", ["ring", "ulysses"])
@pytest.mark.parametrize("causal", [False, True])
def test_context_parallel_attention_matches_reference(method, causal):
    from mxnet_tpu.parallel import make_mesh, context_parallel_attention
    np.random.seed(0)
    B, L, H, D = 2, 32, 8, 16   # L split over sp=8 -> 4 per device
    q = np.random.randn(B, L, H, D).astype(np.float32)
    k = np.random.randn(B, L, H, D).astype(np.float32)
    v = np.random.randn(B, L, H, D).astype(np.float32)
    mesh = make_mesh(axes=("sp",))
    out = context_parallel_attention(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), mesh, causal=causal,
                                     method=method)
    ref = _ref_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-5)


def test_ring_attention_gradients_match_local():
    """SP must be transparent to training: grads through the ring equal
    grads through plain attention."""
    from mxnet_tpu.parallel import make_mesh, context_parallel_attention
    np.random.seed(1)
    B, L, H, D = 1, 16, 4, 8
    q = jnp.asarray(np.random.randn(B, L, H, D).astype(np.float32))
    k = jnp.asarray(np.random.randn(B, L, H, D).astype(np.float32))
    v = jnp.asarray(np.random.randn(B, L, H, D).astype(np.float32))
    mesh = make_mesh(axes=("sp",))

    def ring_loss(q, k, v):
        return context_parallel_attention(q, k, v, mesh, causal=True,
                                          method="ring").sum()

    def local_loss(q, k, v):
        scale = D ** -0.5
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        mask = jnp.tril(jnp.ones((L, L), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v).sum()

    # under jax.jit: eagerly each primitive of the ring's backward pass
    # dispatches (and compiles) on its own
    g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    g_loc = jax.grad(local_loss, argnums=(0, 1, 2))(q, k, v)
    for gr, gl in zip(g_ring, g_loc):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gl),
                                   rtol=5e-4, atol=5e-5)


def test_ring_attention_long_sequence_sp2():
    """sp=2 with the remaining devices on dp: mixed-axis mesh works."""
    from mxnet_tpu.parallel import make_mesh, context_parallel_attention
    np.random.seed(2)
    B, L, H, D = 4, 64, 2, 8
    q = np.random.randn(B, L, H, D).astype(np.float32)
    mesh = make_mesh(axes=("dp", "sp"), shape=(4, 2))
    out = context_parallel_attention(jnp.asarray(q), jnp.asarray(q),
                                     jnp.asarray(q), mesh, causal=True)
    ref = _ref_attention(q, q, q, True)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-5)


def test_ulysses_rejects_indivisible_heads():
    from mxnet_tpu.parallel import make_mesh, context_parallel_attention
    mesh = make_mesh(axes=("sp",))  # sp=8
    q = jnp.zeros((1, 16, 6, 4), jnp.float32)  # 6 heads % 8 != 0
    with pytest.raises(Exception, match="heads"):
        context_parallel_attention(q, q, q, mesh, method="ulysses")


# -- pipeline parallelism ------------------------------------------------------

def _stage_fn(params, x):
    w, b = params
    return jnp.tanh(x @ w + b)


def _make_stages(n_stages, d, seed=0):
    rng = np.random.RandomState(seed)
    ws = jnp.asarray(rng.randn(n_stages, d, d).astype(np.float32) * 0.5)
    bs = jnp.asarray(rng.randn(n_stages, d).astype(np.float32) * 0.1)
    return (ws, bs)


def test_pipeline_matches_sequential():
    from mxnet_tpu.parallel import make_mesh, pipeline_parallel
    d, batch, n_stages = 6, 16, 4
    mesh = make_mesh(axes=("pp",), shape=(n_stages,),
                     devices=_devices(n_stages))
    stacked = _make_stages(n_stages, d)
    apply = pipeline_parallel(_stage_fn, mesh, n_microbatches=4)
    x = jnp.asarray(np.random.RandomState(1).randn(batch, d)
                    .astype(np.float32))
    out = apply(stacked, x)
    # sequential reference
    ref = x
    for s in range(n_stages):
        ref = _stage_fn((stacked[0][s], stacked[1][s]), ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_pipeline_gradients_match_sequential():
    from mxnet_tpu.parallel import make_mesh, pipeline_parallel
    d, batch, n_stages = 4, 8, 4
    mesh = make_mesh(axes=("pp",), shape=(n_stages,),
                     devices=_devices(n_stages))
    stacked = _make_stages(n_stages, d, seed=2)
    apply = pipeline_parallel(_stage_fn, mesh, n_microbatches=2)
    x = jnp.asarray(np.random.RandomState(3).randn(batch, d)
                    .astype(np.float32))

    def pipe_loss(params):
        return (apply(params, x) ** 2).mean()

    def seq_loss(params):
        ws, bs = params
        h = x
        for s in range(n_stages):
            h = _stage_fn((ws[s], bs[s]), h)
        return (h ** 2).mean()

    gp = jax.grad(pipe_loss)(stacked)
    gs = jax.grad(seq_loss)(stacked)
    for a, b in zip(gp, gs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-5)


def test_pipeline_training_step_descends():
    from mxnet_tpu.parallel import make_mesh, pipeline_parallel
    d, batch, n_stages = 4, 16, 4
    mesh = make_mesh(axes=("pp",), shape=(n_stages,),
                     devices=_devices(n_stages))
    params = _make_stages(n_stages, d, seed=4)
    apply = pipeline_parallel(_stage_fn, mesh, n_microbatches=4)
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(batch, d).astype(np.float32))
    y = jnp.asarray(rng.randn(batch, d).astype(np.float32))

    @jax.jit
    def step(params):
        loss, g = jax.value_and_grad(
            lambda p: ((apply(p, x) - y) ** 2).mean())(params)
        return tuple(p - 0.2 * gi for p, gi in zip(params, g)), loss

    params, l0 = step(params)
    params, l1 = step(params)
    assert float(l1) < float(l0)


# -- expert parallelism (MoE) --------------------------------------------------

def _expert_fn(params, x):
    w1, w2 = params
    return jnp.maximum(x @ w1, 0) @ w2


def test_moe_matches_per_token_reference():
    from mxnet_tpu.parallel import make_mesh, moe_parallel
    rng = np.random.RandomState(0)
    d, dh, T = 8, 16, 64
    mesh = make_mesh(axes=("ep",), devices=_devices(8))  # 1 expert/device
    E = 8
    w1 = jnp.asarray(rng.randn(E, d, dh).astype(np.float32) * 0.3)
    w2 = jnp.asarray(rng.randn(E, dh, d).astype(np.float32) * 0.3)
    gate_w = jnp.asarray(rng.randn(d, E).astype(np.float32))
    x = jnp.asarray(rng.randn(T, d).astype(np.float32))

    apply = moe_parallel(_expert_fn, mesh, capacity_factor=8.0)  # no drops
    y, aux = apply(x, gate_w, (w1, w2))

    # dense per-token reference: top-1 expert output scaled by gate prob
    xn = np.asarray(x)
    logits = xn @ np.asarray(gate_w)
    probs = np.exp(logits - logits.max(1, keepdims=True))
    probs /= probs.sum(1, keepdims=True)
    pick = probs.argmax(1)
    ref = np.zeros_like(xn)
    for t in range(T):
        e = pick[t]
        h = np.maximum(xn[t] @ np.asarray(w1[e]), 0) @ np.asarray(w2[e])
        ref[t] = probs[t, e] * h
    np.testing.assert_allclose(np.asarray(y), ref, rtol=2e-4, atol=2e-5)
    assert np.isfinite(float(aux))


def test_moe_capacity_drops_tokens_to_zero():
    from mxnet_tpu.parallel import make_mesh, moe_parallel
    rng = np.random.RandomState(1)
    d, T = 4, 32
    mesh = make_mesh(axes=("ep",), devices=_devices(8))
    E = 8
    w1 = jnp.asarray(rng.randn(E, d, d).astype(np.float32))
    w2 = jnp.asarray(rng.randn(E, d, d).astype(np.float32))
    # force every token to expert 0 -> capacity overflows
    gate_w = jnp.asarray(
        np.concatenate([np.full((d, 1), 5.0),
                        np.zeros((d, E - 1))], axis=1).astype(np.float32))
    x = jnp.asarray(np.abs(rng.randn(T, d)).astype(np.float32))
    apply = moe_parallel(_expert_fn, mesh, capacity_factor=1.0)
    y, _aux = apply(x, gate_w, (w1, w2))
    yn = np.asarray(y)
    zero_rows = (np.abs(yn).sum(axis=1) == 0).sum()
    assert zero_rows > 0            # overflow tokens were dropped
    assert zero_rows < T            # but capacity tokens went through


def test_moe_trains_with_gradients():
    from mxnet_tpu.parallel import make_mesh, moe_parallel
    rng = np.random.RandomState(2)
    d, T, E = 4, 32, 8
    mesh = make_mesh(axes=("ep",), devices=_devices(8))
    params = (jnp.asarray(rng.randn(E, d, d).astype(np.float32) * 0.3),
              jnp.asarray(rng.randn(E, d, d).astype(np.float32) * 0.3))
    gate_w = jnp.asarray(rng.randn(d, E).astype(np.float32) * 0.3)
    x = jnp.asarray(rng.randn(T, d).astype(np.float32))
    tgt = jnp.asarray(rng.randn(T, d).astype(np.float32))
    apply = moe_parallel(_expert_fn, mesh, capacity_factor=4.0)

    @jax.jit
    def step(params, gate_w):
        def loss_fn(p, g):
            y, aux = apply(x, g, p)
            return ((y - tgt) ** 2).mean() + 0.01 * aux
        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            params, gate_w)
        p, g = grads
        return (tuple(a - 0.1 * b for a, b in zip(params, p)),
                gate_w - 0.1 * g, loss)

    params, gate_w, l0 = step(params, gate_w)
    params, gate_w, l1 = step(params, gate_w)
    assert float(l1) < float(l0)


def test_pipeline_rejects_stage_count_mismatch():
    from mxnet_tpu.parallel import make_mesh, pipeline_parallel
    mesh = make_mesh(axes=("pp",), shape=(4,), devices=_devices(4))
    stacked = _make_stages(8, 4)      # 8 stages on a 4-device axis
    apply = pipeline_parallel(_stage_fn, mesh, n_microbatches=4)
    with pytest.raises(ValueError, match="stacked stages"):
        apply(stacked, jnp.zeros((8, 4), jnp.float32))


def test_moe_rejects_gate_expert_mismatch():
    from mxnet_tpu.parallel import make_mesh, moe_parallel
    mesh = make_mesh(axes=("ep",), devices=_devices(8))
    params = (jnp.zeros((8, 4, 4), jnp.float32),
              jnp.zeros((8, 4, 4), jnp.float32))
    gate_w = jnp.zeros((4, 16), jnp.float32)   # 16 routes, 8 experts
    apply = moe_parallel(_expert_fn, mesh)
    with pytest.raises(ValueError, match="gate_w"):
        apply(jnp.zeros((16, 4), jnp.float32), gate_w, params)


def _flash_ring_readings(mesh, q, k, v, causal, g=None, **keywords):
    """context_parallel_attention by the ring, traced under one
    ``jax.jit`` - eagerly every step of an interpreted kernel's grid
    dispatches on its own: the output, and with a cotangent `g` also the
    gradients by q, k and v."""
    from mxnet_tpu.parallel import context_parallel_attention

    def run(q, k, v):
        return context_parallel_attention(q, k, v, mesh, causal=causal,
                                          **keywords)

    def readings(q, k, v, g):
        if g is None:
            return run(q, k, v)
        out, vjp = jax.vjp(run, q, k, v)
        return (out,) + vjp(g)

    return jax.jit(readings)(q, k, v, g)


@pytest.mark.parametrize("case", ["full", "causal", "aligned-shards"])
def test_ring_attention_flash_path_matches_single_device(case):
    """Block-aligned shards route ring hops through the Pallas flash
    kernel (interpret off-TPU): outputs AND gradients must match the
    single-device attention reference ("full" and "causal": 256 a shard
    over sp=4).  "aligned-shards": per-shard shapes aligned to the flash
    blocks over sp=2 with the other devices on dp - each ring hop runs
    the REAL Pallas kernel (interpret on CPU, Mosaic on TPU) and the
    output must still match the dense reference."""
    from jax.sharding import Mesh
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel import ring as ring_mod
    from mxnet_tpu.ops.attention import _attention_jnp, attention_impl_scope

    if case == "aligned-shards":
        np.random.seed(3)
        B, L, H, D = 1, 512, 1, 128          # sp=2 -> 256 per shard
        q = np.random.randn(B, L, H, D).astype(np.float32)
        k = np.random.randn(B, L, H, D).astype(np.float32)
        v = np.random.randn(B, L, H, D).astype(np.float32)
        mesh = make_mesh(axes=("dp", "sp"), shape=(4, 2))
        with attention_impl_scope("pallas"):
            assert ring_mod._flash_ok(
                jnp.zeros((B, L // 2, H, D)), jnp.zeros((B, L // 2, H, D)))
            out = _flash_ring_readings(mesh, jnp.asarray(q), jnp.asarray(k),
                                       jnp.asarray(v), True)
        ref = _ref_attention(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3,
                                   atol=2e-4)
        return

    causal = case == "causal"
    devs = np.array(jax.devices("cpu")[:4])
    mesh = Mesh(devs, ("sp",))
    B, L, H, D = 1, 1024, 2, 128          # 256 per shard: block-aligned
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, L, H, D), jnp.float32) * 0.1
    k = jnp.asarray(rng.randn(B, L, H, D), jnp.float32) * 0.1
    v = jnp.asarray(rng.randn(B, L, H, D), jnp.float32) * 0.1
    g = jnp.asarray(rng.randn(B, L, H, D), jnp.float32) * 0.1
    scale = 1.0 / np.sqrt(D)
    with attention_impl_scope("pallas"):      # engage flash off-TPU
        # the flash path must actually engage on these shapes
        assert ring_mod._flash_ok(
            jnp.zeros((B, L // 4, H, D)), jnp.zeros((B, L // 4, H, D)))
        out, dq, dk, dv = _flash_ring_readings(mesh, q, k, v, causal, g,
                                               sp_axis="sp", method="ring",
                                               scale=scale)

    def ref(q, k, v):
        o = _attention_jnp(q.transpose(0, 2, 1, 3),
                           k.transpose(0, 2, 1, 3),
                           v.transpose(0, 2, 1, 3), scale, causal)
        return o.transpose(0, 2, 1, 3)
    want, vjp_r = jax.vjp(ref, q, k, v)
    dq_r, dk_r, dv_r = vjp_r(g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=2e-4)
    for got, ref_g, nm in ((dq, dq_r, "dq"), (dk, dk_r, "dk"),
                           (dv, dv_r, "dv")):
        err = np.abs(np.asarray(got) - np.asarray(ref_g)).max()
        rel = err / max(np.abs(np.asarray(ref_g)).max(), 1e-6)
        assert rel < 5e-3, (causal, nm, rel)


# -- 2-bit gradient compression (reference: gradient_compression.cc) --------

def test_quantize_2bit_matches_numpy_reference():
    """Multi-step error feedback vs a step-by-step numpy re-implementation
    of Quantize2BitImpl."""
    import numpy as np
    from mxnet_tpu.kvstore.gradient_compression import quantize_2bit
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    t = 0.4
    g_steps = [rng.randn(16).astype(np.float32) * 0.3 for _ in range(6)]
    res_ref = np.zeros(16, np.float32)
    res = jnp.zeros(16)
    for g in g_steps:
        # numpy reference: residual += g; emit level; residual -= level
        res_ref = res_ref + g
        level = np.where(res_ref >= t, t,
                         np.where(res_ref <= -t, -t, 0.0)).astype(np.float32)
        res_ref -= level
        q, res = quantize_2bit(jnp.asarray(g), res, t)
        np.testing.assert_allclose(np.asarray(q), level, atol=1e-6)
        np.testing.assert_allclose(np.asarray(res), res_ref, atol=1e-5)
        lv = np.array([-t, 0.0, t], np.float32)
        assert all(np.isclose(lv, v).any() for v in np.asarray(q))


def test_pack_unpack_2bit_roundtrip():
    import numpy as np
    from mxnet_tpu.kvstore.gradient_compression import pack_2bit, unpack_2bit

    t = 0.25
    rng = np.random.RandomState(1)
    levels = rng.choice([-t, 0.0, t], size=50).astype(np.float32)
    words = pack_2bit(levels, t)
    assert words.dtype == np.uint32 and len(words) == 4  # ceil(50/16)
    back = unpack_2bit(words, 50, t)
    np.testing.assert_allclose(back, levels)
    # 2 bits/element on the wire: 50 elems -> 4 words = 16 bytes vs 200
    assert words.nbytes * 8 >= 2 * 50


def test_kvstore_local_2bit_error_feedback_converges():
    """Single-process: compressed pushes never lose gradient mass — the
    cumulative pulled sum tracks the true sum within the threshold band,
    even for gradients far below the threshold."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import kvstore, nd

    kv = kvstore.create("local")
    t = 1.0
    kv.set_gradient_compression({"type": "2bit", "threshold": t})
    g = np.array([0.09, -0.21, 0.0, 0.35], np.float32)  # all |g| < t
    kv.init(3, nd.zeros((4,)))
    total = np.zeros(4, np.float32)
    for _ in range(40):
        kv.push(3, nd.array(g))
        out = nd.zeros((4,))
        kv.pull(3, out=out)
        levels = out.asnumpy()
        assert set(np.round(np.unique(levels), 5)) <= {-t, 0.0, t}
        total += levels
    true = 40 * g
    assert np.all(np.abs(total - true) <= t + np.abs(g).max() + 1e-5), \
        (total, true)


def test_2bit_compressed_dp_training_converges():
    """2-device DP with {'type': '2bit'}: final loss within a whisker of
    uncompressed training (threshold sits at raw-summed-grad scale — the
    same tuning contract as the reference's PS compression)."""
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import nd, autograd, gluon

    def train(compression):
        mx.random.seed(0)
        ctxs = [mx.cpu(0), mx.cpu(1)]
        net = gluon.nn.Dense(1)
        net.initialize(mx.init.Xavier(), ctx=ctxs)
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1}, kvstore="device",
                           compression_params=compression)
        rng = onp.random.RandomState(0)
        Xn = rng.randn(64, 4).astype("float32")
        w_true = onp.array([[1.0, -2.0, 0.5, 3.0]], "float32")
        yn = Xn @ w_true.T
        halves = [(nd.array(Xn[:32], ctx=ctxs[0]),
                   nd.array(yn[:32], ctx=ctxs[0])),
                  (nd.array(Xn[32:], ctx=ctxs[1]),
                   nd.array(yn[32:], ctx=ctxs[1]))]
        for _ in range(300):
            losses = []
            with autograd.record():
                for X, y in halves:
                    losses.append(((net(X) - y) ** 2).mean())
            for l in losses:
                l.backward()
            tr.step(64)
        return sum(float(l.asnumpy()) for l in losses) / 2

    plain = train(None)
    comp = train({"type": "2bit", "threshold": 5.0})
    # convergence delta bound: compressed within 2x of uncompressed + eps
    assert comp < 2 * plain + 0.1, (plain, comp)
