"""Attention + BERT tests (reference pattern: GluonNLP bert tests +
src/operator/contrib/transformer.cc op tests in test_operator.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo import bert as bert_mod


def _np_attention(q, k, v, scale, causal=False, mask=None):
    logits = np.einsum("bhqd,bhkd->bhqk", q, k).astype(np.float64) * scale
    if causal:
        Tq, Tk = q.shape[2], k.shape[2]
        cm = np.tril(np.ones((Tq, Tk), bool), Tk - Tq)
        logits = np.where(cm, logits, -np.inf)
    if mask is not None:
        logits = np.where(mask, logits, -1e30)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", p, v)


def test_attention_core_matches_numpy():
    from mxnet_tpu.ops.attention import attention_core
    np.random.seed(0)
    B, H, T, D = 2, 3, 8, 4
    q = np.random.randn(B, H, T, D).astype(np.float32)
    k = np.random.randn(B, H, T, D).astype(np.float32)
    v = np.random.randn(B, H, T, D).astype(np.float32)
    scale = 1.0 / np.sqrt(D)
    out = np.asarray(attention_core(q, k, v, scale=scale))
    ref = _np_attention(q, k, v, scale)
    assert np.allclose(out, ref, atol=1e-5)
    out_c = np.asarray(attention_core(q, k, v, scale=scale, causal=True))
    ref_c = _np_attention(q, k, v, scale, causal=True)
    assert np.allclose(out_c, ref_c, atol=1e-5)


# The shapes that take the flash kernels (ops/attention.flash_rule), run in
# Pallas interpret mode on the CPU against the jnp composition: head size
# 64 (BERT-base; two heads a 128-lane block in the packed layout) and 128,
# full and causal, float32 and bf16, in both layouts the kernels read -
# (B, H, T, D) as attention_core takes it and the packed (B, T, H*D) that
# multi_head_attention holds.
FLASH_CASES = [
    pytest.param(D, causal, dtype, layout, 512,
                 id="d%d-%s-%s-%s" % (D, "causal" if causal else "full",
                                      dtype, layout))
    for D in (64, 128) for causal in (False, True)
    for dtype in ("float32", "bfloat16") for layout in ("bhtd", "packed")
] + [
    # causal calls that cross more than one block in both directions: the
    # unmasked blocks below the diagonal, the masked ones on it, and the
    # one backward kernel summing dq over its key blocks.  768 is a
    # multiple of the 256-row unit only; the others of the 512-row block.
    pytest.param(D, True, dtype, layout, T,
                 id="d%d-causal-%s-%s-t%d" % (D, dtype, layout, T))
    for T in (768, 1024, 2048) for D in (128, 256)
    for dtype in ("float32", "bfloat16") for layout in ("bhtd", "packed")
    if T == 1024 or (D, dtype, layout) in (
        (128, "float32", "bhtd"), (128, "bfloat16", "packed"),
        (256, "bfloat16", "bhtd"), (256, "float32", "packed"))
] + [
    # ... and a long non-causal one: the same sum without a diagonal
    pytest.param(128, False, "bfloat16", "packed", 2048,
                 id="d128-full-bfloat16-packed-t2048")]


def _flash_case(D, dtype, layout, seed, T=512):
    """(q, k, v, g) as float32 arrays already rounded to `dtype`, the
    flash call on them in `layout`, and its float32 tolerance.  The long
    lengths run one row of one or two heads: interpret mode is slow."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as att
    B, H = (2, 4) if T == 512 else (1, 2 if T <= 1024 else 1)
    rng = np.random.RandomState(seed)
    q, k, v, g = (jnp.asarray(rng.randn(B, H, T, D), dtype)
                  .astype(jnp.float32) for _ in range(4))
    heads = H if layout == "packed" else None

    def lay(x):
        x = x.astype(dtype)
        return x.transpose(0, 2, 1, 3).reshape(B, T, H * D) if heads else x

    def unlay(x):
        return x.reshape(B, T, H, D).transpose(0, 2, 1, 3) if heads else x

    def call(fn, q, k, v, scale, causal):
        out = fn(lay(q), lay(k), lay(v), scale, causal, heads)
        if isinstance(out, tuple):
            return unlay(out[0]), out[1]
        return unlay(out)

    return (q, k, v, g), call


@pytest.mark.parametrize("D,causal,dtype,layout,T", FLASH_CASES)
def test_flash_forward_matches_jnp_cpu_interpret(D, causal, dtype, layout,
                                                 T):
    """Output and logsumexp residual against the composition."""
    from mxnet_tpu.ops import attention as att
    (q, k, v, _), call = _flash_case(D, dtype, layout, seed=0, T=T)
    scale = 1.0 / np.sqrt(D)
    out, lse = call(att._flash_fwd, q, k, v, scale, causal)
    assert out.dtype == np.dtype(dtype) or str(out.dtype) == dtype
    ref = _np_attention(np.asarray(q), np.asarray(k), np.asarray(v), scale,
                        causal=causal)
    tol = 2e-4 if dtype == "float32" else 0.05 * np.abs(ref).max()
    err = np.abs(np.asarray(out, np.float32) - ref).max()
    assert err < tol, err
    # lse residual: logsumexp of the scaled (masked) scores
    s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q), np.asarray(k)) * scale
    if causal:
        s = np.where(np.tril(np.ones(s.shape[-2:], bool)), s, -np.inf)
    lse_ref = np.log(np.exp(s - s.max(-1, keepdims=True)).sum(-1)) \
        + s.max(-1)
    assert lse.shape == lse_ref.shape and lse.dtype == np.float32
    assert np.allclose(lse, lse_ref, atol=1e-4 if dtype == "float32"
                       else 0.05), np.abs(lse - lse_ref).max()


@pytest.mark.parametrize("D,causal,dtype,layout,T", FLASH_CASES)
def test_flash_backward_matches_jnp_cpu_interpret(D, causal, dtype, layout,
                                                  T):
    """The blockwise Pallas backward (recompute-from-LSE, O(L) memory) must
    produce the same dq/dk/dv as differentiating the jnp composition;
    bf16 inputs (the MXU-native training dtype) give bf16 gradients near
    the float32 reference."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as att
    (q, k, v, g), call = _flash_case(D, dtype, layout, seed=1, T=T)
    scale = 1.0 / np.sqrt(D)

    def loss(q, k, v):
        out = call(att.flash_attention, q, k, v, scale, causal)
        assert str(out.dtype) == dtype
        return jnp.sum(out.astype(jnp.float32) * g)

    def loss_ref(q, k, v):
        return jnp.sum(att._attention_jnp(q, k, v, scale, causal) * g)

    got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, ("dq", "dk", "dv")):
        rel = np.abs(np.asarray(a) - np.asarray(b)).max() \
            / max(np.abs(np.asarray(b)).max(), 1e-6)
        assert rel < (2e-4 if dtype == "float32" else 0.05), (name, rel)


@pytest.mark.parametrize("D,causal,dtype,layout,T", [
    pytest.param(128, True, "float32", "bhtd", 1024, id="causal-t1024"),
    pytest.param(64, False, "bfloat16", "packed", 512, id="full-t512")])
def test_flash_backward_two_kernel_form_matches_jnp(monkeypatch, D, causal,
                                                    dtype, layout, T):
    """Where q, dO and dq do not fit in fast memory the backward is the
    dk/dv kernel and the dq kernel; no shape a CPU can interpret is that
    long, so the memory is taken away here."""
    from mxnet_tpu.ops import attention as att
    monkeypatch.setattr(att, "_FAST_MEMORY", 0)
    x = np.zeros((1, 1, T, D), dtype="float32")
    geo = att._Geometry(x, x, None)
    block_q, _, block_kv = geo.blocks(causal)
    assert geo.fused_backward(block_kv, block_q) is None
    test_flash_backward_matches_jnp_cpu_interpret(D, causal, dtype, layout,
                                                  T)


def test_flash_blocks_of_the_bert_shapes_are_pinned():
    """What the BERT cells' calls take - 512 positions, 12 heads of 64,
    non-causal: 512-row blocks, one key block, so one backward kernel
    that writes dq as it comes - is what it was before the causal calls
    got blocks of their own."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as att
    packed = jnp.zeros((32, 512, 768), jnp.bfloat16)   # multi_head_attention
    assert att._Geometry(packed, packed, 12).blocks(False) == (512, 512, 512)
    bhtd = jnp.zeros((32, 12, 512, 64), jnp.bfloat16)  # attention_core
    assert att._Geometry(bhtd, bhtd, None).blocks(False) == (512, 512, 512)
    # ... and neither asks Mosaic for more fast memory than it grants
    geo = att._Geometry(packed, packed, 12)
    assert att._Geometry.mosaic(geo.fused_backward(512, 512)) == {}
    assert att._Geometry.mosaic(geo.streamed(512, 512, 512)) == {}


def test_flash_with_lse_backpropagates_the_lse_cotangent():
    """Ring attention's building block, causal over two key blocks, with
    cotangents on BOTH outputs: the lse term runs the same backward
    kernel (dq summed over the key blocks) and must match the
    composition's gradient."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as att
    B, H, T, D = 1, 2, 1024, 128
    rng = np.random.RandomState(5)
    q, k, v, g = (jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
                  for _ in range(4))
    h = jnp.asarray(rng.randn(B, H, T), jnp.float32)
    scale = 1.0 / np.sqrt(D)
    assert att._Geometry(q, k, None).blocks(True)[2] * 2 == T

    def loss(q, k, v):
        out, lse = att.flash_attention_with_lse(q, k, v, scale, True)
        return jnp.sum(out * g) + jnp.sum(lse * h)

    def loss_ref(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        return jnp.sum(att._attention_jnp(q, k, v, scale, True) * g) \
            + jnp.sum(jax.nn.logsumexp(s, axis=-1) * h)

    got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, ("dq", "dk", "dv")):
        rel = np.abs(np.asarray(a) - np.asarray(b)).max() \
            / max(np.abs(np.asarray(b)).max(), 1e-6)
        assert rel < 2e-4, (name, rel)


def test_flash_rule_is_stated_once():
    """What takes the kernels: no mask, T a multiple of 256, head size 64
    or a multiple of 128, bf16/float32, square when causal - and the ring
    asks the same function."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as att
    from mxnet_tpu.parallel import ring
    rule = att.flash_rule
    assert rule(512, 512, 64) and rule(512, 512, 128) and rule(256, 512, 256)
    assert rule(512, 512, 64, causal=True, dtype=jnp.float32)
    assert not rule(128, 128, 64) and not rule(512, 384, 64)
    assert not rule(512, 512, 96) and not rule(512, 512, 32)
    assert not rule(512, 512, 64, mask=np.ones((1, 1, 512, 512)))
    assert not rule(256, 512, 64, causal=True)
    assert not rule(512, 512, 64, dtype=jnp.float16)
    q = jnp.zeros((1, 512, 2, 64), jnp.bfloat16)       # (B, L, H, D) shard
    assert not att.use_flash(512, 512, 64)              # the CPU: composition
    assert not ring._flash_ok(q, q)
    with att.attention_impl_scope("pallas"):
        assert att.use_flash(512, 512, 64) and ring._flash_ok(q, q)
        assert not ring._flash_ok(q[:, :128], q[:, :128])
    with att.attention_impl_scope("xla"):
        assert not att.use_flash(512, 512, 64)


def test_mha_takes_packed_kernels_without_transposes():
    """multi_head_attention hands its (B, T, H*D) tensors to the kernels
    as they are; a masked or a short call lowers to the composition's own
    program, letter for letter."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as att
    from mxnet_tpu.ops.nn import _mha
    x = jnp.asarray(np.random.RandomState(3).randn(2, 256, 128), jnp.float32)

    def text(impl, **kw):
        with att.attention_impl_scope(impl):
            return jax.jit(lambda q: _mha(q, q, q, num_heads=2, **kw)) \
                .lower(x).as_text()

    heads_first = "dims = [0, 2, 1, 3]"     # (B, T, H, D) <-> (B, H, T, D)
    assert heads_first not in text("pallas") and heads_first in text("xla")
    with att.attention_impl_scope("pallas"):
        got = _mha(x, x, x, num_heads=2)
    with att.attention_impl_scope("xla"):
        want = _mha(x, x, x, num_heads=2)
    assert np.allclose(got, want, atol=2e-4)
    mask = jnp.ones((2, 1, 256, 256), bool)
    assert text("pallas", mask=mask) == text("xla", mask=mask)
    short = jax.jit(lambda q: _mha(q, q, q, num_heads=2))
    with att.attention_impl_scope("pallas"):
        a = short.lower(x[:, :128]).as_text()
    with att.attention_impl_scope("xla"):
        b = short.lower(x[:, :128]).as_text()
    assert a == b


def test_flash_runs_per_shard_under_a_layout():
    """Inside attention_partition_scope the kernels run under shard_map
    over the layout's batch axes (GSPMD cannot partition them), and an op
    program traced there is not handed to a trace outside."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as att
    from mxnet_tpu.parallel import SpecLayout, make_mesh
    mesh = make_mesh(axes=("data", "fsdp"), shape=(2, 2),
                     devices=jax.devices()[:4])
    layout = SpecLayout.infer(mesh)
    x = jnp.asarray(np.random.RandomState(4).randn(4, 256, 128), jnp.float32)

    def f(q):
        return att.attention_heads(q, q, q, 2)

    with att.attention_impl_scope("pallas"):
        plain = jax.make_jaxpr(f)(x)
        with att.attention_partition_scope(layout):
            sharded = jax.make_jaxpr(f)(x)
            xs = jax.device_put(x, layout.batch_sharding())
            got = jax.jit(f)(xs)
        again = jax.make_jaxpr(f)(x)
        want = f(x)
    assert "shard_map" in str(sharded)
    assert "shard_map" not in str(plain) and "shard_map" not in str(again)
    assert got.sharding.is_equivalent_to(layout.batch_sharding(), 3)
    assert np.allclose(got, want, atol=2e-4)
    # a batch the mesh does not divide stays whole
    with att.attention_impl_scope("pallas"), \
            att.attention_partition_scope(layout):
        assert "shard_map" not in str(jax.make_jaxpr(f)(x[:3]))


@pytest.mark.parametrize("mesh_shape", [None, (2, 2)],
                         ids=["one-device", "data2-fsdp2"])
def test_bert_compiled_step_takes_flash_and_matches_xla(mesh_shape):
    """A BERT with 64-wide heads at T = 256 through the compiled train
    step, the kernels forced (the CPU interprets) against the composition:
    the same losses.  Under a data,fsdp layout CompiledStep's partition
    scope puts the kernels under shard_map."""
    import jax
    from mxnet_tpu.ops import attention as att
    from mxnet_tpu.parallel import SpecLayout, make_mesh
    rng = np.random.RandomState(0)
    tok = rng.randint(0, 50, (4, 256))
    lab = rng.randint(0, 50, (4, 256)).astype("float32")
    sce = gluon.loss.SoftmaxCrossEntropyLoss()
    seen = {"flash": 0, "sharded": 0}
    orig = att.flash_attention

    def spy(q, *a, **kw):
        seen["flash"] += 1
        seen["sharded"] += q.shape[0] == 1     # 4 rows over 2 x 2 devices
        return orig(q, *a, **kw)

    def losses(impl):
        mx.random.seed(0)
        net = bert_mod.get_bert(num_layers=1, units=128, num_heads=2,
                                vocab_size=50, max_length=256, dropout=0.0,
                                use_classifier=False)
        net.initialize(mx.init.Normal(0.02))
        net.hybridize()
        two = mx.nd.zeros((2, 256), dtype="int32")
        net(two, two)
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.5, "momentum": 0.9})
        layout = None if mesh_shape is None else SpecLayout.infer(
            make_mesh(axes=("data", "fsdp"), shape=mesh_shape,
                      devices=jax.devices()[:4]))
        step = trainer.make_compiled_step(
            net, lambda outs, y: sce(outs[-1], y), layout=layout)
        data = (mx.nd.array(tok, dtype="int32"),
                mx.nd.zeros((4, 256), dtype="int32"))
        with att.attention_impl_scope(impl):
            out = [float(step.step(data, mx.nd.array(lab)).asnumpy().mean())
                   for _ in range(3)]
        assert step.compiled, step.fallback_reason
        return out

    want = losses("xla")
    att.flash_attention = spy
    try:
        got = losses("pallas")
    finally:
        att.flash_attention = orig
    assert seen["flash"] >= 1
    assert seen["sharded"] == (seen["flash"] if mesh_shape else 0)
    assert got[-1] < got[0]
    assert np.allclose(got, want, rtol=2e-4), (got, want)


def test_interleaved_selfatt_ops():
    """interleaved_matmul_selfatt_qk + valatt == plain attention."""
    np.random.seed(0)
    T, N, H, D = 6, 2, 2, 4
    qkv = np.random.randn(T, N, H * 3 * D).astype(np.float32)
    s = mx.nd.invoke("_contrib_interleaved_matmul_selfatt_qk",
                     mx.nd.array(qkv), heads=H)
    att = s.softmax(axis=-1)
    out = mx.nd.invoke("_contrib_interleaved_matmul_selfatt_valatt",
                       mx.nd.array(qkv), att, heads=H)
    assert out.shape == (T, N, H * D)
    # reference: deinterleave manually
    x = qkv.reshape(T, N, H, 3, D)
    q = x[:, :, :, 0].transpose(1, 2, 0, 3)
    k = x[:, :, :, 1].transpose(1, 2, 0, 3)
    v = x[:, :, :, 2].transpose(1, 2, 0, 3)
    ref = _np_attention(q, k, v, 1.0 / np.sqrt(D))
    ref = ref.transpose(2, 0, 1, 3).reshape(T, N, H * D)
    assert np.allclose(out.asnumpy(), ref, atol=1e-4)


def test_mha_block():
    np.random.seed(0)
    blk = bert_mod.MultiHeadAttention(units=16, num_heads=4)
    blk.initialize(mx.init.Xavier())
    x = mx.nd.array(np.random.randn(2, 5, 16).astype(np.float32))
    out = blk(x)
    assert out.shape == (2, 5, 16)


def test_bert_tiny_forward_and_heads():
    net = bert_mod.get_bert(num_layers=2, units=32, num_heads=4,
                            vocab_size=100, max_length=16, dropout=0.0)
    net.initialize(mx.init.Normal(0.02))
    tokens = mx.nd.array(np.random.randint(0, 100, (3, 10)).astype(np.float32))
    segments = mx.nd.array(np.zeros((3, 10), np.float32))
    seq, pooled, nsp, mlm = net(tokens, segments)
    assert seq.shape == (3, 10, 32)
    assert pooled.shape == (3, 32)
    assert nsp.shape == (3, 2)
    assert mlm.shape == (3, 10, 100)


def test_bert_valid_length_masks_padding():
    net = bert_mod.get_bert(num_layers=1, units=16, num_heads=2,
                            vocab_size=50, max_length=8, dropout=0.0,
                            use_decoder=False, use_classifier=False)
    net.initialize(mx.init.Normal(0.02))
    tok = np.random.randint(1, 50, (1, 6)).astype(np.float32)
    vl = mx.nd.array([4.0])
    seq1, _ = net(mx.nd.array(tok), None, vl)
    # changing a padded token must not change valid positions' output
    tok2 = tok.copy()
    tok2[0, 5] = (tok2[0, 5] + 7) % 50
    seq2, _ = net(mx.nd.array(tok2), None, vl)
    assert np.allclose(seq1.asnumpy()[:, :4], seq2.asnumpy()[:, :4],
                       atol=1e-5)


def test_bert_mlm_training_descends():
    np.random.seed(0)
    mx.random.seed(0)
    V = 30
    net = bert_mod.get_bert(num_layers=1, units=16, num_heads=2,
                            vocab_size=V, max_length=8, dropout=0.0,
                            use_pooler=False, use_classifier=False)
    net.initialize(mx.init.Normal(0.05))
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.01})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    tokens = np.random.randint(0, V, (8, 8)).astype(np.float32)
    first = last = None
    for _ in range(15):
        x = mx.nd.array(tokens)
        with autograd.record():
            seq, mlm = net(x)
            loss = loss_fn(mlm.reshape((-1, V)),
                           mx.nd.array(tokens.reshape(-1))).mean()
        loss.backward()
        trainer.step(1)
        v = float(loss.asscalar())
        first = first if first is not None else v
        last = v
    assert last < first * 0.5, (first, last)


def test_optimize_for_selects_attention_lowering():
    """optimize_for(backend) must actually change the attention dispatch
    (VERDICT: previously a recorded string with no effect)."""
    import warnings
    from mxnet_tpu.ops import attention as att
    np.random.seed(0)
    B, H, T, D = 1, 1, 256, 128
    q = np.random.randn(B, H, T, D).astype(np.float32)
    k = np.random.randn(B, H, T, D).astype(np.float32)
    v = np.random.randn(B, H, T, D).astype(np.float32)

    calls = {"flash": 0}
    orig_flash = att.flash_attention

    def spy(*a, **kw):
        calls["flash"] += 1
        return orig_flash(*a, **kw)

    att.flash_attention = spy
    try:
        att.set_attention_impl("xla")
        att.attention_core(q, k, v)
        assert calls["flash"] == 0          # forced OFF even when aligned
        att.set_attention_impl("pallas")
        out_p = np.asarray(att.attention_core(q, k, v))
        assert calls["flash"] == 1          # forced ON even on CPU
    finally:
        att.flash_attention = orig_flash
        att.set_attention_impl(None)
    out_x = np.asarray(att.attention_core(q, k, v))
    assert np.allclose(out_p, out_x, atol=2e-4)

    # the Block surface stamps a PER-BLOCK property (never the global);
    # unknown backends warn
    net = nn.Dense(4, in_units=8)
    net.initialize()
    x = mx.nd.ones((2, 8))
    net.optimize_for(x, backend="pallas")
    assert att._FORCED_IMPL is None          # global untouched
    assert net._backend == "pallas"
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        net.optimize_for(x, backend="tensorrt")
    assert any("unknown subgraph backend" in str(x.message) for x in w)
    assert net._backend is None
