"""The dtype rule (ops/nn.py:_rms_norm, PR 38): a norm's result has its
DATA's dtype, whatever dtype its gain is kept in.  `Mamba2Mixer` keeps its
grouped norm's gain float32 in a cast net; before the rule that one gain
promoted the mixer's output, the residual stream and with it every
activation of `NemotronH` after its first scan layer to float32.  Held
here: the operator on mixed and on equal dtypes (equal: bit for bit the
expression it was, written out below), the three decoder zoos block by
block after ``cast("bfloat16")``, and a walk of the cast Nemotron
program for a product that reads a float32 activation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.block import functionalize
from mxnet_tpu.gluon.model_zoo import glm_moe_lite, laguna, nemotron_h
from mxnet_tpu.ops import nn as ops_nn
from mxnet_tpu.ops.registry import register

CTX = mx.cpu()


# -- the operator -------------------------------------------------------------

def rms_norm_as_it_was(data, gamma, axis=-1, eps=1e-6):
    """`ops/nn.py:_rms_norm` before PR 38: the result takes the PROMOTION
    of data and gain."""
    x32 = data.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=axis, keepdims=True)
    return (x32 * lax.rsqrt(ms + eps)).astype(data.dtype) * gamma


def _operands(shape, data_dtype, gain_dtype, seed=0):
    """Data of `shape` and a gain over its last axis (over its last two
    where it has four: the mixer's grouped norm)."""
    rng = np.random.RandomState(seed)
    gain = shape[-2:] if len(shape) == 4 else shape[-1:]
    return (jnp.asarray(rng.randn(*shape) * 3.0, data_dtype),
            jnp.asarray(1.0 + 0.5 * rng.randn(*gain), gain_dtype))


def _primitives(fn, *args):
    return [eqn.primitive.name for eqn in jax.make_jaxpr(fn)(*args).eqns]


@pytest.mark.parametrize("shape", [(2, 48, 64), (2, 48, 2, 32)],
                         ids=["block", "grouped"])
def test_a_float32_gain_returns_the_datas_bfloat16(shape):
    """bf16 data, float32 gain: bf16 back, within one bf16 ulp of the
    whole computation in float32 (the gain applied before the one
    rounding); as it was the result was float32."""
    data, gamma = _operands(shape, jnp.bfloat16, jnp.float32)
    assert rms_norm_as_it_was(data, gamma, eps=1e-5).dtype == jnp.float32
    got = ops_nn._rms_norm(data, gamma, eps=1e-5)
    assert got.dtype == jnp.bfloat16 and got.shape == data.shape
    want = np.asarray(rms_norm_as_it_was(data.astype(jnp.float32), gamma,
                                         eps=1e-5))
    # a bf16 value in [2^e, 2^(e+1)) has 7 fraction bits: ulp 2^(e-7)
    ulp = 2.0 ** (np.floor(np.log2(np.abs(want) + 1e-30)) - 7)
    assert (np.abs(np.asarray(got, np.float32) - want) <= ulp).all()
    # and a float32 net that keeps a gain in bf16 stays float32
    back = ops_nn._rms_norm(data.astype(jnp.float32),
                            gamma.astype(jnp.bfloat16), eps=1e-5)
    assert back.dtype == jnp.float32


def test_the_block_and_the_eager_operator_follow_the_rule():
    """Through `nd` (what an eager user calls) and `nn.RMSNorm` with its
    gain left float32 beside bf16 data."""
    data, gamma = _operands((2, 8, 16), jnp.bfloat16, jnp.float32)
    x = nd.array(np.asarray(data, np.float32), ctx=CTX).astype("bfloat16")
    out = nd.RMSNorm(x, nd.array(np.asarray(gamma), ctx=CTX), eps=1e-5)
    assert out.dtype == jnp.bfloat16
    block = nn.RMSNorm(16, 1e-5)
    block.initialize(ctx=CTX)
    assert block.gamma.dtype == np.float32 and block(x).dtype == jnp.bfloat16
    block.cast("bfloat16")
    assert block(x).dtype == jnp.bfloat16


@pytest.mark.parametrize("shape", [(2, 48, 64), (2, 48, 2, 32)],
                         ids=["block", "grouped"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float16", "float32"])
def test_operands_of_one_dtype_run_the_expression_it_was(dtype, shape):
    """Data and gain of one dtype (every `RMSNorm` of the GLM and Laguna
    nets, cast with the net): the result bit for bit, the gradients bit
    for bit, and the same primitives in the same order - no
    `convert_element_type` more."""
    data, gamma = _operands(shape, dtype, dtype)
    got = ops_nn._rms_norm(data, gamma, eps=1e-5)
    want = rms_norm_as_it_was(data, gamma, eps=1e-5)
    assert got.dtype == want.dtype == jnp.dtype(dtype)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))

    def loss(fn):
        return lambda d, g: (fn(d, g, eps=1e-5).astype(jnp.float32)
                             * jnp.arange(d.shape[-1])).sum()

    mine, theirs = loss(ops_nn._rms_norm), loss(rms_norm_as_it_was)
    for g, w in zip(jax.grad(mine, (0, 1))(data, gamma),
                    jax.grad(theirs, (0, 1))(data, gamma)):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))
    assert _primitives(mine, data, gamma) == _primitives(theirs, data, gamma)
    assert _primitives(jax.grad(mine, (0, 1)), data, gamma) \
        == _primitives(jax.grad(theirs, (0, 1)), data, gamma)


# -- the three decoder zoos, cast ---------------------------------------------

def _glm():
    return glm_moe_lite.GLMMoeLite(
        vocab_size=96, units=64, num_layers=3, first_dense=1, num_heads=4,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=24,
        qk_rope_head_dim=8, v_head_dim=32, hidden_size=128,
        moe_hidden_size=48, num_experts=8, top_k=2, num_shared=1,
        routed_scale=1.8, held=(2, 3), epsilon=1e-5, num_mtp=1)


def _nemotron():
    return nemotron_h.NemotronH(
        vocab_size=96, units=64, pattern="MEM*E", mamba_heads=4,
        mamba_head_dim=8, state_size=16, mamba_groups=2, chunk_size=16,
        num_heads=4, num_kv_heads=1, head_dim=16, moe_hidden_size=24,
        moe_latent_size=32, shared_hidden_size=40, num_experts=16, top_k=5,
        held=(2, 3, 4), num_mtp=1, mtp_pattern="*E")


def _laguna():
    rope = dict(laguna.ROPE_XS_2)
    rope["full_attention"] = dict(rope["full_attention"], beta_fast=4,
                                  original_max_position_embeddings=16)
    return laguna.Laguna(
        vocab_size=96, units=48, num_layers=3,
        layer_types=("full_attention", "sliding_attention",
                     "sliding_attention"),
        mlp_layer_types=("dense", "sparse", "sparse"),
        num_heads_per_layer=(6, 8, 8), num_kv_heads=2, head_dim=16,
        sliding_window=8, rope_parameters=rope, hidden_size=96,
        moe_hidden_size=24, shared_hidden_size=24, num_experts=16, top_k=4,
        held=(4, 5, 6, 7))


ZOOS = {"glm_moe_lite": _glm, "nemotron_h": _nemotron, "laguna": _laguna}
ROWS, SEQ = 2, 48


def _cast(net):
    mx.random.seed(11)
    net.initialize(mx.init.Normal(0.05), ctx=CTX)
    net.cast("bfloat16")
    return net


def _ids():
    return np.random.RandomState(0).randint(
        0, 96, (ROWS, SEQ)).astype(np.int32)


def _floating(out):
    """The floating results of a block (a router's choices are int32)."""
    outs = out if isinstance(out, (tuple, list)) else (out,)
    return [o for o in outs if jnp.issubdtype(o.dtype, jnp.floating)]


@pytest.mark.parametrize("zoo", sorted(ZOOS))
def test_a_cast_decoder_runs_in_the_dtype_it_was_cast_to(zoo):
    """After ``cast("bfloat16")`` EVERY block of the net - each layer, each
    mixer and norm inside it, the MTP module, both heads - returns
    bfloat16, whatever its parameters are kept in (float32: every router's
    matrix; `Mamba2Mixer`'s dt_bias, A_log, D and its norm's gain), and the
    gradients of the loss reach every parameter in that parameter's
    dtype."""
    net = _cast(ZOOS[zoo]())
    seen = {}

    def watch(block, path):
        block.register_forward_hook(
            lambda _b, _inputs, out: seen.setdefault(path, []).extend(
                str(o.dtype) for o in _floating(out)))
        for name, child in block._children.items():
            watch(child, path + "." + name if path else name)

    watch(net, "")
    net.hybridize()          # one trace: the hooks see the tracers' dtypes
    loss_fn = glm_moe_lite.NextTokenLoss(0.3)
    ids = nd.array(_ids(), ctx=CTX, dtype="int32")
    with autograd.record():
        outs = net(ids)
        loss = loss_fn(outs, ids).mean()
    loss.backward()
    heads = outs if isinstance(outs, tuple) else (outs,)
    assert len(heads) == (1 if zoo == "laguna" else 2)
    assert all(h.dtype == jnp.bfloat16 for h in heads)
    wrong = {path: dtypes for path, dtypes in seen.items()
             if set(dtypes) != {"bfloat16"}}
    assert not wrong, wrong
    layers = [path for path in seen if path.startswith("blocks.")
              and path.count(".") == 1]
    assert len(layers) == len(net.blocks) >= 3
    assert ("mtp" in seen) == (zoo != "laguna") and "lm_head" in seen
    kept = {name: p for name, p in net.collect_params().items()
            if p.grad_req != "null"}
    float32 = sorted({name.rsplit(".", 1)[-1] for name, p in kept.items()
                      if p.dtype == np.float32})
    assert float32 == (["A_log", "D", "dt_bias", "norm_gamma"]
                       if zoo == "nemotron_h" else []) + ["router_weight"]
    for name, p in kept.items():
        grad = p.grad()
        assert grad.dtype == p.data().dtype, name
        values = np.asarray(grad._jax, np.float32)
        assert np.isfinite(values).all() and np.abs(values).max() > 0, name


# -- no product reads a float32 activation ------------------------------------

def _equations(jaxpr):
    """Every equation under `jaxpr`, those of its sub-programs (jit,
    checkpoint, custom_vjp, cond, scan) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (tuple, list))
                        else (param,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


GROUPED_KERNELS = "grouped_product"      # ops/grouped.py's pallas_calls


def _product_operands(eqn):
    """(what the product is, its two operands' avals), or None for an
    equation that is no matrix product.  A grouped product - a ragged
    dot, or a call of the grouped kernels that take its place on a chip
    (their operands follow the scalar-prefetched integer maps) - is
    `grouped`: its rows are gathered tokens whatever their number."""
    name = eqn.primitive.name
    if name == "dot_general":
        return "dense", [v.aval for v in eqn.invars[:2]]
    if name in ("ragged_dot", "ragged_dot_general"):
        return "grouped", [v.aval for v in eqn.invars[:2]]
    if name == "pallas_call" \
            and eqn.params["name"].startswith(GROUPED_KERNELS):
        return "grouped", [v.aval for v in eqn.invars
                           if jnp.issubdtype(v.aval.dtype, jnp.floating)][:2]
    return None


def _float32_activation_products(jaxpr):
    """(primitive, scope, operand shapes) of every matrix product under
    `jaxpr` that takes a float32 operand of the activations' shape - its
    leading axes the rows and positions, or their product; the rows of a
    grouped product, however many the buffer has - outside the router's
    scope (``moe/route`` scores in float32, as published)."""
    found = []
    for eqn in _equations(jaxpr):
        scope = str(eqn.source_info.name_stack)
        product = _product_operands(eqn)
        if product is None or "route" in scope.split("/"):
            continue
        kind, operands = product
        if any(a.dtype == jnp.float32 and (a.shape[:2] == (ROWS, SEQ)
                                           or a.shape[:1] == (ROWS * SEQ,))
               for a in operands) \
                or (kind == "grouped" and any(
                    a.dtype == jnp.float32 and a.ndim == 2
                    for a in operands)):
            found.append((eqn.primitive.name, scope,
                          [tuple(a.shape) for a in operands]))
    return found


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["ragged-dot", "grouped-kernels"])
def test_the_walk_sees_a_float32_row_of_a_grouped_product(monkeypatch,
                                                          kernels):
    """The expert layer's products, forward and backward, by
    ``lax.ragged_dot`` and by the grouped kernels that stand in its place
    on a chip: float32 rows are found in every one of them - the rows, the
    cotangent the row kernel reads, both operands of the weight kernel -
    and bf16 rows in none."""
    from mxnet_tpu.ops import grouped
    if kernels:
        monkeypatch.setattr(grouped, "grouped_product", grouped._product)
    counts = jnp.asarray([100, 0, 156], jnp.int32)

    def products(dtype):
        rows = jnp.zeros((grouped._ROW_TILE, 128), dtype)
        w = jnp.zeros((3, 128, 256), dtype)
        return _float32_activation_products(jax.make_jaxpr(jax.grad(
            lambda r, w: grouped.grouped_product(r, w, counts)
            .astype(jnp.float32).sum(), (0, 1)))(rows, w).jaxpr)

    assert len(products(jnp.float32)) == 3
    assert not products(jnp.bfloat16)


def _program(net):
    """The jaxpr of the net's loss and its gradients, training mode."""
    fn, params = functionalize(net)
    ids = jnp.asarray(_ids())

    def loss(values):
        main, mtp = fn(values, ids, training=True)
        return main.astype(jnp.float32).mean() \
            + mtp.astype(jnp.float32).mean()

    return jax.make_jaxpr(jax.value_and_grad(loss))(params).jaxpr


def test_no_product_of_the_cast_nemotron_reads_a_float32_activation():
    """Forward + backward of the cast net: outside ``moe/route`` no
    `dot_general` and no ragged product takes a float32 operand of the
    activations' shape (the scan's float32 parts - decays, running sums,
    the carried state - are no such operand: its four products take the
    inputs' dtype).  So the next parameter kept float32 under `cast`
    cannot promote the stream unseen."""
    found = _float32_activation_products(_program(_cast(_nemotron())))
    assert not found, found
    # and the walk does see the promoted stream where there is one: the
    # mixer's float32 gain under the expression as it was
    net = _cast(_nemotron())
    register("RMSNorm", rms_norm_as_it_was, replace=True)
    try:
        leaked = _float32_activation_products(_program(net))
    finally:
        register("RMSNorm", ops_nn._rms_norm, replace=True)
    assert len(leaked) > 20, leaked
