"""The expert layer routes without a scalar gather (parallel/moe.py, PR 36):
the chosen scores, their cotangent and the dispatch's index maps are
compares and sums over the small axis, and each is held here to the gather
it replaced - written out below, where the layer no longer has it."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mxnet_tpu import base
from mxnet_tpu.parallel import moe

KEEP = jax.checkpoint_policies.save_only_these_names(base.RECOMPUTE_KEEP)
# published experts, choices a token: the GLM, Laguna and Nemotron cells'
SHAPES = [(64, 4), (256, 8), (512, 22)]
TOKENS, WIDTH = 96, 48


def gathered_route(x, router_w, bias, top_k, scale=1.0, norm_topk_prob=True):
    """`moe.topk_route` as it was: the chosen scores a gather of N * k
    scalars (whose transpose is a scatter-add into the (N, E) table)."""
    with jax.default_matmul_precision("highest"):
        logits = jnp.dot(x.astype(jnp.float32),
                         router_w.astype(jnp.float32).T)
    scores = jax.nn.sigmoid(logits)
    _, idx = lax.top_k(scores + lax.stop_gradient(bias.astype(jnp.float32)),
                       top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_topk_prob:
        chosen = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), chosen * scale


def _router(experts, dtype, ties, seed=3):
    """Tokens, router matrix, correction; with `ties` every expert has a
    twin of equal weights and correction, so every score is an exact tie
    and the top-k's k-th choice falls between equals."""
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(TOKENS, WIDTH), dtype)
    router_w = rng.randn(experts, WIDTH).astype(np.float32) * WIDTH ** -0.5
    bias = rng.randn(experts).astype(np.float32) * 0.02
    if ties:
        router_w[1::2], bias[1::2] = router_w[::2], bias[::2]
    return x, jnp.asarray(router_w), jnp.asarray(bias)


@pytest.mark.parametrize("ties", [False, True], ids=["drawn", "ties"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("norm", [False, True], ids=["plain", "normalised"])
@pytest.mark.parametrize("experts,top_k", SHAPES)
def test_the_route_is_the_gathered_one(experts, top_k, norm, dtype, ties):
    """The same experts, and their scores the gather's bit for bit; the
    normalised weights to float32 rounding (XLA sums the k terms in
    another order once their producer changes)."""
    x, router_w, bias = _router(experts, dtype, ties)
    scale = 2.5 if norm else 1.0
    idx, weights = jax.jit(
        lambda *a: moe.topk_route(*a, top_k, scale, norm))(x, router_w, bias)
    want_idx, want = jax.jit(
        lambda *a: gathered_route(*a, top_k, scale, norm))(x, router_w, bias)
    assert idx.dtype == jnp.int32 and weights.dtype == jnp.float32
    np.testing.assert_array_equal(idx, want_idx)
    # a token's k experts are distinct, ties or not: what the cotangent's
    # "at most one term a cell" rests on
    assert all(len(set(row)) == top_k for row in np.asarray(idx))
    if norm:
        np.testing.assert_allclose(weights, want, rtol=1e-6, atol=0)
    else:
        np.testing.assert_array_equal(weights, want)


@pytest.mark.parametrize("norm", [False, True], ids=["plain", "normalised"])
@pytest.mark.parametrize("experts,top_k", SHAPES)
def test_the_routes_gradients_are_the_gathered_ones(experts, top_k, norm):
    """By the tokens and the router's matrix, against ``jax.grad`` of the
    gather form (whose transpose is the scatter-add); the selection
    correction gets none."""
    x, router_w, bias = _router(experts, "float32", ties=False)
    weigh = jnp.asarray(np.random.RandomState(4).randn(TOKENS, top_k),
                        jnp.float32)

    def grads(route):
        return jax.jit(jax.grad(
            lambda *a: (route(*a, top_k, 2.5, norm)[1] * weigh).sum(),
            argnums=(0, 1, 2)))(x, router_w, bias)

    got, want = grads(moe.topk_route), grads(gathered_route)
    for g, w, name in zip(got[:2], want[:2], ("tokens", "router")):
        scale = float(jnp.abs(w).max())
        assert scale > 0
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-6 * scale,
                                   err_msg=name)
    assert not np.asarray(got[2]).any()


def test_the_chosen_scores_cotangent_is_the_scatter_add():
    """`_chosen_scores` alone, at the table: the chosen sigmoids are the
    gather's bit for bit, and every (token, expert) cell of the logits'
    cotangent is the scatter-add's through the sigmoid's derivative (to
    float32 rounding; the cells no choice names are 0) - from the chosen
    scores alone, so the backward pass reads no (N, E) table."""
    rng = np.random.RandomState(5)
    logits = jnp.asarray(rng.randn(TOKENS, 64), jnp.float32)
    idx = jnp.asarray(np.argsort(rng.rand(TOKENS, 64), axis=1)[:, :6],
                      jnp.int32)
    g = jnp.asarray(rng.randn(TOKENS, 6), jnp.float32)
    got, pull = jax.vjp(lambda l: moe._chosen_scores(l, idx, 64), logits)
    want, pull_gathered = jax.vjp(
        lambda l: jnp.take_along_axis(jax.nn.sigmoid(l), idx, axis=-1),
        logits)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(pull(g)[0], pull_gathered(g)[0], rtol=1e-6,
                               atol=0)
    backward = jax.make_jaxpr(lambda g: pull(g)[0])(g)
    assert not [v for v in backward.jaxpr.constvars
                if v.aval.shape == logits.shape]


@pytest.mark.parametrize("held", [(3, 9), (0, 1, 2, 3), (15,), (7, 2, 11)])
def test_an_assignments_place_among_the_held_experts(held):
    """`_slot_of` against the table lookup it replaced, for sets of held
    experts that are no prefix of the ids and not in order; and the layer's
    counts are those experts' assignments."""
    experts, top_k = 16, 4
    rng = np.random.RandomState(6)
    idx = np.argsort(rng.rand(TOKENS, experts), axis=1)[:, :top_k] \
        .astype(np.int32)
    table = np.full((experts,), len(held), np.int32)
    table[np.asarray(held)] = np.arange(len(held))
    got = jax.jit(lambda i: moe._slot_of(i, held))(jnp.asarray(idx.ravel()))
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(got, table[idx.ravel()])
    d, f = 16, 8
    y, counts, elsewhere, _ = moe.held_expert_ffn(
        jnp.asarray(rng.randn(TOKENS, d), jnp.float32), jnp.asarray(idx),
        jnp.full((TOKENS, top_k), 0.25, jnp.float32),
        jnp.asarray(rng.randn(len(held), d, 2 * f), jnp.float32),
        jnp.asarray(rng.randn(len(held), f, d), jnp.float32), held, experts)
    np.testing.assert_array_equal(counts, [(idx == e).sum() for e in held])
    assert float(elsewhere) == idx.size - float(counts.sum())
    assert bool(jnp.isfinite(y).all())


def test_a_held_expert_that_is_not_published_is_refused():
    x = jnp.zeros((8, 4))
    with pytest.raises(ValueError, match="held experts"):
        moe.held_expert_ffn(x, jnp.zeros((8, 2), jnp.int32),
                            jnp.zeros((8, 2)), jnp.zeros((1, 4, 8)),
                            jnp.zeros((1, 4, 4)), (16,), 16)


# tokens, top-k, held, published: a short buffer and the exact one (k <= H:
# GLM's and Laguna's kind; k > H: Nemotron's), and a layer with one path
LAYERS = {"k-under-h": (1024, 2, 4, 32), "k-over-h": (1024, 6, 2, 32),
          "one-path": (64, 2, 4, 8)}


def _layer(case, dtype="float32"):
    n, k, h, experts = LAYERS[case]
    d, f = 32, 16
    rng = np.random.RandomState(7)
    args = (jnp.asarray(rng.randn(n, d), dtype),
            jnp.asarray(rng.randn(experts, d) * d ** -0.5, jnp.float32),
            jnp.asarray(rng.randn(experts) * 0.02, jnp.float32),
            jnp.asarray(rng.randn(h, d, 2 * f) * d ** -0.5, dtype),
            jnp.asarray(rng.randn(h, f, d) * f ** -0.5, dtype))
    out_weight = jnp.asarray(rng.randn(n, d), jnp.float32)

    def layer(*a):
        return moe.token_choice_moe(*a, held=tuple(range(h)), top_k=k,
                                    scale=2.5)

    def loss(fn):
        return lambda *a: (fn(*a)[0].astype(jnp.float32) * out_weight).sum()

    return layer, loss, args


@pytest.mark.parametrize("case", sorted(LAYERS))
def test_a_recomputed_layer_keeps_the_chosen_scores(case):
    """Under ``jax.checkpoint`` with `Block.recompute`'s policy the layer
    keeps one value more than its choice and its sort order - the chosen
    scores, 4 N k bytes - and its result and gradients are the
    unrecomputed ones."""
    n, k, h, _ = LAYERS[case]
    layer, loss, args = _layer(case)
    by = (0, 1, 3, 4)
    plain = jax.jit(jax.value_and_grad(loss(layer), argnums=by))(*args)
    with base.recomputed_block_trace(), base.recompute_tally() as kept:
        step = jax.jit(jax.value_and_grad(
            loss(jax.checkpoint(layer, policy=KEEP)), argnums=by))
        recomputed = step(*args)
    # idx, the chosen scores, order, position, sizes
    assert kept.values == 5
    assert kept.bytes == 4 * (n * k + n * k + 2 * n * k + h + 1)
    for got, want in zip(jax.tree_util.tree_leaves(recomputed),
                         jax.tree_util.tree_leaves(plain)):
        np.testing.assert_allclose(got, want, rtol=1e-6,
                                   atol=1e-6 * float(jnp.abs(want).max()))


def _scalar_index_ops(jaxpr, found):
    """(primitive, indices) of every gather and scatter of single scalars
    under `jaxpr`.  Of a ``cond`` only the last branch is walked: the
    layer's conditionals are (exact buffer, short buffer), and the exact
    one - the rare branch - may keep its worst-case gathers."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        scalars = False
        if name == "gather":
            scalars = int(np.prod(eqn.params["slice_sizes"])) == 1
        elif name.startswith("scatter"):
            updates = eqn.invars[2].aval.shape
            scalars = all(updates[d] == 1 for d in eqn.params[
                "dimension_numbers"].update_window_dims)
        if scalars:
            found.append((name, int(np.prod(eqn.invars[1].aval.shape[:-1]))))
        if name == "cond":
            _scalar_index_ops(eqn.params["branches"][-1].jaxpr, found)
            continue
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (tuple, list))
                        else (param,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _scalar_index_ops(inner, found)
    return found


@pytest.mark.parametrize("recomputed", [False, True],
                         ids=["plain", "recomputed"])
@pytest.mark.parametrize("case", ["k-under-h", "k-over-h"])
def test_no_gather_or_scatter_walks_every_assignment(case, recomputed):
    """Forward + backward of `token_choice_moe` at shapes with a short
    buffer: outside the exact buffer's branch no gather or scatter of
    scalars is N * k indices long (a TPU walks them one at a time, 9 ns
    each); the longest is the short buffer's.  So the next edit cannot
    bring one back unseen."""
    n, k, h, experts = LAYERS[case]
    short = moe.short_rows(n, k, h, experts)
    assert short is not None and short < n * k
    layer, loss, args = _layer(case, "bfloat16")
    fn = jax.checkpoint(layer, policy=KEEP) if recomputed else layer
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        loss(fn), argnums=(0, 1, 3, 4)))(*args)
    found = _scalar_index_ops(jaxpr.jaxpr, [])
    assert found, "the short path's own gathers should be seen"
    assert max(count for _, count in found) <= short, found
    # and the walk does see one where it is: the gathered form
    seen = _scalar_index_ops(jax.make_jaxpr(jax.grad(
        lambda *a: gathered_route(*a, k)[1].sum()))(*args[:3]).jaxpr, [])
    assert sorted(count for _, count in seen) == [n * k, n * k]


def test_the_ladder_rehearses_with_its_router(tmp_path):
    """`tools/moe_ladder.py --route 1 --platform cpu --cell tiny`: both
    rungs run, the gathered form (kept in the tool only) and the layer as
    it is agree, and `moe` is left as it was."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "moe_ladder.py")
    spec = importlib.util.spec_from_file_location("moe_ladder", path)
    ladder = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ladder)
    assert ladder.CELLS["laguna"] == (8192, 2048, 512, 8, 32, 256, "swiglu")
    before = (moe.topk_route, moe._slot_of, moe._Short.choices_of)
    out = tmp_path / "ladder.jsonl"
    ladder.main(["--route", "1", "--platform", "cpu", "--cell", "tiny",
                 "--load", "1", "--dtype", "float32", "--reps", "1",
                 "--inner", "1", "--out", str(out)])
    assert before == (moe.topk_route, moe._slot_of, moe._Short.choices_of)
    lines = [json.loads(line) for line in out.read_text().splitlines()]
    assert [line["rung"] for line in lines] == ["gathered", "as it is"]
    for line in lines:
        assert line["route"] == 1 and line["exact_buffer_ran"] == 0
        assert line["here"] == lines[0]["here"] > 0
        assert line["err_y"] < 1e-5 and line["err_grads"] < 1e-5
        assert all(line[name + "_ms"] > 0 for name in
                   ("forward", "forward_backward", "recomputed"))
