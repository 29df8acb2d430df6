"""The grouped product's Pallas kernels (ops/grouped.py) against
``lax.ragged_dot`` and ``jax.grad`` of it, in interpret mode on the CPU
at small lane-aligned shapes: the forward, the cotangent of the rows (the
row kernel on the weights transposed) and of the weights (the weight
kernel); groups that are empty, end inside a tile or span several; a load
of nothing and one that fills the buffer; and a poisoned tail - NaN in
every row past the load, in both operands, must reach no output.  What
interpret mode cannot show (tiling, fast memory) is
tests/test_aot_compile.py's; times are a chip run's (PERF.md section 6,
PR 39).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from mxnet_tpu.ops import attention, grouped
from mxnet_tpu.parallel import moe

TILE = grouped._ROW_TILE
ROWS = 4 * TILE

# rows a group; the buffer is ROWS long
LOADS = {
    "ends-inside-a-tile": [TILE // 2, TILE // 4, TILE, 3],
    "an-empty-group-between": [TILE // 2, 0, TILE + 7, TILE // 2],
    "empty-groups-first-and-last": [0, TILE + 5, 70, 0],
    "one-group-spans-three-tiles": [9, 3 * TILE - 20, 11, 0],
    "tile-aligned-groups": [TILE, TILE, 0, TILE],
    "a-load-of-nothing": [0, 0, 0, 0],
    "a-load-that-fills-the-buffer": [TILE + 1, TILE - 1, 2 * TILE - 9, 9],
    "all-rows-in-the-last-group": [0, 0, 0, ROWS],
    "a-few-rows-a-group": [5, 1, 2, 3],
}
# (K, N) of the expert matrices: a gated expert's first matrix is twice
# its width, an ungated one's is its width; the second of either
WIDTHS = {"swiglu-in": (128, 512), "relu2-in": (128, 384),
          "down": (384, 128)}


def _operands(counts, k, n, dtype, poison=None):
    """rows (ROWS, K), w (H, K, N), cotangent (ROWS, N), counts; past the
    load the rows and the cotangent hold zeros, or `poison`."""
    rng = np.random.RandomState(sum(counts) + k)
    h, load = len(counts), sum(counts)
    rows = rng.randn(ROWS, k).astype(np.float32)
    g = rng.randn(ROWS, n).astype(np.float32)
    rows[load:] = g[load:] = 0.0 if poison is None else poison
    w = rng.randn(h, k, n).astype(np.float32) * k ** -0.5
    return (jnp.asarray(rows, dtype), jnp.asarray(w, dtype),
            jnp.asarray(g, dtype), jnp.asarray(counts, jnp.int32))


def _three(product, rows, w, g, counts):
    """(out, cotangent of the rows, cotangent of the weights) in float32."""
    out, back = jax.vjp(lambda r, w: product(r, w, counts), rows, w)
    return [np.asarray(v, np.float32) for v in (out,) + back(g)]


def _close(got, want, dtype):
    # bf16: both sides round a float32 sum once; the sums' order differs
    rtol = 1e-5 if dtype == "float32" else 1.6e-2
    for name, a, b in zip(("out", "d_rows", "d_weights"), got, want):
        assert np.isfinite(a).all(), name
        np.testing.assert_allclose(a, b, rtol=rtol,
                                   atol=rtol * np.abs(b).max() + 1e-30,
                                   err_msg=name)


@pytest.mark.parametrize("load", sorted(LOADS))
def test_the_three_products_are_ragged_dots(load):
    rows, w, g, counts = _operands(LOADS[load], 128, 256, jnp.float32)
    got = _three(grouped._product, rows, w, g, counts)
    _close(got, _three(lax.ragged_dot, rows, w, g, counts), "float32")
    # past the load every output is exactly zero
    assert not got[0][sum(LOADS[load]):].any()
    assert not got[1][sum(LOADS[load]):].any()
    for group, count in enumerate(LOADS[load]):
        assert count or not got[2][group].any()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_both_kinds_of_expert_at_their_widths(widths, dtype):
    k, n = WIDTHS[widths]
    args = _operands(LOADS["an-empty-group-between"], k, n, jnp.dtype(dtype))
    got = _three(grouped._product, *args)
    assert got[0].shape == (ROWS, n) and got[2].shape == (4, k, n)
    _close(got, _three(lax.ragged_dot, *args), dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("load", ["ends-inside-a-tile",
                                  "an-empty-group-between",
                                  "a-load-of-nothing",
                                  "a-few-rows-a-group"])
def test_a_poisoned_tail_reaches_no_output(load, dtype):
    """NaN in every row past ``counts.sum()``, rows and cotangent alike:
    the output and both cotangents are finite and equal, bit for bit, to
    the clean run's (a zero weight would not do: 0 x NaN is NaN)."""
    clean = _operands(LOADS[load], 128, 256, jnp.dtype(dtype))
    dirty = _operands(LOADS[load], 128, 256, jnp.dtype(dtype), np.nan)
    assert np.isnan(np.asarray(dirty[0], np.float32)).any()
    want = _three(grouped._product, *clean)
    for got, ref in zip(_three(grouped._product, *dirty), want):
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("load", sorted(LOADS))
def test_the_visits_stop_at_the_load(load):
    """A tile is visited once for every group with rows in it, in the
    groups' order, and no tile past the load: the load's tiles + at most
    one more a boundary."""
    counts = LOADS[load]
    tiles = ROWS // TILE
    steps, visits, group, tile, offsets = grouped._visits(
        jnp.asarray(counts, jnp.int32), tiles, TILE, False)
    ends = np.cumsum(counts)
    want = [(t, g) for g, (lo, hi) in enumerate(zip(ends - counts, ends))
            for t in range(tiles) if max(lo, t * TILE) < min(hi, (t + 1) * TILE)]
    assert steps == tiles + len(counts) - 1 and int(visits) == len(want)
    assert list(zip(np.asarray(tile)[:len(want)].tolist(),
                    np.asarray(group)[:len(want)].tolist())) == want
    assert len(want) <= -(-sum(counts) // TILE) + sum(c > 0 for c in counts)
    # past the visits nothing moves: the last visit's tile and group again
    if want:
        assert set(np.asarray(tile)[len(want):].tolist()) <= {want[-1][0]}
        assert set(np.asarray(group)[len(want):].tolist()) <= {want[-1][1]}
    assert np.asarray(offsets).tolist() == [0] + ends.tolist()
    # the weight kernel also visits a group with no row, once
    _, with_empty, *_ = grouped._visits(jnp.asarray(counts, jnp.int32),
                                        tiles, TILE, True)
    assert int(with_empty) == len(want) + sum(c == 0 for c in counts)


def test_the_rule_reads_shapes_and_dtypes_alone():
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert grouped.product_rule(5632, 1024, 2688, bf16, bf16)
    assert grouped.product_rule(16384, 512, 2048, bf16, bf16)
    assert grouped.product_rule(TILE, 128, 128, f32, f32)
    assert not grouped.product_rule(TILE, 32, 128, f32, f32)     # lanes
    assert not grouped.product_rule(TILE, 128, 64, f32, f32)
    assert not grouped.product_rule(TILE + 8, 128, 128, f32, f32)
    assert not grouped.product_rule(TILE, 128, 128, f32, bf16)   # one dtype
    assert not grouped.product_rule(TILE, 128, 128, jnp.float16, jnp.float16)


def _primitives(fn, *args):
    # a new function a call: a trace is remembered by function, and what
    # the caller patches is not among the arguments
    return str(jax.make_jaxpr(lambda *a: fn(*a))(*args))


def test_off_the_chip_and_off_the_rule_the_ragged_dot_stays(monkeypatch):
    rows, w, _, counts = _operands(LOADS["a-few-rows-a-group"], 128, 256,
                                   jnp.float32)
    here = _primitives(grouped.grouped_product, rows, w, counts)
    assert "ragged_dot" in here and "pallas_call" not in here
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    there = _primitives(grouped.grouped_product, rows, w, counts)
    assert "pallas_call" in there and "ragged_dot" not in there
    grads = _primitives(jax.grad(
        lambda r, w: grouped.grouped_product(r, w, counts).sum(), (0, 1)),
        rows, w)
    assert grads.count("pallas_call") == 3 and "ragged_dot" not in grads
    narrow = _primitives(grouped.grouped_product, rows[:, :32], w[:, :32],
                         counts)
    assert "ragged_dot" in narrow and "pallas_call" not in narrow


@pytest.mark.parametrize("activation", moe.EXPERT_ACTIVATIONS)
@pytest.mark.parametrize("sized", ["short", "exact", "one-size"])
def test_the_expert_layer_through_the_kernels(monkeypatch, activation,
                                              sized):
    """`held_expert_ffn` with the kernels in the place of every ragged
    product - both buffer sizes' branches, forward and backward - gives
    what it gives with ``lax.ragged_dot``."""
    n, d, f, k, experts = 512, 128, 128, 2, 16
    held = (3, 4, 5, 6) if sized != "one-size" else tuple(range(16))
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    w_in = jnp.asarray(rng.randn(len(held), d,
                                 f * (2 if activation == "swiglu" else 1))
                       * d ** -0.5, jnp.float32)
    w_down = jnp.asarray(rng.randn(len(held), f, d) * f ** -0.5, jnp.float32)
    scores = rng.rand(n, experts)
    if sized == "exact":            # every token on two held experts
        scores[:, 3:5] += 2.0
    idx = jnp.asarray(np.argsort(-scores, axis=1)[:, :k], jnp.int32)
    weights = jnp.asarray(rng.rand(n, k), jnp.float32)
    assert (moe.short_rows(n, k, len(held), experts) is None) \
        == (sized == "one-size")

    def run():
        def loss(x, weights, w_in, w_down):
            y, _, _, exact = moe.held_expert_ffn(
                x, idx, weights, w_in, w_down, held, experts, activation)
            return (y * jnp.cos(jnp.arange(d))).sum(), exact
        (value, exact), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3), has_aux=True)(x, weights, w_in,
                                                       w_down)
        return float(exact), [np.asarray(v) for v in (value,) + grads]

    exact, want = run()
    assert exact == (sized == "exact")
    monkeypatch.setattr(grouped, "grouped_product", grouped._product)
    assert _primitives(lambda x: moe.held_expert_ffn(
        x, idx, weights, w_in, w_down, held, experts, activation)[0],
        x).count("ragged_dot") == 0
    _, got = run()
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-5,
                                   atol=2e-5 * np.abs(b).max())
