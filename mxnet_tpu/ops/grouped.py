"""The grouped matrix product: rows sorted into groups, one weight matrix
a group - the held experts' projections (parallel/moe.py) - as two Pallas
TPU kernels that stop at the load.

``grouped_product(rows (M, K), w (H, K, N), counts (H,) int32) -> (M, N)``
is ``lax.ragged_dot``'s mathematics and dtypes: row r of group g - the
rows ``counts[:g].sum() <= r < counts[:g + 1].sum()`` - times ``w[g]``,
accumulated in float32, returned in the rows' dtype.  What differs is the
work: XLA:TPU expands a ``ragged_dot`` into kernels of its own
(``ragged-dot-metadata`` / ``ragged-dot-none``) that walk the WHOLE buffer,
at a ninth to a quarter of the chip's peak where a group holds a few
hundred rows (PERF.md section 6, PR 39).  Here the group offsets, the map
from a visit to its (row tile, group) and the number of visits are computed
on the device from `counts` and scalar-prefetched: a row tile is visited
once for every group that has rows in it (the load's tiles + at most one
more a group boundary), and no tile past ``counts.sum()`` is multiplied.

**The tail is zero.**  Rows past the load hold no assignment; what they
hold is the caller's (the expert layer writes zeros there, a test writes
NaN).  Every output is zero there whatever they hold: the row kernel
writes a visited tile through a select on the group's rows and fills the
tiles past the load with zeros without reading anything; the weight kernel
selects a group's rows out of BOTH operands of a boundary tile before it
multiplies (0 x NaN is not 0), and writes zeros for a group with no row.

**Three products, two kernels** under one ``jax.custom_vjp``, so that no
cotangent falls back to ``ragged_dot``: the forward and the cotangent of
the rows are the row kernel (the second on the weights transposed, which
is an index map and a contraction over the other axis, not a copy); the
cotangent of the weights is the weight kernel, ``rows[g].T @ cotangent[g]``
summed in float32 over the group's tiles.

**The rule** (`product_rule`, from the shapes alone): K and N multiples of
the 128 lanes, M a multiple of the row tile, rows and weights of one dtype,
bfloat16 or float32.  The contraction is taken whole (512-3,072 lanes in
the cells); the other width is cut into the fewest lane-multiple pieces
that keep the kernel's blocks inside `_BLOCK_BYTES` of fast memory; the
row tile is `_ROW_TILE`.  On a TPU a product inside the rule runs the
kernels; anywhere else, and for any other shape (the small layers of the
CPU tests), ``lax.ragged_dot`` stays - as the composition stays beside the
flash kernels (ops/attention.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import attention

__all__ = ["grouped_product", "product_rule"]

_LANES = attention._LANES
# Rows a tile.  A tile a group boundary crosses is visited once a group,
# so a boundary costs up to one tile of work, and the smallest tile the
# MXU fills wastes least: on the v5e 128 rows beat 256 and 512 at every
# product of the three decoder cells (Nemotron's 1024 -> 2688 forward:
# 0.230 / 0.243 / 0.295 ms against `ragged_dot`'s 0.662; PERF.md section
# 6, PR 39: tools/grouped_ladder.py).
_ROW_TILE = 128
# What a kernel's blocks may take of fast memory (two buffers an operand,
# the float32 accumulator): the other width is cut to fit it, and the
# kernel states its need to Mosaic (`attention._Geometry.mosaic`).  Every
# product of the three cells but one fits whole (GLM's 2048 -> 3072 in two
# pieces); blocks held to 6 MiB were 10-20 % slower, the weight kernel 2 x.
_BLOCK_BYTES = 40 << 20


def product_rule(m, k, n, rows_dtype, w_dtype) -> bool:
    """May this product run the kernels?  The shapes and dtypes alone."""
    return (m % _ROW_TILE == 0 and k % _LANES == 0 and n % _LANES == 0
            and jnp.dtype(rows_dtype) == jnp.dtype(w_dtype)
            and jnp.dtype(rows_dtype) in (jnp.dtype(jnp.bfloat16),
                                          jnp.dtype(jnp.float32)))


def grouped_product(rows, w, counts):
    """``lax.ragged_dot(rows, w, counts)``: by the kernels on a TPU where
    `product_rule` holds, by XLA otherwise."""
    if product_rule(rows.shape[0], w.shape[1], w.shape[2], rows.dtype,
                    w.dtype) and attention._on_tpu():
        return _product(rows, w, counts)
    return lax.ragged_dot(rows, w, counts)


@jax.custom_vjp
def _product(rows, w, counts):
    return _by_rows(rows, w, counts, False)


def _product_fwd(rows, w, counts):
    return _product(rows, w, counts), (rows, w, counts)


def _product_bwd(res, g):
    rows, w, counts = res
    return _by_rows(g, w, counts, True), _by_weights(rows, g, counts), None


_product.defvjp(_product_fwd, _product_bwd)


def _pieces(width, most, bytes_of):
    """The fewest equal lane-multiple pieces of `width` whose blocks
    (`bytes_of(piece)`) fit `most` bytes: (piece, the bytes it needs)."""
    lanes = width // _LANES
    for pieces in range(1, lanes + 1):
        if lanes % pieces == 0:
            piece = width // pieces
            if bytes_of(piece) <= most or piece == _LANES:
                return piece, bytes_of(piece)


def _mosaic(need):
    """pallas_call's compiler parameters: the width's pieces may run in
    any order, the visits in theirs; `need` bytes of fast memory stated
    where they pass Mosaic's own grant."""
    from jax.experimental.pallas import tpu as pltpu
    stated = attention._Geometry.mosaic(need).get("compiler_params")
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=stated and stated.vmem_limit_bytes)}


def _visits(counts, tiles, tm, empty):
    """Which (row tile, group) each grid step visits, from `counts` (H,)
    over `tiles` row tiles of `tm`: a tile once for every group with rows
    in it, groups in order - and, with `empty`, once (any tile) for a group
    with no row, which still has a result to write.  Compares and sums
    over the H groups, no scatter.  Returns (steps (static: the most there
    can be, ``tiles + H - 1``), visits () how many of them are visits,
    group (steps,), tile (steps,) - past the visits both repeat the last
    visit's, so that no block moves -, offsets (H + 1,) where each group
    starts)."""
    h = counts.shape[0]
    steps = tiles + h - 1
    ends = jnp.cumsum(counts)
    starts = ends - counts
    first = starts // tm
    span = jnp.where(counts > 0, (ends - 1) // tm - first + 1, int(empty))
    stop = jnp.cumsum(span)
    visits = stop[-1]
    step = jnp.minimum(jnp.arange(steps, dtype=jnp.int32),
                       jnp.maximum(visits - 1, 0))
    group = jnp.minimum((step[:, None] >= stop[None, :]).sum(1), h - 1) \
        .astype(jnp.int32)
    mine = group[:, None] == jnp.arange(h)[None, :]
    tile = jnp.where(mine, (first - (stop - span))[None, :], 0).sum(1) + step
    tile = jnp.clip(tile, 0, tiles - 1).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               ends.astype(jnp.int32)])
    return steps, visits.astype(jnp.int32), group, tile, offsets


def _bounds(offsets_ref, g, tile, tm):
    """Group g's rows [lo, hi), and whether the tile lies wholly inside."""
    lo, hi = offsets_ref[g], offsets_ref[g + 1]
    return lo, hi, (lo <= tile * tm) & ((tile + 1) * tm <= hi)


def _of_group(lo, hi, tile, tm, width):
    """(tm, width) bool: the tile's rows that are the group's."""
    rows = tile * tm + lax.broadcasted_iota(jnp.int32, (tm, width), 0)
    return (rows >= lo) & (rows < hi)


def _rows_kernel(group_ref, tile_ref, out_tile_ref, offsets_ref, ends_ref,
                 rows_ref, w_ref, out_ref, *, tm, transposed):
    """One visit: the row tile times the group's weights, written through
    the group's rows; past the visits, zeros into the tiles past the load,
    then nothing."""
    import jax.experimental.pallas as pl

    v = pl.program_id(1)
    visits, filled = ends_ref[0], ends_ref[1]

    @pl.when(v < visits)
    def _visit():
        dot = attention._dot_nt if transposed else attention._dot
        acc = dot(rows_ref[...], w_ref[...])
        tile = out_tile_ref[v]
        lo, hi, whole = _bounds(offsets_ref, group_ref[v], tile, tm)

        @pl.when(whole)
        def _store():
            out_ref[...] = acc.astype(out_ref.dtype)

        @pl.when(jnp.logical_not(whole))
        def _merge():
            # the tile's rows before the group's are earlier groups',
            # written by their visits just before this one: kept.  Those
            # after it are a later group's, whose visit follows, or the
            # tail's: zeros, whatever the buffer or the operand held
            width = out_ref.shape[1]
            held = jnp.where(_of_group(0, lo, tile, tm, width),
                             out_ref[...].astype(jnp.float32), 0.0)
            out_ref[...] = jnp.where(_of_group(lo, hi, tile, tm, width), acc,
                                     held).astype(out_ref.dtype)

    @pl.when((v >= visits) & (v < filled))
    def _fill():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)


def _by_rows(rows, w, counts, transposed):
    """(M, K) x (H, K, N) -> (M, N) by the groups' rows; `transposed`:
    (M, N) x (H, K, N) -> (M, K), the cotangent of the rows."""
    return _rows_call(rows, w, counts, transposed, _ROW_TILE, _BLOCK_BYTES,
                      attention._interpret())


# The calls are jitted, what they read outside their operands static: a
# step holds one call a layer, a pass (forward, recomputed, backward) and
# a buffer size - 144 in the Nemotron cell - and jax then traces and lowers
# a kernel once a shape, where each call on its own cost the step 11 s of
# set-up warm and 38 cold (PERF.md section 6, PR 39).
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _rows_call(rows, w, counts, transposed, tm, block_bytes, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, kc = rows.shape
    n = w.shape[1] if transposed else w.shape[2]
    size = rows.dtype.itemsize
    tn, need = _pieces(n, block_bytes, lambda tn: 2 * size * (
        tm * kc + kc * tn + tm * tn) + 3 * 4 * tm * tn)
    tiles = m // tm
    steps, visits, group, tile, offsets = _visits(counts, tiles, tm, False)
    # a step past the visits takes the next tile past those the load
    # reaches into, as long as there is one: `filled` is where they end
    cover = (offsets[-1] + tm - 1) // tm
    step = jnp.arange(steps, dtype=jnp.int32)
    out_tile = jnp.where(step < visits, tile,
                         jnp.minimum(cover + step - visits, tiles - 1))
    ends = jnp.stack([visits, visits + tiles - cover])
    if transposed:
        w_block = pl.BlockSpec((None, tn, kc),
                               lambda j, v, group, *_: (group[v], j, 0))
    else:
        w_block = pl.BlockSpec((None, kc, tn),
                               lambda j, v, group, *_: (group[v], 0, j))
    return pl.pallas_call(
        functools.partial(_rows_kernel, tm=tm, transposed=transposed),
        name="grouped_product_rows",
        interpret=interpret,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n // tn, steps),
            in_specs=[
                pl.BlockSpec((tm, kc),
                             lambda j, v, group, tile, *_: (tile[v], 0)),
                w_block],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, v, group, tile, out_tile, *_:
                (out_tile[v], j))),
        out_shape=jax.ShapeDtypeStruct((m, n), rows.dtype),
        **_mosaic(need),
    )(group, tile, out_tile, offsets, ends, rows, w)


def _weights_kernel(group_ref, tile_ref, offsets_ref, visits_ref, rows_ref,
                    g_ref, out_ref, acc_ref, *, tm):
    """One visit: the group's rows of the tile, transposed, times the
    same rows of the cotangent, added to the group's float32 sum; the sum
    is written with the group's last visit."""
    import jax.experimental.pallas as pl

    v = pl.program_id(1)
    visits = visits_ref[0]
    g = group_ref[v]
    last = jnp.maximum(visits - 1, 0)

    @pl.when(v < visits)
    def _visit():
        @pl.when((v == 0) | (group_ref[jnp.maximum(v - 1, 0)] != g))
        def _start():
            acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

        tile = tile_ref[v]
        lo, hi, whole = _bounds(offsets_ref, g, tile, tm)

        @pl.when(whole)
        def _add():
            acc_ref[...] += attention._dot_tn(rows_ref[...], g_ref[...])

        @pl.when(jnp.logical_not(whole) & (hi > lo))
        def _add_some():
            # a select on both operands: 0 x NaN is not 0
            def some(ref):
                return jnp.where(_of_group(lo, hi, tile, tm, ref.shape[1]),
                                 ref[...].astype(jnp.float32), 0.0) \
                    .astype(ref.dtype)

            acc_ref[...] += attention._dot_tn(some(rows_ref), some(g_ref))

        @pl.when((v == last) | (group_ref[jnp.minimum(v + 1, last)] != g))
        def _store():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _by_weights(rows, g, counts):
    """(M, K), (M, N) -> (H, K, N) float32-accumulated, in the rows'
    dtype: ``rows[group].T @ g[group]``, zeros for a group with no row."""
    return _weights_call(rows, g, counts, _ROW_TILE, _BLOCK_BYTES,
                         attention._interpret())


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _weights_call(rows, g, counts, tm, block_bytes, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = rows.shape
    n = g.shape[1]
    h = counts.shape[0]
    size = rows.dtype.itemsize
    tn, need = _pieces(n, block_bytes, lambda tn: 2 * size * (
        tm * k + tm * tn + k * tn) + 2 * 4 * k * tn)
    steps, visits, group, tile, offsets = _visits(counts, m // tm, tm, True)
    return pl.pallas_call(
        functools.partial(_weights_kernel, tm=tm),
        name="grouped_product_weights",
        interpret=interpret,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, steps),
            in_specs=[
                pl.BlockSpec((tm, k),
                             lambda j, v, group, tile, *_: (tile[v], 0)),
                pl.BlockSpec((tm, tn),
                             lambda j, v, group, tile, *_: (tile[v], j))],
            out_specs=pl.BlockSpec(
                (None, k, tn), lambda j, v, group, *_: (group[v], 0, j)),
            scratch_shapes=[pltpu.VMEM((k, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((h, k, n), rows.dtype),
        **_mosaic(need),
    )(group, tile, offsets, visits[None], rows, g)
