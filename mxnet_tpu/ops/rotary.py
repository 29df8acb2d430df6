"""Rotary positions on the packed layout: the ``rotary_embedding``
operator (ops/nn.py) as one Pallas TPU kernel that turns the ``(B, T,
H*D)`` tensor a projection writes where it lies.

The operator's composition reshapes the packed tensor to ``(B, T, H,
D)``, slices, casts, turns, concatenates and reshapes back.  On a TPU the
last two axes are the tiled ones, so ``(T, H*D) -> (T, H, D)`` is no
bitcast but a relayout - the heads move into the sublanes - and XLA does
it on the float32 copy, there and back, in the forward, in the recomputed
forward and in the backward (the transposes of slices and concatenates
are pads and adds over the same four axes), and fuses the q projection's
product into the first of them, which then writes float32: 26.6 ms of
the Laguna cell's 283.7 ms step under the scope `rotary`, 11 of them the
products (PERF.md section 6, PR 40).  The flash
kernels on either side read the packed layout as it is.

**The kernel** reads a block of ``rows x (heads a block * D)`` lanes once
and writes it once.  A head is `D` lanes of the block; inside them, in
float32, ``y = x*C + partner*S`` on the lanes that turn and ``y = x`` on
the lanes that pass (a select: bit for bit, whatever the partner holds),
rounded ONCE to the data's dtype.  ``partner`` is the other lane of the
half-split pair, a lane rotate inside the head's `D` lanes (the XLU):
lane i + half for the pair's first lane, lane i - half for its second -
one rotate where the whole head turns (``rotary_dim == D``: the two
coincide), two and a select otherwise.  `C` and `S` are ``(T, D)``
float32 tables XLA makes from the operator's own angles (float32, ``pos *
inv_freq``, `attention_factor` on cos and sin): cos on both lanes of a
pair, -sin on its first and +sin on its second.  `first` (the first
`rotary_dim` lanes turn) and the default (the last) are the same kernel
with other tables.  The grid is (batch, row blocks, head blocks) with the
heads innermost, so that a row block's tables are fetched once.

**The backward is the same kernel.**  A turn is `attention_factor` times
an orthogonal map, so the cotangent is the turn by the negative angle
(`S` negated, `C` the same) of the incoming cotangent: one
``jax.custom_vjp`` with no residual - nothing of the input is kept, and a
recomputed block's second run is the kernel again.

**The rule** (`rotary_rule`, from what the call shows alone): `D` a
multiple of the 128 lanes (and no wider than a block's `_BLOCK_LANES`),
`rotary_dim` even and at most `D`, `T` a
multiple of `_ROWS`, bfloat16 or float32.  On a TPU a call inside the
rule runs the kernel - Laguna's q and k, GLM's query of ``[192 nope | 64
rope]`` = 256 lanes a head; anywhere else, and for any other shape (GLM's
one 64-lane rotary key, a head that is no whole number of 128-lane
blocks, the narrow heads of the CPU tests), the operator's composition
stays.  Under a mesh layout
(`attention.attention_partition_scope`) the call goes through the
``shard_map`` the flash kernels use (`attention._partition_spec`): rows
and heads are independent, so any split of batch and heads is exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import attention

__all__ = ["rotary_rule", "turn"]

_LANES = attention._LANES
# Rows and lanes of a block: the rule asks for `T` in multiples of
# `_ROWS`, and a block holds the most whole heads that fit `_BLOCK_LANES`
# (and divide the head count).  On the v5e 256-, 512- and 1,024-row blocks
# of 1,024 or 2,048 lanes are level (64 heads of 128, forward: 0.465-0.469
# ms against the composition's 3.70 and a plain copy's 0.439) and 128 rows
# 9-13 % slower: 256 rows, the flash kernels' unit, keeps the rule widest
# (tools/rotary_ladder.py; PERF.md section 6, PR 40).
_ROWS = attention._UNIT
_BLOCK_LANES = 1024


def rotary_rule(t, d, rotary_dim, dtype) -> bool:
    """May this call run the kernel?  The shapes and the dtype alone: `t`
    positions, heads of `d` lanes of which `rotary_dim` turn."""
    return (d % _LANES == 0 and d <= _BLOCK_LANES
            and 0 < rotary_dim <= d and rotary_dim % 2 == 0
            and t % _ROWS == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


def count(path):
    """The counter ``rotary_calls{path}``: the operator's traced calls by
    what runs them, ``kernel`` or ``composition`` (trace time, as
    ``attention_pairs_visited``: a step traced once counts once)."""
    from .. import telemetry
    telemetry.registry.counter(
        "rotary_calls", "traced calls of the rotary_embedding operator by "
        "what runs them: the Pallas kernel on the packed layout, or the "
        "composition", {"path": path}).inc()


def turn(data, num_heads, rotary_dim, theta, first, yarn, attention_factor):
    """The operator's result for a call inside `rotary_rule`, by the
    kernel; under the layout `CompiledStep` noted, per shard."""
    # hashable: they are the custom_vjp's and the jitted call's statics
    keywords = (int(rotary_dim), float(theta), bool(first),
                None if yarn is None else tuple(float(y) for y in yarn),
                float(attention_factor))
    layout = attention._LAYOUT_SCOPE.value
    spec = None if layout is None else attention._partition_spec(
        layout, data.shape, num_heads)
    if spec is None or all(e is None for e in spec):
        return _turn(data, num_heads, *keywords)
    if spec[2] is not None:
        num_heads //= layout.tp
    return jax.shard_map(lambda data: _turn(data, num_heads, *keywords),
                         mesh=layout.mesh, in_specs=(spec,), out_specs=spec,
                         check_vma=False)(data)


def _run(data, back, *keywords):
    return _call(data, *keywords, back, _ROWS, _BLOCK_LANES,
                 attention._interpret())


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def _turn(data, num_heads, rotary_dim, theta, first, yarn, attention_factor):
    return _run(data, False, num_heads, rotary_dim, theta, first, yarn,
                attention_factor)


def _turn_fwd(data, *keywords):
    return _run(data, False, *keywords), None


def _turn_bwd(*keywords_nothing_and_cotangent):
    *keywords, _, g = keywords_nothing_and_cotangent
    return (_run(g, True, *keywords),)


_turn.defvjp(_turn_fwd, _turn_bwd)


def tables(t, d, rotary_dim, theta, first, yarn, attention_factor, back):
    """`C`, `S` (t, d) float32: the operator's angles (its lines: float32
    ``pos * inv_freq``, `attention_factor` on cos and sin) laid over a
    head's lanes - cos on both lanes of a pair, -sin on the first and
    +sin on the second (`back`: the negative angle, the signs exchanged);
    1 and 0 on the lanes that pass."""
    from .nn import yarn_inv_freq
    r, half = rotary_dim, rotary_dim // 2
    if yarn is None:
        inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / r))
    else:
        inv = jnp.asarray(yarn_inv_freq(r, theta, *yarn), jnp.float32)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if attention_factor != 1.0:
        cos, sin = cos * attention_factor, sin * attention_factor
    c = jnp.concatenate([cos, cos], axis=1)
    s = jnp.concatenate([sin, -sin] if back else [-sin, sin], axis=1)
    if r < d:
        passing = jnp.ones((t, d - r), jnp.float32)
        c = jnp.concatenate([c, passing] if first else [passing, c], axis=1)
        s = jnp.concatenate([s, 0 * passing] if first else [0 * passing, s],
                            axis=1)
    return c, s


def _kernel(c_ref, s_ref, x_ref, out_ref, *, d, start, r):
    """One block: every head's `d` lanes turned on the lanes [start,
    start + r), passed on the others."""
    from jax.experimental.pallas import tpu as pltpu

    half = r // 2
    if r < d:
        lane = lax.broadcasted_iota(jnp.int32, c_ref.shape, 1)
        first_of_pair = lane < start + half
        turns = (lane >= start) & (lane < start + r)
    for h in range(x_ref.shape[1] // d):
        lanes = slice(h * d, (h + 1) * d)
        x = x_ref[:, lanes].astype(jnp.float32)
        # jnp.roll's sense: lane i of roll(x, n) is lane i - n of x
        partner = pltpu.roll(x, half, 1)
        if r < d:
            partner = jnp.where(first_of_pair, pltpu.roll(x, d - half, 1),
                                partner)
        y = x * c_ref[...] + partner * s_ref[...]
        if r < d:
            y = jnp.where(turns, y, x)
        out_ref[:, lanes] = y.astype(out_ref.dtype)


# Jitted as the grouped kernels' calls are (ops/grouped.py): a step holds
# a call a layer, a tensor (q, k) and a pass (forward, recomputed,
# backward) - 30 in the Laguna cell - and jax traces and lowers the kernel
# once a shape.
@functools.partial(jax.jit, static_argnums=tuple(range(1, 11)))
def _call(data, num_heads, rotary_dim, theta, first, yarn, attention_factor,
          back, rows, block_lanes, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, hd = data.shape
    d = hd // num_heads
    c, s = tables(t, d, rotary_dim, theta, first, yarn, attention_factor,
                  back)
    heads = max(h for h in range(1, num_heads + 1)
                if num_heads % h == 0 and h * d <= block_lanes)
    table = pl.BlockSpec((rows, d), lambda n, i, j: (i, 0))
    block = pl.BlockSpec((None, rows, heads * d), lambda n, i, j: (n, i, j))
    # two buffers a block and a table + a head's float32 temporaries: 3
    # MiB at the cells' shapes, and the rule's widest (float32 heads of
    # 1,024 lanes) still compiles inside Mosaic's own grant of fast memory
    return pl.pallas_call(
        functools.partial(_kernel, d=d, start=0 if first else d - rotary_dim,
                          r=rotary_dim),
        name="rotary_turn",
        interpret=interpret,
        grid=(b, t // rows, num_heads // heads),
        in_specs=[table, table, block],
        out_specs=block,
        out_shape=attention._sds(data.shape, data.dtype, data),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(c, s, data)
