"""Attention ops: Pallas flash attention + MXNet transformer parity ops.

Reference: src/operator/contrib/transformer.cc
(_contrib_interleaved_matmul_selfatt_qk, _contrib_interleaved_matmul_
selfatt_valatt, _contrib_interleaved_matmul_encdec_qk/valatt) — the fused
attention matmuls GluonNLP's BERT uses.

TPU-native: unmasked attention runs blockwise flash kernels in Pallas,
forward and backward, so the T×T scores never reach HBM.  What the code
does, in the order a call meets it:

* **The rule** (`flash_rule`, stated once; `use_flash` adds the backend
  and the `set_attention_impl` / `attention_impl_scope` override): no
  mask, `Tq` and `Tk` multiples of the 256-row unit, head size 64 or a
  multiple of 128 (a multiple of 128 where query heads share key/value
  heads), bf16 or float32, and `Tq == Tk` when causal.  Anything else
  takes the jnp composition, which XLA fuses.
* **Two layouts, one set of kernels.**  `attention_core` takes
  `(B, H, T, D)`: one head a block.  `attention_heads` takes the
  `(B, T, H·D)` tensors a projection produces (what
  `multi_head_attention` holds) and reads 128-lane blocks straight from
  them — two 64-wide heads a block, told apart by lane masks, so every
  tile is lane-dense and no transpose surrounds the call.  (On the v5e a
  `(B, H, T, 64)` operand is padded to 128 lanes in HBM and costs its four
  transposes: one BERT-base layer took 1.94 ms that way against 1.21.)
* **Grouped key/value heads** (k and v with fewer heads than q: H / Hkv
  query heads read one key/value head).  The kernels' key and value
  blocks are indexed by ``head // group``: the one head in HBM is read for
  each of its query heads and no repeated copy is ever made.  The
  backward yields dk and dv a QUERY head (float32) and one XLA pass sums
  each group's - the transpose of the sharing.
* **bf16 into the MXU**: operands stay in their own dtype, products
  accumulate in float32 (`preferred_element_type`), probabilities are
  cast to the input dtype before the second product — the precision of
  the composition.  Softmax statistics are float32, kept one value a lane
  (`(B, H, 1, T)`: a trailing dim of 8 is padded to 128 lanes in HBM).
* **Blocks from the shapes** (`_Geometry.blocks`): a non-causal call takes
  512-row blocks where they divide and up to 1024 keys as one block (no
  online rescaling); longer or causal calls stream key blocks with
  running (max, sum), in blocks sized from T, the lane width and the
  fast memory their float32 tiles take.
* **Causal calls do the causal work** (`_visits`): blocks above the
  diagonal are never visited, blocks wholly below it run unmasked, and
  only the blocks the diagonal crosses - cut to the smaller of the two
  block sizes - pay for the mask.
* **Backward** recomputes the probabilities from the saved logsumexp
  (FlashAttention-2) on transposed scores, keys down the sublanes, so
  that `p.T @ dO` and `ds.T @ q` are plain products.  One kernel over key
  blocks yields dq, dk and dv from one pass over the scores (5 products):
  dk and dv a key block, dq summed in float32 in fast memory across the
  key blocks and written once.  q, dO and dq stay resident for that;
  where they do not fit (`_Geometry.fused_backward`) a second kernel
  over query blocks makes dq (7 products).
* **Under a mesh** the kernels are opaque to GSPMD (jax refuses to lower
  a Mosaic kernel it would have to partition), so `CompiledStep` opens
  `attention_partition_scope(layout)` around its forward trace and the
  call is wrapped in `shard_map` over the batch (data×fsdp) and head (tp)
  axes: each chip runs its own rows.  (`custom_partitioning` is not
  available: libtpu has no emitter for `CustomSPMDPartitioning`.)
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .registry import register

__all__ = ["attention_core", "attention_heads", "flash_attention",
           "flash_rule", "use_flash", "cached_attention",
           "cached_attention_multi", "paged_attention",
           "paged_attention_multi"]

from ..base import recompute_keep

# the kernels' block unit, in rows of queries and of keys alike: the rule
# asks for multiples of it, the blocks a call takes are whole units
# (`_Geometry.blocks` chooses them from the shapes), and the diagonal of a
# causal call is cut to it.  A constant: every run on record took 256.  On
# the v5e 512-row blocks beat 256 (one BERT-base layer, forward +
# backward: 1.77 against 2.53 ms, PERF.md section 6) and one key block
# of up to 1024 rows beats a loop with online rescaling (2.53 against
# 3.46 ms).
_UNIT = 256

_LANES = 128            # a vector register's lanes: the packed block width

# Fast memory (VMEM) of one TensorCore, and what Mosaic grants a kernel
# that asks for nothing.  A kernel whose blocks, scratch and tiles need
# more than the grant states its need (`_Geometry.mosaic`); the backward
# keeps q, dO and dq resident only where that need stays under 3/4 of the
# memory (`_Geometry.fused_backward`).  The v5e's numbers; tier-1 holds
# them to its compiler (tests/test_aot_compile.py).
_FAST_MEMORY = 128 << 20
_FAST_MEMORY_GRANT = 16 << 20


def _on_tpu() -> bool:
    try:
        return jax.default_backend() == "tpu"
    except Exception:
        return False


def _interpret() -> bool:
    """Pallas kernels execute via Mosaic on TPU, interpret elsewhere —
    one code path, testable on CPU, real lowering on hardware."""
    return not _on_tpu()


# Lowering config (reference role: optimize_for(backend) /
# MXNET_SUBGRAPH_BACKEND): None = heuristic dispatch, "pallas" = force the
# flash kernel wherever the rule permits (any backend; CPU interprets),
# "xla" = force the jnp composition.  Two levels:
#   * process-wide default via set_attention_impl (MXNET_SUBGRAPH_BACKEND
#     role);
#   * a thread-local SCOPE (attention_impl_scope) that the subgraph
#     backend-property registry pushes around one block's trace, so
#     per-block optimize_for never leaks into other blocks.
# The scopes are jax user contexts: part of the key of jax's trace caches,
# so an op's own jitted program traced inside a scope is never handed to
# a trace outside it, nor the reverse.
_FORCED_IMPL = None
_UNSET = "unset"
_IMPL_SCOPE = jax.make_user_context(_UNSET)
_LAYOUT_SCOPE = jax.make_user_context(None)


def set_attention_impl(impl):
    global _FORCED_IMPL
    if impl not in (None, "pallas", "xla"):
        raise ValueError("attention impl must be None, 'pallas' or 'xla'")
    prev = _FORCED_IMPL
    _FORCED_IMPL = impl
    return prev


def current_attention_impl():
    scoped = _IMPL_SCOPE.value
    return _FORCED_IMPL if scoped == _UNSET else scoped


class _Scope:
    """`with` over one value of a jax user context (re-entrant)."""

    def __init__(self, context, value):
        self._context, self._value, self._entered = context, value, []

    def __enter__(self):
        entered = self._context(self._value)
        entered.__enter__()
        self._entered.append(entered)
        return self

    def __exit__(self, *exc):
        self._entered.pop().__exit__(*exc)
        return False


class attention_impl_scope(_Scope):
    """Scoped override: the innermost scope wins over the global."""

    def __init__(self, impl):
        if impl not in (None, "pallas", "xla"):
            raise ValueError("attention impl must be None, 'pallas' or "
                             "'xla'")
        super().__init__(_IMPL_SCOPE, impl)


class attention_partition_scope(_Scope):
    """Trace-time note of the mesh layout a program is sharded by
    (`parallel.SpecLayout`, or None): inside it a flash call runs under
    `shard_map` over the layout's batch and head axes.  `CompiledStep`
    opens it around the forward trace; it is no user-facing switch."""

    def __init__(self, layout):
        super().__init__(_LAYOUT_SCOPE, layout)


def flash_rule(Tq, Tk, D, causal=False, mask=None, dtype=jnp.bfloat16,
               group=1, window=None):
    """THE dispatch rule: may this call run the flash kernels?  A
    function of what the call shows and of nothing else.  `group`: query
    heads a key/value head (1: as many of each); a shared key/value head
    fills its own 128-lane block.  `window` (a causal call's, in keys a
    query sees with its own): a multiple of the 256-row unit."""
    return (mask is None and Tq % _UNIT == 0 and Tk % _UNIT == 0
            and (D % _LANES == 0 or (D == 64 and group == 1))
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32))
            and (not causal or Tq == Tk)
            and (window is None
                 or (causal and window > 0 and window % _UNIT == 0)))


def use_flash(Tq, Tk, D, causal=False, mask=None, dtype=jnp.bfloat16,
              group=1, window=None):
    """`flash_rule` under the configured implementation: "xla" never,
    "pallas" wherever the rule holds (the CPU interprets), otherwise on a
    TPU only."""
    impl = current_attention_impl()
    if impl == "xla":
        return False
    return flash_rule(Tq, Tk, D, causal, mask, dtype, group, window) and \
        (impl == "pallas" or _on_tpu())


# ---------------------------------------------------------------------------
# jnp reference path (always-correct fallback)
# ---------------------------------------------------------------------------


def _attention_jnp(q, k, v, scale, causal, mask=None, window=None):
    """q: (B, H, T, D); k, v: (B, Hkv, T, D), H / Hkv query heads a
    key/value head.  `window`: a causal call's band - a query sees the
    `window` keys that end with its own."""
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        Tq, Tk = q.shape[2], k.shape[2]
        cm = jnp.tril(jnp.ones((Tq, Tk), bool), Tk - Tq)
        if window is not None:
            cm &= ~jnp.tril(jnp.ones((Tq, Tk), bool), Tk - Tq - window)
        logits = jnp.where(cm, logits, -jnp.inf)
    if mask is not None:
        logits = jnp.where(mask.astype(bool), logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


# ---------------------------------------------------------------------------
# How the kernels see their operands
# ---------------------------------------------------------------------------


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying the input's varying-mesh-axes (vma) so
    pallas_call works INSIDE shard_map(check_vma=True) — ring attention
    runs these kernels per shard."""
    try:
        aval = jax.typeof(like)
        vma = getattr(aval, "vma", None)
        if vma:
            return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    except Exception:
        pass
    return jax.ShapeDtypeStruct(shape, dtype)


class _Geometry:
    """Block specs of one call.  ``heads=None``: q/k/v are (B, H, T, D)
    and a block holds one head.  ``heads=H``: they are (B, T, H*D) and a
    block is max(D, 128) lanes wide — `per_block` heads side by side.
    Either way a kernel sees 2-D (rows, width) tiles and per-row
    statistics (logsumexp, the backward's delta) as (per_block, 1, rows)
    of a (B, H, 1, T) float32 array - one value a LANE, so HBM holds them
    compactly (a trailing dim of 1 or 8 would be padded to 128 lanes:
    100 MB a BERT-base layer where 0.8 MB do); the grid is
    (B, H // per_block, row blocks).  k and v may hold fewer heads:
    `group` query heads read one (`kv_tile`)."""

    def __init__(self, q, k, heads):
        self.packed = heads is not None
        if self.packed:
            self.B, self.Tq, hd = q.shape
            self.H, self.D = heads, hd // heads
            self.width = max(self.D, _LANES)
        else:
            self.B, self.H, self.Tq, self.D = q.shape
            self.width = self.D
        self.Tk = k.shape[1] if self.packed else k.shape[2]
        self.Hkv = k.shape[2] // self.D if self.packed else k.shape[1]
        self.group = self.H // self.Hkv
        self.itemsize = q.dtype.itemsize
        self.per_block = self.width // self.D
        if self.H % self.per_block:
            raise ValueError("%d heads of size %d do not fill %d-lane "
                             "blocks" % (self.H, self.D, self.width))
        if self.H != self.group * self.Hkv or \
                (self.group > 1 and self.per_block > 1):
            raise ValueError("%d query heads on %d key/value heads of "
                             "size %d" % (self.H, self.Hkv, self.D))

    def grid(self, rows, block):
        return (self.B, self.H // self.per_block, rows // block)

    def shape(self, rows, heads=None):
        """Shape of a q-like array with `rows` positions (of `heads`
        heads: k and v have Hkv)."""
        heads = self.H if heads is None else heads
        return (self.B, rows, heads * self.D) if self.packed \
            else (self.B, heads, rows, self.D)

    def tile(self, rows, blocked, group=1):
        """A (rows, width) tile: the grid's row block when `blocked`,
        else the whole sequence (resident across the row blocks); of the
        head block ``h // group``."""
        import jax.experimental.pallas as pl
        if self.packed:
            return pl.BlockSpec(
                (None, rows, self.width),
                (lambda b, h, i: (b, i, h // group)) if blocked
                else (lambda b, h, i: (b, 0, h // group)))
        return pl.BlockSpec(
            (None, None, rows, self.D),
            (lambda b, h, i: (b, h // group, i, 0)) if blocked
            else (lambda b, h, i: (b, h // group, 0, 0)))

    def kv_tile(self, rows, blocked):
        """`tile` of k or v: the key/value head the grid's query head
        reads.  Consecutive query heads of a group name the same block,
        which the pipeline then does not fetch again."""
        return self.tile(rows, blocked, self.group)

    def stats(self, rows, blocked):
        """(per_block, 1, rows) statistics: of the grid's row block when
        `blocked`, else of the whole sequence."""
        import jax.experimental.pallas as pl
        return pl.BlockSpec(
            (None, self.per_block, 1, rows),
            (lambda b, h, i: (b, h, 0, i)) if blocked
            else (lambda b, h, i: (b, h, 0, 0)))

    def blocks(self, causal, window=None):
        """(query rows, key rows of the forward and dq kernels, key rows
        of the dk/dv kernel) a block: twice the unit where it divides T
        (and a window: a block is no longer than the band is wide, so a
        block meets at most two of the other side's on each edge) and a
        step's tiles at that size take no more than a quarter of
        fast memory (any width a head has had here).  A non-causal forward
        takes up to four units of keys as ONE block: no online rescaling
        (at T = 512 a head's whole K and V are 64 KB each).  A causal call
        takes the same rows as a long non-causal one (`_visits` says which
        blocks it visits): on the v5e, T = 4096 at 256 lanes, forward +
        backward, 512-row blocks took 10.0 ms where 256-row blocks took
        12.1 and 1024 rows on either side 10.8-10.9 (larger blocks waste
        more of the diagonal; PERF.md section 6, PR 29)."""
        def rows(seq):
            fits = self._working(2 * _UNIT, 2 * _UNIT, 4) \
                <= _FAST_MEMORY // 4
            return 2 * _UNIT if math.gcd(seq, window or 0) % (2 * _UNIT) \
                == 0 and fits else _UNIT

        bq, bk = rows(self.Tq), rows(self.Tk)
        one_block = not causal and self.Tk <= 4 * _UNIT
        return bq, (self.Tk if one_block else bk), bk

    def _bytes(self, rows, itemsize=None):
        return rows * self.width * (itemsize or self.itemsize)

    def fused_backward(self, block_kv, block_q):
        """Fast memory of the one-kernel backward, in bytes, or None where
        it passes 3/4 of `_FAST_MEMORY` and the two-kernel form is taken:
        q, dO and the dq block resident (two buffers each: the pipeline's),
        their statistics (8 sublanes a value), the float32 dq sum where
        more than one key block adds to it, and the working set of a
        step."""
        need = (6 * self._bytes(self.Tq)
                + 4 * self.per_block * 8 * self.Tq * 4
                + (self._bytes(self.Tq, 4) if self.Tk > block_kv else 0)
                + self._working(block_kv, block_q, 4))
        return need if need <= _FAST_MEMORY // 4 * 3 else None

    def streamed(self, seq, held, stream):
        """Fast memory of a kernel that keeps two `seq`-row operands
        resident (the forward's and the dq kernel's K and V, the dk/dv
        kernel's q and dO) beside `held`-row blocks."""
        return (4 * self._bytes(seq)
                + 4 * self.per_block * 8 * seq * 4
                + self._working(held, stream, 3))

    def _working(self, held, stream, tiles):
        """Blocks of `held` rows (four operands, two buffers each), their
        float32 accumulators with a loop's second copy, and `tiles`
        float32 score tiles with their casts."""
        return (8 * self._bytes(held) + 4 * self._bytes(held, 4)
                + tiles * held * stream * (4 + self.itemsize)
                + self._bytes(stream, 4))

    @staticmethod
    def mosaic(need):
        """pallas_call's compiler parameters for a kernel that needs
        `need` bytes of fast memory: none under Mosaic's own grant."""
        if need <= _FAST_MEMORY_GRANT:
            return {}
        from jax.experimental.pallas import tpu as pltpu
        return {"compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=min(need + need // 4, _FAST_MEMORY // 8 * 7))}


def _head_masks(per_block, d):
    """One lane mask a head of a packed block ([None] for one head)."""
    if per_block == 1:
        return [None]
    lane = lax.broadcasted_iota(jnp.int32, (1, per_block * d), 1)
    return [(lane >= g * d) & (lane < (g + 1) * d)
            for g in range(per_block)]


def _only(x, mask):
    """`x` with the lanes of the block's other heads zeroed: a product
    contracting over the lanes then sees this head alone, at the cost of
    the full-depth pass a 64-deep product takes anyway."""
    return x if mask is None else jnp.where(mask, x, jnp.zeros_like(x))


def _merge(out, part, mask):
    """Take this head's lanes of `part` into `out`."""
    return part if out is None or mask is None \
        else jnp.where(mask, part, out)


def _to_row(col):
    """(rows, 1) -> (1, rows): through a 128-lane transpose, the shape
    Mosaic's transpose unit takes."""
    return jnp.broadcast_to(col, (col.shape[0], _LANES)).T[:1]


def _to_col(row):
    """(1, rows) -> (rows, 1)."""
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T[:, :1]


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    """a.T @ b: contracts the rows of both."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """a @ b.T without a transposed operand."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _exact_scale(scale):
    """True when scaling by `scale` is exact in any float dtype (a power
    of two: 1/8 at head size 64), so it can be folded into q."""
    return math.frexp(scale)[0] == 0.5


def _mask_causal(s, q0, k0, q_axis):
    """Scores with key positions after the query's set to -inf; queries
    run along `q_axis` of `s` from `q0`, keys along the other from `k0`."""
    q_pos = q0 + lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = k0 + lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    return jnp.where(q_pos >= k_pos, s, -jnp.inf)


def _mask_window(s, q0, k0, q_axis, window):
    """Scores with key positions `window` or more before the query's set
    to -inf (the band's lower edge); axes as `_mask_causal`."""
    q_pos = q0 + lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = k0 + lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    return jnp.where(q_pos - k_pos < window, s, -jnp.inf)


def _masked(s, q0, k0, q_axis, edge, window):
    """`s` under the edge a masked segment of `_visits` names: the band's
    lower one (``"window"``) or the diagonal."""
    if edge == "window":
        return _mask_window(s, q0, k0, q_axis, window)
    return _mask_causal(s, q0, k0, q_axis)


def _rows(start_block, block):
    import jax.experimental.pallas as pl
    return pl.ds(pl.multiple_of(start_block * block, block), block)


def _loop(lo, count, body, init):
    """`count` steps of `body` from `lo`: fori_loop, the body once where
    the count is one, nothing where it is zero."""
    if isinstance(count, int) and count <= 1:
        return body(lo, init) if count > 0 else init
    return lax.fori_loop(lo, lo + count, body, init)


def _visits(causal, start, held, stream, seq, held_is_query, window=None):
    """What a block of `held` rows from `start` visits of the other,
    streamed operand of `seq` rows: segments (first block, blocks, rows a
    block, masked), in the order to run them.  Non-causal: every block of
    `stream` rows.  Causal, `held_is_query` (forward, dq): the keys before
    `start` in blocks of `stream` rows, what `stream` does not divide in
    units (the smaller of the two blocks), then the units the diagonal
    crosses, masked; key 0 comes first, so the running max is finite from
    the first step on and no row is ever fully masked in all it has seen.
    Causal, the held rows are keys (dk/dv): the diagonal's units, masked,
    then units up to a multiple of `stream`, then the queries after it.
    Blocks above the diagonal are not visited.
    Causal with a `window` (a query sees the `window` keys that end with
    its own; a multiple of both blocks, `_Geometry.blocks`): the band has
    a LOWER edge beside the diagonal, and blocks wholly beyond it are not
    visited either.  In units: the diagonal's, masked as above (first:
    every query row holds its own key, so the running max is finite from
    there on), the units between the edges, unmasked, then the `held`
    rows' worth of units the lower edge crosses, masked ``"window"``;
    what falls before row 0 or past `seq` is cut off (`start` may be
    traced: the counts are then, and a count below 1 runs nothing)."""
    if not causal:
        return [(0, seq // stream, stream, False)]
    unit = math.gcd(held, stream)
    per = stream // unit
    diagonal = (start // unit, held // unit, unit, True)
    if window is not None:
        if window % unit or held > window:
            raise ValueError("a window of %d keys under blocks of %d and %d"
                             % (window, held, stream))
        at, own, band = start // unit, held // unit, window // unit
        least, most = (min, max) if isinstance(at, int) \
            else (jnp.minimum, jnp.maximum)
        if held_is_query:
            first, edge = most(at + own - band, 0), most(at - band, 0)
            return [diagonal, (first, at - first, unit, False),
                    (edge, at + own - band - edge, unit, "window")]
        last = seq // unit
        return [diagonal,
                (at + own, least(at + band, last) - at - own, unit, False),
                (at + band, least(at + band + own, last) - at - band, unit,
                 "window")]
    if held_is_query:
        whole = start // stream
        below = [(0, whole, stream, False)]
        if per > 1:
            below.append((whole * per, start // unit - whole * per, unit,
                          False))
        return below + [diagonal]
    end = (start + held) // unit
    whole = (end + per - 1) // per
    below = [(whole, seq // stream - whole, stream, False)]
    if per > 1:
        below.insert(0, (end, whole * per - end, unit, False))
    return [diagonal] + below


# ---------------------------------------------------------------------------
# Pallas flash kernel (forward)
# ---------------------------------------------------------------------------


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                      block_k, d, window=None):
    # refs: q/o (block_q, width), k/v (seq_k, width),
    # lse (per_block, 1, block_q); grid = (B, H // per_block, Tq // block_q)
    import jax.experimental.pallas as pl

    block_q, width = q_ref.shape
    q0 = pl.program_id(2) * block_q
    fold = _exact_scale(scale)
    q_all = q_ref[:] * scale if fold else q_ref[:]
    visits = _visits(causal, q0, block_q, block_k, k_ref.shape[0], True,
                     window)
    out = None
    for g, mask in enumerate(_head_masks(width // d, d)):
        q = _only(q_all, mask)

        def visit(block, masked, q=q):
            def body(kb, carry):
                m, l, acc = carry
                rows = _rows(kb, block)
                s = _dot_nt(q, k_ref[rows, :])
                if not fold:
                    s = s * scale
                if masked:
                    s = _masked(s, q0, kb * block, 0, masked, window)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m - m_new)
                l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
                acc_new = alpha * acc + _dot(p.astype(v_ref.dtype),
                                             v_ref[rows, :])
                return m_new, l_new, acc_new
            return body

        carry = (jnp.full((block_q, 1), -jnp.inf, jnp.float32),
                 jnp.zeros((block_q, 1), jnp.float32),
                 jnp.zeros((block_q, width), jnp.float32))
        for lo, count, block, masked in visits:
            carry = _loop(lo, count, visit(block, masked), carry)
        m, l, acc = carry
        out = _merge(out, acc / l, mask)
        # logsumexp residual for the backward: p = exp(s - lse)
        lse_ref[g] = _to_row(m + jnp.log(l))
    o_ref[:] = out.astype(o_ref.dtype)


def _flash_fwd_res(q, k, v, scale, causal, heads=None, window=None):
    """(out, lse): out shaped like q, lse (B, H, 1, Tq) float32 — the
    residual the backward consumes."""
    import jax.experimental.pallas as pl

    geo = _Geometry(q, k, heads)
    block_q, block_k, _ = geo.blocks(causal, window)
    kernel = functools.partial(_flash_fwd_kernel, scale=scale, causal=causal,
                               block_k=block_k, d=geo.D, window=window)
    return pl.pallas_call(
        kernel,
        interpret=_interpret(),
        grid=geo.grid(geo.Tq, block_q),
        in_specs=[geo.tile(block_q, True), geo.kv_tile(geo.Tk, False),
                  geo.kv_tile(geo.Tk, False)],
        out_specs=[geo.tile(block_q, True), geo.stats(block_q, True)],
        out_shape=[
            _sds(geo.shape(geo.Tq), q.dtype, q),
            _sds((geo.B, geo.H, 1, geo.Tq), jnp.float32, q),
        ],
        **geo.mosaic(geo.streamed(geo.Tk, block_q, block_k)),
    )(q, k, v)


def _flash_fwd(q, k, v, scale, causal, heads=None):
    """(out, lse) with lse (B, H, Tq)."""
    out, lse = _flash_fwd_res(q, k, v, scale, causal, heads)
    return out, lse[:, :, 0]


# ---------------------------------------------------------------------------
# Pallas flash backward (FlashAttention-2 recompute-from-LSE formulation):
# O(L) memory — the T×T score matrix is never materialized.  The kernel
# over key blocks works on TRANSPOSED scores (keys down the sublanes), so
# dv = p.T @ dO and dk = ds.T @ q are plain products and the per-query
# statistics broadcast as rows.  It yields dq as well (one product with a
# transposed operand): scores and probabilities are computed once, 5
# products where two kernels make 7.  With one key block dq is written as
# it comes; with more, each key block adds its part to a float32 sum in
# fast memory (the key-block axis of the grid runs in order) and the last
# one writes it out.  Where q, dO and dq do not fit in fast memory
# (`_Geometry.fused_backward`) the kernel over query blocks makes dq.
# delta = rowsum(dO * O), the softmax-jacobian correction term, is one
# small XLA pass outside.
# ---------------------------------------------------------------------------


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, scale, causal, block_k, d, window=None):
    import jax.experimental.pallas as pl

    block_q, width = q_ref.shape
    q0 = pl.program_id(2) * block_q
    fold = _exact_scale(scale)
    q_all = q_ref[:] * scale if fold else q_ref[:]
    do_all = do_ref[:]
    visits = _visits(causal, q0, block_q, block_k, k_ref.shape[0], True,
                     window)
    out = None
    for g, mask in enumerate(_head_masks(width // d, d)):
        q, do = _only(q_all, mask), _only(do_all, mask)
        lse, delta = _to_col(lse_ref[g]), _to_col(delta_ref[g])

        def visit(block, masked, q=q, do=do, lse=lse, delta=delta):
            def body(kb, dq):
                rows = _rows(kb, block)
                k_blk = k_ref[rows, :]
                s = _dot_nt(q, k_blk)
                if not fold:
                    s = s * scale
                if masked:
                    s = _masked(s, q0, kb * block, 0, masked, window)
                p = jnp.exp(s - lse)
                dp = _dot_nt(do, v_ref[rows, :])
                ds = (p * (dp - delta)).astype(k_ref.dtype)
                return dq + _dot(ds, k_blk)
            return body

        dq = jnp.zeros((block_q, width), jnp.float32)
        for lo, count, block, masked in visits:
            dq = _loop(lo, count, visit(block, masked), dq)
        out = _merge(out, dq * scale, mask)
    dq_ref[:] = out.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dq_ref=None, dq_sum=None, *,
                          scale, causal, block_q, d, window=None):
    # lse/delta: (per_block, 1, seq_q); the scores here are (block_k,
    # block_q).  With dq_ref, dq (seq_q, width) comes from the same
    # scores: written a query block at a time where the one key block
    # holds every key, else summed over the key blocks in dq_sum (float32
    # scratch) and written by the last.
    import jax.experimental.pallas as pl

    block_k, width = k_ref.shape
    k_idx = pl.program_id(2)
    k0 = k_idx * block_k
    fold = _exact_scale(scale)
    k_all = k_ref[:] * scale if fold else k_ref[:]
    v_all = v_ref[:]
    visits = _visits(causal, k0, block_k, block_q, q_ref.shape[0], False,
                     window)
    if dq_sum is not None:
        @pl.when(k_idx == 0)
        def _():
            dq_sum[:] = jnp.zeros(dq_sum.shape, dq_sum.dtype)
    dk_out = dv_out = None
    for g, mask in enumerate(_head_masks(width // d, d)):
        k, v = _only(k_all, mask), _only(v_all, mask)

        def visit(block, masked, k=k, v=v, g=g, mask=mask):
            def body(qb, carry):
                dk, dv = carry
                rows = _rows(qb, block)
                q_blk, do_blk = q_ref[rows, :], do_ref[rows, :]
                s = _dot_nt(k, q_blk)
                if not fold:
                    s = s * scale
                if masked:
                    s = _masked(s, qb * block, k0, 1, masked, window)
                p = jnp.exp(s - lse_ref[g, :, rows])
                dv_new = dv + _dot(p.astype(do_blk.dtype), do_blk)
                dp = _dot_nt(v, do_blk)
                ds = (p * (dp - delta_ref[g, :, rows])).astype(q_blk.dtype)
                if dq_sum is not None:
                    # the other heads' lanes of k are zero: parts add up
                    dq_sum[rows, :] += _dot_tn(ds, k)
                elif dq_ref is not None:
                    # k carries the folded scale already; else scale here
                    dq = _dot_tn(ds, k) if fold else _dot_tn(ds, k) * scale
                    dq_ref[rows, :] = _merge(
                        None if g == 0 else dq_ref[rows, :],
                        dq.astype(dq_ref.dtype), mask)
                return dk + _dot(ds, q_blk), dv_new
            return body

        carry = (jnp.zeros((block_k, width), jnp.float32),
                 jnp.zeros((block_k, width), jnp.float32))
        for lo, count, block, masked in visits:
            carry = _loop(lo, count, visit(block, masked), carry)
        dk, dv = carry
        dk_out = _merge(dk_out, dk * scale, mask)
        dv_out = _merge(dv_out, dv, mask)
    dk_ref[:] = dk_out.astype(dk_ref.dtype)
    dv_ref[:] = dv_out.astype(dv_ref.dtype)
    if dq_sum is not None:
        @pl.when(k_idx == pl.num_programs(2) - 1)
        def _():
            dq = dq_sum[:] if fold else dq_sum[:] * scale
            dq_ref[:] = dq.astype(dq_ref.dtype)


def _flash_bwd(q, k, v, o, lse, g, scale, causal, heads=None, window=None):
    """(dq, dk, dv) from the forward's residuals and the cotangent `g`
    of its output.  lse: (B, H, 1, Tq) float32.  A `window` changes what
    a block visits and nothing of what stays resident: q, dO and dq are
    fetched and written once a head whatever the band (a band-long
    residency would need tiles at row offsets no block grid has, to save
    a transfer of 2 MB a head at T = 8192)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    geo = _Geometry(q, k, heads)
    block_q, block_k, block_kv = geo.blocks(causal, window)
    # delta = rowsum(dO * O): (B, H, 1, Tq) float32, one fused pass
    prod = g.astype(jnp.float32) * o.astype(jnp.float32)
    if geo.packed:
        delta = prod.reshape(geo.B, geo.Tq, geo.H, geo.D).sum(-1) \
            .transpose(0, 2, 1)
    else:
        delta = prod.sum(-1)
    delta = delta[:, :, None, :]
    # q, dO and dq resident: the dk/dv kernel yields dq too
    need = geo.fused_backward(block_kv, block_q)
    fused = need is not None
    summed = fused and geo.Tk > block_kv
    if not fused:
        need = geo.streamed(geo.Tq, block_kv, block_q)
    key_tile = geo.kv_tile(block_kv, True)
    whole_q = geo.tile(geo.Tq, False)
    # dk and dv come out a QUERY head; a group's are summed below (in
    # float32, which is then what the kernel writes)
    grad_tile = geo.tile(block_kv, True)
    grad_type = jnp.float32 if geo.group > 1 else None
    grads = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, d=geo.D, window=window),
        interpret=_interpret(),
        grid=geo.grid(geo.Tk, block_kv),
        in_specs=[whole_q, key_tile, key_tile, whole_q,
                  geo.stats(geo.Tq, False), geo.stats(geo.Tq, False)],
        out_specs=[grad_tile, grad_tile] + [whole_q] * fused,
        out_shape=[_sds(geo.shape(geo.Tk), grad_type or k.dtype, q),
                   _sds(geo.shape(geo.Tk), grad_type or v.dtype, q)]
        + [_sds(geo.shape(geo.Tq), q.dtype, q)] * fused,
        scratch_shapes=[pltpu.VMEM((geo.Tq, geo.width), jnp.float32)]
        * summed,
        **geo.mosaic(need),
    )(q, k, v, g, lse, delta)
    dk, dv = (_group_sum(geo, x, like) for x, like in
              zip(grads[:2], (k, v)))
    if fused:
        return grads[2], dk, dv
    q_tile = geo.tile(block_q, True)
    whole_k = geo.kv_tile(geo.Tk, False)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, scale=scale, causal=causal,
                          block_k=block_k, d=geo.D, window=window),
        interpret=_interpret(),
        grid=geo.grid(geo.Tq, block_q),
        in_specs=[q_tile, whole_k, whole_k, q_tile,
                  geo.stats(block_q, True), geo.stats(block_q, True)],
        out_specs=q_tile,
        out_shape=_sds(geo.shape(geo.Tq), q.dtype, q),
        **geo.mosaic(geo.streamed(geo.Tk, block_q, block_k)),
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


def _group_sum(geo, grad, like):
    """A query head's dk or dv summed over the heads that share the
    key/value head: `like`'s shape and dtype."""
    if geo.group == 1:
        return grad
    if geo.packed:
        grad = grad.reshape(geo.B, geo.Tk, geo.Hkv, geo.group, geo.D).sum(3)
    else:
        grad = grad.reshape(geo.B, geo.Hkv, geo.group, geo.Tk,
                            geo.D).sum(2)
    return grad.reshape(like.shape).astype(like.dtype)


def _kept(results, count=True):
    """The forward kernel's (out, lse) as a recomputed block keeps them
    (``base.recompute_keep``).  The forward rules hand the tagged values
    back as the primal output AND as the residuals, so every output of
    the forward call is known to the block's backward pass and its
    second run holds no forward kernel: out's bytes (as many as the
    block's input, which is kept anyway) for a pass whose time grows
    with T*T.  q, k and v are made again by the projections.  The
    primal functions tag too, and count: jax traces a forward rule when
    the call is differentiated, later than the operator that made it."""
    return tuple(recompute_keep(x, count) for x in results)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_with_lse(q, k, v, scale, causal):
    """Blockwise flash attention returning (out, lse) — the ring-attention
    building block (partials merge via logsumexp).  Differentiable in BOTH
    outputs: the lse cotangent contributes
        dq += scale * g_lse ⊙ (P K)          (P K = this kernel with v:=k)
        dk += scale * Pᵀ (g_lse ⊙ q)          (the dkv kernel's dv pass)
    so the merge weights backpropagate without materializing P."""
    out, lse = _kept(_flash_fwd_res(q, k, v, scale, causal))
    return out, lse[:, :, 0]


def _flash_lse_vjp_fwd(q, k, v, scale, causal):
    # symbolic_zeros=True wraps primals in CustomVJPPrimal
    q, k, v = (x.value if hasattr(x, "value") else x for x in (q, k, v))
    out, lse = _kept(_flash_fwd_res(q, k, v, scale, causal), count=False)
    return (out, lse[:, :, 0]), (q, k, v, out, lse)


def _flash_lse_vjp_bwd(scale, causal, res, cts):
    from jax.custom_derivatives import SymbolicZero
    g_out, g_lse = cts
    q, k, v, o, lse = res
    if isinstance(g_out, SymbolicZero):
        # out unused downstream: no kernel passes needed for its term
        dq = jnp.zeros(q.shape, q.dtype)
        dk = jnp.zeros(k.shape, k.dtype)
        dv = jnp.zeros(v.shape, v.dtype)
    else:
        dq, dk, dv = _flash_bwd(q, k, v, o, lse, g_out, scale, causal)
    if not isinstance(g_lse, SymbolicZero):
        # the lse term costs one extra fwd + one bwd kernel pass — the
        # symbolic-zero gate skips it when only `out` was used downstream
        gl = jnp.where(jnp.isfinite(lse[:, :, 0]), g_lse, 0.0)[..., None]
        pk = _flash_fwd(q, k, k.astype(q.dtype), scale, causal)[0]
        dq = (dq.astype(jnp.float32)
              + scale * gl * pk.astype(jnp.float32)).astype(dq.dtype)
        g2 = (gl * q.astype(jnp.float32)).astype(q.dtype)
        _, _, dk2 = _flash_bwd(q, k, jnp.zeros_like(v), jnp.zeros_like(o),
                               lse, g2, scale, causal)
        dk = (dk.astype(jnp.float32)
              + scale * dk2.astype(jnp.float32)).astype(dk.dtype)
    return dq, dk, dv


flash_attention_with_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd,
                                symbolic_zeros=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, scale, causal, heads=None, window=None):
    """Blockwise flash attention: (B, H, T, D) operands, or with `heads`
    the packed (B, T, heads*D) ones.  The output is laid out like q.
    `window`: a causal call's band (`_visits`)."""
    return _kept(_flash_fwd_res(q, k, v, scale, causal, heads, window))[0]


def _flash_vjp_fwd(q, k, v, scale, causal, heads, window):
    out, lse = _kept(_flash_fwd_res(q, k, v, scale, causal, heads, window),
                     count=False)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(scale, causal, heads, window, res, g):
    # the transposed call keeps the forward's name stack, so these
    # kernels too trace under attention_core
    q, k, v, o, lse = res
    return _flash_bwd(q, k, v, o, lse, g, scale, causal, heads, window)


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _band(causal, window, Tk):
    """A call's `window` as the kernels and the composition take it: None
    where the band holds every key a causal query sees anyway."""
    if window is None:
        return None
    if not causal or window < 1:
        raise ValueError("attention: window=%r is a causal call's band of "
                         "at least one key" % (window,))
    return None if window >= Tk else int(window)


def _count_pairs(geo, window):
    """The counters ``attention_pairs_needed{kind}`` and
    ``attention_pairs_visited{kind}`` of one traced causal kernel call,
    forward: the (query, key) pairs a query may see, and the pairs in the
    blocks `_visits` runs for them (kind ``window`` with a band, else
    ``full``).  Their ratio is what the block size costs: 1.0 is a
    kernel that touches the band alone."""
    from .. import telemetry
    block_q, block_k, _ = geo.blocks(True, window)
    T, W = geo.Tq, geo.Tq if window is None else window
    visited = sum(block_q * rows * max(count, 0)
                  for q0 in range(0, T, block_q)
                  for _, count, rows, _ in _visits(
                      True, q0, block_q, block_k, geo.Tk, True, window))
    kind = {"kind": "full" if window is None else "window"}
    for name, pairs, doc in (
            ("attention_pairs_needed", T * W - W * (W - 1) // 2,
             "(query, key) pairs inside the band of the traced causal "
             "flash calls, forward"),
            ("attention_pairs_visited", visited,
             "(query, key) pairs in the blocks the traced causal flash "
             "calls visit, forward")):
        telemetry.registry.counter(name, doc, kind).inc(
            geo.B * geo.H * pairs)


def _partition_spec(layout, shape, heads):
    """PartitionSpec of q/k/v under `layout`: the batch over data×fsdp
    where it divides, the heads over tp where whole blocks remain."""
    spec = layout.batch_spec_for(shape)
    entries = list(spec) + [None] * (len(shape) - len(spec))
    axis, unit = (1, 1) if heads is None \
        else (2, max(shape[2] // heads, _LANES))
    if layout.tp > 1 and shape[axis] % (layout.tp * unit) == 0:
        entries[axis] = layout.tp_axis
    return P(*entries)


def _flash(q, k, v, scale, causal, heads=None, window=None):
    """flash_attention — per shard under the layout CompiledStep noted:
    the kernels are opaque to GSPMD, which would gather their operands
    and run every row on every chip."""
    def call(q, k, v, heads=heads):
        return flash_attention(q, k, v, float(scale), bool(causal), heads,
                               window)

    if causal:
        _count_pairs(_Geometry(q, k, heads), window)

    layout = _LAYOUT_SCOPE.value
    spec = P() if layout is None else _partition_spec(layout, q.shape, heads)
    if layout is not None and k.shape != q.shape:
        # fewer key/value heads: tp takes the head axis only where it
        # divides them too
        kv_heads = None if heads is None \
            else heads * k.shape[2] // q.shape[2]
        if _partition_spec(layout, k.shape, kv_heads) != spec:
            axis = 1 if heads is None else 2
            spec = P(*(None if i == axis else e
                       for i, e in enumerate(spec)))
    if all(e is None for e in spec):
        return call(q, k, v)
    if heads is not None and spec[2] is not None:
        call = functools.partial(call, heads=heads // layout.tp)
    return jax.shard_map(call, mesh=layout.mesh, in_specs=(spec,) * 3,
                         out_specs=spec, check_vma=False)(q, k, v)


def attention_core(q, k, v, scale=None, causal=False, mask=None,
                   window=None):
    """Dispatch: Pallas flash where `use_flash` says so, jnp composition
    otherwise.  q: (B, H, T, D); k, v: (B, Hkv, T, D) with H a multiple
    of Hkv (grouped key/value heads: query heads ``g * H/Hkv ..`` read
    head g).  `window` (causal calls): a query sees the `window` keys
    that end with its own; one that holds every key is no window.  Both
    paths trace under the scope ``attention_core``, so a device trace
    names the attention whatever implements it."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    window = _band(causal, window, k.shape[2])
    flash = use_flash(q.shape[2], k.shape[2], q.shape[3], causal, mask,
                      q.dtype, q.shape[1] // k.shape[1], window)
    with jax.named_scope("attention_core"):
        if flash:
            return _flash(q, k, v, scale, causal, window=window)
        return _attention_jnp(q, k, v, scale, causal, mask, window)


def attention_heads(q, k, v, num_heads, scale=None, causal=False, mask=None,
                    window=None):
    """attention_core for the (B, T, H*D) tensors a projection produces.
    k and v may be (B, T, Hkv*D) with H a multiple of Hkv: grouped
    key/value heads, ``H/Hkv`` query heads on each.  Where the flash
    kernels run they read those tensors as they are (two 64-wide heads to
    a 128-lane block; a shared key/value head, a block of its own, for
    each of its query heads): no transpose and no repeated copy on either
    side.  Otherwise: split, transpose, `attention_core`, and back."""
    B, Tq, HD = q.shape
    D = HD // num_heads
    kv_heads = k.shape[2] // D
    if num_heads % kv_heads or v.shape[2] != k.shape[2]:
        raise ValueError("attention_heads: %d query heads of size %d on "
                         "k %s, v %s" % (num_heads, D, k.shape, v.shape))
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    window = _band(causal, window, k.shape[1])
    if use_flash(Tq, k.shape[1], D, causal, mask, q.dtype,
                 num_heads // kv_heads, window) \
            and num_heads % (max(D, _LANES) // D) == 0:
        with jax.named_scope("attention_core"):
            return _flash(q, k, v, scale, causal, heads=num_heads,
                          window=window)
    qh = q.reshape(B, Tq, num_heads, D).transpose(0, 2, 1, 3)
    kh = k.reshape(B, -1, kv_heads, D).transpose(0, 2, 1, 3)
    vh = v.reshape(B, -1, kv_heads, D).transpose(0, 2, 1, 3)
    out = attention_core(qh, kh, vh, scale=scale, causal=causal, mask=mask,
                         window=window)
    return out.transpose(0, 2, 1, 3).reshape(B, Tq, HD)


# ---------------------------------------------------------------------------
# Cached (decode-time) attention: one query token per sequence attending
# over a fixed-capacity KV page buffer under a valid-length mask — the
# autoregressive serving hot path (mxnet_tpu/serve/decode.py).  The page
# buffer is the full pre-allocated slot extent, so the program shape
# never depends on how far a generation has progressed: zero retraces
# across a sequence's whole lifetime, and the pool arrays can be donated
# through every decode step (HBM stays flat).
# ---------------------------------------------------------------------------


def cached_attention(q, k_pages, v_pages, cur_len, scale=None):
    """Single-position attention over per-sequence KV cache pages.

    ``q``: (B, H, D) — the current token's query per sequence;
    ``k_pages``/``v_pages``: (B, P, H, D) — each sequence's KV page
    buffer at its FULL capacity P (positions >= ``cur_len`` hold stale
    or zero entries); ``cur_len``: (B,) int — how many leading positions
    are valid (includes the current token's just-written entry).
    Returns (B, H, D).

    Masked positions get a finite -1e30 (never -inf): ``cur_len`` >= 1
    by contract, so every row has at least one live key and the softmax
    stays NaN-free even for scratch/padded lanes.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    P = k_pages.shape[1]
    logits = jnp.einsum("bhd,bphd->bhp", q, k_pages,
                        preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(P)[None, None, :] < cur_len[:, None, None]
    logits = jnp.where(valid, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhp,bphd->bhd", probs, v_pages)


def cached_attention_multi(q, k_pages, v_pages, pos, scale=None):
    """Multi-position attention over per-sequence KV cache pages.

    The speculative-verify generalization of :func:`cached_attention`:
    T query rows per sequence, each attending over the prefix ending at
    its OWN absolute position — the causal mask a chunk of in-flight
    draft tokens needs when the target model scores all of them in one
    dispatch.

    ``q``: (B, T, H, D) — T query tokens per sequence; ``k_pages``/
    ``v_pages``: (B, P, H, D) full-capacity page buffers (rows >= a
    query's position hold stale entries); ``pos``: (B, T) int — each
    query row's absolute position (its own KV entry is already written,
    so row t attends keys [0, pos[b, t]]).  Returns (B, T, H, D).
    Masking keeps the finite -1e30 discipline of the single-position
    path so scratch/padded lanes stay NaN-free.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    P = k_pages.shape[1]
    logits = jnp.einsum("bthd,bphd->bthp", q, k_pages,
                        preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(P)[None, None, :] <= pos[:, :, None]
    logits = jnp.where(valid[:, :, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bthp,bphd->bthd", probs, v_pages)


def paged_attention_multi(q, k_heap, v_heap, block_tables, pos,
                          scale=None):
    """Multi-position attention over a PAGED KV heap — the speculative
    verify dispatch's core (ISSUE 20).

    Gathers each lane's physical pages into the (B, extent, H, D) view
    :func:`cached_attention_multi` expects and delegates, exactly as
    :func:`paged_attention` does for the single-position step, so the
    verify program shares the flat path's masking/softmax semantics and
    greedy accept/reject stays bit-exact against plain decode.

    ``q``: (B, T, H, D); ``k_heap``/``v_heap``: (n_pages, page_len, H,
    D) one layer's heap slice; ``block_tables``: (B, pages_per_slot)
    int32; ``pos``: (B, T) absolute positions.  Returns (B, T, H, D).
    """
    B = q.shape[0]
    page_len = k_heap.shape[1]
    extent = block_tables.shape[1] * page_len
    k = k_heap[block_tables].reshape((B, extent) + k_heap.shape[2:])
    v = v_heap[block_tables].reshape((B, extent) + v_heap.shape[2:])
    return cached_attention_multi(q, k, v, pos, scale=scale)


def paged_attention(q, k_heap, v_heap, block_tables, cur_len,
                    scale=None):
    """Single-position attention over a PAGED KV heap (ISSUE 18).

    The paged decode engine keeps one shared page heap instead of
    per-slot extents; each sequence's logical key positions map to
    physical pages through its block table.  This gathers every lane's
    pages into the (B, extent, H, D) view :func:`cached_attention`
    expects and delegates — the masking/softmax discipline (finite
    -1e30, ``cur_len`` >= 1) is identical, so flat-vs-paged greedy
    decode parity holds at the token level.

    ``q``: (B, H, D); ``k_heap``/``v_heap``: (n_pages, page_len, H, D)
    — ONE layer's slice of the shared heap; ``block_tables``:
    (B, pages_per_slot) int32 physical page ids (scratch lanes carry
    all-zero rows: page 0 is reserved, masked by ``cur_len``);
    ``cur_len``: (B,) int valid leading positions.  Returns (B, H, D).
    """
    B = q.shape[0]
    page_len = k_heap.shape[1]
    extent = block_tables.shape[1] * page_len
    k = k_heap[block_tables].reshape((B, extent) + k_heap.shape[2:])
    v = v_heap[block_tables].reshape((B, extent) + v_heap.shape[2:])
    return cached_attention(q, k, v, cur_len, scale=scale)


# ---------------------------------------------------------------------------
# MXNet transformer parity ops (interleaved QKV layout, reference:
# src/operator/contrib/transformer.cc).  Input: (T, N, H*3*D) where the
# projection interleaves [q1..qD, k1..kD, v1..vD] per head.
# ---------------------------------------------------------------------------


def _split_interleaved_qkv(qkv, heads):
    T, N, HC = qkv.shape
    D = HC // (heads * 3)
    x = qkv.reshape(T, N, heads, 3, D)
    # -> (N, heads, T, D)
    q = x[:, :, :, 0].transpose(1, 2, 0, 3)
    k = x[:, :, :, 1].transpose(1, 2, 0, 3)
    v = x[:, :, :, 2].transpose(1, 2, 0, 3)
    return q, k, v


@register("_contrib_interleaved_matmul_selfatt_qk")
def _selfatt_qk(queries_keys_values, heads=1):
    """scores = scaled q @ k^T → (N*heads, T, T)."""
    q, k, _ = _split_interleaved_qkv(queries_keys_values, heads)
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    s = jnp.einsum("nhqd,nhkd->nhqk", q * scale, k,
                   preferred_element_type=jnp.float32).astype(q.dtype)
    N, H, T, _ = s.shape
    return s.reshape(N * H, T, T)


@register("_contrib_interleaved_matmul_selfatt_valatt")
def _selfatt_valatt(queries_keys_values, attention, heads=1):
    """out = att @ v → (T, N, H*D)."""
    _, _, v = _split_interleaved_qkv(queries_keys_values, heads)
    N, H, T, D = v.shape
    att = attention.reshape(N, H, T, T)
    out = jnp.einsum("nhqk,nhkd->nhqd", att, v)
    return out.transpose(2, 0, 1, 3).reshape(T, N, H * D)


@register("_contrib_interleaved_matmul_encdec_qk")
def _encdec_qk(queries, keys_values, heads=1):
    Tq, N, HC = queries.shape
    D = HC // heads
    q = queries.reshape(Tq, N, heads, D).transpose(1, 2, 0, 3)
    Tk = keys_values.shape[0]
    kv = keys_values.reshape(Tk, N, heads, 2, D)
    k = kv[:, :, :, 0].transpose(1, 2, 0, 3)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, q.dtype))
    s = jnp.einsum("nhqd,nhkd->nhqk", q * scale, k,
                   preferred_element_type=jnp.float32).astype(q.dtype)
    return s.reshape(N * heads, Tq, Tk)


@register("_contrib_interleaved_matmul_encdec_valatt")
def _encdec_valatt(keys_values, attention, heads=1):
    Tk, N, HC = keys_values.shape
    D = HC // (heads * 2)
    kv = keys_values.reshape(Tk, N, heads, 2, D)
    v = kv[:, :, :, 1].transpose(1, 2, 0, 3)
    Tq = attention.shape[1]
    att = attention.reshape(N, heads, Tq, Tk)
    out = jnp.einsum("nhqk,nhkd->nhqd", att, v)
    return out.transpose(2, 0, 1, 3).reshape(Tq, N, heads * D)


# ---------------------------------------------------------------------------
# sliding-window attention (reference: src/operator/contrib/
# sldwin_atten-inl.h — GluonNLP's Longformer ops).  Banded layout:
# score[b, i, h, j] pairs query i with key i + (j - w)*dilation, j in
# [0, 2w] (symmetric) or [0, w] (causal-left only); out-of-range or
# beyond-valid-length entries are masked.  O(L*w) memory, gather-based —
# the band never materializes the full L×L matrix.
# ---------------------------------------------------------------------------


def _sldwin_offsets(w, symmetric):
    lo = -w
    hi = w if symmetric else 0
    return jnp.arange(lo, hi + 1)


def _sldwin_kidx(L, w, dilation, symmetric):
    offs = _sldwin_offsets(w, symmetric) * dilation       # (J,)
    idx = jnp.arange(L)[:, None] + offs[None, :]          # (L, J)
    valid = (idx >= 0) & (idx < L)
    return jnp.clip(idx, 0, L - 1), valid


@register("_contrib_sldwin_atten_score", aliases=["sldwin_atten_score"],
          no_jit=True)  # per-head dilation tensor must be concrete
def _sldwin_atten_score(query, key, dilation, w=1, symmetric=True):
    """query/key: (B, L, H, D); dilation: (H,) ints → (B, L, H, J)."""
    B, L, H, D = query.shape
    outs = []
    dil = jnp.asarray(dilation).reshape(-1)
    for h in range(H):
        d = int(dil[h]) if dil.shape[0] > 1 else int(dil[0])
        idx, valid = _sldwin_kidx(L, int(w), d, bool(symmetric))
        kg = key[:, :, h, :][:, idx, :]                   # (B, L, J, D)
        s = jnp.einsum("bld,bljd->blj", query[:, :, h, :], kg)
        outs.append(jnp.where(valid[None], s, 0.0))
    return jnp.stack(outs, axis=2)                        # (B, L, H, J)


@register("_contrib_sldwin_atten_mask_like",
          aliases=["sldwin_atten_mask_like"], differentiable=False,
          no_jit=True)
def _sldwin_atten_mask_like(score, dilation, valid_length, w=1,
                            symmetric=True):
    """1.0 where the band entry addresses a real, in-valid-length key."""
    B, L, H, J = score.shape
    dil = jnp.asarray(dilation).reshape(-1)
    vl = jnp.asarray(valid_length).reshape(B, 1, 1)
    masks = []
    for h in range(H):
        d = int(dil[h]) if dil.shape[0] > 1 else int(dil[0])
        idx, valid = _sldwin_kidx(L, int(w), d, bool(symmetric))
        in_len = (idx[None] < vl) & (jnp.arange(L)[None, :, None] < vl)
        masks.append(valid[None] & in_len)
    return jnp.stack(masks, axis=2).astype(score.dtype)


@register("_contrib_sldwin_atten_context",
          aliases=["sldwin_atten_context"], no_jit=True)
def _sldwin_atten_context(score, value, dilation, w=1, symmetric=True):
    """score: (B, L, H, J); value: (B, L, H, D) → (B, L, H, D)."""
    B, L, H, J = score.shape
    dil = jnp.asarray(dilation).reshape(-1)
    outs = []
    for h in range(H):
        d = int(dil[h]) if dil.shape[0] > 1 else int(dil[0])
        idx, valid = _sldwin_kidx(L, int(w), d, bool(symmetric))
        vg = value[:, :, h, :][:, idx, :]                 # (B, L, J, D)
        s = jnp.where(valid[None], score[:, :, h, :], 0.0)
        outs.append(jnp.einsum("blj,bljd->bld", s, vg))
    return jnp.stack(outs, axis=2)
