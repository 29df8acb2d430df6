"""Sequence/context parallelism: ring attention + all-to-all (Ulysses).

No reference counterpart (SURVEY.md §5.7: the reference caps at
single-device attention) — this is the TPU-native long-context layer the
rebuild adds as first-class: sequences sharded over an 'sp' mesh axis so
context length scales with the number of chips.

Two standard schemes, both over ``shard_map``:

* **Ring attention** (`ring_attention`): K/V blocks rotate around the sp
  ring via ``ppermute`` while each device's Q stays put; partial attention
  accumulates with the online-softmax (flash) recurrence, so the full
  L×L score matrix never materializes and each hop's compute overlaps the
  next hop's ICI transfer (XLA's latency-hiding scheduler).  Memory per
  chip: O(L/n · L/n) per block instead of O(L²).
* **Ulysses / all-to-all** (`ulysses_attention`): ``all_to_all`` swaps the
  sharded axis from sequence to heads, runs exact local attention on full
  sequences for H/n heads, and swaps back.  Cheaper at moderate L (two
  all-to-alls), requires heads % n == 0.

Both are differentiable (shard_map + collectives have transfer rules), so
they drop into training steps; numerical equality against single-device
attention is pinned by tests on the 8-device CPU mesh.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ring_attention", "ulysses_attention",
           "context_parallel_attention"]


def _block_attn(q, k, v, q_off, k_off, causal, scale):
    """One (q-block × kv-block) partial flash step.

    Returns (o_partial, m_block, l_block): unnormalized output, row max,
    row sum for the online-softmax merge.  Shapes: q (B, Lq, H, D),
    k/v (B, Lk, H, D).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        qpos = q_off + jnp.arange(q.shape[1])
        kpos = k_off + jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1)                       # (B, H, Lq)
    # all-masked rows: exp(-inf - -inf) = nan; pin m to 0 there
    m = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)                       # (B, H, Lq)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)       # unnormalized
    return o, m, l


def _flash_ok(q, k) -> bool:
    """Shard shapes eligible for the blockwise Pallas kernel per hop:
    attention_core's own rule (ops/attention.use_flash), asked for the
    shard's (B, L, H, D) shapes."""
    from ..ops.attention import use_flash
    return use_flash(q.shape[1], k.shape[1], q.shape[3], dtype=q.dtype)


def _ring_attention_flash(q, k, v, *, axis_name, causal, scale):
    """Flash-kernel ring: each hop runs the blockwise Pallas kernel on its
    K/V shard, producing a NORMALIZED partial plus its logsumexp; partials
    merge with the standard (out, lse) combine
        lse' = logaddexp(lse, lse_b);  out' = out·e^{lse-lse'} + out_b·e^{lse_b-lse'}
    so per-hop memory is O(L/n · D) and the score matrix never exists.
    q/k/v here are (B, Lq, H, D) (sequence-sharded); kernel layout is
    (B, H, L, D)."""
    from ..ops.attention import flash_attention_with_lse
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    qh = q.transpose(0, 2, 1, 3)                   # (B, H, Lq, D)

    def hop(kt, vt, src):
        kh = kt.transpose(0, 2, 1, 3)
        vh = vt.transpose(0, 2, 1, 3)

        def full(_):
            return flash_attention_with_lse(qh, kh, vh, scale, False)

        def diag(_):
            return flash_attention_with_lse(qh, kh, vh, scale, True)

        def skip(_):
            z = jnp.zeros(qh.shape, qh.dtype)
            neg = jnp.full(qh.shape[:3], -jnp.inf, jnp.float32)
            # match the pallas branches' varying-axes type (check_vma)
            if hasattr(lax, "pcast"):
                z, neg = (lax.pcast(x, (axis_name,), to="varying")
                          for x in (z, neg))
            else:
                z, neg = (lax.pvary(x, (axis_name,)) for x in (z, neg))
            return z, neg
        if not causal:
            return full(None)
        # causal over the GLOBAL sequence: earlier shards attend fully,
        # same shard causally, later shards not at all
        branch = jnp.where(src < idx, 0, jnp.where(src == idx, 1, 2))
        return lax.switch(branch, [full, diag, skip], None)

    # the ring length is STATIC (mesh axis size): unroll in Python — each
    # hop's kernel launch can then overlap the next hop's ppermute (XLA's
    # latency-hiding scheduler), and no loop-carried pallas lowering is
    # needed
    out = jnp.zeros(qh.shape, jnp.float32)
    lse = jnp.full(qh.shape[:3], -jnp.inf, jnp.float32)
    if hasattr(lax, "pcast"):
        out, lse = (lax.pcast(x, (axis_name,), to="varying")
                    for x in (out, lse))
    else:
        out, lse = (lax.pvary(x, (axis_name,)) for x in (out, lse))
    kt, vt = k, v
    perm = [(j, (j + 1) % n) for j in range(n)]
    for t in range(n):
        src = (idx - t) % n
        out_b, lse_b = hop(kt, vt, src)
        lse_new = jnp.logaddexp(lse, lse_b)
        lse_safe = jnp.where(jnp.isfinite(lse_new), lse_new, 0.0)
        wa = jnp.where(jnp.isfinite(lse), jnp.exp(lse - lse_safe), 0.0)
        wb = jnp.where(jnp.isfinite(lse_b), jnp.exp(lse_b - lse_safe), 0.0)
        out = out * wa[..., None] + out_b.astype(jnp.float32) * wb[..., None]
        lse = lse_new
        if t != n - 1:
            kt = lax.ppermute(kt, axis_name, perm)
            vt = lax.ppermute(vt, axis_name, perm)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ring_attention(q, k, v, *, axis_name: str = "sp", causal: bool = False,
                   scale: Optional[float] = None):
    """Ring attention over the ``axis_name`` collective axis.

    Call INSIDE shard_map with q/k/v sequence-sharded on that axis:
    q, k, v: (B, L_local, H, D).  Returns (B, L_local, H, D).

    Hops run the blockwise Pallas flash kernel when the shard shapes are
    block-aligned (Mosaic on TPU, interpret elsewhere); otherwise the jnp
    online-softmax block recurrence below.
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    lq = q.shape[1]
    lk = k.shape[1]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if _flash_ok(q, k):
        return _ring_attention_flash(q, k, v, axis_name=axis_name,
                                     causal=causal, scale=scale)
    q_off = idx * lq

    # checkpoint the block step: backward recomputes the block's score
    # matrix instead of saving it as a scan residual — per-device backward
    # memory drops from O(n·(L/n)²) to O(L/n·D), matching the flash
    # kernel's recompute-from-stats design (ops/attention._flash_bwd)
    blk = jax.checkpoint(
        lambda q_, k_, v_, qo, ko: _block_attn(q_, k_, v_, qo, ko,
                                               causal, scale))

    def body(t, carry):
        o, m, l, kt, vt = carry
        # block t originated on device (idx - t) mod n
        src = (idx - t) % n
        ob, mb, lb = blk(q, kt, vt, q_off, src * lk)
        # online-softmax merge of (o, m, l) with the new block
        m_new = jnp.maximum(m, mb)
        alpha = jnp.exp(m - m_new)                # rescale old accumulator
        beta = jnp.exp(mb - m_new)
        l_new = l * alpha + lb * beta
        o_new = o * alpha.transpose(0, 2, 1)[..., None] + \
            ob * beta.transpose(0, 2, 1)[..., None]
        # rotate K/V around the ring for the next step
        perm = [(j, (j + 1) % n) for j in range(n)]
        kt = lax.ppermute(kt, axis_name, perm)
        vt = lax.ppermute(vt, axis_name, perm)
        return o_new, m_new, l_new, kt, vt

    o0 = jnp.zeros(q.shape, jnp.promote_types(q.dtype, jnp.float32))
    m0 = jnp.full((q.shape[0], q.shape[2], lq), -jnp.inf)
    l0 = jnp.zeros((q.shape[0], q.shape[2], lq))
    # the loop body makes these device-varying over sp (they depend on
    # axis_index); mark the initial carry to match (shard_map vma typing)
    if hasattr(lax, "pcast"):
        o0, m0, l0 = (lax.pcast(x, (axis_name,), to="varying")
                      for x in (o0, m0, l0))
    else:
        o0, m0, l0 = (lax.pvary(x, (axis_name,)) for x in (o0, m0, l0))
    o, m, l, _, _ = lax.fori_loop(0, n, body, (o0, m0, l0,
                                               k.astype(o0.dtype),
                                               v.astype(o0.dtype)))
    l = jnp.maximum(l, 1e-38)                     # fully-masked rows
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(q, k, v, *, axis_name: str = "sp",
                      causal: bool = False, scale: Optional[float] = None):
    """DeepSpeed-Ulysses SP: all_to_all seq-shard → head-shard, exact local
    attention over the FULL sequence on H/n heads, all_to_all back.

    Call INSIDE shard_map; q/k/v (B, L_local, H, D) with H % n == 0.
    """
    n = lax.psum(1, axis_name)
    if q.shape[2] % n != 0:
        raise ValueError(
            "ulysses_attention: heads (%d) must divide by the %r axis size "
            "(%d); use ring_attention otherwise" % (q.shape[2], axis_name, n))
    if scale is None:
        scale = q.shape[-1] ** -0.5

    def seq_to_heads(x):
        # (B, L/n, H, D) -> (B, L, H/n, D): gather seq, scatter heads
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    # local exact attention through the shared dispatch: blockwise Pallas
    # flash on TPU (no L×L materialization); jnp fallback elsewhere
    from ..ops.attention import attention_core
    out = attention_core(qh.transpose(0, 2, 1, 3), kh.transpose(0, 2, 1, 3),
                         vh.transpose(0, 2, 1, 3), scale=scale,
                         causal=causal)
    return heads_to_seq(out.transpose(0, 2, 1, 3).astype(q.dtype))


def context_parallel_attention(q, k, v, mesh: Mesh, *, sp_axis: str = "sp",
                               causal: bool = False, method: str = "ring",
                               scale: Optional[float] = None):
    """User-facing wrapper: shard q/k/v (B, L, H, D) over ``sp_axis`` on
    dim 1 and run the chosen SP attention.  Output sharding matches input.
    """
    fn = {"ring": ring_attention, "ulysses": ulysses_attention}[method]
    spec = P(None, sp_axis, None, None)
    inner = functools.partial(fn, axis_name=sp_axis, causal=causal,
                              scale=scale)
    # check_vma off: interpret-mode pallas inside shard_map trips jax's
    # varying-axes checker on kernel constants ("Primitive mul requires
    # varying manual axes to match ... as a temporary workaround pass
    # check_vma=False") — the jax-recommended workaround
    mapped = shard_map(inner, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    sharding = NamedSharding(mesh, spec)
    q, k, v = (jax.device_put(x, sharding) for x in (q, k, v))
    return mapped(q, k, v)
