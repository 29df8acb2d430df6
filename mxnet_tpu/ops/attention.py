"""Attention ops: Pallas flash attention + MXNet transformer parity ops.

Reference: src/operator/contrib/transformer.cc
(_contrib_interleaved_matmul_selfatt_qk, _contrib_interleaved_matmul_
selfatt_valatt, _contrib_interleaved_matmul_encdec_qk/valatt) — the fused
attention matmuls GluonNLP's BERT uses.

TPU-native: the hot path is a blockwise online-softmax (flash) attention
kernel in Pallas (SURVEY.md §2.1 cuDNN row: "attention → Pallas flash
attention").  Blocks stream K/V through VMEM with running (max, sum)
accumulators so the T×T score matrix never materializes in HBM; the MXU
does the two matmuls per block.  Backward recomputes attention from the
saved inputs (rematerialization — trade FLOPs for HBM, SURVEY.md design
notes).  Non-TPU backends and unaligned shapes fall back to the jnp
composition, which XLA fuses well at moderate sequence length.
"""
from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register

__all__ = ["attention_core", "flash_attention", "cached_attention",
           "cached_attention_multi", "paged_attention",
           "paged_attention_multi"]

# kernel block sizes: 256x256 keeps the fp32 accumulators + two operand
# tiles comfortably inside v5e VMEM; overridable via env so a chip run
# can sweep candidates without code edits
from ..base import get_env

_BLOCK_Q = get_env("MX_FLASH_BLOCK_Q", 256, int)
_BLOCK_K = get_env("MX_FLASH_BLOCK_K", 256, int)

# Mosaic requires the last two dims of every block to be (8k, 128k) or
# equal to the full array dims — a rank-2 (BH, T) residual with a
# squeezed-BH block violates that.  The LSE therefore rides with a small
# trailing lane dim (all lanes duplicate the value); 8 = one sublane's
# width, and 8 == the full array dim satisfies the lowering rule while
# costing 8x (not 128x) the compact residual's HBM.
_LSE_LANES = 8


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
# flash kernel wherever alignment permits (any backend; CPU interprets),
# "xla" = force the jnp composition.  Two levels:
#   * process-wide default via set_attention_impl (MXNET_SUBGRAPH_BACKEND
#     role);
#   * a thread-local SCOPE (attention_impl_scope) that the subgraph
#     backend-property registry pushes around one block's trace, so
#     per-block optimize_for never leaks into other blocks.
_FORCED_IMPL = None
_IMPL_TLS = threading.local()


def set_attention_impl(impl):
    global _FORCED_IMPL
    if impl not in (None, "pallas", "xla"):
        raise ValueError("attention impl must be None, 'pallas' or 'xla'")
    prev = _FORCED_IMPL
    _FORCED_IMPL = impl
    return prev


def current_attention_impl():
    stack = getattr(_IMPL_TLS, "stack", None)
    if stack:
        return stack[-1]
    return _FORCED_IMPL


class attention_impl_scope:
    """Scoped override: the innermost scope wins over the global."""

    def __init__(self, impl):
        if impl not in (None, "pallas", "xla"):
            raise ValueError("attention impl must be None, 'pallas' or "
                             "'xla'")
        self._impl = impl

    def __enter__(self):
        if not hasattr(_IMPL_TLS, "stack"):
            _IMPL_TLS.stack = []
        _IMPL_TLS.stack.append(self._impl)
        return self

    def __exit__(self, *exc):
        _IMPL_TLS.stack.pop()
        return False


# ---------------------------------------------------------------------------
# jnp reference path (always-correct fallback; also the recompute backward)
# ---------------------------------------------------------------------------


def _attention_jnp(q, k, v, scale, causal):
    """q,k,v: (B, H, T, D)."""
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        Tq, Tk = q.shape[2], k.shape[2]
        mask = jnp.tril(jnp.ones((Tq, Tk), bool), Tk - Tq)
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


# ---------------------------------------------------------------------------
# Pallas flash kernel (forward)
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

def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                      block_k, seq_k):
    # refs: q (block_q, D), k/v (seq_k, D), o (block_q, D),
    # lse (block_q, _LSE_LANES) — lanes duplicate the value; grid=(BH, Tq/bq)
    import jax.experimental.pallas as pl

    block_q, d = q_ref.shape
    q = q_ref[:].astype(jnp.float32) * scale
    q_idx = pl.program_id(1)

    m = jnp.full((block_q, 1), -jnp.inf, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    acc = jnp.zeros((block_q, d), jnp.float32)

    num_kb = seq_k // block_k

    def body(kb, carry):
        m, l, acc = carry
        k_blk = k_ref[pl.dslice(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[pl.dslice(kb * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        if causal:
            q_pos = q_idx * block_q + \
                lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + \
                lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # guard fully-masked rows: exp(-inf - -inf) would be nan
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe)
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = alpha * acc + jnp.dot(p, v_blk,
                                        preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    if causal:
        # only key blocks at or before this query block contribute
        # (ceil-div: correct for any block_q/block_k ratio)
        num_kb_eff = ((q_idx + 1) * block_q + block_k - 1) // block_k
        num_kb_eff = jnp.minimum(num_kb_eff, num_kb)
        m, l, acc = lax.fori_loop(0, num_kb_eff, body, (m, l, acc))
    else:
        m, l, acc = lax.fori_loop(0, num_kb, body, (m, l, acc))

    o_ref[:] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    # logsumexp residual for the flash backward: lse = m + log(l)
    # (softmax prob recomputes as exp(s - lse)); -inf for fully-masked rows
    lse = jnp.where(l > 0,
                    jnp.where(jnp.isfinite(m), m, 0.0)
                    + jnp.log(jnp.maximum(l, 1e-30)),
                    -jnp.inf)
    lse_ref[:] = jnp.broadcast_to(lse, (block_q, _LSE_LANES))


def _flash_fwd_res(q, k, v, scale, causal, block_q=_BLOCK_Q,
                   block_k=_BLOCK_K):
    """q,k,v: (B, H, T, D) with T % block == 0.  Returns (out, lse_lanes)
    with lse_lanes (B*H, Tq, _LSE_LANES) fp32 — the laned residual the
    backward kernels consume directly (no rebroadcast on the bwd path)."""
    import jax.experimental.pallas as pl

    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    qr = q.reshape(B * H, Tq, D)
    kr = k.reshape(B * H, Tk, D)
    vr = v.reshape(B * H, Tk, D)
    kernel = functools.partial(_flash_fwd_kernel, scale=scale, causal=causal,
                               block_k=block_k, seq_k=Tk)
    out, lse_lanes = pl.pallas_call(
        kernel,
        interpret=_interpret(),
        grid=(B * H, Tq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Tk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Tk, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, _LSE_LANES), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            _sds((B * H, Tq, D), q.dtype, qr),
            _sds((B * H, Tq, _LSE_LANES), jnp.float32, qr),
        ],
    )(qr, kr, vr)
    return out.reshape(B, H, Tq, D), lse_lanes


def _lse_from_lanes(lse_lanes, B, H, Tq):
    """(B*H, Tq, _LSE_LANES) laned residual -> public (B, H, Tq)."""
    return lse_lanes[:, :, 0].reshape(B, H, Tq)


def _flash_fwd(q, k, v, scale, causal, block_q=_BLOCK_Q, block_k=_BLOCK_K):
    """Public-shape wrapper: returns (out, lse) with lse (B, H, Tq)."""
    B, H, Tq, _ = q.shape
    out, lse_lanes = _flash_fwd_res(q, k, v, scale, causal, block_q, block_k)
    return out, _lse_from_lanes(lse_lanes, B, H, Tq)


# ---------------------------------------------------------------------------
# Pallas flash backward (FlashAttention-2 recompute-from-LSE formulation):
# O(L) memory — the T×T score matrix is never materialized.  Two kernels:
# dq iterates q-blocks (streaming K/V), dk/dv iterates k-blocks (streaming
# Q/dO).  delta = rowsum(dO * O) is the softmax-jacobian correction term.
# ---------------------------------------------------------------------------


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                         dq_ref, *, scale, causal, block_k, seq_k):
    import jax.experimental.pallas as pl

    block_q, d = q_ref.shape
    q = q_ref[:].astype(jnp.float32)
    do = do_ref[:].astype(jnp.float32)
    # lanes all duplicate the value; a lane-reduce recovers (block_q, 1)
    lse = jnp.max(lse_ref[:], axis=-1, keepdims=True)
    # softmax-jacobian row term, computed in-kernel (saves a (BH, T)
    # residual array + its laned rebroadcast)
    delta = jnp.sum(do * o_ref[:].astype(jnp.float32), axis=-1,
                    keepdims=True)
    q_idx = pl.program_id(1)
    lse_safe = jnp.where(jnp.isfinite(lse), lse, 0.0)

    num_kb = seq_k // block_k

    def body(kb, dq):
        k_blk = k_ref[pl.dslice(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[pl.dslice(kb * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = q_idx * block_q + \
                lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + \
                lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        p = jnp.where(jnp.isfinite(s) & jnp.isfinite(lse),
                      jnp.exp(s - lse_safe), 0.0)
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + jnp.dot(ds, k_blk, preferred_element_type=jnp.float32)

    dq = jnp.zeros((block_q, d), jnp.float32)
    if causal:
        num_kb_eff = jnp.minimum(
            ((q_idx + 1) * block_q + block_k - 1) // block_k, num_kb)
        dq = lax.fori_loop(0, num_kb_eff, body, dq)
    else:
        dq = lax.fori_loop(0, num_kb, body, dq)
    dq_ref[:] = (dq * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                          dk_ref, dv_ref, *, scale, causal, block_q, seq_q):
    import jax.experimental.pallas as pl

    block_k, d = k_ref.shape
    k = k_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)
    k_idx = pl.program_id(1)

    num_qb = seq_q // block_q

    def body(qb, carry):
        dk, dv = carry
        q_blk = q_ref[pl.dslice(qb * block_q, block_q), :].astype(jnp.float32)
        do_blk = do_ref[pl.dslice(qb * block_q, block_q), :].astype(
            jnp.float32)
        lse = jnp.max(lse_ref[pl.dslice(qb * block_q, block_q), :],
                      axis=-1, keepdims=True)
        delta = jnp.sum(
            do_blk * o_ref[pl.dslice(qb * block_q, block_q), :].astype(
                jnp.float32), axis=-1, keepdims=True)
        lse_safe = jnp.where(jnp.isfinite(lse), lse, 0.0)
        s = jnp.dot(q_blk, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qb * block_q + \
                lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = k_idx * block_k + \
                lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        p = jnp.where(jnp.isfinite(s) & jnp.isfinite(lse),
                      jnp.exp(s - lse_safe), 0.0)
        dv_new = dv + jnp.dot(p.T, do_blk,
                              preferred_element_type=jnp.float32)
        dp = jnp.dot(do_blk, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_new = dk + jnp.dot(ds.T, q_blk,
                              preferred_element_type=jnp.float32)
        return dk_new, dv_new

    dk = jnp.zeros((block_k, d), jnp.float32)
    dv = jnp.zeros((block_k, d), jnp.float32)
    if causal:
        # only query blocks at or after this key block contribute
        qb_start = (k_idx * block_k) // block_q
        dk, dv = lax.fori_loop(qb_start, num_qb, body, (dk, dv))
    else:
        dk, dv = lax.fori_loop(0, num_qb, body, (dk, dv))
    dk_ref[:] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse_lanes, g, scale, causal,
               block_q=_BLOCK_Q, block_k=_BLOCK_K):
    """lse_lanes: (B*H, Tq, _LSE_LANES) fp32 as produced by
    _flash_fwd_res; delta is recomputed in-kernel from o/do blocks."""
    import jax.experimental.pallas as pl

    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    qr = q.reshape(B * H, Tq, D)
    kr = k.reshape(B * H, Tk, D)
    vr = v.reshape(B * H, Tk, D)
    outr = o.reshape(B * H, Tq, D)
    gr = g.reshape(B * H, Tq, D)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, scale=scale, causal=causal,
                          block_k=block_k, seq_k=Tk),
        interpret=_interpret(),
        grid=(B * H, Tq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Tk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Tk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, _LSE_LANES), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
        out_shape=_sds((B * H, Tq, D), q.dtype, qr),
    )(qr, kr, vr, outr, gr, lse_lanes)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, seq_q=Tq),
        interpret=_interpret(),
        grid=(B * H, Tk // block_k),
        in_specs=[
            pl.BlockSpec((None, Tq, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Tq, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Tq, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Tq, _LSE_LANES), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            _sds((B * H, Tk, D), k.dtype, qr),
            _sds((B * H, Tk, D), v.dtype, qr),
        ],
    )(qr, kr, vr, outr, gr, lse_lanes)

    return (dq.reshape(B, H, Tq, D), dk.reshape(B, H, Tk, D),
            dv.reshape(B, H, Tk, D))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_with_lse(q, k, v, scale, causal):
    """Blockwise flash attention returning (out, lse) — the ring-attention
    building block (partials merge via logsumexp).  Differentiable in BOTH
    outputs: the lse cotangent contributes
        dq += scale * g_lse ⊙ (P K)          (P K = this kernel with v:=k)
        dk += scale * Pᵀ (g_lse ⊙ q)          (the dkv kernel's dv pass)
    so the merge weights backpropagate without materializing P."""
    return _flash_fwd(q, k, v, scale, causal)


def _flash_lse_vjp_fwd(q, k, v, scale, causal):
    # symbolic_zeros=True wraps primals in CustomVJPPrimal
    q, k, v = (x.value if hasattr(x, "value") else x for x in (q, k, v))
    B, H, Tq, _ = q.shape
    out, lse_lanes = _flash_fwd_res(q, k, v, scale, causal)
    return (out, _lse_from_lanes(lse_lanes, B, H, Tq)), (q, k, v, out,
                                                         lse_lanes)


def _flash_lse_vjp_bwd(scale, causal, res, cts):
    from jax.custom_derivatives import SymbolicZero
    g_out, g_lse = cts
    q, k, v, o, lse_lanes = res
    B, H, Tq, _ = q.shape
    if isinstance(g_out, SymbolicZero):
        # out unused downstream: no kernel passes needed for its term
        dq = jnp.zeros(q.shape, q.dtype)
        dk = jnp.zeros(k.shape, k.dtype)
        dv = jnp.zeros(v.shape, v.dtype)
    else:
        dq, dk, dv = _flash_bwd(q, k, v, o, lse_lanes, g_out, scale,
                                causal)
    if not isinstance(g_lse, SymbolicZero):
        # the lse term costs one extra fwd + one bwd kernel pass — the
        # symbolic-zero gate skips it when only `out` was used downstream
        lse = _lse_from_lanes(lse_lanes, B, H, Tq)
        gl = jnp.where(jnp.isfinite(lse), g_lse, 0.0)[..., None]
        pk = _flash_fwd(q, k, k.astype(q.dtype), scale, causal)[0]
        dq = (dq.astype(jnp.float32)
              + scale * gl * pk.astype(jnp.float32)).astype(dq.dtype)
        g2 = (gl * q.astype(jnp.float32)).astype(q.dtype)
        _, _, dk2 = _flash_bwd(q, k, jnp.zeros_like(v), jnp.zeros_like(o),
                               lse_lanes, g2, scale, causal)
        dk = (dk.astype(jnp.float32)
              + scale * dk2.astype(jnp.float32)).astype(dk.dtype)
    return dq, dk, dv


flash_attention_with_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd,
                                symbolic_zeros=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, scale, causal):
    """Blockwise flash attention, (B, H, T, D) layout."""
    return _flash_fwd(q, k, v, scale, causal)[0]


def _flash_vjp_fwd(q, k, v, scale, causal):
    out, lse_lanes = _flash_fwd_res(q, k, v, scale, causal)
    return out, (q, k, v, out, lse_lanes)


def _flash_vjp_bwd(scale, causal, res, g):
    # blockwise Pallas backward: O(L) memory (recompute-from-LSE), never
    # building the T×T score matrix the old jnp rematerialization needed
    q, k, v, o, lse_lanes = res
    return _flash_bwd(q, k, v, o, lse_lanes, g, scale, causal)


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def attention_core(q, k, v, scale=None, causal=False, mask=None):
    """Dispatch: Pallas flash on TPU for aligned mask-free shapes, jnp
    composition otherwise.  q,k,v: (B, H, T, D).  Both paths trace under
    the scope ``attention_core``, so a device trace names the attention
    whatever implements it."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    Tq, Tk, D = q.shape[2], k.shape[2], q.shape[3]
    aligned = (mask is None and Tq % _BLOCK_Q == 0 and Tk % _BLOCK_K == 0
               and D % 128 == 0 and (not causal or Tq == Tk))
    impl = current_attention_impl()
    if impl == "xla":
        use_flash = False
    elif impl == "pallas":
        use_flash = aligned          # CPU interprets; TPU lowers via Mosaic
    else:
        use_flash = _on_tpu() and aligned
    with jax.named_scope("attention_core"):
        if use_flash:
            return flash_attention(q, k, v, float(scale), bool(causal))
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                            preferred_element_type=jnp.float32) * scale
        if causal:
            cm = jnp.tril(jnp.ones((Tq, Tk), bool), Tk - Tq)
            logits = jnp.where(cm, logits, -jnp.inf)
        if mask is not None:
            logits = jnp.where(mask.astype(bool), logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


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
