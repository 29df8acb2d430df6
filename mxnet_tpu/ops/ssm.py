"""State-space layers: the selective scan of Mamba-2 (SSD) in its chunked
dual form, with its backward, and the causal depthwise convolution in
front of it.

No reference counterpart.  Source: Dao & Gu 2024, "Transformers are SSMs"
(arXiv:2405.21060), section 6 and listing 1.  Per head, with the head's
group's ``B_t, C_t`` (N lanes each), ``a_t = dt_t * A`` (A < 0):

    h_t = exp(a_t) h_{t-1} + dt_t x_t (x) B_t        h: (P, N)
    y_t = h_t C_t

`ssd_scan` computes it chunk by chunk (`chunk` positions, the published
128).  With ``cs`` the running sum of ``a`` inside a chunk:

* inside a chunk, ``y_i += sum_{j<=i} (C_i.B_j) exp(cs_i - cs_j) dt_j
  x_j``: a masked (chunk x chunk) matrix a head, times the chunk's x;
* a chunk's own state ``sum_j exp(cs_last - cs_j) dt_j x_j (x) B_j``, and
  across chunks ``h <- exp(cs_last) h + that``: ``lax.scan`` over T/chunk
  steps of one multiply-add;
* what the chunks before it left: ``y_i += exp(cs_i) C_i . h_before``.

Decays, running sums and the carried state are float32 whatever the
inputs; the four products take their operands in the inputs' dtype and
accumulate in float32 (what the published kernels do).  The decay matrix
is ``exp`` of a masked DIFFERENCE: ``exp(cs_i) * exp(-cs_j)`` overflows
once a chunk's sum passes 88.

**The backward is written** (`jax.custom_vjp`), not left to autodiff:
it makes the decays again from ``dt`` and ``A``, reads the states the
forward carried into each chunk, runs ONE reverse ``lax.scan`` over the
chunks for the state's cotangent and gets every input's gradient from
chunk-sized products; nothing of size (chunk x chunk) a head is kept
between the passes.  Inside a recomputed block (`Block.recompute`) the
forward rule tags its output and the carried states
``base.recompute_keep``, as the flash kernels do: the block's second run
holds no scan.

It is a composition of ``jax.numpy`` chunk products, one implementation
whatever the shapes.  The ladder on the chip (`tools/ssm_ladder.py`;
PERF.md section 6, PR 33; one layer of 8192 positions, 16 heads of 64,
state 128, bf16): the recurrence over positions 15.1 ms forward alone;
this form 0.40 ms forward and 1.24 forward + backward at the published
chunk of 128 (2.32 at 64, 1.05 at 256) - and jax's own derivative of the
same forward takes the same time (1.20): what the written rule buys is
not speed but what is kept between the passes, the carried states alone.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..base import recompute_keep
from .registry import register

__all__ = ["ssd_scan", "causal_conv1d"]


def _f32(x):
    return x.astype(jnp.float32)


def _by_chunk(x, groups, chunk):
    """(B, T, H, P) -> (B, n, G, R, Q, P): T = n * Q positions, H = G * R
    heads."""
    B, T, H, P = x.shape
    return x.reshape(B, T // chunk, chunk, groups, H // groups, P) \
        .transpose(0, 1, 3, 4, 2, 5)


def _chunked(x, dt, b, c, groups, chunk):
    """The inputs a chunk and a group: x (B, n, G, R, Q, P), dt (B, n, G,
    R, Q) float32, b and c (B, n, G, Q, N)."""
    B, T, H, _ = x.shape
    n, R = T // chunk, H // groups
    x = _by_chunk(x, groups, chunk)
    dt = _f32(dt).reshape(B, n, chunk, groups, R).transpose(0, 1, 3, 4, 2)
    b = b.reshape(B, n, chunk, groups, -1).transpose(0, 1, 3, 2, 4)
    c = c.reshape(B, n, chunk, groups, -1).transpose(0, 1, 3, 2, 4)
    return x, dt, b, c


def _decays(dt, a_head):
    """(cs, the masked decay matrix exp(cs_i - cs_j) for j <= i else 0)
    of a chunk and a head, float32: cs (B, n, G, R, Q), the matrix (...,
    Q, Q) with i down the rows."""
    cs = jnp.cumsum(dt * a_head[:, :, None], axis=-1)
    q = cs.shape[-1]
    seen = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    diff = cs[..., :, None] - cs[..., None, :]
    return cs, jnp.exp(jnp.where(seen, diff, -jnp.inf))


def _dot(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def _carry(decay, own, reverse=False):
    """The state a chunk STARTS from (`reverse`: the cotangent it ends
    with): ``h <- decay_c h + own_c`` over the chunks, h = 0 first.
    decay (B, n, G, R), own (B, n, G, R, P, N); returns own's shape."""
    def step(h, inputs):
        d, s = inputs
        return d[..., None, None] * h + s, h

    xs = (jnp.moveaxis(decay, 1, 0), jnp.moveaxis(own, 1, 0))
    _, before = lax.scan(step, jnp.zeros_like(own[:, 0]), xs,
                         reverse=reverse)
    return jnp.moveaxis(before, 0, 1)


def _forward(x, dt, a, b, c, chunk):
    """(y (B, T, H, P) in x's dtype, the state each chunk starts from
    (B, n, G, R, P, N) float32)."""
    B, T, H, P = x.shape
    groups = b.shape[2]
    dtype = x.dtype
    xc, dtc, bc, cc = _chunked(x, dt, b, c, groups, chunk)
    a_head = _f32(a).reshape(groups, H // groups)
    cs, decay = _decays(dtc, a_head)
    scores = _dot("bngqs,bngks->bngqk", cc, bc)
    w = scores[:, :, :, None] * decay * dtc[..., None, :]
    y = _dot("bngrqk,bngrkp->bngrqp", w.astype(dtype), xc)
    last = cs[..., -1:]
    into = (_f32(xc) * (jnp.exp(last - cs) * dtc)[..., None]).astype(dtype)
    own = _dot("bngrkp,bngks->bngrps", into, bc)
    before = _carry(jnp.exp(last[..., 0]), own)
    y = y + _dot("bngqs,bngrps->bngrqp", cc, before.astype(dtype)) \
        * jnp.exp(cs)[..., None]
    y = y.transpose(0, 1, 4, 2, 3, 5).reshape(B, T, H, P).astype(dtype)
    return y, before


def _backward(x, dt, a, b, c, before, g, chunk):
    """Cotangents of (x, dt, a, b, c) from that of y."""
    B, T, H, P = x.shape
    groups = b.shape[2]
    dtype = x.dtype
    xc, dtc, bc, cc = _chunked(x, dt, b, c, groups, chunk)
    gc = _by_chunk(g.astype(dtype), groups, chunk)
    a_head = _f32(a).reshape(groups, H // groups)
    cs, decay = _decays(dtc, a_head)
    scores = _dot("bngqs,bngks->bngqk", cc, bc)[:, :, :, None]
    dt_k = dtc[..., None, :]
    held = before.astype(dtype)

    # inside a chunk: y = w x with w = scores * decay * dt_k
    dw = _dot("bngrqp,bngrkp->bngrqk", gc, xc)
    moved = dw * scores * decay              # d(decay * dt_k), times itself
    d_dt = moved.sum(-2)
    moved = moved * dt_k
    d_cs = moved.sum(-1) - moved.sum(-2)
    d_scores = (dw * decay * dt_k).sum(3).astype(dtype)
    d_c = _dot("bngqk,bngks->bngqs", d_scores, bc)
    d_b = _dot("bngqk,bngqs->bngks", d_scores, cc)
    w = (scores * decay * dt_k).astype(dtype)
    d_x = _dot("bngrqk,bngrqp->bngrkp", w, gc)

    # what the chunks before left: y += exp(cs) * (c . before)
    out = jnp.exp(cs)[..., None]
    from_before = _dot("bngqs,bngrps->bngrqp", cc, held)
    d_cs = d_cs + (_f32(gc) * from_before * out).sum(-1)
    g_out = (_f32(gc) * out).astype(dtype)
    d_c = d_c + _dot("bngrqp,bngrps->bngqs", g_out, held)
    last = cs[..., -1:]
    chunk_decay = jnp.exp(last[..., 0])
    after = _carry(chunk_decay, _dot("bngrqp,bngqs->bngrps", g_out, cc),
                   reverse=True)             # cotangent of a chunk's END

    # a chunk's own state: own = (x * into)^T b, end = decay * start + own
    to_end = jnp.exp(last - cs)
    into = to_end * dtc
    spread = _dot("bngks,bngrps->bngrkp", bc, after.astype(dtype))
    d_x = d_x + spread * into[..., None]
    d_b = d_b + _dot("bngrkp,bngrps->bngks",
                     (_f32(xc) * into[..., None]).astype(dtype),
                     after.astype(dtype))
    d_into = (_f32(xc) * spread).sum(-1)
    d_dt = d_dt + d_into * to_end
    d_into = d_into * into
    d_last = d_into.sum(-1) + chunk_decay * (after * before).sum((-1, -2))
    d_cs = (d_cs - d_into).at[..., -1].add(d_last)

    # cs = cumsum(dt * a): the reverse running sum
    d_a_t = jnp.flip(jnp.cumsum(jnp.flip(d_cs, -1), -1), -1)
    d_dt = d_dt + d_a_t * a_head[:, :, None]
    d_a = (d_a_t * dtc).sum((0, 1, 4)).reshape(H)

    def positions(v, lead):
        """Back to (B, T, ...) from the chunked layout; `lead` axes lie
        between the chunk axis and the positions."""
        order = (0, 1, 2 + lead) + tuple(range(2, 2 + lead)) \
            + tuple(range(3 + lead, v.ndim))
        v = v.transpose(order)
        return v.reshape((B, T) + v.shape[3:])

    return (positions(d_x, 2).reshape(B, T, H, P).astype(x.dtype),
            positions(d_dt, 2).reshape(B, T, H).astype(dt.dtype),
            d_a.astype(a.dtype),
            positions(d_b, 1).astype(b.dtype),
            positions(d_c, 1).astype(c.dtype))


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd(x, dt, a, b, c, chunk):
    # tagged and counted here, as the flash kernels' results are: jax
    # traces the forward rule below later, when the call is differentiated
    return tuple(map(recompute_keep, _forward(x, dt, a, b, c, chunk)))[0]


def _ssd_fwd(x, dt, a, b, c, chunk):
    # a recomputed block keeps what the scan made: its second run holds
    # no scan, and the backward reads the carried states from the first
    y, before = (recompute_keep(v, count=False)
                 for v in _forward(x, dt, a, b, c, chunk))
    return y, (x, dt, a, b, c, before)


def _ssd_bwd(chunk, res, g):
    x, dt, a, b, c, before = res
    return _backward(x, dt, a, b, c, before, g, chunk)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(x, dt, a, b, c, chunk=128):
    """The selective scan, zero state before position 0.

    x: (B, T, H, P) the heads' inputs; dt: (B, T, H) step sizes, positive
    (after softplus); a: (H,) the heads' A, negative; b, c: (B, T, G, N),
    one a group of H / G heads.  T a multiple of `chunk`.  Returns y (B,
    T, H, P) in x's dtype: the skip ``D x`` and the gate are the
    caller's."""
    if x.shape[1] % chunk:
        raise ValueError("ssd_scan: %d positions are no multiple of the "
                         "chunk %d" % (x.shape[1], chunk))
    if x.shape[2] % b.shape[2]:
        raise ValueError("ssd_scan: %d heads in %d groups"
                         % (x.shape[2], b.shape[2]))
    return _ssd(x, dt, a, b, c, int(chunk))


@register("ssm_scan")
def _ssm_scan(x, b, c, dt, z, dt_bias, a_log, d, num_heads=1, num_groups=1,
              chunk=128):
    """Mamba-2's scan on the packed tensors a projection produces, under
    the scope ``scan``: x, z (B, T, H*P); b, c (B, T, G*N); dt (B, T, H);
    dt_bias, a_log, d (H,) float32.  ``dt <- softplus(dt + dt_bias)``, ``A
    = -exp(a_log)``, the scan, the skip ``d * x`` and the gate ``*
    silu(z)``.  Returns (B, T, H*P) in x's dtype.  A `T` that is no
    multiple of `chunk` is one chunk (a short sequence, a test)."""
    B, T, HP = x.shape
    H, G = num_heads, num_groups
    with jax.named_scope("scan"):
        step = jax.nn.softplus(_f32(dt) + _f32(dt_bias))
        xh = x.reshape(B, T, H, HP // H)
        y = ssd_scan(xh, step, -jnp.exp(_f32(a_log)),
                     b.reshape(B, T, G, -1), c.reshape(B, T, G, -1),
                     chunk if T % chunk == 0 else T)
        y = _f32(y) + _f32(d)[:, None] * _f32(xh)
        y = y.reshape(B, T, HP) * jax.nn.silu(_f32(z))
        return y.astype(x.dtype)


@register("causal_conv1d")
def causal_conv1d(data, weight, bias):
    """Mamba's convolution: causal and depthwise along T of (B, T, C),
    then silu.  ``out_t = silu(sum_k weight[:, k] * data[t - K + 1 + k] +
    bias)`` with zeros before position 0; weight (C, K).  Under the scope
    ``conv``.  K shifted adds in float32: K is 4."""
    k = weight.shape[1]
    t = data.shape[1]
    with jax.named_scope("conv"):
        padded = jnp.pad(_f32(data), ((0, 0), (k - 1, 0), (0, 0)))
        out = _f32(bias)
        for i in range(k):
            out = out + padded[:, i:i + t] * _f32(weight[:, i])
        return jax.nn.silu(out).astype(data.dtype)
