"""Autoregressive decode serving: device-resident KV cache + continuous
batching (ISSUE 15 tentpole).

Until this module every served request was one fixed-shape forward; the
sequence-generation traffic that dominates real serving (one prompt in,
many tokens out) would have held its whole micro-batch hostage for the
longest generation.  This is the decode engine that opens it, built on
the same disciplines the rest of ``serve/`` runs on:

* **Split prefill / decode, both AOT-bucketed.**  Prefill (process the
  whole prompt, fill the KV pages, emit the first token) compiles one
  program per PROMPT-LENGTH bucket (``MX_SERVE_DECODE_PROMPT_BUCKETS``);
  decode (one token for every active sequence) compiles one program per
  ACTIVE-SLOT-COUNT bucket (powers of two up to
  ``MX_SERVE_DECODE_SLOTS``).  Both register through
  ``programs.register_program`` so the compile cache, census and
  zero-retrace accounting carry over unchanged — after
  :meth:`DecodeServable.warm` serve time is pure cached-executable
  dispatch.

* **Device-resident KV pool, donated every step.**  K/V pages for every
  slot live in two fixed arrays ``(layers, slots+1, max_len, heads,
  head_dim)`` (+1 = the scratch slot padded decode lanes park on),
  owner-tagged ``kv_cache`` in ``programs.buffer_census()`` and donated
  through every prefill/decode dispatch — the pool is allocated once
  and HBM stays flat across any number of generations.  Retiring a
  sequence "evicts" its pages by bookkeeping alone: the slot's length
  resets on reuse and stale entries beyond it are masked, never read.

* **Continuous batching.**  The decode pump packs ALL active sequences
  into the smallest covering slot bucket each step (ONE device dispatch
  regardless of the active count), and at step boundaries retires
  finished sequences and admits queued prefills into the freed slots —
  a long generation never blocks a short one.  Sampled tokens stay
  device-resident between steps (the program writes the next input
  token into a donated pool-shaped array), so the pump never syncs the
  host; a separate harvester thread reads each step's emitted tokens
  asynchronously, stamps per-token latency and flags EOS/limit
  completions for the next boundary.  ``mode="request"`` is the
  request-level strawman (admit a batch, run it to completion) the
  bench lane compares against.

Slot state machine (one slot)::

    FREE --admit/prefill--> ACTIVE --harvest flags done--> FINISHED
      ^                                                       |
      +------------- retire at step boundary (kv_evict) ------+

Concurrency/lint contract: ``DecodeBatcher._tick`` / ``_admit`` /
``_retire`` / ``_step`` / ``_dispatch_prefill`` and the
``DecodeServable`` dispatch path are mxlint hot-path roots — no host
sync may land between state dequeue and device dispatch (the
tests/test_mxlint.py reinjection test proves a blocking host read there
trips the rule).  The device→host token read lives ONLY in the
harvester thread (``_harvest_once``).  Result/stream wait budgets ride
``mxnet_tpu.fault.Deadline`` (virtual-time aware, like the
micro-batcher's coalescing window); the pump's idle wait is a plain
short condition poll.

Telemetry: ``prefill`` / ``decode_step`` / ``kv_evict`` phases land in
``step_phase_seconds``; ``serve.decode.token_seconds`` histograms
per-token latency (first token = submit→harvest incl. queue + prefill,
then inter-token gaps); counters ``serve.decode.requests`` / ``tokens``
/ ``steps`` / ``prefills`` / ``sequences`` / ``rejected`` and the
``serve.decode.occupancy`` active-slots histogram drive the bench lane
and the fleet plane.

**The paged engine (ISSUE 18).**  :class:`PagedDecodeServable` /
:class:`PagedDecodeBatcher` rebuild the KV store as a shared PAGE HEAP
``(L, kv_pages, kv_page_len, H, Dh)`` (owner ``kv_pages`` in the
census, donated every dispatch) addressed through per-session block
tables, so admission is bounded by FREE PAGES, not slots — a mix of
2-token and 10k-token sessions packs tightly into the same bytes the
flat pool spends on worst-case extents.  Full read-only prompt pages
are hash-shared across sessions (rolling content hash chained at page
boundaries, refcounted adoption, copy-on-write at divergence:
``mxnet_tpu/serve/paging.py``), and prompts prefill as page-aligned
CHUNK trains that interleave with decode steps inside the pump's
1-dispatch-per-tick cadence.  Greedy decode stays token-identical to
the flat engine and :func:`reference_generate` — sharing and chunking
change WHEN work happens, never what it computes.  Select with
``MX_SERVE_KV_PAGES`` > 0 (``python -m mxnet_tpu.serve --decode``);
knobs: ``MX_SERVE_KV_PAGE_LEN``, ``MX_SERVE_PREFIX_SHARE``,
``MX_SERVE_PREFILL_CHUNK``.
"""
from __future__ import annotations

import functools
import queue as _queue
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as _np
import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError, get_env
from .. import fault as _fault
from .. import telemetry as _telemetry
from ..ops.attention import (attention_core, cached_attention,
                             paged_attention, paged_attention_multi)
from .batcher import Overloaded, result_timeout as _result_timeout
from .paging import PageAllocator, page_hashes

__all__ = ["DecodeConfig", "DecodeServable", "DecodeBatcher",
           "PagedDecodeServable", "PagedDecodeBatcher",
           "DraftDecodeServable", "SpeculativeDecodeBatcher",
           "demo_lm_params", "demo_spec_pair", "reference_generate"]

# extra pool positions past prompt+generation capacity: the pump may
# run a few steps ahead of the harvester (bounded by the harvest queue)
# before a finished sequence is retired, and those overrun writes must
# still land inside the slot's pages
_OVERRUN_MARGIN = 8


class DecodeConfig:
    """Decode-engine geometry: model dims + pool/bucket layout.

    Slot buckets are the powers of two up to ``slots`` (plus ``slots``
    itself) — every active-set size packs into the smallest covering
    bucket, so the decode program table is closed over 1..slots.
    ``max_len`` is the per-slot page capacity: top prompt bucket +
    ``max_tokens`` + the pipeline overrun margin, rounded up to whole
    ``page``-sized pages.
    """

    def __init__(self, vocab: int = 48, dim: int = 32, heads: int = 4,
                 layers: int = 2, slots: Optional[int] = None,
                 max_tokens: Optional[int] = None,
                 page: Optional[int] = None,
                 prompt_buckets: Optional[Sequence[int]] = None,
                 eos_id: Optional[int] = None, seed: int = 7,
                 kv_pages: Optional[int] = None,
                 kv_page_len: Optional[int] = None,
                 prefix_share: Optional[bool] = None,
                 prefill_chunk: Optional[int] = None,
                 spec_k: Optional[int] = None):
        self.vocab = int(vocab)
        self.dim = int(dim)
        self.heads = int(heads)
        if self.dim % self.heads:
            raise MXNetError("decode: dim %d must divide by heads %d"
                             % (self.dim, self.heads))
        self.head_dim = self.dim // self.heads
        self.layers = int(layers)
        self.slots = int(slots if slots is not None else
                         get_env("MX_SERVE_DECODE_SLOTS", 8, int))
        if self.slots < 1:
            raise MXNetError("decode: need >= 1 slot")
        self.max_tokens = int(max_tokens if max_tokens is not None else
                              get_env("MX_SERVE_DECODE_MAX_TOKENS", 32,
                                      int))
        self.page = int(page if page is not None else
                        get_env("MX_SERVE_DECODE_PAGE", 16, int))
        if prompt_buckets is None:
            raw = get_env("MX_SERVE_DECODE_PROMPT_BUCKETS") or "4,8,16"
            prompt_buckets = [int(p) for p in str(raw).split(",")
                              if p.strip()]
        self.prompt_buckets: Tuple[int, ...] = \
            tuple(sorted({int(b) for b in prompt_buckets}))
        if not self.prompt_buckets or self.prompt_buckets[0] < 1:
            raise MXNetError("decode: prompt buckets must be positive, "
                             "got %r" % (prompt_buckets,))
        sizes = set()
        b = 1
        while b < self.slots:
            sizes.add(b)
            b *= 2
        sizes.add(self.slots)
        self.slot_buckets: Tuple[int, ...] = tuple(sorted(sizes))
        self.eos_id = None if eos_id is None else int(eos_id)
        need = self.prompt_buckets[-1] + self.max_tokens + _OVERRUN_MARGIN
        self.pages = -(-need // self.page)
        self.max_len = self.pages * self.page
        self.seed = int(seed)
        # -- paged pool geometry (ISSUE 18) ---------------------------------
        # the paged engine swaps per-slot flat extents for one shared
        # page heap; a session holds only the pages its actual
        # prompt+generation extent needs, so admission is bounded by
        # free pages, not slots
        self.kv_page_len = int(
            kv_page_len if kv_page_len is not None else
            get_env("MX_SERVE_KV_PAGE_LEN", 0, int) or self.page)
        if self.kv_page_len < 1:
            raise MXNetError("decode: MX_SERVE_KV_PAGE_LEN must be "
                             ">= 1, got %d" % self.kv_page_len)
        self.pages_per_slot = -(-need // self.kv_page_len)
        self.slot_extent = self.pages_per_slot * self.kv_page_len
        n_pages = int(kv_pages if kv_pages is not None else
                      get_env("MX_SERVE_KV_PAGES", 0, int))
        if n_pages <= 0:
            # auto: the same HBM the flat pool's (slots+1) extents take
            n_pages = (self.slots + 1) * self.pages_per_slot
        # floor: the scratch page plus one worst-case session
        self.kv_pages = max(n_pages, self.pages_per_slot + 1)
        share = (prefix_share if prefix_share is not None else
                 get_env("MX_SERVE_PREFIX_SHARE", 1, int))
        self.prefix_share = bool(int(share))
        chunk = int(prefill_chunk if prefill_chunk is not None else
                    get_env("MX_SERVE_PREFILL_CHUNK", 0, int))
        if chunk <= 0:
            chunk = self.kv_page_len
        # chunks are page-aligned by construction: round up
        self.prefill_chunk = \
            -(-chunk // self.kv_page_len) * self.kv_page_len
        # -- speculative window width (ISSUE 20) ----------------------------
        # the verify program writes positions len..len+k into the slot's
        # pages before acceptance truncates back, so k may never exceed
        # the overrun margin the pool geometry already reserves
        k = int(spec_k if spec_k is not None else
                get_env("MX_SERVE_SPEC_K", 4, int))
        self.spec_k = max(1, min(k, _OVERRUN_MARGIN))

    def prompt_bucket_for(self, n: int) -> Optional[int]:
        for b in self.prompt_buckets:
            if b >= n:
                return b
        return None

    def slot_bucket_for(self, n: int) -> int:
        for b in self.slot_buckets:
            if b >= n:
                return b
        return self.slot_buckets[-1]

    def __repr__(self):
        return ("DecodeConfig(vocab=%d, dim=%d, heads=%d, layers=%d, "
                "slots=%d, max_tokens=%d, page=%d, max_len=%d)"
                % (self.vocab, self.dim, self.heads, self.layers,
                   self.slots, self.max_tokens, self.page, self.max_len))


def demo_lm_params(config: Optional[DecodeConfig] = None
                   ) -> Dict[str, jnp.ndarray]:
    """Seeded deterministic demo LM parameters (the decode analogue of
    ``serve.demo.demo_block``): both sides of a chaos run build these
    independently, so generated-token *correctness* is assertable
    across processes.  The unembedding is scaled up so greedy-argmax
    margins are decisive — bucket packing must not flip a token on a
    float whisker."""
    cfg = config or DecodeConfig()
    rs = _np.random.RandomState(cfg.seed)
    d = cfg.dim

    def mat(rows, cols, scale):
        return jnp.asarray(rs.randn(rows, cols).astype(_np.float32)
                           * scale)

    params: Dict[str, jnp.ndarray] = {
        "emb": mat(cfg.vocab, d, 1.0),
        "unemb": mat(d, cfg.vocab, 4.0 / (d ** 0.5)),
    }
    for l in range(cfg.layers):
        for name in ("wq", "wk", "wv", "wo"):
            params["l%d.%s" % (l, name)] = mat(d, d, 1.0 / (d ** 0.5))
        params["l%d.w1" % l] = mat(d, 2 * d, 1.0 / (d ** 0.5))
        params["l%d.w2" % l] = mat(2 * d, d, 1.0 / ((2 * d) ** 0.5))
    return params


def demo_spec_pair(config: DecodeConfig, draft_layers: int = 1,
                   residual_eps: float = 1e-4):
    """A draft-friendly (target, draft) parameter pair for speculative
    decoding (ISSUE 20).

    The target is ``config.layers`` deep, but every layer past
    ``draft_layers`` has its residual write-back matrices (``wo`` /
    ``w2``) scaled by ``residual_eps`` — those layers still run at full
    cost, yet perturb the residual stream by ~eps, so the target's
    greedy argmax (decisive margins: the demo unembedding is scaled x4)
    almost always equals what the first ``draft_layers`` layers alone
    predict.  The draft is exactly that shallow prefix, sharing the
    embedding/unembedding tables, so acceptance runs near 100% while
    the draft costs ``draft_layers / layers`` of a target step — the
    regime speculative decoding pays off in.

    Returns ``(target_params, draft_config, draft_params)``; the draft
    config shares every pool/bucket dimension with ``config`` (slot ids
    and lengths line up 1:1) but is only ``draft_layers`` deep.
    """
    cfg = config
    draft_layers = max(1, min(int(draft_layers), cfg.layers))
    target = demo_lm_params(cfg)
    for l in range(draft_layers, cfg.layers):
        target["l%d.wo" % l] = target["l%d.wo" % l] * residual_eps
        target["l%d.w2" % l] = target["l%d.w2" % l] * residual_eps
    draft_cfg = DecodeConfig(
        vocab=cfg.vocab, dim=cfg.dim, heads=cfg.heads,
        layers=draft_layers, slots=cfg.slots,
        max_tokens=cfg.max_tokens, page=cfg.page,
        prompt_buckets=cfg.prompt_buckets, eos_id=cfg.eos_id,
        seed=cfg.seed, kv_pages=cfg.kv_pages,
        kv_page_len=cfg.kv_page_len, prefix_share=cfg.prefix_share,
        prefill_chunk=cfg.prefill_chunk, spec_k=cfg.spec_k)
    draft = {"emb": target["emb"], "unemb": target["unemb"]}
    for l in range(draft_layers):
        for name in ("wq", "wk", "wv", "wo", "w1", "w2"):
            key = "l%d.%s" % (l, name)
            draft[key] = target[key]
    return target, draft_cfg, draft


# ---------------------------------------------------------------------------
# traced program bodies (pure; jit-purity applies via register_program)
# ---------------------------------------------------------------------------


def _block_mlp(params, l, x):
    h = jnp.maximum(x @ params["l%d.w1" % l], 0.0)
    return x + h @ params["l%d.w2" % l]


def _decode_body(cfg: DecodeConfig, params, k_pool, v_pool, tokens,
                 lengths, slot_ids):
    """One decode step over the packed active set.

    ``k_pool``/``v_pool``: (L, S+1, P, H, Dh) donated; ``tokens`` /
    ``lengths``: (S+1,) int32 donated (tokens = each slot's NEXT input
    token, device-resident so the pump never reads the host between
    steps); ``slot_ids``: (b,) int32, padded lanes carry the scratch
    index S.  Returns the four state arrays (aliased in place via
    donation) plus the (b,) sampled tokens for the harvester.
    """
    tok = tokens[slot_ids]                              # (b,)
    lens = lengths[slot_ids]                            # (b,)
    x = params["emb"][tok]                              # (b, D)
    b = x.shape[0]
    pos = lens                     # this token's KV write position
    for l in range(cfg.layers):
        k_new = (x @ params["l%d.wk" % l]).reshape(
            b, cfg.heads, cfg.head_dim)
        v_new = (x @ params["l%d.wv" % l]).reshape(
            b, cfg.heads, cfg.head_dim)
        k_pool = k_pool.at[l, slot_ids, pos].set(k_new)
        v_pool = v_pool.at[l, slot_ids, pos].set(v_new)
        q = (x @ params["l%d.wq" % l]).reshape(b, cfg.heads,
                                               cfg.head_dim)
        att = cached_attention(q, k_pool[l, slot_ids],
                               v_pool[l, slot_ids], lens + 1)
        x = x + att.reshape(b, cfg.dim) @ params["l%d.wo" % l]
        x = _block_mlp(params, l, x)
    logits = x @ params["unemb"]                        # (b, V)
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    tokens = tokens.at[slot_ids].set(nxt)
    lengths = lengths.at[slot_ids].set(lens + 1)
    # park the scratch slot: padded lanes read/write it every step, so
    # its bookkeeping must reset or its fake length would creep past
    # the pool extent
    tokens = tokens.at[cfg.slots].set(0)
    lengths = lengths.at[cfg.slots].set(0)
    return k_pool, v_pool, tokens, lengths, nxt


def _prefill_body(cfg: DecodeConfig, params, k_pool, v_pool, tokens,
                  lengths, slot_id, prompt, n):
    """Process one padded prompt into slot ``slot_id``: causal attention
    over the prompt (keys masked to the true length ``n``), KV pages
    written for every position, first generated token sampled from the
    last REAL position.  Rows past ``n`` compute garbage that is never
    attended (decode masks by length) and is overwritten as the
    generation advances."""
    Lp = prompt.shape[0]
    x = params["emb"][prompt]                           # (Lp, D)
    valid = jnp.arange(Lp) < n
    for l in range(cfg.layers):
        k = (x @ params["l%d.wk" % l]).reshape(Lp, cfg.heads,
                                               cfg.head_dim)
        v = (x @ params["l%d.wv" % l]).reshape(Lp, cfg.heads,
                                               cfg.head_dim)
        k_pool = lax.dynamic_update_slice(
            k_pool, k[None, None], (l, slot_id, 0, 0, 0))
        v_pool = lax.dynamic_update_slice(
            v_pool, v[None, None], (l, slot_id, 0, 0, 0))
        q = (x @ params["l%d.wq" % l]).reshape(Lp, cfg.heads,
                                               cfg.head_dim)
        q4 = q.transpose(1, 0, 2)[None]                 # (1, H, Lp, Dh)
        k4 = k.transpose(1, 0, 2)[None]
        v4 = v.transpose(1, 0, 2)[None]
        att = attention_core(q4, k4, v4, causal=True,
                             mask=valid[None, None, None, :])
        x = x + att[0].transpose(1, 0, 2).reshape(Lp, cfg.dim) \
            @ params["l%d.wo" % l]
        x = _block_mlp(params, l, x)
    x_last = jnp.take(x, jnp.maximum(n - 1, 0), axis=0)
    logits = x_last @ params["unemb"]
    t0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    tokens = tokens.at[slot_id].set(t0)
    lengths = lengths.at[slot_id].set(n)
    return k_pool, v_pool, tokens, lengths, t0


def _paged_decode_body(cfg: DecodeConfig, params, k_heap, v_heap,
                       tokens, lengths, slot_ids, block_tbls):
    """One decode step over the packed active set, PAGED pool (ISSUE
    18).

    ``k_heap``/``v_heap``: (L, kv_pages, kv_page_len, H, Dh) donated —
    the ONE shared heap; ``block_tbls``: (b, pages_per_slot) int32
    physical page ids per lane (padded lanes carry all-zero rows: page
    0 is the reserved scratch page).  The new token's KV entry scatters
    to ``block_tbls[lane][pos // page_len]`` at offset ``pos %
    page_len``; attention gathers each lane's pages back into its
    logical extent via :func:`paged_attention`.  Decode never writes a
    SHARED page: generation positions live past the prompt, in pages
    the session allocated privately.
    """
    pl = cfg.kv_page_len
    tok = tokens[slot_ids]                              # (b,)
    lens = lengths[slot_ids]                            # (b,)
    x = params["emb"][tok]                              # (b, D)
    b = x.shape[0]
    pos = lens                     # this token's logical write position
    page_idx = jnp.clip(pos // pl, 0, cfg.pages_per_slot - 1)
    phys = jnp.take_along_axis(block_tbls, page_idx[:, None],
                               axis=1)[:, 0]            # (b,)
    off = pos % pl
    for l in range(cfg.layers):
        k_new = (x @ params["l%d.wk" % l]).reshape(
            b, cfg.heads, cfg.head_dim)
        v_new = (x @ params["l%d.wv" % l]).reshape(
            b, cfg.heads, cfg.head_dim)
        k_heap = k_heap.at[l, phys, off].set(k_new)
        v_heap = v_heap.at[l, phys, off].set(v_new)
        q = (x @ params["l%d.wq" % l]).reshape(b, cfg.heads,
                                               cfg.head_dim)
        att = paged_attention(q, k_heap[l], v_heap[l], block_tbls,
                              lens + 1)
        x = x + att.reshape(b, cfg.dim) @ params["l%d.wo" % l]
        x = _block_mlp(params, l, x)
    logits = x @ params["unemb"]                        # (b, V)
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    tokens = tokens.at[slot_ids].set(nxt)
    lengths = lengths.at[slot_ids].set(lens + 1)
    # park the scratch slot (same discipline as the flat body)
    tokens = tokens.at[cfg.slots].set(0)
    lengths = lengths.at[cfg.slots].set(0)
    return k_heap, v_heap, tokens, lengths, nxt


def _prefill_chunk_body(cfg: DecodeConfig, params, k_heap, v_heap,
                        tokens, lengths, slot_id, block_tbl, chunk,
                        start, nvalid, emit, cow_src, cow_dst):
    """One page-aligned prefill CHUNK into the paged heap (ISSUE 18).

    ``chunk``: (prefill_chunk,) token ids for absolute positions
    ``start .. start+Lc-1`` (rows past ``nvalid`` are padding — their
    KV writes land in the session's own reserved pages or the scratch
    page and are masked/overwritten, never attended); ``block_tbl``:
    (pages_per_slot,) this session's physical pages.  Row ``r``
    attends causally over absolute keys ``0 .. start+r``, gathered
    through the block table — earlier chunks' (or a DONOR's shared)
    pages included, so chunking is bit-compatible with one monolithic
    prefill.

    Copy-on-write folds in here: the program FIRST copies page
    ``cow_src`` -> ``cow_dst`` (both scalars; ``src == dst == 0`` is
    the self-copy no-op for chunks with no divergence), so a full
    prompt-coverage prefix hit needs only this ONE replay-chunk
    dispatch to fork the donor's last page and emit the first token —
    one trace signature regardless, keeping the chunk program table
    closed.

    ``emit`` > 0 (the final chunk) samples the first generated token
    from row ``nvalid - 1`` and arms the slot's next-input token;
    earlier chunks leave it untouched.  ``lengths[slot]`` advances to
    ``start + nvalid`` either way.
    """
    pl = cfg.kv_page_len
    Lc = chunk.shape[0]
    k_heap = k_heap.at[:, cow_dst].set(k_heap[:, cow_src])
    v_heap = v_heap.at[:, cow_dst].set(v_heap[:, cow_src])
    x = params["emb"][chunk]                            # (Lc, D)
    p = start + jnp.arange(Lc)                          # absolute pos
    page_idx = jnp.clip(p // pl, 0, cfg.pages_per_slot - 1)
    phys = block_tbl[page_idx]                          # (Lc,)
    off = p % pl
    ext = cfg.pages_per_slot * pl
    # causal-prefix mask: row r sees absolute keys 0..start+r (>= 1
    # live key per row, so the finite -1e30 masking stays NaN-free)
    mask = jnp.arange(ext)[None, :] <= p[:, None]       # (Lc, ext)
    for l in range(cfg.layers):
        k = (x @ params["l%d.wk" % l]).reshape(Lc, cfg.heads,
                                               cfg.head_dim)
        v = (x @ params["l%d.wv" % l]).reshape(Lc, cfg.heads,
                                               cfg.head_dim)
        k_heap = k_heap.at[l, phys, off].set(k)
        v_heap = v_heap.at[l, phys, off].set(v)
        q = (x @ params["l%d.wq" % l]).reshape(Lc, cfg.heads,
                                               cfg.head_dim)
        k_all = k_heap[l, block_tbl].reshape(ext, cfg.heads,
                                             cfg.head_dim)
        v_all = v_heap[l, block_tbl].reshape(ext, cfg.heads,
                                             cfg.head_dim)
        q4 = q.transpose(1, 0, 2)[None]                 # (1, H, Lc, Dh)
        k4 = k_all.transpose(1, 0, 2)[None]
        v4 = v_all.transpose(1, 0, 2)[None]
        att = attention_core(q4, k4, v4, mask=mask[None, None])
        x = x + att[0].transpose(1, 0, 2).reshape(Lc, cfg.dim) \
            @ params["l%d.wo" % l]
        x = _block_mlp(params, l, x)
    x_last = jnp.take(x, jnp.maximum(nvalid - 1, 0), axis=0)
    logits = x_last @ params["unemb"]
    t0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    tokens = tokens.at[slot_id].set(
        jnp.where(emit > 0, t0, tokens[slot_id]))
    lengths = lengths.at[slot_id].set(start + nvalid)
    return k_heap, v_heap, tokens, lengths, t0


def _draft_step_body(cfg: DecodeConfig, params, k_pool, v_pool, tokens,
                     lengths, props, slot_ids, col):
    """One DRAFT autoregressive step (ISSUE 20): the flat decode body
    on the draft's own tiny KV pool, with the sampled token ALSO
    written into column ``col`` of the device-resident proposals
    buffer ``props`` (slots+1, spec_k) — the verify dispatch reads the
    whole window from there, so the k draft steps + verify chain never
    syncs the host.  ``col`` is a traced scalar: one program per slot
    bucket covers every window position."""
    k_pool, v_pool, tokens, lengths, nxt = _decode_body(
        cfg, params, k_pool, v_pool, tokens, lengths, slot_ids)
    props = props.at[slot_ids, col].set(nxt)
    # park the scratch row (padded lanes write it every step)
    props = props.at[cfg.slots].set(0)
    return k_pool, v_pool, tokens, lengths, props


def _draft_prefill_body(cfg: DecodeConfig, params, k_pool, v_pool,
                        tokens, lengths, tgt_tokens, slot_id, prompt,
                        n):
    """Prefill the DRAFT's KV pool for one admitted session (ISSUE
    20): the flat prefill body, except the slot's next-input token is
    adopted from the TARGET's token array (read-only input) rather
    than the draft's own first-token prediction — the window invariant
    is that draft and target agree on (next token, length) at every
    window boundary, and the first committed token is the target's.
    Passing ``tgt_tokens`` as a program input also makes XLA order
    this dispatch after the target's emitting prefill chunk."""
    k_pool, v_pool, tokens, lengths, _t0 = _prefill_body(
        cfg, params, k_pool, v_pool, tokens, lengths, slot_id, prompt,
        n)
    tokens = tokens.at[slot_id].set(tgt_tokens[slot_id])
    return k_pool, v_pool, tokens, lengths


def _verify_body(cfg: DecodeConfig, params, k_heap, v_heap, t_tok,
                 t_len, d_tok, d_len, props, slot_ids, block_tbls):
    """Verify one speculative window in ONE dispatch (ISSUE 20).

    Window invariant on entry (per active lane, slot ``s``, length
    ``L``, next token ``t``): the draft ran k steps from (t, L), so
    ``props[s]`` holds its proposals d_1..d_k and the draft KV covers
    positions L..L+k-1 (inputs t, d_1..d_{k-1}).  This program runs
    the TARGET over the k+1 inputs ``[t, d_1..d_k]`` at positions
    ``L..L+k`` through the paged heap — the chunked-prefill scatter/
    gather pattern with a per-position causal mask — and argmaxes
    every position: ``a_j`` is the target's greedy token after input
    j.  Acceptance is the standard longest-prefix rule, CAPPED at
    k-1 so the committed state never depends on position L+k (whose
    input d_k may be wrong):

        m  = max prefix with d_j == a_{j-1}        (0..k)
        m' = min(m, k-1)
        emit a_0..a_{m'}  (1..k tokens; all provably equal what
                           non-speculative greedy decode emits)
        next token = a_{m'},  new length = L + m' + 1

    On full acceptance (m == k) this emits k tokens and the next
    token a_{k-1} == d_k is exactly the draft's current state — both
    models stay in lockstep with no host round-trip; on a rejection
    the program itself rewrites the DRAFT's (token, length) arrays
    (donated in) to the corrected values, so the draft's stale KV
    past the new length is masked garbage, overwritten by its next
    window's steps.  Target KV entries past L+m' are likewise stale
    and land inside the slot's pages (k <= _OVERRUN_MARGIN).

    Returns (k_heap, v_heap, t_tok, t_len, d_tok, d_len, emitted,
    n_em): ``emitted`` (b, k) holds a_0..a_{k-1}, of which the first
    ``n_em[lane]`` are real — the harvester appends exactly those.
    """
    pl = cfg.kv_page_len
    K = props.shape[1]
    E = K + 1
    lens = t_len[slot_ids]                              # (b,) = L
    cur = t_tok[slot_ids]                               # (b,)
    d = props[slot_ids]                                 # (b, K)
    inp = jnp.concatenate([cur[:, None], d], axis=1)    # (b, E)
    x = params["emb"][inp]                              # (b, E, D)
    b = x.shape[0]
    pos = lens[:, None] + jnp.arange(E)[None, :]        # (b, E)
    page_idx = jnp.clip(pos // pl, 0, cfg.pages_per_slot - 1)
    phys = jnp.take_along_axis(block_tbls, page_idx, axis=1)  # (b, E)
    off = pos % pl
    for l in range(cfg.layers):
        k_new = (x @ params["l%d.wk" % l]).reshape(
            b, E, cfg.heads, cfg.head_dim)
        v_new = (x @ params["l%d.wv" % l]).reshape(
            b, E, cfg.heads, cfg.head_dim)
        k_heap = k_heap.at[l, phys, off].set(k_new)
        v_heap = v_heap.at[l, phys, off].set(v_new)
        q = (x @ params["l%d.wq" % l]).reshape(b, E, cfg.heads,
                                               cfg.head_dim)
        att = paged_attention_multi(q, k_heap[l], v_heap[l],
                                    block_tbls, pos)
        x = x + att.reshape(b, E, cfg.dim) @ params["l%d.wo" % l]
        x = _block_mlp(params, l, x)
    logits = x @ params["unemb"]                        # (b, E, V)
    a = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # (b, E)
    # accept d_{i+1} while it equals a_i, longest prefix, capped k-1
    match = (d == a[:, :K]).astype(jnp.int32)           # (b, K)
    m = jnp.sum(jnp.cumprod(match, axis=1), axis=1)     # (b,) 0..K
    m_cap = jnp.minimum(m, K - 1)
    n_em = (m_cap + 1).astype(jnp.int32)                # (b,) 1..K
    emitted = a[:, :K]                                  # (b, K)
    new_tok = jnp.take_along_axis(a, m_cap[:, None], axis=1)[:, 0]
    new_len = lens + n_em
    t_tok = t_tok.at[slot_ids].set(new_tok)
    t_len = t_len.at[slot_ids].set(new_len)
    d_tok = d_tok.at[slot_ids].set(new_tok)
    d_len = d_len.at[slot_ids].set(new_len)
    # park the scratch slot on BOTH state pairs (padded lanes)
    t_tok = t_tok.at[cfg.slots].set(0)
    t_len = t_len.at[cfg.slots].set(0)
    d_tok = d_tok.at[cfg.slots].set(0)
    d_len = d_len.at[cfg.slots].set(0)
    return (k_heap, v_heap, t_tok, t_len, d_tok, d_len, emitted,
            n_em)


# geometry-keyed jit cache for the reference oracle: a load driver
# replays MANY reference decodes against one model — per-token eager
# dispatch would dominate its wall time.  Plain jax.jit, deliberately
# NOT register_program: the oracle is a verification tool, not a
# serving path, and must not pollute the serve census.
_reference_jits: Dict[Tuple, Tuple] = {}
_reference_jits_lock = threading.Lock()


def _reference_step_fns(cfg: DecodeConfig):
    key = (cfg.vocab, cfg.dim, cfg.heads, cfg.layers, cfg.slots,
           cfg.max_len)
    with _reference_jits_lock:
        fns = _reference_jits.get(key)
        if fns is None:
            fns = (jax.jit(functools.partial(_prefill_body, cfg)),
                   jax.jit(functools.partial(_decode_body, cfg)))
            _reference_jits[key] = fns
        return fns


def reference_generate(prompt: Sequence[int], max_new: int,
                       params: Optional[Dict] = None,
                       config: Optional[DecodeConfig] = None,
                       eos_id: Optional[int] = None) -> List[int]:
    """Local greedy-decode oracle: drives the SAME prefill/decode
    bodies through a private single-slot state (no pool sharing), so a
    load driver can recompute what a correct replica must answer — the
    decode analogue of ``demo.demo_expected``."""
    cfg = config or DecodeConfig()
    params = params if params is not None else demo_lm_params(cfg)
    lp = cfg.prompt_bucket_for(len(prompt))
    if lp is None:
        raise MXNetError("reference_generate: prompt of %d tokens "
                         "exceeds the top prompt bucket %d"
                         % (len(prompt), cfg.prompt_buckets[-1]))
    prefill_fn, decode_fn = _reference_step_fns(cfg)
    shape = (cfg.layers, cfg.slots + 1, cfg.max_len, cfg.heads,
             cfg.head_dim)
    k = jnp.zeros(shape, jnp.float32)
    v = jnp.zeros(shape, jnp.float32)
    tok = jnp.zeros((cfg.slots + 1,), jnp.int32)
    ln = jnp.zeros((cfg.slots + 1,), jnp.int32)
    padded = _np.zeros(lp, _np.int32)
    padded[:len(prompt)] = list(prompt)
    k, v, tok, ln, t0 = prefill_fn(params, k, v, tok, ln,
                                   _np.int32(0), jnp.asarray(padded),
                                   _np.int32(len(prompt)))
    out = [int(t0)]
    ids = jnp.zeros((1,), jnp.int32)
    while len(out) < max_new:
        if eos_id is not None and out[-1] == eos_id:
            break
        k, v, tok, ln, nxt = decode_fn(params, k, v, tok, ln, ids)
        out.append(int(nxt[0]))
    return out[:max_new]


class _CensusHandle:
    """Weakref-able holder so one servable can own two census buckets
    (its KV pool under ``kv_cache``, its parameters under ``serve``)."""

    __slots__ = ("fn", "__weakref__")

    def __init__(self, fn):
        self.fn = fn


def _counter(name, doc):
    return _telemetry.registry.counter(name, doc=doc)


class DecodeServable:
    """One immutable decode-model version: params + device-resident KV
    pool + the two bucketed AOT program tables (prefill by prompt
    bucket, decode by slot bucket).

    The KV state (pool pages, per-slot next-token and length arrays) is
    DONATED through every dispatch: ``_state`` always holds the only
    live copy, rebound from the program outputs, so pool bytes in
    ``buffer_census()['kv_cache']`` are constant for the servable's
    lifetime.  Only the pump thread may dispatch (single-writer state).
    """

    #: engine discriminator on the health surface; the paged subclass
    #: overrides both (its heap is censused under ``kv_pages``)
    engine = "flat"
    census_owner = "kv_cache"

    def _alloc_state(self) -> Dict[str, jnp.ndarray]:
        cfg = self.config
        shape = (cfg.layers, cfg.slots + 1, cfg.max_len, cfg.heads,
                 cfg.head_dim)
        return {
            "k": jnp.zeros(shape, jnp.float32),
            "v": jnp.zeros(shape, jnp.float32),
            "tok": jnp.zeros((cfg.slots + 1,), jnp.int32),
            "len": jnp.zeros((cfg.slots + 1,), jnp.int32),
        }

    def param_platform(self) -> str:
        """Where the parameters AND the KV pool live, for HEALTH (both
        are built uncommitted with jnp, so they land on jax's default
        device — the chip, unless the process is CPU-pinned)."""
        from .servable import platform_of
        return platform_of(list(self.params.values()) +
                           [self._state["k"], self._state["v"]])

    def __init__(self, params: Optional[Dict] = None,
                 config: Optional[DecodeConfig] = None,
                 name: str = "demo-lm", version: int = 1):
        self.config = config or DecodeConfig()
        self.params = params if params is not None \
            else demo_lm_params(self.config)
        self.name = str(name)
        self.version = int(version)
        self._state: Dict[str, jnp.ndarray] = self._alloc_state()
        from .. import programs as _programs
        self._kv_handle = _CensusHandle(
            lambda: list(self._state.values()))
        self._params_handle = _CensusHandle(
            lambda: list(self.params.values()))
        _programs.track_buffers(self.census_owner, self._kv_handle,
                                lambda h: h.fn())
        _programs.track_buffers("serve", self._params_handle,
                                lambda h: h.fn())
        self._lock = threading.Lock()
        self._step_programs: Dict[int, object] = {}
        self._prefill_programs: Dict[int, object] = {}
        self._verify_programs: Dict[int, object] = {}
        self.retraces = 0            # program builds (warm pays them)
        self.hits = 0                # dispatches answered by the table
        self.warmed = False
        self._c_retrace = _counter(
            "serve.retraces", "serve-side program builds (should be 0 "
            "after warmup; warm() pays them at deploy)")
        self._c_hits = _counter(
            "serve.bucket_hits", "dispatches answered by a pre-built "
            "bucket program")

    # -- HBM census (ISSUE 20 bin-packing) ----------------------------------
    def program_prefix(self) -> str:
        """Program-registry name prefix this servable's programs live
        under (the budget packer reads their memory_analysis here)."""
        return "serve.decode."

    def live_bytes(self) -> int:
        """Resident bytes: params + the whole KV state (pool or page
        heap) — exactly the arrays the buffer census owner-tags."""
        n = sum(int(getattr(a, "nbytes", 0))
                for a in self.params.values())
        n += sum(int(getattr(a, "nbytes", 0))
                 for a in self._state.values())
        return n

    def footprint_bytes(self) -> int:
        """Measured HBM footprint for the ModelHost budget packer:
        live bytes plus the peak transient bytes of any registered
        decode program (populated by :meth:`warm`)."""
        from .. import programs as _programs
        mem = _programs.program_memory_bytes(self.program_prefix())
        return self.live_bytes() + int(mem["temp_bytes_peak"])

    # -- program tables -----------------------------------------------------
    def step_program(self, bucket: int):
        """The decode program for one slot bucket (builds on miss,
        counted as a retrace — warm() pre-builds every bucket)."""
        bucket = int(bucket)
        with self._lock:
            prog = self._step_programs.get(bucket)
            if prog is not None:
                self.hits += 1
        if prog is not None:
            self._c_hits.inc()
            return prog
        cfg = self.config

        def run_decode(params, k_pool, v_pool, tokens, lengths,
                       slot_ids):
            return _decode_body(cfg, params, k_pool, v_pool, tokens,
                                lengths, slot_ids)

        from .. import programs as _programs
        with _telemetry.phase("retrace"):
            prog = _programs.register_program(
                "serve.decode.step.s%d" % bucket, run_decode,
                donate_argnums=(1, 2, 3, 4))
        with self._lock:
            prog = self._step_programs.setdefault(bucket, prog)
            self.retraces += 1
        self._c_retrace.inc()
        return prog

    def prefill_program(self, prompt_bucket: int):
        prompt_bucket = int(prompt_bucket)
        with self._lock:
            prog = self._prefill_programs.get(prompt_bucket)
            if prog is not None:
                self.hits += 1
        if prog is not None:
            self._c_hits.inc()
            return prog
        cfg = self.config

        def run_prefill(params, k_pool, v_pool, tokens, lengths,
                        slot_id, prompt, n):
            return _prefill_body(cfg, params, k_pool, v_pool, tokens,
                                 lengths, slot_id, prompt, n)

        from .. import programs as _programs
        with _telemetry.phase("retrace"):
            prog = _programs.register_program(
                "serve.decode.prefill.p%d" % prompt_bucket, run_prefill,
                donate_argnums=(1, 2, 3, 4))
        with self._lock:
            prog = self._prefill_programs.setdefault(prompt_bucket,
                                                     prog)
            self.retraces += 1
        self._c_retrace.inc()
        return prog

    # -- dispatch (pump thread only; mxlint hot-path roots) -----------------
    def dispatch_step(self, slot_ids: _np.ndarray):
        """ONE device program over the packed active set; rebinds the
        donated state and returns the (b,) emitted-token device array
        (async — the harvester syncs it)."""
        from ..engine import engine as _engine
        prog = self.step_program(len(slot_ids))
        st = self._state
        k, v, tok, ln, out = prog(self.params, st["k"], st["v"],
                                  st["tok"], st["len"], slot_ids)
        self._state = {"k": k, "v": v, "tok": tok, "len": ln}
        _engine.count_dispatch(1)
        return out

    def dispatch_prefill(self, slot: int, prompt: _np.ndarray, n: int):
        """ONE device program filling ``slot``'s KV pages from a padded
        prompt; returns the first generated token as a () device
        array."""
        from ..engine import engine as _engine
        prog = self.prefill_program(prompt.shape[0])
        st = self._state
        k, v, tok, ln, t0 = prog(self.params, st["k"], st["v"],
                                 st["tok"], st["len"],
                                 _np.int32(slot), prompt, _np.int32(n))
        self._state = {"k": k, "v": v, "tok": tok, "len": ln}
        _engine.count_dispatch(1)
        return t0

    def warm(self) -> "DecodeServable":
        """Pre-build + pre-run EVERY prefill and decode bucket (against
        the scratch slot), then reset the generation bookkeeping —
        after this, serve time never pays a trace."""
        cfg = self.config
        for lp in cfg.prompt_buckets:
            self.dispatch_prefill(cfg.slots,
                                  _np.zeros(lp, _np.int32), lp)
        for b in cfg.slot_buckets:
            self.dispatch_step(_np.full(b, cfg.slots, _np.int32))
        jax.block_until_ready(self._state["k"])
        # scratch-slot bookkeeping back to empty; the pool's warmed
        # garbage is masked by zero lengths and overwritten on reuse
        self._state["tok"] = jnp.zeros_like(self._state["tok"])
        self._state["len"] = jnp.zeros_like(self._state["len"])
        self.warmed = True
        return self

    def kv_state_bytes(self) -> int:
        """Current KV-state footprint (pool pages + token/length
        arrays) — the number that must stay FLAT across generations."""
        return sum(int(a.nbytes) for a in self._state.values())

    def kv_slot_bytes(self) -> int:
        """One slot's share of the KV pool (the scratch lane counts as
        a slot here — the pool is ``slots + 1`` lanes wide), i.e. the
        bytes a free slot represents as ADMISSION headroom."""
        return self.kv_state_bytes() // (self.config.slots + 1)


class PagedDecodeServable(DecodeServable):
    """The PAGED decode servable (ISSUE 18): same model, but the KV
    store is one shared page heap ``(L, kv_pages, kv_page_len, H,
    Dh)`` — owner-tagged ``kv_pages`` in the census, donated through
    every dispatch — addressed per session through host-side block
    tables.  Two program tables replace the flat pair:

    * ``serve.decode.paged.step.s{b}`` per slot bucket — the decode
      step with per-lane block tables (scatter the new KV entry to its
      physical page, gather the lane's pages for attention);
    * ``serve.decode.paged.prefill.c{Lc}`` — ONE chunk program (the
      chunk length is the compile unit, not the prompt bucket): any
      admitted prompt prefills as a train of page-aligned chunks, and
      the CoW page fork rides the same signature, so the trace set is
      closed with a single prefill program regardless of prompt
      length.

    The monolithic flat prefill has no paged analogue —
    :meth:`dispatch_prefill` raises; the pump schedules
    :meth:`dispatch_chunk` trains instead.
    """

    engine = "paged"
    census_owner = "kv_pages"

    def _alloc_state(self) -> Dict[str, jnp.ndarray]:
        cfg = self.config
        heap = (cfg.layers, cfg.kv_pages, cfg.kv_page_len, cfg.heads,
                cfg.head_dim)
        return {
            "k": jnp.zeros(heap, jnp.float32),
            "v": jnp.zeros(heap, jnp.float32),
            "tok": jnp.zeros((cfg.slots + 1,), jnp.int32),
            "len": jnp.zeros((cfg.slots + 1,), jnp.int32),
        }

    # -- program tables -----------------------------------------------------
    def step_program(self, bucket: int):
        bucket = int(bucket)
        with self._lock:
            prog = self._step_programs.get(bucket)
            if prog is not None:
                self.hits += 1
        if prog is not None:
            self._c_hits.inc()
            return prog
        cfg = self.config

        def run_decode(params, k_heap, v_heap, tokens, lengths,
                       slot_ids, block_tbls):
            return _paged_decode_body(cfg, params, k_heap, v_heap,
                                      tokens, lengths, slot_ids,
                                      block_tbls)

        from .. import programs as _programs
        with _telemetry.phase("retrace"):
            prog = _programs.register_program(
                "serve.decode.paged.step.s%d" % bucket, run_decode,
                donate_argnums=(1, 2, 3, 4))
        with self._lock:
            prog = self._step_programs.setdefault(bucket, prog)
            self.retraces += 1
        self._c_retrace.inc()
        return prog

    def chunk_program(self):
        """THE prefill program: one signature (chunk length
        ``prefill_chunk``) covers every admitted prompt as a chunk
        train."""
        lc = self.config.prefill_chunk
        with self._lock:
            prog = self._prefill_programs.get(lc)
            if prog is not None:
                self.hits += 1
        if prog is not None:
            self._c_hits.inc()
            return prog
        cfg = self.config

        def run_chunk(params, k_heap, v_heap, tokens, lengths, slot_id,
                      block_tbl, chunk, start, nvalid, emit, cow_src,
                      cow_dst):
            return _prefill_chunk_body(cfg, params, k_heap, v_heap,
                                       tokens, lengths, slot_id,
                                       block_tbl, chunk, start, nvalid,
                                       emit, cow_src, cow_dst)

        from .. import programs as _programs
        with _telemetry.phase("retrace"):
            prog = _programs.register_program(
                "serve.decode.paged.prefill.c%d" % lc, run_chunk,
                donate_argnums=(1, 2, 3, 4))
        with self._lock:
            prog = self._prefill_programs.setdefault(lc, prog)
            self.retraces += 1
        self._c_retrace.inc()
        return prog

    def prefill_program(self, prompt_bucket: int):
        raise MXNetError("paged decode servable has no monolithic "
                         "prefill program; prompts prefill as chunk "
                         "trains (chunk_program)")

    # -- dispatch (pump thread only; mxlint hot-path roots) -----------------
    def dispatch_step(self, slot_ids: _np.ndarray,
                      block_tbls: _np.ndarray):
        """ONE device program over the packed active set + its block
        tables; rebinds the donated heap state."""
        from ..engine import engine as _engine
        prog = self.step_program(len(slot_ids))
        st = self._state
        k, v, tok, ln, out = prog(self.params, st["k"], st["v"],
                                  st["tok"], st["len"], slot_ids,
                                  block_tbls)
        self._state = {"k": k, "v": v, "tok": tok, "len": ln}
        _engine.count_dispatch(1)
        return out

    def dispatch_prefill(self, slot: int, prompt: _np.ndarray, n: int):
        raise MXNetError("paged decode servable has no monolithic "
                         "prefill dispatch; use dispatch_chunk")

    def dispatch_chunk(self, slot: int, block_tbl: _np.ndarray,
                       chunk: _np.ndarray, start: int, nvalid: int,
                       emit: bool, cow_src: int = 0, cow_dst: int = 0):
        """ONE device program writing one page-aligned prefill chunk
        (plus the optional CoW page fork) through ``slot``'s block
        table; returns the chunk's sampled token as a () device array
        (meaningful only when ``emit``)."""
        from ..engine import engine as _engine
        prog = self.chunk_program()
        st = self._state
        k, v, tok, ln, t0 = prog(
            self.params, st["k"], st["v"], st["tok"], st["len"],
            _np.int32(slot), block_tbl, chunk, _np.int32(start),
            _np.int32(nvalid), _np.int32(1 if emit else 0),
            _np.int32(cow_src), _np.int32(cow_dst))
        self._state = {"k": k, "v": v, "tok": tok, "len": ln}
        _engine.count_dispatch(1)
        return t0

    def verify_program(self, bucket: int):
        """The speculative VERIFY program for one slot bucket (ISSUE
        20): all k+1 window positions of every lane in one dispatch —
        multi-position paged attention, per-position argmax, the
        accept-longest-prefix rule and the draft-state correction all
        traced into a single program."""
        bucket = int(bucket)
        with self._lock:
            prog = self._verify_programs.get(bucket)
            if prog is not None:
                self.hits += 1
        if prog is not None:
            self._c_hits.inc()
            return prog
        cfg = self.config

        def run_verify(params, k_heap, v_heap, t_tok, t_len, d_tok,
                       d_len, props, slot_ids, block_tbls):
            return _verify_body(cfg, params, k_heap, v_heap, t_tok,
                                t_len, d_tok, d_len, props, slot_ids,
                                block_tbls)

        from .. import programs as _programs
        with _telemetry.phase("retrace"):
            prog = _programs.register_program(
                "serve.decode.verify.k%d.s%d" % (cfg.spec_k, bucket),
                run_verify, donate_argnums=(1, 2, 3, 4, 5, 6))
        with self._lock:
            prog = self._verify_programs.setdefault(bucket, prog)
            self.retraces += 1
        self._c_retrace.inc()
        return prog

    def dispatch_verify(self, draft: "DraftDecodeServable",
                        slot_ids: _np.ndarray,
                        block_tbls: _np.ndarray):
        """ONE verify dispatch over the packed window set: donates the
        target heap state AND the draft's token/length arrays (both
        rebound), reads the draft's device-resident proposals buffer —
        no host sync anywhere; the (emitted, n_em) pair goes to the
        harvester."""
        from ..engine import engine as _engine
        prog = self.verify_program(len(slot_ids))
        st = self._state
        dst = draft._state
        k, v, tt, tl, dt, dl, emitted, n_em = prog(
            self.params, st["k"], st["v"], st["tok"], st["len"],
            dst["tok"], dst["len"], dst["props"], slot_ids,
            block_tbls)
        self._state = {"k": k, "v": v, "tok": tt, "len": tl}
        draft._state = {"k": dst["k"], "v": dst["v"], "tok": dt,
                        "len": dl, "props": dst["props"]}
        _engine.count_dispatch(1)
        return emitted, n_em

    def warm(self) -> "PagedDecodeServable":
        """Pre-build + pre-run the chunk program and every decode slot
        bucket against the scratch page/slot, then reset the
        bookkeeping — zero serve-time retraces, as the flat engine."""
        cfg = self.config
        tbl = _np.zeros(cfg.pages_per_slot, _np.int32)
        self.dispatch_chunk(cfg.slots, tbl,
                            _np.zeros(cfg.prefill_chunk, _np.int32),
                            0, cfg.prefill_chunk, False)
        for b in cfg.slot_buckets:
            self.dispatch_step(
                _np.full(b, cfg.slots, _np.int32),
                _np.zeros((b, cfg.pages_per_slot), _np.int32))
        jax.block_until_ready(self._state["k"])
        self._state["tok"] = jnp.zeros_like(self._state["tok"])
        self._state["len"] = jnp.zeros_like(self._state["len"])
        self.warmed = True
        return self

    def page_bytes(self) -> int:
        """One physical page's K+V bytes across all layers — the unit
        the allocator's headroom gauges convert to bytes with."""
        cfg = self.config
        return (2 * cfg.layers * cfg.kv_page_len * cfg.heads *
                cfg.head_dim * 4)

    def kv_slot_bytes(self) -> int:
        """A worst-case session's heap share (its full block-table
        extent) — what one admission can cost at most."""
        return self.page_bytes() * self.config.pages_per_slot


class DraftDecodeServable(DecodeServable):
    """The DRAFT servable for speculative decoding (ISSUE 20): a small
    flat-pool decode model whose steps write their sampled tokens into
    a device-resident PROPOSALS buffer ``(slots+1, spec_k)`` instead
    of feeding the harvester — the target's verify program reads the
    whole window from it, so draft + verify form a pure device-side
    chain.  Geometry (slots, buckets, pool length) must match the
    target's so slot ids and lengths line up 1:1; only depth/width
    differ.  Co-hosted under the ModelHost HBM budget like any other
    servable (its pool is censused ``kv_cache``, its params
    ``serve``)."""

    engine = "draft"

    def program_prefix(self) -> str:
        return "serve.decode.draft."

    def _alloc_state(self) -> Dict[str, jnp.ndarray]:
        st = super()._alloc_state()
        cfg = self.config
        st["props"] = jnp.zeros((cfg.slots + 1, cfg.spec_k),
                                jnp.int32)
        return st

    # -- program tables -----------------------------------------------------
    def step_program(self, bucket: int):
        bucket = int(bucket)
        with self._lock:
            prog = self._step_programs.get(bucket)
            if prog is not None:
                self.hits += 1
        if prog is not None:
            self._c_hits.inc()
            return prog
        cfg = self.config

        def run_draft(params, k_pool, v_pool, tokens, lengths, props,
                      slot_ids, col):
            return _draft_step_body(cfg, params, k_pool, v_pool,
                                    tokens, lengths, props, slot_ids,
                                    col)

        from .. import programs as _programs
        with _telemetry.phase("retrace"):
            prog = _programs.register_program(
                "serve.decode.draft.s%d" % bucket, run_draft,
                donate_argnums=(1, 2, 3, 4, 5))
        with self._lock:
            prog = self._step_programs.setdefault(bucket, prog)
            self.retraces += 1
        self._c_retrace.inc()
        return prog

    def prefill_program(self, prompt_bucket: int):
        prompt_bucket = int(prompt_bucket)
        with self._lock:
            prog = self._prefill_programs.get(prompt_bucket)
            if prog is not None:
                self.hits += 1
        if prog is not None:
            self._c_hits.inc()
            return prog
        cfg = self.config

        def run_prefill(params, k_pool, v_pool, tokens, lengths,
                        tgt_tokens, slot_id, prompt, n):
            return _draft_prefill_body(cfg, params, k_pool, v_pool,
                                       tokens, lengths, tgt_tokens,
                                       slot_id, prompt, n)

        from .. import programs as _programs
        with _telemetry.phase("retrace"):
            prog = _programs.register_program(
                "serve.decode.draft.prefill.p%d" % prompt_bucket,
                run_prefill, donate_argnums=(1, 2, 3, 4))
        with self._lock:
            prog = self._prefill_programs.setdefault(prompt_bucket,
                                                     prog)
            self.retraces += 1
        self._c_retrace.inc()
        return prog

    # -- dispatch (pump thread only; mxlint hot-path roots) -----------------
    def dispatch_step(self, slot_ids: _np.ndarray, col: int):
        """ONE draft step over the packed window set, writing window
        column ``col`` of the proposals buffer."""
        from ..engine import engine as _engine
        prog = self.step_program(len(slot_ids))
        st = self._state
        k, v, tok, ln, props = prog(self.params, st["k"], st["v"],
                                    st["tok"], st["len"], st["props"],
                                    slot_ids, _np.int32(col))
        self._state = {"k": k, "v": v, "tok": tok, "len": ln,
                       "props": props}
        _engine.count_dispatch(1)
        return props

    def dispatch_prefill(self, slot: int, prompt: _np.ndarray, n: int,
                         tgt_tokens=None):
        """ONE draft-prefill dispatch; ``tgt_tokens`` is the TARGET's
        token array (read-only), whose ``slot`` entry arms the draft's
        next-input token."""
        from ..engine import engine as _engine
        prog = self.prefill_program(prompt.shape[0])
        st = self._state
        if tgt_tokens is None:
            tgt_tokens = jnp.zeros_like(st["tok"])
        k, v, tok, ln = prog(self.params, st["k"], st["v"], st["tok"],
                             st["len"], tgt_tokens, _np.int32(slot),
                             prompt, _np.int32(n))
        self._state = {"k": k, "v": v, "tok": tok, "len": ln,
                       "props": st["props"]}
        _engine.count_dispatch(1)
        return None

    def warm(self) -> "DraftDecodeServable":
        """Pre-build + pre-run every draft prefill and step bucket
        against the scratch slot, then reset the bookkeeping."""
        cfg = self.config
        zeros_tok = jnp.zeros((cfg.slots + 1,), jnp.int32)
        for lp in cfg.prompt_buckets:
            self.dispatch_prefill(cfg.slots,
                                  _np.zeros(lp, _np.int32), lp,
                                  tgt_tokens=zeros_tok)
        for b in cfg.slot_buckets:
            self.dispatch_step(_np.full(b, cfg.slots, _np.int32), 0)
        jax.block_until_ready(self._state["k"])
        self._state["tok"] = jnp.zeros_like(self._state["tok"])
        self._state["len"] = jnp.zeros_like(self._state["len"])
        self._state["props"] = jnp.zeros_like(self._state["props"])
        self.warmed = True
        return self


class _PendingGen:
    """One admitted generation request: prompt in, tokens accumulating
    out.  The pump owns its slot; the HARVESTER appends tokens, stamps
    per-token latency and flags completion; handler threads block in
    :meth:`result` / stream via :meth:`wait_new`."""

    __slots__ = ("prompt", "max_new", "eos_id", "trace_ctx", "submit_t",
                 "slot", "token_times", "_cv", "_tokens", "_done",
                 "_err", "_last_t")

    def __init__(self, prompt: List[int], max_new: int,
                 eos_id: Optional[int],
                 trace_ctx: Optional[Tuple[str, str]] = None):
        self.prompt = prompt
        self.max_new = int(max_new)
        self.eos_id = eos_id
        self.trace_ctx = trace_ctx
        self.submit_t = time.perf_counter()
        self.slot: Optional[int] = None
        self.token_times: List[float] = []   # per-token latency (s)
        self._cv = threading.Condition()
        self._tokens: List[int] = []
        self._done = False
        self._err: Optional[BaseException] = None
        self._last_t: Optional[float] = None

    # -- harvester side -----------------------------------------------------
    def _append(self, tok: int, now: float) -> Tuple[bool, bool]:
        """Record one harvested token; returns (appended, finished).
        Tokens arriving after completion (pipeline overrun) are
        dropped."""
        with self._cv:
            if self._done:
                return False, True
            base = self._last_t if self._last_t is not None \
                else self.submit_t
            self.token_times.append(now - base)
            self._last_t = now
            self._tokens.append(int(tok))
            finished = len(self._tokens) >= self.max_new or (
                self.eos_id is not None and int(tok) == self.eos_id)
            if finished:
                self._done = True
            self._cv.notify_all()
            return True, finished

    def _fail(self, err: BaseException) -> None:
        with self._cv:
            if not self._done:
                self._err = err
                self._done = True
            self._cv.notify_all()

    # -- consumer side ------------------------------------------------------
    def done(self) -> bool:
        with self._cv:
            return self._done

    def tokens_so_far(self) -> List[int]:
        with self._cv:
            return list(self._tokens)

    def wait_new(self, have: int, timeout: float
                 ) -> Tuple[List[int], bool]:
        """Block until more than ``have`` tokens exist (or the
        generation completes / the wait times out); returns (the tokens
        past ``have``, done)."""
        deadline = _fault.Deadline(timeout)
        with self._cv:
            while len(self._tokens) <= have and not self._done:
                remaining = deadline.remaining()
                if remaining <= 0:
                    break
                self._cv.wait(timeout=min(0.05, remaining))
            return list(self._tokens[have:]), self._done

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Block (bounded) for the whole generation; raises on engine
        failure or timeout."""
        timeout = _result_timeout(timeout)
        deadline = _fault.Deadline(timeout)
        with self._cv:
            while not self._done:
                remaining = deadline.remaining()
                if remaining <= 0:
                    raise MXNetError(
                        "serve: generation timed out after %.3gs "
                        "(%d/%d tokens)" % (timeout, len(self._tokens),
                                            self.max_new))
                self._cv.wait(timeout=min(0.1, remaining))
            if self._err is not None:
                raise self._err
            return list(self._tokens)


class DecodeBatcher:
    """The continuous-batching decode engine: admission queue + slot
    allocator + decode pump (pure dispatch) + token harvester (the only
    device→host reader)."""

    def __init__(self, servable: DecodeServable,
                 queue_cap: Optional[int] = None,
                 mode: str = "continuous", on_tick=None,
                 autostart: bool = True):
        if mode not in ("continuous", "request"):
            raise MXNetError("DecodeBatcher mode must be 'continuous' "
                             "or 'request', got %r" % (mode,))
        self._sv = servable
        if not servable.warmed:
            servable.warm()
        self._cap = int(queue_cap if queue_cap is not None else
                        get_env("MX_SERVE_QUEUE_CAP", 256, int))
        self._mode = mode
        self._on_tick = on_tick
        self._q: deque = deque()
        self._cv = threading.Condition()
        self._slot_lk = threading.Lock()
        self._slots: List[Optional[_PendingGen]] = \
            [None] * servable.config.slots
        # bounded pump->harvester handoff: one step boundary emits at
        # most `slots` prefill items + 1 step item, so this bound can
        # never wedge a synchronous (autostart=False) driver, while in
        # threaded mode it caps how far the pump runs ahead of the
        # host-side token reads
        self._harvest_q: _queue.Queue = _queue.Queue(
            maxsize=servable.config.slots + 4)
        self._stop = threading.Event()
        reg = _telemetry.registry
        self._c_requests = reg.counter(
            "serve.decode.requests", doc="admitted generation requests")
        self._c_rejected = reg.counter(
            "serve.decode.rejected", doc="generation requests shed at "
            "admission (queue cap) or refused (prompt too long)")
        self._c_tokens = reg.counter(
            "serve.decode.tokens", doc="generated tokens harvested")
        self._c_steps = reg.counter(
            "serve.decode.steps", doc="decode-step device dispatches "
            "(exactly 1 per step regardless of the active count)")
        self._c_prefills = reg.counter(
            "serve.decode.prefills", doc="prefill device dispatches "
            "(one per admitted sequence)")
        self._c_seqs = reg.counter(
            "serve.decode.sequences", doc="generations retired complete")
        # per-model labeled twins (ISSUE 20): the unlabeled aggregates
        # stay (bench/dispatch_count read them); the labeled series is
        # what fleet.py rolls up per co-hosted model
        _lbl = {"model": servable.name}
        self._c_requests_m = reg.counter(
            "serve.decode.requests", doc="admitted generation requests",
            labels=_lbl)
        self._c_tokens_m = reg.counter(
            "serve.decode.tokens", doc="generated tokens harvested",
            labels=_lbl)
        self._c_seqs_m = reg.counter(
            "serve.decode.sequences", doc="generations retired complete",
            labels=_lbl)
        self._g_queue = reg.gauge(
            "serve.decode.queue", doc="generation requests queued")
        self._g_active = reg.gauge(
            "serve.decode.active_slots", doc="sequences in decode slots")
        # first-class capacity signals (ISSUE 17): the router and
        # autoscaler read these per-replica off the merged FLEET
        # snapshot — no more deriving load from occupancy histograms
        self._g_occupancy = reg.gauge(
            "serve.decode.slot_occupancy",
            doc="fraction of decode slots holding an active sequence "
                "(0..1; router load signal)")
        self._g_headroom = reg.gauge(
            "serve.decode.kv_headroom_bytes",
            doc="KV-pool bytes behind currently-FREE decode slots "
                "(admission headroom; router/autoscaler signal)")
        self._h_occ = reg.histogram(
            "serve.decode.occupancy", doc="active sequences per decode "
            "step", buckets=(1, 2, 4, 8, 16, 32, 64))
        self._h_token = reg.histogram(
            "serve.decode.token_seconds", doc="per-token latency: first "
            "token = submit->harvest (queue + prefill included), then "
            "inter-token gaps",
            buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                     0.1, 0.25, 0.5, 1.0, 2.5))
        self._set_capacity_gauges(0)
        self._pump = threading.Thread(
            target=self._loop, daemon=True, name="mx-serve-decode-pump")
        self._harvester = threading.Thread(
            target=self._harvest_loop, daemon=True,
            name="mx-serve-decode-harvest")
        if autostart:
            self._pump.start()
            self._harvester.start()

    @property
    def servable(self) -> DecodeServable:
        return self._sv

    @property
    def version(self) -> int:
        return self._sv.version

    @property
    def mode(self) -> str:
        return self._mode

    # -- admission ----------------------------------------------------------
    def queue_depth(self) -> int:
        with self._cv:
            return len(self._q)

    def active_count(self) -> int:
        with self._slot_lk:
            return sum(1 for g in self._slots if g is not None)

    def page_stats(self) -> Optional[Dict]:
        """Paged-engine capacity detail for the health surface; the
        flat engine has none."""
        return None

    def _set_capacity_gauges(self, active: int) -> None:
        """Publish the per-replica capacity signals for ``active``
        occupied slots (called wherever occupancy changes)."""
        slots = self._sv.config.slots
        self._g_occupancy.set(active / float(slots) if slots else 0.0)
        self._g_headroom.set(
            max(0, slots - active) * self._sv.kv_slot_bytes())

    def submit(self, prompt: Sequence[int],
               max_new: Optional[int] = None,
               eos_id: Optional[int] = None,
               trace_ctx: Optional[Tuple[str, str]] = None
               ) -> _PendingGen:
        """Admit one generation request.  ``eos_id`` overrides the
        config's stop token for this request (stop tokens are
        per-request in real serving).  Raises :class:`Overloaded` when
        the bounded queue is full, MXNetError when the request can
        never be served (empty/over-bucket prompt, bad token ids)."""
        cfg = self._sv.config
        try:
            prompt = [int(t) for t in prompt]
        except (TypeError, ValueError):
            self._c_rejected.inc()
            raise MXNetError("serve: GENERATE prompt must be a sequence "
                             "of token ids")
        if not prompt:
            self._c_rejected.inc()
            raise MXNetError("serve: GENERATE needs >= 1 prompt token")
        if any(t < 0 or t >= cfg.vocab for t in prompt):
            self._c_rejected.inc()
            raise MXNetError("serve: prompt token out of vocab range "
                             "[0, %d)" % cfg.vocab)
        if cfg.prompt_bucket_for(len(prompt)) is None:
            self._c_rejected.inc()
            raise MXNetError(
                "serve: prompt of %d tokens exceeds the top prompt "
                "bucket %d (MX_SERVE_DECODE_PROMPT_BUCKETS)"
                % (len(prompt), cfg.prompt_buckets[-1]))
        limit = cfg.max_tokens if max_new is None \
            else max(1, min(int(max_new), cfg.max_tokens))
        stop = cfg.eos_id if eos_id is None else int(eos_id)
        gen = _PendingGen(prompt, limit, stop, trace_ctx=trace_ctx)
        with self._cv:
            if len(self._q) >= self._cap:
                self._c_rejected.inc()
                raise Overloaded(
                    "serve: decode admission queue full (%d/%d; "
                    "MX_SERVE_QUEUE_CAP) - retry later or add replicas"
                    % (len(self._q), self._cap))
            self._q.append(gen)
            self._g_queue.set(len(self._q))
            self._cv.notify_all()
        self._c_requests.inc()
        self._c_requests_m.inc()
        return gen

    # -- the decode pump (mxlint hot-path roots) ----------------------------
    def _loop(self) -> None:
        while not self._stop.is_set():
            idle = self._tick()
            if self._on_tick is not None:
                self._on_tick()
            if idle:
                with self._cv:
                    if not self._q:
                        self._cv.wait(timeout=0.01)
        # stop: refuse whatever is still queued so no handler thread is
        # left waiting on a generation nobody will advance
        with self._cv:
            leftover = list(self._q)
            self._q.clear()
            self._g_queue.set(0)
        with self._slot_lk:
            leftover += [g for g in self._slots if g is not None]
            self._slots = [None] * len(self._slots)
        for g in leftover:
            g._fail(MXNetError("serve: decode engine stopped"))

    def _tick(self) -> bool:
        """One step boundary: retire finished sequences, admit queued
        prefills into the freed slots, then ONE decode dispatch over
        the packed active set.  Returns True when there was nothing to
        do (idle)."""
        self._retire()
        self._admit()
        active = self._active()
        if not active:
            return True
        try:
            self._step(active)
        except BaseException as e:            # XLA failure: fail the set
            for _slot, g in active:
                g._fail(e)
        return False

    # -- locked slot/queue helpers ------------------------------------------
    # the ONLY direct touches of ``_slots`` / ``_q`` outside __init__ /
    # _loop / submit: the paged subclass schedules through these, so
    # the lock discipline lives (and is lint-attributed) in one class
    def _finished_slots(self) -> List[Tuple[int, _PendingGen]]:
        with self._slot_lk:
            return [(i, g) for i, g in enumerate(self._slots)
                    if g is not None and g.done()]

    def _free_slot_ids(self) -> List[int]:
        with self._slot_lk:
            return [i for i, g in enumerate(self._slots) if g is None]

    def _clear_slots(self, ids: Sequence[int]) -> None:
        with self._slot_lk:
            for i in ids:
                self._slots[i] = None

    def _bind_slot(self, slot: int, gen: _PendingGen) -> None:
        with self._slot_lk:
            self._slots[slot] = gen

    def _peek_queued(self) -> Optional[_PendingGen]:
        """Head of the admission queue without taking it (the pump is
        the only consumer, so a later pop returns the same request)."""
        with self._cv:
            return self._q[0] if self._q else None

    def _pop_queued(self) -> Optional[_PendingGen]:
        with self._cv:
            if not self._q:
                return None
            gen = self._q.popleft()
            self._g_queue.set(len(self._q))
            return gen

    def _retire(self) -> None:
        """Step boundary, phase ``kv_evict``: free the slots of
        completed sequences.  Eviction is bookkeeping — the pool pages
        stay allocated (flat HBM); the next prefill into the slot
        resets its length and overwrites from position 0, and stale
        entries beyond the new length are masked, never read."""
        done = self._finished_slots()
        if not done:
            return
        with _telemetry.phase("kv_evict"):
            self._clear_slots([i for i, _g in done])
        self._c_seqs.inc(len(done))
        self._c_seqs_m.inc(len(done))
        active = self.active_count()
        self._g_active.set(active)
        self._set_capacity_gauges(active)

    def _admit(self) -> None:
        """The slot allocator: fill free slots from the queue at the
        step boundary, one prefill dispatch each.  Request-level mode
        (the bench strawman) admits only when the whole previous batch
        has retired — exactly the behavior continuous batching
        exists to beat."""
        free = self._free_slot_ids()
        occupied = self._sv.config.slots - len(free)
        if self._mode == "request" and occupied:
            return
        while free:
            gen = self._pop_queued()
            if gen is None:
                break
            slot = free.pop(0)
            gen.slot = slot
            self._bind_slot(slot, gen)
            try:
                self._dispatch_prefill(gen, slot)
            except BaseException as e:
                self._clear_slots([slot])
                gen._fail(e)

    def _active(self) -> List[Tuple[int, _PendingGen]]:
        with self._slot_lk:
            return [(i, g) for i, g in enumerate(self._slots)
                    if g is not None and not g.done()]

    def _dispatch_prefill(self, gen: _PendingGen, slot: int) -> None:
        cfg = self._sv.config
        lp = cfg.prompt_bucket_for(len(gen.prompt))
        padded = _np.zeros(lp, _np.int32)
        padded[:len(gen.prompt)] = gen.prompt
        with _telemetry.phase("prefill") as span:
            if gen.trace_ctx is not None:
                span.event("request", req_trace=gen.trace_ctx[0],
                           req_span=gen.trace_ctx[1], slot=slot)
            t0 = self._sv.dispatch_prefill(slot, padded,
                                           len(gen.prompt))
        self._c_prefills.inc()
        active = self.active_count()
        self._g_active.set(active)
        self._set_capacity_gauges(active)
        self._hq_put(([gen], t0))

    def _step(self, active: List[Tuple[int, _PendingGen]]) -> None:
        """ONE decode dispatch: pack the active slots into the smallest
        covering bucket (padded lanes park on the scratch slot) — no
        host sync anywhere on this path; the emitted-token array goes
        to the harvester."""
        cfg = self._sv.config
        bucket = cfg.slot_bucket_for(len(active))
        ids = _np.full(bucket, cfg.slots, _np.int32)
        ids[:len(active)] = [slot for slot, _g in active]
        with _telemetry.phase("decode_step") as span:
            for _slot, g in active:
                if g.trace_ctx is not None:
                    span.event("request", req_trace=g.trace_ctx[0],
                               req_span=g.trace_ctx[1])
            out = self._sv.dispatch_step(ids)
        self._c_steps.inc()
        self._h_occ.observe(len(active))
        self._hq_put(([g for _slot, g in active], out))

    def _hq_put(self, item) -> None:
        """Bounded handoff to the harvester: the pump may run at most
        the queue depth ahead of the host-side token reads (that bound
        is what sizes the pool's overrun margin)."""
        while not self._stop.is_set():
            try:
                self._harvest_q.put(item, timeout=0.05)
                return
            except _queue.Full:
                continue

    # -- the harvester (the ONLY device->host reader) -----------------------
    def _harvest_loop(self) -> None:
        while not (self._stop.is_set() and self._harvest_q.empty()):
            self._harvest_once(block=True)

    def _harvest_once(self, block: bool = False) -> bool:
        """Read one dispatch's emitted tokens (the device sync lives
        HERE, overlapping the pump's next dispatch), append them to
        their generations, stamp per-token latency, flag EOS/limit
        completions for the next boundary's retire."""
        try:
            if block:
                gens, out = self._harvest_q.get(timeout=0.05)
            else:
                gens, out = self._harvest_q.get_nowait()
        except _queue.Empty:
            return False
        now = time.perf_counter()
        appended = 0
        if isinstance(out, tuple):
            # speculative verify result: (emitted (b, k), n_em (b,)) —
            # lane ``i`` contributed its first n_em[i] tokens this
            # window (ISSUE 20).  _append drops post-done tokens, so a
            # mid-window EOS/limit truncates here automatically.
            emitted, n_em = out
            em = _np.asarray(emitted)
            ne = _np.asarray(n_em).reshape(-1)
            for lane, g in enumerate(gens):
                for t in em[lane, :int(ne[lane])]:
                    did, finished = g._append(int(t), now)
                    if did:
                        appended += 1
                        self._h_token.observe(g.token_times[-1])
                    if finished:
                        break
        else:
            toks = _np.asarray(out).reshape(-1)
            for g, t in zip(gens, toks[:len(gens)]):
                did, _finished = g._append(int(t), now)
                if did:
                    appended += 1
                    self._h_token.observe(g.token_times[-1])
        if appended:
            self._c_tokens.inc(appended)
            self._c_tokens_m.inc(appended)
        return True

    # -- synchronous driving (tests, the dispatch-count budget) -------------
    def step_sync(self) -> bool:
        """One boundary + dispatch + synchronous harvest — the
        deterministic test face (requires ``autostart=False``: no
        pipeline lag, token counts exact).  Returns False once idle
        with an empty queue."""
        idle = self._tick()
        while self._harvest_once(block=False):
            pass
        with self._cv:
            empty = not self._q
        return not (idle and empty)

    def drain_sync(self, max_ticks: int = 10000) -> None:
        """step_sync until idle (tests)."""
        for _ in range(max_ticks):
            if not self.step_sync():
                return
        raise MXNetError("decode: drain_sync did not converge in %d "
                         "ticks" % max_ticks)

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "DecodeBatcher":
        if not self._pump.is_alive():
            self._pump.start()
        if not self._harvester.is_alive():
            self._harvester.start()
        return self

    def close(self, timeout: float = 5.0) -> None:
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        if self._pump.is_alive():
            self._pump.join(timeout=timeout)
        if self._harvester.is_alive():
            self._harvester.join(timeout=timeout)


class _PagedSeq:
    """Host bookkeeping for one admitted PAGED session: its block
    table, the page references it holds, the remaining prefill-chunk
    train, and the full-page hashes to publish once the train has
    dispatched.  Pump-thread-only."""

    __slots__ = ("gen", "table", "held", "chunks", "publish", "t0")

    def __init__(self, gen, table, held, chunks, publish):
        self.gen = gen
        self.table = table          # np.int32 (pages_per_slot,)
        self.held = held            # page ids to release at retire
        self.chunks = chunks        # deque of pending chunk dispatches
        self.publish = publish      # [(chain_hash, page)] after train
        self.t0 = None              # emit chunk's first token (spec
        #                             engine: harvested only after the
        #                             draft-prefill sentinel)


class PagedDecodeBatcher(DecodeBatcher):
    """The paged continuous-batching engine (ISSUE 18): the flat
    pump's loop with three changes —

    * **Admission is bounded by pages, not slots.**  ``_admit`` plans
      each head-of-queue request against the
      :class:`~mxnet_tpu.serve.paging.PageAllocator`: hash-share full
      prompt pages from earlier sessions, allocate private pages for
      the rest of the worst-case extent, and queue the prefill-chunk
      train.  No pages -> the request WAITS (head-of-line; no
      half-allocation); free slots beyond page capacity are just
      cheap int32 rows, so configs can run slots >> the flat pool's
      count at the same heap bytes.

    * **Chunked prefill interleaves with decode.**  Each tick
      dispatches exactly ONE program: a pending prefill chunk and the
      decode step over the DECODING active set alternate
      (``_chunk_turn``), so a 10k-token admission never stalls
      in-flight generations for more than one chunk-step, and the
      1-dispatch-per-tick budget ``tools/dispatch_count.py`` pins
      holds with chunks counted as steps.

    * **Prefix reuse is plumbed, not special-cased.**  A full-coverage
      hash hit admits with a single CoW replay chunk (fork the donor's
      last page, recompute its final position, emit the first token);
      a partial hit prefills only the suffix chunks.  Decode never
      writes shared pages (generation positions land in private
      pages), and publication happens strictly after the owning
      chunks' dispatches, so sharing is invisible to correctness —
      paged greedy decode is token-identical to the flat engine and
      the oracle.

    Continuous-only: the request-level strawman stays on the flat
    engine.
    """

    def __init__(self, servable: PagedDecodeServable,
                 queue_cap: Optional[int] = None,
                 mode: str = "continuous", on_tick=None,
                 autostart: bool = True):
        if not isinstance(servable, PagedDecodeServable):
            raise MXNetError("PagedDecodeBatcher needs a "
                             "PagedDecodeServable")
        if mode != "continuous":
            raise MXNetError("the paged engine is continuous-only; "
                             "mode=%r belongs to the flat engine's "
                             "bench strawman" % (mode,))
        # pre-super wiring: the base __init__ publishes capacity gauges
        # through our override, which needs the allocator + extra
        # instruments in place
        self._sv = servable
        self._alloc = PageAllocator(servable.config.kv_pages)
        self._seqs: Dict[int, _PagedSeq] = {}
        self._chunk_turn = False
        self._chunk_rr = -1      # last slot whose chunk was served
        reg = _telemetry.registry
        self._c_chunks = reg.counter(
            "serve.decode.prefill_chunks",
            doc="prefill-chunk device dispatches (a prompt admits as a "
                "train of page-aligned chunks interleaved with decode "
                "steps)")
        self._c_shared = reg.counter(
            "serve.decode.shared_page_hits",
            doc="prompt pages adopted from the prefix hash table "
                "instead of prefilled (each one is a skipped chunk's "
                "worth of work and a page of HBM not allocated)")
        self._c_cow = reg.counter(
            "serve.decode.cow_forks",
            doc="copy-on-write page forks (full prompt-coverage prefix "
                "hits replaying only their final position)")
        self._g_free_pages = reg.gauge(
            "serve.decode.kv_free_pages",
            doc="KV heap pages currently allocatable (free + evictable "
                "cached prefix pages); the paged admission headroom "
                "the fleet plane reports")
        self._g_shared_saved = reg.gauge(
            "serve.decode.kv_shared_saved_bytes",
            doc="KV heap bytes prefix sharing is saving right now "
                "(extra references on hashed pages x page bytes)")
        super().__init__(servable, queue_cap=queue_cap, mode=mode,
                         on_tick=on_tick, autostart=autostart)

    # -- capacity surface ---------------------------------------------------
    def _set_capacity_gauges(self, active: int) -> None:
        slots = self._sv.config.slots
        self._g_occupancy.set(active / float(slots) if slots else 0.0)
        pb = self._sv.page_bytes()
        free = self._alloc.free_pages()
        self._g_headroom.set(free * pb)
        self._g_free_pages.set(free)
        self._g_shared_saved.set(self._alloc.shared_extra_refs() * pb)

    def page_stats(self) -> Dict:
        cfg = self._sv.config
        pb = self._sv.page_bytes()
        st = self._alloc.stats()
        return {
            "engine": "paged",
            "kv_pages": cfg.kv_pages,
            "kv_page_len": cfg.kv_page_len,
            "prefill_chunk": cfg.prefill_chunk,
            "prefix_share": cfg.prefix_share,
            "kv_free_pages": st["free"],
            "kv_cached_pages": st["cached"],
            "shared_hits": st["shared_hits"],
            "shared_saved_bytes":
                self._alloc.shared_extra_refs() * pb,
        }

    # -- the paged pump (mxlint hot-path roots) -----------------------------
    def _tick(self) -> bool:
        """One boundary, ONE dispatch: retire, admit (bookkeeping
        only), then EITHER the next pending prefill chunk OR the
        decode step — alternating while both kinds of work exist."""
        self._retire()
        self._admit()
        chunk_slot = self._next_chunk_slot()
        active = self._active()
        if chunk_slot is not None and (self._chunk_turn or not active):
            self._chunk_turn = False
            self._dispatch_chunk_for(chunk_slot)
            return False
        self._chunk_turn = True
        if not active:
            return chunk_slot is None
        try:
            self._step(active)
        except BaseException as e:            # XLA failure: fail the set
            for _slot, g in active:
                g._fail(e)
        return False

    def _retire(self) -> None:
        """Step boundary, phase ``kv_evict``: release finished
        sessions' page references.  A released page whose content is
        published under a prefix hash parks in the allocator's LRU
        cache — still adoptable — instead of freeing; the heap itself
        never reallocates (flat HBM)."""
        done = self._finished_slots()
        if not done:
            return
        with _telemetry.phase("kv_evict"):
            self._clear_slots([i for i, _g in done])
            for i, _g in done:
                seq = self._seqs.pop(i, None)
                if seq is not None:
                    for p in seq.held:
                        self._alloc.release(p)
        self._c_seqs.inc(len(done))
        self._c_seqs_m.inc(len(done))
        active = self.active_count()
        self._g_active.set(active)
        self._set_capacity_gauges(active)

    def _admit(self) -> None:
        """Admission bounded by PAGES: plan the head-of-queue request
        (prefix lookup + private-page allocation + chunk train) and
        take a slot only when its worst-case extent fits.  Pure
        bookkeeping — the chunks dispatch on later ticks."""
        while True:
            free = self._free_slot_ids()
            if not free:
                return
            gen = self._peek_queued()
            if gen is None:
                return
            plan = self._plan(gen)
            if plan is None:
                return            # head-of-line waits for free pages
            self._pop_queued()    # == gen: the pump is the only consumer
            slot = free[0]
            gen.slot = slot
            table, held, chunks, publish = plan
            self._bind_slot(slot, gen)
            self._seqs[slot] = _PagedSeq(gen, table, held, chunks,
                                         publish)
            active = self.active_count()
            self._g_active.set(active)
            self._set_capacity_gauges(active)

    def _plan(self, gen: _PendingGen):
        """Map one request onto the heap: shared prefix pages adopted
        by hash, private pages allocated for the rest of the
        worst-case extent, prefill chunks laid out page-aligned.
        Returns (table, held, chunks, publish) or None when the pages
        don't fit (nothing is retained on failure)."""
        cfg = self._sv.config
        pl = cfg.kv_page_len
        prompt = gen.prompt
        n = len(prompt)
        need_pages = min(
            cfg.pages_per_slot,
            -(-(n + gen.max_new + _OVERRUN_MARGIN) // pl))
        hashes = page_hashes(prompt, pl) if cfg.prefix_share else []
        shared: List[int] = []
        for h in hashes:
            p = self._alloc.lookup(h)
            if p is None:
                break
            shared.append(p)
        cow_src = None
        if shared and len(shared) * pl == n:
            # full coverage: fork the donor's last page (CoW) and
            # replay only the final position to emit the first token
            cow_src = shared.pop()
        priv = self._alloc.alloc(need_pages - len(shared))
        if priv is None:
            for p in shared:
                self._alloc.release(p)
            if cow_src is not None:
                self._alloc.release(cow_src)
            return None
        if shared or cow_src is not None:
            self._c_shared.inc(len(shared) +
                               (1 if cow_src is not None else 0))
        table = _np.zeros(cfg.pages_per_slot, _np.int32)
        table[:len(shared)] = shared
        table[len(shared):need_pages] = priv
        held = shared + priv
        if cow_src is not None:
            held.append(cow_src)   # keep the donor page live until
            #                        retire: its fork copy must not
            #                        race a reuse of the page
        chunks: deque = deque()
        publish: List[Tuple[int, int]] = []
        Lc = cfg.prefill_chunk
        if cow_src is not None:
            self._c_cow.inc()
            buf = _np.zeros(Lc, _np.int32)
            buf[0] = prompt[n - 1]
            chunks.append((buf, n - 1, 1, True, int(cow_src),
                           int(priv[0])))
        else:
            start0 = len(shared) * pl
            for s in range(start0, n, Lc):
                e = min(n, s + Lc)
                buf = _np.zeros(Lc, _np.int32)
                buf[:e - s] = prompt[s:e]
                chunks.append((buf, s, e - s, e == n, 0, 0))
            if cfg.prefix_share:
                for i in range(len(shared), n // pl):
                    publish.append((hashes[i], int(table[i])))
        return table, held, chunks, publish

    def _active(self) -> List[Tuple[int, _PendingGen]]:
        """The DECODING active set: sessions whose prefill-chunk train
        has fully dispatched (prefilling sessions are not packed into
        decode steps)."""
        return [(i, g) for i, g in super()._active()
                if not (i in self._seqs and self._seqs[i].chunks)]

    def _next_chunk_slot(self) -> Optional[int]:
        # _seqs entries are popped exactly when their slot clears
        # (_retire / _drop_seq, both on the pump), so a live entry
        # implies a live slot.  ROUND-ROBIN over chunk-pending
        # sessions: a 10k-token train must not starve a later
        # admission's one-chunk prefill of its first token.
        pending = sorted(i for i in self._seqs if self._seqs[i].chunks)
        if not pending:
            return None
        for i in pending:
            if i > self._chunk_rr:
                return i
        return pending[0]

    def _dispatch_chunk_for(self, slot: int) -> None:
        """ONE prefill-chunk dispatch.  The train's last chunk emits
        the first token (handed to the harvester like the flat
        prefill's) and triggers hash publication — strictly after the
        pages' writes are in the dispatch stream."""
        seq = self._seqs[slot]
        gen = seq.gen
        self._chunk_rr = slot
        chunk, start, nvalid, emit, cow_src, cow_dst = \
            seq.chunks.popleft()
        try:
            with _telemetry.phase("prefill") as span:
                if gen.trace_ctx is not None:
                    span.event("request", req_trace=gen.trace_ctx[0],
                               req_span=gen.trace_ctx[1], slot=slot)
                t0 = self._sv.dispatch_chunk(slot, seq.table, chunk,
                                             start, nvalid, emit,
                                             cow_src, cow_dst)
        except BaseException as e:
            self._drop_seq(slot)
            gen._fail(e)
            return
        self._c_chunks.inc()
        if not seq.chunks:
            # train complete = the flat engine's "prefill" unit
            self._c_prefills.inc()
            for h, page in seq.publish:
                self._alloc.publish(h, page)
            seq.publish = []
            active = self.active_count()
            self._g_active.set(active)
            self._set_capacity_gauges(active)
            self._hq_put(([gen], t0))

    def _drop_seq(self, slot: int) -> None:
        self._clear_slots([slot])
        seq = self._seqs.pop(slot, None)
        if seq is not None:
            for p in seq.held:
                self._alloc.release(p)

    def _dispatch_prefill(self, gen: _PendingGen, slot: int) -> None:
        raise MXNetError("paged engine prefills via chunk trains, "
                         "never the monolithic prefill")

    def _step(self, active: List[Tuple[int, _PendingGen]]) -> None:
        """ONE decode dispatch over the packed DECODING set, each lane
        carrying its block-table row (padded lanes: all-zero rows ->
        the scratch page)."""
        cfg = self._sv.config
        bucket = cfg.slot_bucket_for(len(active))
        ids = _np.full(bucket, cfg.slots, _np.int32)
        ids[:len(active)] = [slot for slot, _g in active]
        tbls = _np.zeros((bucket, cfg.pages_per_slot), _np.int32)
        for lane, (slot, _g) in enumerate(active):
            tbls[lane] = self._seqs[slot].table
        with _telemetry.phase("decode_step") as span:
            for _slot, g in active:
                if g.trace_ctx is not None:
                    span.event("request", req_trace=g.trace_ctx[0],
                               req_span=g.trace_ctx[1])
            out = self._sv.dispatch_step(ids, tbls)
        self._c_steps.inc()
        self._h_occ.observe(len(active))
        self._hq_put(([g for _slot, g in active], out))


class SpeculativeDecodeBatcher(PagedDecodeBatcher):
    """The SPECULATIVE paged engine (ISSUE 20): the paged pump, but
    decode advances in WINDOWS of ``spec_k`` tokens —

    * **k draft ticks + 1 verify tick per window.**  The window's
      active set freezes at the first draft tick; each draft tick is
      one dispatch of the co-hosted draft servable writing its
      proposal into the device-resident proposals buffer; the verify
      tick is ONE target dispatch over all k+1 window positions of
      every lane (multi-position paged attention), which accepts the
      longest agreeing prefix, corrects the next token from the
      target's own argmax, and rewrites the draft's (token, length)
      state in-program — the whole window is a device-side chain with
      zero host syncs, and the 1-dispatch-per-tick budget holds.

    * **Output is bit-identical to plain paged greedy decode.**  Every
      emitted token is the target's own argmax under the committed
      prefix (the draft only chooses how many of them one dispatch
      yields), so correctness never depends on draft quality — a
      worthless draft just degrades throughput to ~1 token per 2
      dispatches, a draft-friendly model approaches k tokens per k+1
      (cheap) dispatches.

    * **Admission grows a draft-prefill sentinel.**  A session's
      prefill-chunk train ends with one extra dispatch that prefills
      the DRAFT's KV pool and adopts the target's emitted first token
      (read-only input -> XLA orders it after the emit chunk); the
      first token is harvested only once the sentinel has dispatched,
      so a session never enters a window with a cold draft.
    """

    def __init__(self, servable: PagedDecodeServable,
                 draft: DraftDecodeServable,
                 queue_cap: Optional[int] = None,
                 mode: str = "continuous", on_tick=None,
                 autostart: bool = True):
        if not isinstance(draft, DraftDecodeServable):
            raise MXNetError("SpeculativeDecodeBatcher needs a "
                             "DraftDecodeServable draft")
        tcfg = servable.config
        dcfg = draft.config
        if (tcfg.slots != dcfg.slots or tcfg.vocab != dcfg.vocab
                or tcfg.prompt_buckets != dcfg.prompt_buckets
                or tcfg.max_tokens != dcfg.max_tokens
                or tcfg.spec_k != dcfg.spec_k):
            raise MXNetError(
                "speculative decode: draft/target geometry mismatch "
                "(slots, vocab, prompt buckets, max_tokens and spec_k "
                "must agree; got target=%r draft=%r)" % (tcfg, dcfg))
        self._draft = draft
        self._win_active: Optional[List[Tuple[int, _PendingGen]]] = \
            None
        self._win_step = 0
        reg = _telemetry.registry
        self._c_draft_steps = reg.counter(
            "serve.decode.draft_steps",
            doc="draft-model decode dispatches (spec_k per speculative "
                "window)")
        self._c_draft_prefills = reg.counter(
            "serve.decode.draft_prefills",
            doc="draft KV prefill dispatches (the sentinel ending each "
                "admission's chunk train)")
        self._c_windows = reg.counter(
            "serve.decode.spec_windows",
            doc="speculative verify dispatches (each commits 1..spec_k "
                "tokens for every window lane)")
        # warm everything BEFORE the pump threads exist: target buckets
        # + chunk program (base warm), draft buckets + prefills, and
        # the verify bucket table (scratch lanes only — the programs
        # park the scratch slot themselves)
        if not servable.warmed:
            servable.warm()
        if not draft.warmed:
            draft.warm()
        for b in tcfg.slot_buckets:
            servable.dispatch_verify(
                draft, _np.full(b, tcfg.slots, _np.int32),
                _np.zeros((b, tcfg.pages_per_slot), _np.int32))
        jax.block_until_ready(servable._state["k"])
        super().__init__(servable, queue_cap=queue_cap, mode=mode,
                         on_tick=on_tick, autostart=autostart)

    @property
    def draft(self) -> DraftDecodeServable:
        return self._draft

    def page_stats(self) -> Dict:
        st = super().page_stats()
        st["engine"] = "speculative"
        st["spec_k"] = self._sv.config.spec_k
        st["draft_model"] = self._draft.name
        st["draft_layers"] = self._draft.config.layers
        return st

    # -- the speculative pump (mxlint hot-path roots) -----------------------
    def _tick(self) -> bool:
        """One boundary, ONE dispatch.  Mid-window ticks only advance
        the window (the active set is frozen; retire/admit/chunks wait
        for the boundary); boundary ticks run the paged engine's
        retire/admit/chunk alternation and open the next window."""
        if self._win_active is not None:
            self._window_tick()
            return False
        self._retire()
        self._admit()
        chunk_slot = self._next_chunk_slot()
        active = self._active()
        if chunk_slot is not None and (self._chunk_turn or not active):
            self._chunk_turn = False
            self._dispatch_chunk_for(chunk_slot)
            return False
        self._chunk_turn = True
        if not active:
            return chunk_slot is None
        self._win_active = active
        self._win_step = 0
        self._window_tick()
        return False

    def _window_tick(self) -> None:
        """One dispatch of the current window: draft step ``_win_step``
        while < spec_k, else the verify dispatch that closes the
        window and hands (emitted, n_em) to the harvester."""
        active = self._win_active
        cfg = self._sv.config
        bucket = cfg.slot_bucket_for(len(active))
        ids = _np.full(bucket, cfg.slots, _np.int32)
        ids[:len(active)] = [slot for slot, _g in active]
        try:
            if self._win_step < cfg.spec_k:
                with _telemetry.phase("draft_step"):
                    self._draft.dispatch_step(ids, self._win_step)
                self._c_draft_steps.inc()
                self._win_step += 1
                return
            tbls = _np.zeros((bucket, cfg.pages_per_slot), _np.int32)
            for lane, (slot, _g) in enumerate(active):
                tbls[lane] = self._seqs[slot].table
            with _telemetry.phase("decode_step") as span:
                for _slot, g in active:
                    if g.trace_ctx is not None:
                        span.event("request", req_trace=g.trace_ctx[0],
                                   req_span=g.trace_ctx[1])
                out = self._sv.dispatch_verify(self._draft, ids, tbls)
        except BaseException as e:            # XLA failure: fail the set
            self._win_active = None
            self._win_step = 0
            for _slot, g in active:
                g._fail(e)
            return
        self._c_steps.inc()
        self._c_windows.inc()
        self._h_occ.observe(len(active))
        self._win_active = None
        self._win_step = 0
        self._hq_put(([g for _slot, g in active], out))

    # -- admission: chunk train + draft-prefill sentinel --------------------
    def _plan(self, gen: _PendingGen):
        plan = super()._plan(gen)
        if plan is None:
            return None
        table, held, chunks, publish = plan
        # sentinel: chunk=None marks the draft prefill ending the train
        chunks.append((None, 0, len(gen.prompt), False, 0, 0))
        return table, held, chunks, publish

    def _dispatch_chunk_for(self, slot: int) -> None:
        """ONE train dispatch: a target prefill chunk, or the
        draft-prefill sentinel that completes the train.  The emit
        chunk's first token parks on the session (``seq.t0``) and is
        harvested only when the sentinel has dispatched — the window
        invariant needs the draft warm before the first decode."""
        seq = self._seqs[slot]
        gen = seq.gen
        self._chunk_rr = slot
        chunk, start, nvalid, emit, cow_src, cow_dst = \
            seq.chunks.popleft()
        try:
            with _telemetry.phase("prefill") as span:
                if gen.trace_ctx is not None:
                    span.event("request", req_trace=gen.trace_ctx[0],
                               req_span=gen.trace_ctx[1], slot=slot)
                if chunk is None:
                    lp = self._draft.config.prompt_bucket_for(
                        len(gen.prompt))
                    padded = _np.zeros(lp, _np.int32)
                    padded[:len(gen.prompt)] = gen.prompt
                    self._draft.dispatch_prefill(
                        slot, padded, len(gen.prompt),
                        tgt_tokens=self._sv._state["tok"])
                    self._c_draft_prefills.inc()
                else:
                    t0 = self._sv.dispatch_chunk(slot, seq.table,
                                                 chunk, start, nvalid,
                                                 emit, cow_src,
                                                 cow_dst)
                    self._c_chunks.inc()
                    if emit:
                        seq.t0 = t0
        except BaseException as e:
            self._drop_seq(slot)
            gen._fail(e)
            return
        if not seq.chunks:
            # train complete = the flat engine's "prefill" unit
            self._c_prefills.inc()
            for h, page in seq.publish:
                self._alloc.publish(h, page)
            seq.publish = []
            active = self.active_count()
            self._g_active.set(active)
            self._set_capacity_gauges(active)
            self._hq_put(([gen], seq.t0))


# ---------------------------------------------------------------------------
# Program contracts (ISSUE 11): the decode engine's declared proofs.
# ``serve.decode`` covers every slot-bucket decode program:
#   * donation — all four KV-state leaves (k/v pools, token and length
#     arrays) alias input->output in the lowered executable, the static
#     form of "HBM stays flat across decode steps";
#   * trace closure — every active-set size 1..slots resolves to a
#     compiled slot bucket (zero serve-time retraces as a theorem).
# ``serve.prefill`` does the same over the prompt-length bucket set,
# with over-bucket prompts provably rejected at admission (resolve ->
# None).  Builders run only inside the contracts verifier.
# ---------------------------------------------------------------------------

import functools as _functools


@_functools.lru_cache(maxsize=1)
def _decode_contract_built():
    from ..programs import ContractCase, ContractClosure
    cfg = DecodeConfig()
    sv = DecodeServable(config=cfg)
    params_abs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                  for k, v in sv.params.items()}
    pool_abs = jax.ShapeDtypeStruct(
        (cfg.layers, cfg.slots + 1, cfg.max_len, cfg.heads,
         cfg.head_dim), jnp.float32)
    tok_abs = jax.ShapeDtypeStruct((cfg.slots + 1,), jnp.int32)
    scalar_abs = jax.ShapeDtypeStruct((), jnp.int32)

    def step_args(bucket):
        return (params_abs, pool_abs, pool_abs, tok_abs, tok_abs,
                jax.ShapeDtypeStruct((bucket,), jnp.int32))

    def prefill_args(lp):
        return (params_abs, pool_abs, pool_abs, tok_abs, tok_abs,
                scalar_abs, jax.ShapeDtypeStruct((lp,), jnp.int32),
                scalar_abs)

    step_cases = [ContractCase("serve.decode.step.s%d" % b,
                               step_args(b), label="s%d" % b,
                               target=sv.step_program(b))
                  for b in cfg.slot_buckets]
    prefill_cases = [ContractCase("serve.decode.prefill.p%d" % lp,
                                  prefill_args(lp), label="p%d" % lp,
                                  target=sv.prefill_program(lp))
                     for lp in cfg.prompt_buckets]

    def resolve_step(n):
        # every active-set size packs to its covering slot bucket
        return step_args(cfg.slot_bucket_for(int(n)))

    def resolve_prefill(n):
        # prompts pad to their bucket; over-bucket prompts are refused
        # at admission (never reach a jit)
        lp = cfg.prompt_bucket_for(int(n))
        return None if lp is None else prefill_args(lp)

    step_closure = ContractClosure(range(1, cfg.slots + 1),
                                   resolve_step)
    prefill_closure = ContractClosure(
        range(1, cfg.prompt_buckets[-1] + 3), resolve_prefill)
    return step_cases, step_closure, prefill_cases, prefill_closure


@_functools.lru_cache(maxsize=1)
def _paged_contract_built():
    """The paged engine's contract cases/closures (ISSUE 18):

    * ``serve.paged.decode`` — every slot-bucket step program with its
      block-table argument; heap donation proven; closed over active
      set sizes 1..slots.
    * ``serve.paged.prefill`` — THE chunk program: one signature
      (chunk length) serves every admitted prompt length as a chunk
      train, CoW folds into the same signature, so the closure maps
      ANY prompt length 1..top-bucket to the single compiled case —
      zero serve-time retraces as a theorem with a one-program prefill
      table.
    """
    from ..programs import ContractCase, ContractClosure
    cfg = DecodeConfig()
    sv = PagedDecodeServable(config=cfg)
    params_abs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                  for k, v in sv.params.items()}
    heap_abs = jax.ShapeDtypeStruct(
        (cfg.layers, cfg.kv_pages, cfg.kv_page_len, cfg.heads,
         cfg.head_dim), jnp.float32)
    tok_abs = jax.ShapeDtypeStruct((cfg.slots + 1,), jnp.int32)
    scalar_abs = jax.ShapeDtypeStruct((), jnp.int32)
    tbl_abs = jax.ShapeDtypeStruct((cfg.pages_per_slot,), jnp.int32)

    def step_args(bucket):
        return (params_abs, heap_abs, heap_abs, tok_abs, tok_abs,
                jax.ShapeDtypeStruct((bucket,), jnp.int32),
                jax.ShapeDtypeStruct((bucket, cfg.pages_per_slot),
                                     jnp.int32))

    chunk_args = (params_abs, heap_abs, heap_abs, tok_abs, tok_abs,
                  scalar_abs, tbl_abs,
                  jax.ShapeDtypeStruct((cfg.prefill_chunk,),
                                       jnp.int32),
                  scalar_abs, scalar_abs, scalar_abs, scalar_abs,
                  scalar_abs)

    step_cases = [ContractCase("serve.decode.paged.step.s%d" % b,
                               step_args(b), label="s%d" % b,
                               target=sv.step_program(b))
                  for b in cfg.slot_buckets]
    chunk_cases = [ContractCase(
        "serve.decode.paged.prefill.c%d" % cfg.prefill_chunk,
        chunk_args, label="c%d" % cfg.prefill_chunk,
        target=sv.chunk_program())]

    def resolve_step(n):
        return step_args(cfg.slot_bucket_for(int(n)))

    def resolve_chunk(n):
        # ANY admitted prompt length prefills as a train of the ONE
        # chunk signature; over-bucket prompts are refused at
        # admission (never reach a jit)
        if cfg.prompt_bucket_for(int(n)) is None:
            return None
        return chunk_args

    step_closure = ContractClosure(range(1, cfg.slots + 1),
                                   resolve_step)
    chunk_closure = ContractClosure(
        range(1, cfg.prompt_buckets[-1] + 3), resolve_chunk)
    return step_cases, step_closure, chunk_cases, chunk_closure


@_functools.lru_cache(maxsize=1)
def _spec_contract_built():
    """The speculative engine's contract cases/closures (ISSUE 20):

    * ``serve.spec.draft`` — the draft-step slot-bucket table: the
      draft's KV pool, token/length arrays AND the proposals buffer
      all donate in place; the window column is a traced scalar, so
      ONE program per bucket is closed over every k — the closure maps
      any (active-set size, window column) to its compiled case.
    * ``serve.spec.draft.prefill`` — the draft-prefill sentinel per
      prompt bucket (target token array read-only; draft state
      donated).
    * ``serve.spec.verify`` — the verify slot-bucket table: BOTH KV
      states' mutable leaves (target heap + token/length, draft
      token/length) donate in place, proposals read-only; closed over
      every active-set size 1..slots.
    """
    from ..programs import ContractCase, ContractClosure
    cfg = DecodeConfig()
    tparams, dcfg, dparams = demo_spec_pair(cfg)
    sv = PagedDecodeServable(params=tparams, config=cfg)
    draft = DraftDecodeServable(params=dparams, config=dcfg)
    tparams_abs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                   for k, v in sv.params.items()}
    dparams_abs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                   for k, v in draft.params.items()}
    heap_abs = jax.ShapeDtypeStruct(
        (cfg.layers, cfg.kv_pages, cfg.kv_page_len, cfg.heads,
         cfg.head_dim), jnp.float32)
    dpool_abs = jax.ShapeDtypeStruct(
        (dcfg.layers, dcfg.slots + 1, dcfg.max_len, dcfg.heads,
         dcfg.head_dim), jnp.float32)
    tok_abs = jax.ShapeDtypeStruct((cfg.slots + 1,), jnp.int32)
    props_abs = jax.ShapeDtypeStruct((cfg.slots + 1, cfg.spec_k),
                                     jnp.int32)
    scalar_abs = jax.ShapeDtypeStruct((), jnp.int32)

    def draft_args(bucket):
        return (dparams_abs, dpool_abs, dpool_abs, tok_abs, tok_abs,
                props_abs, jax.ShapeDtypeStruct((bucket,), jnp.int32),
                scalar_abs)

    def draft_prefill_args(lp):
        return (dparams_abs, dpool_abs, dpool_abs, tok_abs, tok_abs,
                tok_abs, scalar_abs,
                jax.ShapeDtypeStruct((lp,), jnp.int32), scalar_abs)

    def verify_args(bucket):
        return (tparams_abs, heap_abs, heap_abs, tok_abs, tok_abs,
                tok_abs, tok_abs, props_abs,
                jax.ShapeDtypeStruct((bucket,), jnp.int32),
                jax.ShapeDtypeStruct((bucket, cfg.pages_per_slot),
                                     jnp.int32))

    draft_cases = [ContractCase("serve.decode.draft.s%d" % b,
                                draft_args(b), label="s%d" % b,
                                target=draft.step_program(b))
                   for b in dcfg.slot_buckets]
    dp_cases = [ContractCase("serve.decode.draft.prefill.p%d" % lp,
                             draft_prefill_args(lp), label="p%d" % lp,
                             target=draft.prefill_program(lp))
                for lp in dcfg.prompt_buckets]
    verify_cases = [ContractCase(
        "serve.decode.verify.k%d.s%d" % (cfg.spec_k, b),
        verify_args(b), label="k%d.s%d" % (cfg.spec_k, b),
        target=sv.verify_program(b))
        for b in cfg.slot_buckets]

    def resolve_draft(point):
        # (active-set size, window column): any size packs to its
        # covering bucket; every column 0..spec_k-1 rides the SAME
        # program (the column is traced data, not a signature)
        n, col = point
        if col < 0 or col >= cfg.spec_k:
            return None
        return draft_args(cfg.slot_bucket_for(int(n)))

    def resolve_dp(n):
        lp = cfg.prompt_bucket_for(int(n))
        return None if lp is None else draft_prefill_args(lp)

    def resolve_verify(n):
        return verify_args(cfg.slot_bucket_for(int(n)))

    draft_points = [(n, col) for n in range(1, cfg.slots + 1)
                    for col in range(cfg.spec_k)]
    draft_closure = ContractClosure(draft_points, resolve_draft)
    dp_closure = ContractClosure(
        range(1, cfg.prompt_buckets[-1] + 3), resolve_dp)
    verify_closure = ContractClosure(range(1, cfg.slots + 1),
                                     resolve_verify)
    return (draft_cases, draft_closure, dp_cases, dp_closure,
            verify_cases, verify_closure)


def _declare_decode_contracts():
    from ..programs import declare_contract
    declare_contract(
        "serve.decode", lambda: _decode_contract_built()[0],
        donate_argnums=(1, 2, 3, 4),
        temp_budget_bytes=8 << 20,
        closure=lambda: _decode_contract_built()[1],
        description="decode-step slot-bucket table: KV pool pages + "
                    "per-slot token/length arrays donate in place "
                    "(flat HBM across steps); trace signatures closed "
                    "over every active-set size 1..slots")
    declare_contract(
        "serve.prefill", lambda: _decode_contract_built()[2],
        donate_argnums=(1, 2, 3, 4),
        temp_budget_bytes=8 << 20,
        closure=lambda: _decode_contract_built()[3],
        description="prefill prompt-bucket table: same donated KV "
                    "state; trace signatures closed over the "
                    "MX_SERVE_DECODE_PROMPT_BUCKETS admission set "
                    "(over-bucket prompts provably rejected)")
    declare_contract(
        "serve.paged.decode", lambda: _paged_contract_built()[0],
        donate_argnums=(1, 2, 3, 4),
        temp_budget_bytes=8 << 20,
        closure=lambda: _paged_contract_built()[1],
        description="paged decode-step slot-bucket table (ISSUE 18): "
                    "the shared KV page heap + token/length arrays "
                    "donate in place (flat HBM across steps, one heap "
                    "for every session); trace signatures closed over "
                    "every active-set size 1..slots with per-lane "
                    "block tables")
    declare_contract(
        "serve.paged.prefill", lambda: _paged_contract_built()[2],
        donate_argnums=(1, 2, 3, 4),
        temp_budget_bytes=8 << 20,
        closure=lambda: _paged_contract_built()[3],
        description="paged prefill-chunk program (ISSUE 18): ONE "
                    "signature — chunk length — serves every admitted "
                    "prompt as a page-aligned chunk train, with the "
                    "copy-on-write page fork folded into the same "
                    "signature; heap donation proven, closure maps "
                    "any prompt length to the single compiled case")
    declare_contract(
        "serve.spec.draft", lambda: _spec_contract_built()[0],
        donate_argnums=(1, 2, 3, 4, 5),
        temp_budget_bytes=8 << 20,
        closure=lambda: _spec_contract_built()[1],
        description="speculative DRAFT step table (ISSUE 20): the "
                    "draft's KV pool, token/length arrays and the "
                    "device-resident proposals buffer donate in "
                    "place; the window column is traced data, so the "
                    "table is closed over every (active-set size, "
                    "window column 0..spec_k-1) pair with one program "
                    "per slot bucket")
    declare_contract(
        "serve.spec.draft.prefill", lambda: _spec_contract_built()[2],
        donate_argnums=(1, 2, 3, 4),
        temp_budget_bytes=8 << 20,
        closure=lambda: _spec_contract_built()[3],
        description="speculative draft-prefill sentinel (ISSUE 20): "
                    "draft KV state donated, the TARGET's token array "
                    "read-only (the adopted first token also orders "
                    "the sentinel after the emit chunk); closed over "
                    "the prompt-bucket admission set")
    declare_contract(
        "serve.spec.verify", lambda: _spec_contract_built()[4],
        donate_argnums=(1, 2, 3, 4, 5, 6),
        temp_budget_bytes=8 << 20,
        closure=lambda: _spec_contract_built()[5],
        description="speculative VERIFY table (ISSUE 20): one "
                    "dispatch covers all spec_k+1 window positions — "
                    "target heap + token/length AND the draft's "
                    "token/length donate in place (both models' "
                    "states stay flat and in lockstep), proposals "
                    "read-only; closed over every active-set size "
                    "1..slots")


_declare_decode_contracts()
