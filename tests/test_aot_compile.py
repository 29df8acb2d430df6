"""Ahead-of-time compiles for a described TPU v5e, without the chip.

The TPU's compiler is installed with jax and compiles for a device that is
described (`v5e:2x2`) and not attached, so the kernels of the main path
are held to what Mosaic and XLA:TPU accept at real widths in tier-1:
what interpret mode on the CPU cannot show (tiling, fast-memory limits).
Nothing runs — results and times come only from a chip run.  The cases
are kernels and layers; whole train steps are test_aot_steps.py's.  A
program that several cases read is built and compiled once a module
(`_program`).  Also here: chip_smoke.py's phase functions at tiny sizes
on the CPU, and its refusal to pass without a TPU.
"""
import json
import os
import re
import subprocess
import sys

import pytest

from described_v5e import topo        # noqa: F401 (fixture; sets TPU_LOG_DIR)

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from mxnet_tpu import tpu_kernel
from mxnet_tpu.base import RECOMPUTE_KEEP
from mxnet_tpu.ops import attention as att
from mxnet_tpu.serve.decode import DecodeConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


@pytest.fixture(scope="module")
def chip(topo):                                   # noqa: F811
    """Sharding onto one chip of the described v5e:2x2."""
    return SingleDeviceSharding(topo.devices[0])


def _flash(direction, shape, causal):
    def fwd(q, k, v):
        return att.attention_core(q, k, v, causal=causal)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    return (fwd if direction == "fwd" else bwd), \
        [(shape, jnp.bfloat16)] * 3


def _decode_op(name):
    """The decode engine's attention ops at its default geometry."""
    cfg = DecodeConfig()
    B, H, D, T = cfg.slots, cfg.heads, cfg.head_dim, cfg.spec_k + 1
    f32, i32 = jnp.float32, jnp.int32
    flat = ((B, cfg.max_len, H, D), f32)
    heap = ((cfg.kv_pages, cfg.kv_page_len, H, D), f32)
    table = ((B, cfg.pages_per_slot), i32)
    return {
        "cached_attention": (
            att.cached_attention,
            [((B, H, D), f32), flat, flat, ((B,), i32)]),
        "cached_attention_multi": (
            att.cached_attention_multi,
            [((B, T, H, D), f32), flat, flat, ((B, T), i32)]),
        "paged_attention": (
            att.paged_attention,
            [((B, H, D), f32), heap, heap, table, ((B,), i32)]),
        "paged_attention_multi": (
            att.paged_attention_multi,
            [((B, T, H, D), f32), heap, heap, table, ((B, T), i32)]),
    }[name]


def _user_kernel():
    def body(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0 + 1.0

    kernel = tpu_kernel.Kernel(body, name="axpb", interpret=False)
    return (lambda x: kernel._call_jax((256, 256), x)[0]), \
        [((256, 256), jnp.float32)]


def _heads(direction, shape, heads, mask=False, causal=False, kv_heads=None,
           window=None):
    """multi_head_attention's entry: the packed (B, T, H*D) operands; k
    and v of `kv_heads` heads where query heads share them; `window`, a
    causal call's band."""
    B, T, HD = shape
    kv_shape = (B, T, HD // heads * (kv_heads or heads))

    def fwd(q, k, v):
        m = jnp.ones((B, 1, T, T), bool) if mask else None
        return att.attention_heads(q, k, v, heads, mask=m, causal=causal,
                                   window=window)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    return (fwd if direction == "fwd" else bwd), \
        [(shape, jnp.bfloat16)] + [(kv_shape, jnp.bfloat16)] * 2


def _turned(direction, shape, heads, keywords, dtype=jnp.bfloat16):
    """The ``rotary_embedding`` operator on a packed (B, T, H*D) tensor
    (ops/rotary.py's kernel where its rule holds: one call forward, the
    same kernel on the negative angle backward)."""
    from mxnet_tpu.ops import nn as ops_nn

    def fwd(x):
        return ops_nn._rotary_embedding(x, num_heads=heads, **keywords)

    def bwd(x):
        # a loss whose cotangent reads the result: a constant one would
        # leave a program that reads no argument
        return jax.grad(lambda x: (fwd(x).astype(jnp.float32) ** 2).sum())(x)

    return (fwd if direction == "fwd" else bwd), [(shape, dtype)]


def _turned_heads(kind):
    """One of Laguna-XS.2's attention layers past its projections at the
    cell's shapes: q and k through the rotary operator, then the causal
    core on 8 key/value heads (`kind` "sliding_attention": 64 query
    heads, every lane turns, a band of 512 keys; "full_attention": 48,
    the first 64 lanes with YaRN)."""
    from mxnet_tpu.gluon.model_zoo import laguna
    from mxnet_tpu.ops import nn as ops_nn
    heads, window = (64, 512) if kind == "sliding_attention" else (48, None)
    keywords = laguna.rotary_keywords(laguna.ROPE_XS_2[kind], 128)

    def fwd(q, k, v):
        q = ops_nn._rotary_embedding(q, num_heads=heads, **keywords)
        k = ops_nn._rotary_embedding(k, num_heads=8, **keywords)
        return att.attention_heads(q, k, v, heads, causal=True,
                                   window=window)

    return fwd, [((1, 8192, heads * 128), jnp.bfloat16)] \
        + [((1, 8192, 1024), jnp.bfloat16)] * 2


def _latent_experts(direction):
    """Nemotron-3-Super's expert layer on one chip's share at the cell's
    sizes: 8192 tokens of 4096 lanes routed top-22 over 512 experts, 8
    held, ungated squared-ReLU experts of width 2688 in a 1024-lane
    latent: a buffer of 5632 rows (twice the even share) or of the exact
    8192 x min(22, 8), chosen on the device - both are in the program."""
    from mxnet_tpu.parallel import moe

    def fwd(x, latent, router, correction, up, down):
        return moe.token_choice_moe(x, router, correction, up, down,
                                    held=tuple(range(8)), top_k=22,
                                    scale=5.0, activation="relu2",
                                    expert_input=latent)[0]

    def bwd(*args):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2, 4, 5))(*args)

    return (fwd if direction == "fwd" else bwd), [
        ((8192, 4096), jnp.bfloat16), ((8192, 1024), jnp.bfloat16),
        ((512, 4096), jnp.float32), ((512,), jnp.float32),
        ((8, 1024, 2688), jnp.bfloat16), ((8, 2688, 1024), jnp.bfloat16)]


def _scan(direction):
    """Mamba-2's scan at the cell's sizes: 8192 positions, 16 heads of 64
    in one group of 128 state lanes, chunks of 128 (ops/ssm.py: a
    composition, no kernel of the program's own)."""
    from mxnet_tpu.ops import ssm

    def fwd(x, dt, a, b, c):
        return ssm.ssd_scan(x, dt, a, b, c, 128)

    def bwd(*args):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2, 3, 4))(*args)

    return (fwd if direction == "fwd" else bwd), [
        ((1, 8192, 16, 64), jnp.bfloat16), ((1, 8192, 16), jnp.float32),
        ((16,), jnp.float32), ((1, 8192, 1, 128), jnp.bfloat16),
        ((1, 8192, 1, 128), jnp.bfloat16)]


def _held_experts(direction):
    """GLM-4.7-Flash's expert layer on one chip's share at the cell's
    sizes: 8192 tokens, top-4 of 64 experts, 8 held, width 1536
    (parallel/moe.py: sorts, gathers and two grouped products, which on
    the chip are ops/grouped.py's Pallas kernels, `grouped_product_rows`
    forward and by the rows, `grouped_product_weights` by the weights -
    forward 2; backward 2 by the rows + 2 by the weights, and the
    forward's 2 again in the exact buffer's branch, which keeps nothing of
    its forward; no `ragged-dot-*` kernel of XLA's is left).  The program
    holds both buffer sizes, 8192 rows and the exact 32768, each in its
    branch of a `conditional`."""
    from mxnet_tpu.parallel import moe

    def fwd(x, router, correction, gate_up, down):
        return moe.token_choice_moe(x, router, correction, gate_up, down,
                                    held=tuple(range(8)), top_k=4,
                                    scale=1.8)[0]

    def bwd(*args):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 3, 4))(*args)

    return (fwd if direction == "fwd" else bwd), [
        ((8192, 2048), jnp.bfloat16), ((64, 2048), jnp.float32),
        ((64,), jnp.float32), ((8, 2048, 3072), jnp.bfloat16),
        ((8, 1536, 2048), jnp.bfloat16)]


def _recomputed(forward, argnums=(0, 1, 2)):
    """`forward`'s case as a recomputed block runs it: forward + backward
    under ``jax.checkpoint`` with ``Block.recompute``'s policy.  The
    value is returned with the gradients: a forward pass nobody reads is
    dropped and only the second run remains."""
    fwd, args = forward
    keep = jax.checkpoint_policies.save_only_these_names(RECOMPUTE_KEEP)

    def step(*a):
        value, grads = jax.value_and_grad(
            lambda *a: jax.checkpoint(fwd, policy=keep)(*a)
            .astype(jnp.float32).sum(), argnums=argnums)(*a)
        return (value,) + grads

    return step, args


# the build functions whose program more than one case reads: each is one
# key of `_program`'s cache, so the program is built and compiled once
SHARED = {
    "held-experts-fwd": lambda: _held_experts("fwd"),
    "held-experts-bwd": lambda: _held_experts("bwd"),
    "held-experts-recomputed": lambda: _recomputed(_held_experts("fwd"),
                                                   (0, 1, 3, 4)),
    "latent-experts-bwd": lambda: _latent_experts("bwd"),
    "rotary-every-lane-bwd": lambda: _turned(
        "bwd", (1, 8192, 8192), 64, dict(rotary_dim=128)),
}
_PROGRAMS = {}


def _program(build, topo, chip):
    """(lowered text, compiled text, the compiler's temporary bytes) of
    `build`'s program for the described chip, compiled the first time a
    case asks for it (the caller steers `att._on_tpu`)."""
    if build not in _PROGRAMS:
        fn, args, *four_chips = build()
        layout, out = None, {}
        if four_chips:
            layout, chip = _rows_over_four_chips(topo)
            out = {"out_shardings": (None,) + (chip,) * len(args)}
        abstract = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
                    for shape, dtype in args]
        with att.attention_partition_scope(layout):
            lowered = jax.jit(fn, **out).lower(*abstract)
        compiled = lowered.compile()
        _PROGRAMS[build] = (lowered.as_text(), compiled.as_text(),
                            compiled.memory_analysis().temp_size_in_bytes)
    return _PROGRAMS[build]


# (id, builder of (fn, [(shape, dtype), ...]) - and True for the rows over
#  the four chips' data,fsdp = 2x2 inside attention_partition_scope -,
#  tpu_custom_calls expected or (what else to count in the text, how
#  many), whether the text must equal attention_impl_scope("xla")'s).  The
# backward is ONE kernel for dq, dk and dv at any length, causal or not -
# or the dk/dv kernel and the dq kernel where q, dO and dq do not fit in
# fast memory (`_Geometry.fused_backward`).
CASES = [
    ("flash-%s-%s-%s" % (d, "x".join(map(str, s)), "causal" if c else "full"),
     lambda d=d, s=s, c=c: _flash(d, s, c), 1 if d == "fwd" else 2, False)
    for s in ((8, 8, 512, 128), (2, 8, 2048, 128))
    for c in (True, False)
    for d in ("fwd", "bwd")
] + [
    # BERT-base's own shape, as multi_head_attention hands it over: head
    # size 64, two heads to a 128-lane block.  A change of the rule must
    # show here.
    ("bert-base-8x12x512x64-gets-flash",
     lambda: _heads("fwd", (8, 512, 768), 12), 1, False),
    ("bert-base-8x12x512x64-gets-flash-bwd",
     lambda: _heads("bwd", (8, 512, 768), 12), 2, False),
    # ... and as attention_core's (B, H, T, D): one 64-wide head a block
    ("bhtd-8x12x512x64-gets-flash",
     lambda: _flash("fwd", (8, 12, 512, 64), False), 1, False),
    ("bhtd-8x12x512x64-causal-gets-flash-bwd",
     lambda: _flash("bwd", (8, 12, 512, 64), True), 2, False),
    # GLM-4.7-Flash's latent attention as multi_head_attention hands it
    # over: causal, 20 heads of 256 lanes, 4096 positions - a head's whole
    # K and V (2 MB each) resident in the forward; q, dO and dq (2 MB
    # each, two buffers) and dq's float32 sum (4 MB) in the backward
    ("mla-2x20x4096x256-causal-gets-flash",
     lambda: _heads("fwd", (2, 4096, 5120), 20, causal=True), 1, False),
    ("mla-2x20x4096x256-causal-gets-flash-bwd",
     lambda: _heads("bwd", (2, 4096, 5120), 20, causal=True), 2, False),
    # ... and inside a recomputed block: the forward kernel's out and
    # logsumexp are kept, so the second run holds no forward kernel (3
    # calls if they were not) - on one chip, and per shard under the
    # four chips' layout
    ("mla-2x20x4096x256-causal-recomputed-runs-the-forward-kernel-once",
     lambda: _recomputed(_heads("fwd", (2, 4096, 5120), 20, causal=True)),
     2, False),
    ("mla-8x20x4096x256-causal-recomputed-on-four-chips",
     lambda: _recomputed(_heads("fwd", (8, 4096, 5120), 20, causal=True))
     + (True,), 2, False),
    # the fast-memory arithmetic, held to what Mosaic accepts: the longest
    # causal call of 256 lanes whose q, dO, dq and float32 dq sum stay
    # resident (21,248 rows: 89.3 of the 96 MiB `fused_backward` allows),
    # and the next one that divides into 512-row blocks, which must fall
    # back to the dk/dv kernel and the dq kernel
    ("long-1x1x21248x256-causal-keeps-one-bwd-kernel",
     lambda: _flash("bwd", (1, 1, 21248, 256), True), 2, False),
    ("long-1x1x21504x256-causal-falls-back-to-two",
     lambda: _flash("bwd", (1, 1, 21504, 256), True), 3, False),
    # each buffer size has its branch: 2 grouped kernels a forward branch;
    # in the backward pass 4 in the short buffer's branch and 6 in the
    # exact one's (its forward again), and in the forward's short branch
    # the 2 that make what the backward reads - the exact branch's forward
    # makes nothing the gradients read and is dropped.  None of XLA's own
    # `ragged-dot-*` kernels is left, forward or backward
    ("held-experts-8192x2048-top4-8of64",
     SHARED["held-experts-fwd"], 4, False),
    ("held-experts-8192x2048-top4-8of64-bwd",
     SHARED["held-experts-bwd"], 12, False),
    ("held-experts-8192x2048-top4-8of64-bwd-holds-no-ragged-dot",
     SHARED["held-experts-bwd"], ("ragged-dot", 0), False),
    # the router's top-k and the dispatch's order and its inverse are
    # sorts; a recomputed block keeps all three results and its second
    # run sorts nothing (5 sorts if order and inverse were not kept)
    ("held-experts-8192x2048-top4-8of64-sorts",
     SHARED["held-experts-fwd"], (" sort(", 3), False),
    ("held-experts-8192x2048-top4-8of64-recomputed-sorts-once",
     SHARED["held-experts-recomputed"], (" sort(", 3), False),
    # Nemotron-3-Super's attention: query heads of 128 lanes that share
    # key/value heads, causal, 8192 positions - the cell's share (4 on 1)
    # and the published layer (32 on 2); the kernels index the shared
    # head's block, the backward is still one kernel (dk and dv come out
    # a query head; XLA sums a group's), and a recomputed block still
    # runs the forward kernel once
    ("gqa-1x4on1x8192x128-causal-gets-flash",
     lambda: _heads("fwd", (1, 8192, 512), 4, causal=True, kv_heads=1),
     1, False),
    ("gqa-1x4on1x8192x128-causal-gets-flash-bwd",
     lambda: _heads("bwd", (1, 8192, 512), 4, causal=True, kv_heads=1),
     2, False),
    ("gqa-1x32on2x8192x128-causal-gets-flash-bwd",
     lambda: _heads("bwd", (1, 8192, 4096), 32, causal=True, kv_heads=2),
     2, False),
    ("gqa-1x4on1x8192x128-causal-recomputed-runs-the-forward-kernel-once",
     lambda: _recomputed(_heads("fwd", (1, 8192, 512), 4, causal=True,
                                kv_heads=1)), 2, False),
    # Laguna-XS.2's two kinds of attention at the cell's shapes: 64 query
    # heads on 8 key/value heads under a band of 512 keys (the windowed
    # kernels: the same three, `_visits` names the band's blocks) and 48
    # on 8 with every earlier key; one backward kernel each, and a
    # recomputed block runs the windowed forward kernel once
    ("swa-1x64on8x8192x128-window512-gets-flash",
     lambda: _heads("fwd", (1, 8192, 8192), 64, causal=True, kv_heads=8,
                    window=512), 1, False),
    ("swa-1x64on8x8192x128-window512-gets-flash-bwd",
     lambda: _heads("bwd", (1, 8192, 8192), 64, causal=True, kv_heads=8,
                    window=512), 2, False),
    ("swa-1x64on8x8192x128-window512-recomputed-runs-the-forward-kernel-once",
     lambda: _recomputed(_heads("fwd", (1, 8192, 8192), 64, causal=True,
                                kv_heads=8, window=512)), 2, False),
    ("gqa-1x48on8x8192x128-causal-gets-flash-bwd",
     lambda: _heads("bwd", (1, 8192, 6144), 48, causal=True, kv_heads=8),
     2, False),
    # ... a band that is no multiple of the 256-row unit keeps the
    # composition
    ("swa-1x8on1x512x128-window100-gets-xla",
     lambda: _heads("fwd", (1, 512, 1024), 8, causal=True, kv_heads=1,
                    window=100), 0, False),
    # ... 64-lane heads that share key/value heads have no block of their
    # own: the composition
    ("gqa-1x4on2x512x64-gets-xla",
     lambda: _heads("fwd", (1, 512, 256), 4, causal=True, kv_heads=2),
     0, True),
    # its expert layer (top-22 of 512, 8 held: 5,632 rows or the exact
    # 65,536, a branch each as above: the grouped kernels at 1024 -> 2688
    # -> 1024 lanes) and its scan compile; the scan holds no kernel of the
    # program's own
    ("latent-experts-8192x4096-top22-8of512",
     lambda: _latent_experts("fwd"), 4, False),
    ("latent-experts-8192x4096-top22-8of512-bwd",
     SHARED["latent-experts-bwd"], 12, False),
    ("latent-experts-8192x4096-top22-8of512-bwd-holds-no-ragged-dot",
     SHARED["latent-experts-bwd"], ("ragged-dot", 0), False),
    ("ssd-scan-1x8192x16x64-chunk128", lambda: _scan("fwd"), 0, False),
    ("ssd-scan-1x8192x16x64-chunk128-bwd", lambda: _scan("bwd"), 0, False),
    # rotary positions on the packed layout at the Laguna cell's shapes
    # (ops/rotary.py: lane rotates inside each head's 128 lanes, lane
    # slices of a block of 8 heads): sliding layers' q, every lane; full
    # layers' q and the 8 key heads, the first 64 lanes with YaRN; a
    # 256-lane head whose last 64 turn (GLM's query) and float32 data.
    # The backward is the same kernel; nothing of four axes is left
    ("rotary-1x8192x64x128-every-lane",
     lambda: _turned("fwd", (1, 8192, 8192), 64, dict(rotary_dim=128)),
     1, False),
    ("rotary-1x8192x64x128-every-lane-bwd",
     SHARED["rotary-every-lane-bwd"], 2, False),
    ("rotary-1x8192x64x128-every-lane-bwd-holds-no-four-axes",
     SHARED["rotary-every-lane-bwd"], ("[1,8192,64,128]", 0), False),
    ("rotary-1x8192x48x128-first-64-yarn-bwd",
     lambda: _turned("bwd", (1, 8192, 6144), 48, dict(
         rotary_dim=64, theta=5e5, first=True, yarn=(64, 4096, 64, 1),
         attention_factor=1.4158883083359672)), 2, False),
    ("rotary-1x8192x8x128-first-64-yarn-float32-bwd",
     lambda: _turned("bwd", (1, 8192, 1024), 8, dict(
         rotary_dim=64, theta=5e5, first=True, yarn=(64, 4096, 64, 1),
         attention_factor=1.4158883083359672), jnp.float32), 2, False),
    ("rotary-2x4096x20x256-last-64-bwd",
     lambda: _turned("bwd", (2, 4096, 5120), 20, dict(rotary_dim=64,
                                                      theta=1e6)), 2, False),
    # ... the rule's widest block (float32 heads of 1,024 lanes) fits the
    # fast memory Mosaic grants a kernel that asks for none
    ("rotary-1x2048x2x1024-float32-bwd",
     lambda: _turned("bwd", (1, 2048, 2048), 2, dict(rotary_dim=1024),
                     jnp.float32), 2, False),
    # ... heads of 192 lanes, GLM's one rotary key of 64 and a length that
    # is no multiple of the row block keep the composition
    ("rotary-2x4096x20x192-gets-xla",
     lambda: _turned("bwd", (2, 4096, 3840), 20, dict(rotary_dim=64)),
     0, False),
    ("rotary-2x4096x1x64-gets-xla",
     lambda: _turned("bwd", (2, 4096, 64), 1, dict(rotary_dim=64)),
     0, False),
    ("rotary-1x1000x8x128-gets-xla",
     lambda: _turned("bwd", (1, 1000, 1024), 8, dict(rotary_dim=128)),
     0, False),
    # ... a recomputed attention layer runs it again - nothing of q and k
    # is kept across the recomputation - beside the flash kernels, whose
    # forward is kept: q and k in the forward, in the second run and in
    # the backward (6) + the core's forward and backward (2)
    ("rotary-swa-1x64on8x8192x128-recomputed-turns-q-and-k-three-times",
     lambda: _recomputed(_turned_heads("sliding_attention")), 8, False),
    ("rotary-gqa-1x48on8x8192x128-recomputed-turns-q-and-k-three-times",
     lambda: _recomputed(_turned_heads("full_attention")), 8, False),
    # ... and under the four chips' layout each chip turns its own rows
    # (forward and backward: the second run of a layer nobody reads is
    # dropped)
    ("rotary-8x4096x8x128-on-four-chips",
     lambda: _recomputed(_turned("fwd", (8, 4096, 1024), 8,
                                 dict(rotary_dim=128)), (0,)) + (True,),
     2, False),
    # a mask, or a T that is no block multiple, keeps the composition:
    # the same program text as with the kernels switched off
    ("bert-base-8x12x512x64-masked-gets-xla",
     lambda: _heads("fwd", (8, 512, 768), 12, mask=True), 0, True),
    ("bert-base-8x12x128x64-gets-xla",
     lambda: _heads("fwd", (8, 128, 768), 12), 0, True),
    ("user-kernel", _user_kernel, 1, False),
] + [
    (name, lambda name=name: _decode_op(name), 0, False)
    for name in ("cached_attention", "cached_attention_multi",
                 "paged_attention", "paged_attention_multi")
]


@pytest.mark.parametrize("build,custom_calls,as_xla",
                         [pytest.param(b, n, x, id=i)
                          for i, b, n, x in CASES])
def test_compiles_for_v5e(topo, chip, monkeypatch, build, custom_calls,
                          as_xla):
    # the code asks jax.default_backend(), sees the CPU here and would
    # take interpret mode: steer it in the test, not through an option
    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    what, count = custom_calls if isinstance(custom_calls, tuple) \
        else ("tpu_custom_call", custom_calls)
    lowered, text, _ = _program(build, topo, chip)
    assert text.count(what) == count
    if as_xla:
        fn, args = build()
        abstract = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
                    for shape, dtype in args]
        with att.attention_impl_scope("xla"):
            assert jax.jit(fn).lower(*abstract).as_text() == lowered


# cell: (the layer, what is differentiated, the exact buffer's rows, sorts,
#        the compiler's temporary bytes at the parent (one path, PR 33) and
#        the most they may be now).  The sorts are the parent's: the
#        router's top-k, the dispatch's order and its inverse - and, where k
#        > H, a token's places sorted, in the forward and in the second run
#        (the parent's sixth at the Nemotron shape, of the N * k indices of
#        the chosen scores' scatter-add, went with that scatter: PR 36).
#        The bytes are NOT the parent's: the two buffer sizes share their
#        temporaries (a conditional's branches never run together), but
#        what a conditional hands on is a buffer of its own - the gradients
#        of both expert matrices (here the program's results, in a step
#        temporaries either way), and the short-sized values the backward
#        pass reads again; read 1,218,228,736 and 1,223,611,904 (PR 36: the
#        compare against the expert axis is summed inside its fusion - an
#        (N, k, E) array would be 369 MB more at the Nemotron shape).
SIZED_EXPERTS = {
    "glm": (SHARED["held-experts-recomputed"], 32768, 3, 815_574_528,
            1_250_000_000),
    "nemotron": (lambda: _recomputed(_latent_experts("fwd"),
                                     (0, 1, 2, 4, 5)), 65536, 5,
                 1_163_004_928, 1_280_000_000),
}


@pytest.mark.parametrize("cell", sorted(SIZED_EXPERTS))
def test_a_recomputed_expert_layer_holds_both_buffer_sizes(topo, chip,
                                                           monkeypatch, cell):
    """The held-expert layer at a cell's shape, forward + backward under
    `Block.recompute`'s policy, for a described v5e, with the grouped
    kernels as on the chip (24 calls: 2 + 2 in the forward's branches, the
    same in the second run's, 4 + 6 + ... in the backward's; no
    `ragged-dot`): three conditionals
    (the forward, the recomputed forward, the backward pass - the choice
    is not differentiated through), and none hands on a value as long as
    the exact buffer: a derivative taken THROUGH the choice would fill the
    exact branch's residuals with zeros in the short one (PR 28's second
    branch: 2.2 GB of them at the GLM shape)."""
    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    build, rows, sorts, parent, most = SIZED_EXPERTS[cell]
    _, text, temp = _program(build, topo, chip)
    assert "ragged-dot" not in text and "grouped_product" in text
    assert text.count(" sort(") == sorts
    conditionals = [line.split(" conditional(")[0]
                    for line in text.splitlines() if " conditional(" in line]
    assert len(conditionals) == 3
    # (one column is the choices' weights' cotangent: N * k scalars)
    assert not [c for c in conditionals
                if any(int(width) > 1 for width in
                       re.findall(r"\[%d,(\d+)\]" % rows, c))]
    # with the grouped kernels the Nemotron layer reads 1,162,763,264:
    # XLA's ragged-dot expansion had temporaries of its own (PR 39)
    assert parent * 0.99 < temp <= most


def test_every_grouped_kernel_carries_the_experts_scope(topo, chip,
                                                        monkeypatch):
    """Forward and backward of the GLM cell's expert layer: the `op_name`
    path of every grouped kernel - in the short buffer's branch and in
    the exact one's, whose backward runs its forward again - holds
    `experts` as a whole component, which is how
    benchmark/harness/scope_time.py finds what `moe/experts` counts (a
    ``jax.vjp`` of the whole exact path named them
    ``transpose(jvp(experts))``)."""
    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    _, text, _ = _program(SHARED["held-experts-bwd"], topo, chip)
    names = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(names) == 12
    assert all("grouped_product" in name and "experts" in name.split("/")
               for name in names), names


def _rows_over_four_chips(topo):
    """(the layout of data,fsdp = 2x2 over the described chips, the
    sharding of a batch's rows over all four)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel import SpecLayout
    layout = SpecLayout.infer(Mesh(np.array(topo.devices).reshape(2, 2),
                                   ("data", "fsdp")))
    return layout, NamedSharding(layout.mesh, P(("data", "fsdp")))


def test_flash_is_partitioned_by_batch_on_four_chips(topo, monkeypatch):
    """BERT-base's attention of the fsdp4 cell (128 rows over data,fsdp =
    2x2) inside attention_partition_scope, compiled for the described
    v5e:2x2: every kernel works on its chip's 32 rows and nothing gathers
    a [..,512,768] operand.  Without the scope there is no program at
    all: jax refuses to lower a Mosaic kernel it would have to partition
    itself."""
    monkeypatch.setattr(att, "_on_tpu", lambda: True)
    layout, rows = _rows_over_four_chips(topo)
    fn, args = _heads("bwd", (128, 512, 768), 12)
    abstract = [jax.ShapeDtypeStruct(s, d, sharding=rows) for s, d in args]

    def compiled(scope):
        with att.attention_partition_scope(scope):
            return jax.jit(fn, out_shardings=(rows,) * 3) \
                .lower(*abstract).compile().as_text()

    def lines(text, what):
        return [l for l in text.splitlines() if what in l and " = " in l]

    text = compiled(layout)
    calls = lines(text, "tpu_custom_call")
    assert len(calls) == 2                  # forward; dk, dv, dq in one
    assert all("bf16[32,512,768]" in l and "[128,512,768]" not in l
               for l in calls)
    assert not lines(text, "all-gather")
    with pytest.raises(NotImplementedError, match="shard_map"):
        compiled(None)


def _entry_instructions(text):
    """The entry computation's instructions by name: (result type with
    no layout, opcode, operand names, the line)."""
    body = text.split("\nENTRY", 1)[1].split("\n}", 1)[0]
    out = {}
    for line in body.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?(%\S+) = (.*?) ([a-z][\w\-.]*)\(([^)]*)\)",
                     line)
        if m:
            out[m.group(1)] = (re.sub(r"\{[^}]*\}", "", m.group(2)),
                               m.group(3), re.findall(r"%[\w.\-]+",
                                                      m.group(4)), line)
    return out


def _entry_types(text):
    """The result type of each instruction of the entry computation that
    writes one (a fusion's insides are not written; a parameter, a
    bitcast or a tuple's element is no write)."""
    return [kind for kind, op, _, _ in _entry_instructions(text).values()
            if op not in ("parameter", "bitcast", "get-tuple-element")]


def _composition_loss(logits, label):
    """The sparse-label loss as SoftmaxCrossEntropyLoss and NextTokenLoss
    had it: `log_softmax`, then `pick` (a gather)."""
    from mxnet_tpu.ops import nn as ops_nn, matrix
    return -matrix._pick(ops_nn._log_softmax(logits), label)


# head: (x, the head's weight, the labels' dtype, a bias), and the float32
# vocabulary-wide results and scatters of the entry computation that the
# composition leaves: BERT's log_softmax (the chip trace's `fusion.22`, 2 GB,
# PR 25); at Nemotron's head that and the gather's transpose, a scatter-add
# into float32 zeros of the logits' size
LOSS_HEADS = {
    "bert-32x512x30522": ((32, 512, 768), (30522, 768), jnp.float32, True,
                          1, 0),
    "nemotron-1x8192x16384": ((1, 8192, 4096), (16384, 4096), jnp.int32,
                              False, 2, 1),
}


@pytest.mark.parametrize("form", ["operator", "composition"])
@pytest.mark.parametrize("head", sorted(LOSS_HEADS))
def test_the_sparse_label_loss_writes_no_float32_vocabulary(topo, chip, head,
                                                            form):
    """``value_and_grad`` of a head's product and the mean sparse-label
    loss over its logits cast to float32, compiled for a described v5e:
    with the ``sparse_softmax_cross_entropy`` operator the entry
    computation holds no float32 instruction as wide as the vocabulary
    and no scatter (the float32 copy is fused into the reductions and
    the backward's products); the composition it replaced holds them."""
    from mxnet_tpu.ops import nn as ops_nn
    x, w, label_dtype, bias, wide, scatters = LOSS_HEADS[head]
    loss = ops_nn._sparse_softmax_cross_entropy if form == "operator" \
        else _composition_loss

    def mean_loss(x, w, b, label):
        logits = jnp.einsum("bth,vh->btv", x, w)
        if bias:
            logits = logits + b
        return loss(logits.astype(jnp.float32), label).mean()

    args = [(x, jnp.bfloat16), (w, jnp.bfloat16), (w[:1], jnp.bfloat16),
            (x[:2], label_dtype)]
    abstract = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
                for shape, dtype in args]
    text = jax.jit(jax.value_and_grad(mean_loss, argnums=(0, 1, 2))).lower(
        *abstract).compile().as_text()
    vocab = re.compile(r"f32\[[\d,]*\b%d\b[\d,]*\]" % w[0])
    written = sum(len(vocab.findall(kind)) for kind in _entry_types(text))
    assert (written, text.count(" scatter(")) == \
        ((0, 0) if form == "operator" else (wide, scatters))


def _two_pass_batch_norm(x, gamma, beta, moving_mean, moving_var):
    """BatchNorm's training branch as it was: `jnp.mean`, then `jnp.var`."""
    x32 = x.astype(jnp.float32)
    mean, var = jnp.mean(x32, axis=(0, 2, 3)), jnp.var(x32, axis=(0, 2, 3))
    inv = jax.lax.rsqrt(var + 1e-5).astype(x.dtype)
    out = (x - mean.astype(x.dtype)[:, None, None]) * \
        (inv * gamma.astype(x.dtype))[:, None, None] + \
        beta.astype(x.dtype)[:, None, None]
    return out, 0.9 * moving_mean + 0.1 * mean, 0.9 * moving_var + 0.1 * var


@pytest.mark.parametrize("form", ["operator", "two-pass"])
def test_batch_norm_statistics_come_out_of_the_convolution(topo, chip, form):
    """A convolution -> BatchNorm -> ReLU -> convolution block at stage
    1's width of ResNet-50 (64 -> 256 channels at 56 x 56, batch 8),
    training, forward and backward, for a described v5e: with the
    operator's one pass the entry computation holds no stand-alone
    `kLoop` fusion that reduces the block's activation to a float32
    vector of its channels, and the convolution's output fusion writes
    both statistics beside the activation; the two-pass form leaves two
    such reductions (the variance's, forward and backward) and one
    statistic in the convolution's fusion."""
    from mxnet_tpu.ops import nn as ops_nn
    batch_norm = _two_pass_batch_norm if form == "two-pass" else \
        (lambda *args: ops_nn._batch_norm(*args, fix_gamma=False))
    n, c_in, c, hw = 8, 64, 256, 56

    def loss(x, w1, w2, gamma, beta, moving_mean, moving_var):
        y = ops_nn._convolution(x, w1, None, kernel=(1, 1), num_filter=c,
                                no_bias=True)
        y, new_mean, new_var = batch_norm(y, gamma, beta, moving_mean,
                                          moving_var)
        z = ops_nn._convolution(jnp.maximum(y, 0), w2, None, kernel=(1, 1),
                                num_filter=c_in, no_bias=True)
        return z.astype(jnp.float32).sum(), (new_mean, new_var)

    bf16, f32 = jnp.bfloat16, jnp.float32
    args = [((n, c_in, hw, hw), bf16), ((c, c_in, 1, 1), bf16),
            ((c_in, c, 1, 1), bf16)] + [((c,), f32)] * 4
    abstract = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
                for shape, dtype in args]
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)) \
        .lower(*abstract).compile().as_text()
    entry = _entry_instructions(text)
    activation, stat = "bf16[%d,%d,%d,%d]" % (n, c, hw, hw), "f32[%d]" % c
    alone = [name for name, (kind, op, operands, line) in entry.items()
             if op == "fusion" and "kind=kLoop" in line
             and stat in kind and activation not in kind
             and any(entry[o][0] == activation for o in operands
                     if o in entry)]
    convolution = [kind for kind, op, _, line in entry.values()
                   if op == "fusion" and "kind=kOutput" in line
                   and activation in kind and 'jvp()/conv_general_dilated"'
                   in line]
    assert len(convolution) == 1
    assert (len(alone), convolution[0].count(stat)) == \
        ((0, 2) if form == "operator" else (2, 1))


# ---------------------------------------------------------------------------
# chip_smoke.py
# ---------------------------------------------------------------------------

SMOKE_TINY = {
    "train_bert_base": dict(layers=1, units=32, heads=2, vocab=64, batch=4,
                            seq=16, lr=0.5),
    "eager": dict(rows=32, cols=8, hidden=16),
    "pallas": dict(cases=(((1, 2, 256, 128), True),
                          ((1, 2, 256, 64), False))),
}


@pytest.mark.parametrize("phase", sorted(SMOKE_TINY))
def test_chip_smoke_phase_tiny_on_cpu(phase):
    """The phase functions run end to end at tiny sizes on the CPU; the
    platform they check for is steered from here."""
    import chip_smoke
    out = getattr(chip_smoke, phase)(platform="cpu", **SMOKE_TINY[phase])
    json.dumps(out)                       # each phase prints one JSON line
    if phase == "train_bert_base":
        assert out["compiles_in_steps"] == 0
        assert out["losses"][-1] < out["losses"][0]
        assert out["attention_impl"] == "xla"
    if phase == "pallas":
        assert [c["tpu_custom_calls"] for c in out["cases"]] \
            == [{"forward": 0, "backward": 0}] * 2


def test_chip_smoke_refuses_cpu():
    """Without a TPU the script exits non-zero within seconds and prints
    no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MX_FORCE_CPU", None)
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120, env=env)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
