#!/usr/bin/env python3
"""One attention layer, forward and forward + backward, on the chip: the
microbenchmark behind `ops/attention.py:_Geometry.blocks` (PERF.md
section 6 holds the ladders it gave).

    chiprun --chips 1 -- python3 tools/flash_ladder.py \\
        --shape 2,4096,5120 --heads 20 --causal 1 \\
        --blocks default 256,256,256 512,512,512 --two-kernel 0 1

`--shape B,T,H*D --heads H` is the packed layout `multi_head_attention`
holds, `--shape B,H,T,D` (no `--heads`) `attention_core`'s; `--kv-heads`
gives k and v fewer heads than q (grouped key/value heads) and `--window`
a causal call its band.  `--check 0` leaves the composition out: at 64
heads of 8192 positions its scores are 17 GB.  Every
`--blocks` entry (query rows, key rows of the forward, key rows of the
backward) replaces what `_Geometry.blocks` would choose; `--two-kernel 1`
makes the backward fall back to its two kernels.
`--recompute 1` adds the rung of a recomputed block (PERF.md section 6,
PR 31): the layer forward + backward under `jax.checkpoint`, once with
`Block.recompute`'s policy - the kernels' out and logsumexp are kept and
the second run holds no forward kernel - and once with nothing kept, each
with the kernel calls of its compiled program.
`--root DIR` measures another checkout's `mxnet_tpu` (the parent's, under
`_archive/`).  Each line: the variant, milliseconds (median of `--reps`
timings of `--inner` calls each) and the worst relative error of out, dq,
dk, dv against the jnp composition on the same inputs.  A measurement
needs the TPU; `--platform cpu` rehearses at a small shape.
"""
import argparse
import json
import os
import statistics
import sys
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--shape", default="2,4096,5120")
    ap.add_argument("--heads", type=int, default=0)
    ap.add_argument("--causal", type=int, default=1)
    ap.add_argument("--kv-heads", type=int, default=0)
    ap.add_argument("--window", type=int, default=0)
    ap.add_argument("--check", type=int, default=1)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--blocks", nargs="+", default=["default"])
    ap.add_argument("--two-kernel", nargs="+", type=int, default=[0])
    ap.add_argument("--recompute", type=int, default=0)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--inner", type=int, default=10)
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--out", default="chiprun_out/flash_ladder.jsonl")
    opts = ap.parse_args()

    sys.path.insert(0, os.path.abspath(opts.root))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.base import RECOMPUTE_KEEP
    from mxnet_tpu.ops import attention as att

    device = jax.devices()[0]
    if device.platform != opts.platform:
        raise SystemExit("flash_ladder: needs a %r device, jax found %r"
                         % (opts.platform, device.platform))
    shape = tuple(int(x) for x in opts.shape.split(","))
    causal = bool(opts.causal)
    heads = opts.heads or None
    D = shape[-1] // heads if heads else shape[-1]
    scale = 1.0 / D ** 0.5
    rng = np.random.RandomState(0)
    kv_heads = opts.kv_heads or heads or shape[1]
    kv_shape = shape[:2] + (kv_heads * D,) if heads \
        else (shape[0], kv_heads) + shape[2:]
    q, g = (jnp.asarray(rng.randn(*shape), opts.dtype) for _ in range(2))
    k, v = (jnp.asarray(rng.randn(*kv_shape), opts.dtype) for _ in range(2))
    band = {"window": opts.window} if opts.window else {}

    def flash(q, k, v):
        with att.attention_impl_scope("pallas"):
            if heads:
                return att.attention_heads(q, k, v, heads, causal=causal,
                                           **band)
            return att.attention_core(q, k, v, causal=causal, **band)

    def grads(f):
        def loss(q, k, v, g):
            out = f(q, k, v)
            return (out.astype(jnp.float32) * g).sum(), out
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))

    def timed(f, *args):
        jax.block_until_ready(f(*args))
        times = []
        for _ in range(opts.reps):
            t0 = time.perf_counter()
            for _ in range(opts.inner):
                out = f(*args)
            jax.block_until_ready(out)
            times.append((time.perf_counter() - t0) / opts.inner * 1e3)
        return statistics.median(times)

    def rel(a, b):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-6))

    def composition_row(q, k, v):
        if not heads:
            return att._attention_jnp(q, k, v, scale, causal, **band)
        T = shape[1]

        def split(x, count):
            return x.reshape(1, T, count, D).transpose(0, 2, 1, 3)
        out = att._attention_jnp(split(q, heads), split(k, kv_heads),
                                 split(v, kv_heads), scale, causal, **band)
        return out.transpose(0, 2, 1, 3).reshape((1,) + shape[1:])

    # the composition a batch row at a time: its scores are 1.3 GB a row
    # at the default shape, and the gradient keeps several
    if opts.check:
        reference = grads(composition_row)
        rows = [reference(*(x[b:b + 1] for x in (q, k, v, g)))
                for b in range(shape[0])]
        wdq, wdk, wdv = (np.concatenate([np.asarray(r[0][i], np.float32)
                                         for r in rows]) for i in range(3))
        wout = np.concatenate([np.asarray(r[1], np.float32) for r in rows])
    # a recomputed block's policy, and the one that keeps nothing
    policies = (
        ("kept", jax.checkpoint_policies.save_only_these_names(
            RECOMPUTE_KEEP)),
        ("recomputed", jax.checkpoint_policies.nothing_saveable))
    chosen = att._Geometry.blocks
    fused = getattr(att._Geometry, "fused_backward", None)
    os.makedirs(os.path.dirname(os.path.abspath(opts.out)), exist_ok=True)
    with open(opts.out, "a") as log:
        for two_kernel in opts.two_kernel:
            if fused is not None:
                att._Geometry.fused_backward = \
                    (lambda *a: None) if two_kernel else fused
            for blocks in opts.blocks:
                if blocks == "default":
                    att._Geometry.blocks = chosen
                else:
                    triple = tuple(int(x) for x in blocks.split(","))
                    att._Geometry.blocks = lambda self, *a, t=triple: t
                line = {"root": os.path.relpath(opts.root), "shape": shape,
                        "heads": heads, "kv_heads": kv_heads,
                        "window": opts.window or None,
                        "causal": causal, "blocks": blocks,
                        "two_kernel": bool(two_kernel),
                        "device": device.device_kind}
                try:
                    fwd = jax.jit(lambda q, k, v: flash(q, k, v))
                    grad = grads(flash)
                    (dq, dk, dv), out = grad(q, k, v, g)
                    if opts.check:
                        line["max_rel_err"] = {
                            "out": rel(out, wout), "dq": rel(dq, wdq),
                            "dk": rel(dk, wdk), "dv": rel(dv, wdv)}
                    line["custom_calls"] = grad.lower(q, k, v, g).as_text() \
                        .count("tpu_custom_call")
                    line["forward_ms"] = timed(fwd, q, k, v)
                    line["forward_backward_ms"] = timed(grad, q, k, v, g)
                    for name, policy in policies if opts.recompute else ():
                        again = grads(jax.checkpoint(flash, policy=policy))
                        line["checkpoint_%s_custom_calls" % name] = \
                            again.lower(q, k, v, g).compile().as_text() \
                            .count("tpu_custom_call")
                        line["checkpoint_%s_ms" % name] = \
                            timed(again, q, k, v, g)
                except Exception as e:        # a block Mosaic refuses
                    line["error"] = str(e)[:300]
                print(json.dumps(line), flush=True)
                log.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
