#!/usr/bin/env python3
"""One Mamba-2 scan, forward and forward + backward, on the chip: the
microbenchmark behind `ops/ssm.py:ssd_scan` (PERF.md section 6, PR 33
holds the ladder it gave).

    chiprun --chips 1 -- python3 tools/ssm_ladder.py \\
        --shape 1,8192,16,64 --groups 1 --state 128 --chunks 64 128 256

`--shape B,T,H,P` with `--groups` groups of `--state` lanes.  Rungs, each
at every `--chunks` entry: ``written`` - `ssd_scan` as it is, its backward
written by hand; ``autodiff`` - the same chunked forward differentiated by
jax (what the custom rule replaces); and once ``recurrence`` - the scan
over positions, one state a head, forward only (what the chunked form
replaces; its backward keeps T states).  Each line: the rung,
milliseconds (median of `--reps` timings of `--inner` calls each) forward
and forward + backward, and the worst relative error of y and of the five
gradients against the recurrence in float32.  A measurement needs the
TPU; `--platform cpu` rehearses at a small shape.
"""
import argparse
import json
import os
import statistics
import sys
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="1,8192,16,64")
    ap.add_argument("--groups", type=int, default=1)
    ap.add_argument("--state", type=int, default=128)
    ap.add_argument("--chunks", nargs="+", type=int, default=[128])
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--inner", type=int, default=10)
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--out", default="chiprun_out/ssm_ladder.jsonl")
    opts = ap.parse_args()

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.ops import ssm

    device = jax.devices()[0]
    if device.platform != opts.platform:
        raise SystemExit("ssm_ladder: needs a %r device, jax found %r"
                         % (opts.platform, device.platform))
    B, T, H, P = (int(v) for v in opts.shape.split(","))
    G, N = opts.groups, opts.state
    rng = np.random.RandomState(0)
    dtype = jnp.dtype(opts.dtype)
    x = jnp.asarray(rng.randn(B, T, H, P), dtype)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.1),
                                        (B, T, H))), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, (H,)), jnp.float32)
    b = jnp.asarray(rng.randn(B, T, G, N) * 0.5, dtype)
    c = jnp.asarray(rng.randn(B, T, G, N) * 0.5, dtype)
    weight = jnp.asarray(rng.randn(B, T, H, P), jnp.float32)
    args = (x, dt, a, b, c)

    def recurrence(x, dt, a, b, c):
        x, b, c = (v.astype(jnp.float32) for v in (x, b, c))
        b, c = (jnp.repeat(v, H // G, axis=2) for v in (b, c))

        def position(h, inputs):
            x_t, dt_t, b_t, c_t = inputs
            h = jnp.exp(dt_t * a)[..., None, None] * h \
                + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
            return h, (h * c_t[..., None, :]).sum(-1)

        _, y = jax.lax.scan(position, jnp.zeros((B, H, P, N), jnp.float32),
                            tuple(jnp.moveaxis(v, 1, 0)
                                  for v in (x, dt, b, c)))
        return jnp.moveaxis(y, 0, 1)

    def with_grads(fn):
        return jax.jit(jax.value_and_grad(
            lambda *v: (fn(*v).astype(jnp.float32) * weight).sum(),
            argnums=(0, 1, 2, 3, 4)))

    def timed(f):
        jax.block_until_ready(f(*args))
        times = []
        for _ in range(opts.reps):
            t0 = time.perf_counter()
            for _ in range(opts.inner):
                out = f(*args)
            jax.block_until_ready(out)
            times.append((time.perf_counter() - t0) / opts.inner * 1e3)
        return statistics.median(times)

    def worst(got, want):
        return max(float(jnp.abs(g.astype(jnp.float32)
                                 - w.astype(jnp.float32)).max()
                         / (jnp.abs(w.astype(jnp.float32)).max() + 1e-30))
                   for g, w in zip(got, want))

    want_y = jax.jit(recurrence)(*args)
    want_g = with_grads(recurrence)(*args)[1]
    lines = [{"rung": "recurrence", "forward_ms": timed(jax.jit(recurrence))}]
    for chunk in opts.chunks:
        rungs = {
            "written": lambda *v, q=chunk: ssm.ssd_scan(*v, q),
            "autodiff": lambda *v, q=chunk: ssm._forward(*v, q)[0]}
        for name, fn in rungs.items():
            forward, both = jax.jit(fn), with_grads(fn)
            lines.append({
                "rung": name, "chunk": chunk, "forward_ms": timed(forward),
                "forward_backward_ms": timed(both),
                "err_y": worst([forward(*args)], [want_y]),
                "err_grads": worst(both(*args)[1], want_g)})
    os.makedirs(os.path.dirname(opts.out) or ".", exist_ok=True)
    with open(opts.out, "a") as f:
        for line in lines:
            line.update(shape=opts.shape, groups=G, state=N,
                        dtype=opts.dtype, device=device.device_kind)
            print(json.dumps(line, sort_keys=True), flush=True)
            f.write(json.dumps(line, sort_keys=True) + "\n")


if __name__ == "__main__":
    main()
