#!/usr/bin/env python3
"""The grouped product alone, on the chip: `ops/grouped.py`'s two kernels
against XLA:TPU's expansion of ``lax.ragged_dot``, product by product
(PERF.md section 6, PR 39 holds the ladder it gave).

    chiprun --chips 1 -- python3 tools/grouped_ladder.py \\
        --cell nemotron laguna glm --tiles 128,40 256,40

For each `--cell` (its short buffer's rows, the held experts, the two
projections' widths, a load drawn around `--load` times an even router's
share, uneven as a router's) and each projection, milliseconds (median of
`--reps` timings of `--inner` calls) of the forward product, of the
cotangent of the rows and of the cotangent of the weights: by
``lax.ragged_dot`` and its transposes (rung ``ragged``), by the kernels at
each `--tiles` entry (rows a tile, MiB of fast memory the blocks may
take), and - the yardstick - by one dense product of the load's rows,
which no grouped product can beat.  Every kernel result is compared with
the ragged one's over the load's rows (`*_err`), and what it wrote past
them is given beside (`*_tail`: 0, whatever the ragged product leaves
there).  A measurement needs the TPU; `--platform cpu` rehearses
in interpret mode at `--cell tiny`.
"""
import argparse
import json
import os
import statistics
import sys
import time

# short buffer's rows, held experts, (K, N) of the two projections, the
# even load
CELLS = {
    "nemotron": (5632, 8, ((1024, 2688), (2688, 1024)), 2816),
    "laguna": (16384, 32, ((2048, 1024), (512, 2048)), 8192),
    "glm": (8192, 8, ((2048, 3072), (1536, 2048)), 4096),
    "tiny": (1024, 4, ((128, 256), (256, 128)), 512),
}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", nargs="+", default=["nemotron"],
                    choices=sorted(CELLS))
    ap.add_argument("--load", type=float, default=1.2)
    ap.add_argument("--tiles", nargs="+", default=["128,40"])
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--inner", type=int, default=20)
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--out", default="chiprun_out/grouped_ladder.jsonl")
    opts = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from mxnet_tpu.ops import grouped

    device = jax.devices()[0]
    if device.platform != opts.platform:
        raise SystemExit("grouped_ladder: needs a %r device, jax found %r"
                         % (opts.platform, device.platform))
    dtype = jnp.bfloat16

    def timed(fn, *args):
        out = jax.block_until_ready(fn(*args))
        times = []
        for _ in range(opts.reps):
            t0 = time.perf_counter()
            for _ in range(opts.inner):
                out = fn(*args)
            jax.block_until_ready(out)
            times.append((time.perf_counter() - t0) / opts.inner * 1e3)
        return statistics.median(times), out

    def worst(got, want, load):
        """(worst relative error over the load's rows - a weight
        cotangent has no others -, the largest magnitude past them)."""
        got, want = (np.asarray(v, np.float32) for v in (got, want))
        if got.ndim == 3:
            load = len(got)
        return (float(np.abs(got[:load] - want[:load]).max()
                      / (np.abs(want[:load]).max() + 1e-30)),
                float(np.abs(got[load:]).max()) if load < len(got) else 0.0)

    lines = []
    for cell in opts.cell:
        m, h, projections, even = CELLS[cell]
        rng = np.random.RandomState(0)
        load = min(int(opts.load * even), m)
        counts = rng.multinomial(load, rng.dirichlet(np.full(h, 8.0)))
        counts_d = jnp.asarray(counts, jnp.int32)
        for k, n in projections:
            rows = rng.randn(m, k).astype(np.float32)
            g = rng.randn(m, n).astype(np.float32)
            rows[load:] = g[load:] = 0.0
            rows, g = jnp.asarray(rows, dtype), jnp.asarray(g, dtype)
            w = jnp.asarray(rng.randn(h, k, n) * k ** -0.5, dtype)
            about = {"cell": cell, "rows": m, "load": load, "groups": h,
                     "k": k, "n": n, "max_over_mean":
                     float(counts.max() / counts.mean())}

            def three(product):
                def by_rows(g, w):
                    return jax.vjp(lambda r: product(r, w, counts_d),
                                   rows)[1](g)[0]

                def by_weights(r, g):
                    return jax.vjp(lambda w: product(r, w, counts_d),
                                   w)[1](g)[0]

                return {"forward": (jax.jit(
                    lambda r, w: product(r, w, counts_d)), rows, w),
                    "by_rows": (jax.jit(by_rows), g, w),
                    "by_weights": (jax.jit(by_weights), rows, g)}

            dense = jax.jit(lambda r, w: jnp.dot(
                r, w, preferred_element_type=jnp.float32).astype(r.dtype))
            line = dict(about, rung="dense product of the load's rows")
            line["forward_ms"], _ = timed(dense, rows[:load], w[0])
            lines.append(line)
            want = {}
            line = dict(about, rung="ragged")
            for name, (fn, *args) in three(lax.ragged_dot).items():
                line[name + "_ms"], want[name] = timed(fn, *args)
                line[name + "_tail"] = worst(want[name], want[name], load)[1]
            lines.append(line)
            for tiles in opts.tiles:
                tm, mib = map(int, tiles.split(","))
                grouped._ROW_TILE, grouped._BLOCK_BYTES = tm, mib << 20
                line = dict(about, rung="kernels@" + tiles)
                for name, (fn, *args) in three(grouped._product).items():
                    line[name + "_ms"], got = timed(fn, *args)
                    line[name + "_err"], line[name + "_tail"] = worst(
                        got, want[name], load)
                lines.append(line)
    os.makedirs(os.path.dirname(opts.out) or ".", exist_ok=True)
    with open(opts.out, "a") as f:
        for line in lines:
            line["device"] = device.device_kind
            print(json.dumps(line, sort_keys=True), flush=True)
            f.write(json.dumps(line, sort_keys=True) + "\n")


if __name__ == "__main__":
    main()
