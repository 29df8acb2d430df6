#!/usr/bin/env python3
"""The rotary operator alone, on the chip: `ops/rotary.py`'s kernel on the
packed layout against the operator's composition (`ops/nn.py`), shape by
shape (PERF.md section 6, PR 40 holds the ladder it gave).

    chiprun --chips 1 -- python3 tools/rotary_ladder.py \\
        --shape laguna-q64 laguna-q48-yarn laguna-k8 laguna-k8-yarn \\
        glm-q20 glm-k1 --blocks 256,1024 512,1024 256,2048

For each `--shape` (batch, positions, heads, lanes a head and the rotary
keywords of one call of a cell's step) milliseconds a call (median of
`--reps` timings of `--inner` calls) of the forward and of the backward
(the cotangent of the data from a given cotangent of the result; both
linear, so each is timed on the same tensor): by the composition (rung
``composition``), by the kernel at each `--blocks` entry (rows, lanes a
block; rung ``kernel@rows,lanes``) where `rotary_rule` holds for the
shape - a shape outside it (GLM's one 64-lane rotary key) gets the
composition's row alone -, and - the yardstick - by a plain copy of the
same tensor (read once + write once), which no rotary can beat.  Every
kernel result is compared with the composition's (`*_err`, the largest
difference over the largest magnitude).  A measurement needs the TPU;
`--platform cpu` rehearses in interpret mode at `--shape tiny`.
"""
import argparse
import json
import os
import statistics
import sys
import time


def shapes():
    """name: (batch, positions, heads, lanes a head, the operator's
    keywords) - Laguna-XS.2's two kinds of layer (sliding: 64 query heads,
    every lane turns; full: 48, the first 64 lanes with YaRN), its 8
    key/value heads of each kind, GLM-4.7-Flash's query ([192 nope | 64
    rope] a head) and its one rotary key."""
    from mxnet_tpu.gluon.model_zoo import laguna
    sliding, full = (laguna.rotary_keywords(laguna.ROPE_XS_2[kind], 128)
                     for kind in ("sliding_attention", "full_attention"))
    glm = dict(rotary_dim=64, theta=1e6)
    return {
        "laguna-q64": (1, 8192, 64, 128, sliding),
        "laguna-q48-yarn": (1, 8192, 48, 128, full),
        "laguna-k8": (1, 8192, 8, 128, sliding),
        "laguna-k8-yarn": (1, 8192, 8, 128, full),
        "glm-q20": (2, 4096, 20, 256, glm),
        "glm-k1": (2, 4096, 1, 64, glm),
        "tiny": (1, 512, 2, 128, full),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", nargs="+", default=["laguna-q64"])
    ap.add_argument("--blocks", nargs="+", default=["256,1024"])
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--inner", type=int, default=20)
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--out", default="chiprun_out/rotary_ladder.jsonl")
    opts = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.ops import attention, nn as ops_nn, rotary

    device = jax.devices()[0]
    if device.platform != opts.platform:
        raise SystemExit("rotary_ladder: needs a %r device, jax found %r"
                         % (opts.platform, device.platform))
    dtype = jnp.dtype(opts.dtype)

    def timed(fn, x):
        """(ms a call, its result).  Calls from the host, one program
        each: a call of under ~0.2 ms (8 heads) reads the dispatch, not
        the kernel.  (`--inner` calls chained inside one program misread
        more: the loop's carry adds a copy to every kernel call, and XLA
        folds the plain copy's chain - it read 2.1 TB/s; PR 40.)"""
        fn = jax.jit(fn)
        out = jax.block_until_ready(fn(x))
        times = []
        for _ in range(opts.reps):
            t0 = time.perf_counter()
            for _ in range(opts.inner):
                out = fn(x)
            jax.block_until_ready(out)
            times.append((time.perf_counter() - t0) / opts.inner * 1e3)
        return statistics.median(times), np.asarray(out, np.float32)

    def composition(x, **keywords):
        """The operator as it runs off the rule, whatever the device."""
        on_tpu, attention._on_tpu = attention._on_tpu, lambda: False
        try:
            return ops_nn._rotary_embedding(x, **keywords)
        finally:
            attention._on_tpu = on_tpu

    def both(turn, x):
        """`turn` and its backward - the cotangent of the data from the
        result's -, each a function of the tensor it turns."""
        return {"forward": turn,
                "backward": lambda g: jax.vjp(turn, x)[1](g)[0]}

    lines = []
    known = shapes()
    for name in opts.shape:
        b, t, heads, d, keywords = known[name]
        full = dict(dict(theta=10000.0, first=False, yarn=None,
                         attention_factor=1.0), **keywords)
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(b, t, heads * d), dtype)
        g = jnp.asarray(rng.randn(b, t, heads * d), dtype)
        about = {"shape": name, "batch": b, "positions": t, "heads": heads,
                 "lanes": d, "rotary_dim": full["rotary_dim"],
                 "first": full["first"], "dtype": dtype.name,
                 "mb_read_and_written": 2 * x.size * dtype.itemsize / 1e6}
        line = dict(about, rung="copy")
        line["forward_ms"], _ = timed(lambda x: -x, x)
        lines.append(line)
        want = {}
        line = dict(about, rung="composition")
        for what, fn in both(lambda x: composition(
                x, num_heads=heads, **keywords), x).items():
            line[what + "_ms"], want[what] = timed(fn, g)
        lines.append(line)
        for block in opts.blocks:
            rows, lanes = map(int, block.split(","))
            rotary._ROWS, rotary._BLOCK_LANES = rows, lanes
            if not rotary.rotary_rule(t, d, full["rotary_dim"], dtype):
                continue
            line = dict(about, rung="kernel@" + block)
            for what, fn in both(lambda x: rotary.turn(
                    x, heads, **full), x).items():
                line[what + "_ms"], got = timed(fn, g)
                line[what + "_err"] = float(
                    np.abs(got - want[what]).max()
                    / np.abs(want[what]).max())
            lines.append(line)
    os.makedirs(os.path.dirname(opts.out) or ".", exist_ok=True)
    with open(opts.out, "a") as f:
        for line in lines:
            line["device"] = device.device_kind
            print(json.dumps(line, sort_keys=True), flush=True)
            f.write(json.dumps(line, sort_keys=True) + "\n")
    return lines


if __name__ == "__main__":
    main()
