#!/usr/bin/env python3
"""One held-expert layer after its router, forward and forward + backward,
on the chip: the microbenchmark behind `parallel/moe.py:held_expert_ffn`
(PERF.md section 6, PR 34 holds the ladder it gave).

    chiprun --chips 1 -- python3 tools/moe_ladder.py \\
        --cell nemotron glm --load 1 1.9 2.1 worst

`--cell` names a benchmark cell's expert layer (its tokens, width, top-k,
held and published experts, expert kind); `--load` is the held experts'
load as a multiple of an even router's share (``worst``: every token's
min(k, H) held choices land here).  The choices are drawn on the host: k
distinct experts a token by random scores, the held experts' lifted until
the load is met.  Rungs, each at every load:

- ``exact``: one path over the exact no-drop buffer, what the layer was
  before it had two sizes;
- ``sized``: the layer as it is, the short buffer where the load fits it.

Each line: the rung, the load and which buffer ran, milliseconds (median
of `--reps` timings of `--inner` calls each) forward, forward + backward,
and forward + backward under ``jax.checkpoint`` with `Block.recompute`'s
policy (`--recompute 1`), the compiler's temporary bytes of each program,
and the worst relative error of y and of the four gradients against
``exact``.  A measurement needs the TPU; `--platform cpu` rehearses at a
small shape (`--cell tiny`), and `--describe 1` only compiles for a
described v5e, here, without the chip: bytes and no times.
"""
import argparse
import json
import os
import statistics
import sys
import time

# tokens, width, expert width, top-k, held, published experts, kind
CELLS = {
    "nemotron": (8192, 1024, 2688, 22, 8, 512, "relu2"),
    "glm": (8192, 2048, 1536, 4, 8, 64, "swiglu"),
    "tiny": (2048, 128, 64, 6, 2, 32, "relu2"),
}


def choices(rng, n, k, held, experts, load):
    """(N, k) int32: k distinct experts a token, the first `held` taking
    `load` times an even router's share (None: all they can)."""
    import numpy as np
    scores = rng.rand(n, experts)

    def drawn(lift):
        lifted = scores.copy()
        lifted[:, :held] += lift
        idx = np.argpartition(-lifted, k - 1, axis=1)[:, :k]
        return idx, int((idx < held).sum())

    if load is None:
        return drawn(2.0)[0].astype(np.int32)
    want = load * n * k * held / experts
    lo, hi = -1.0, 1.0
    for _ in range(40):
        mid = (lo + hi) / 2
        if drawn(mid)[1] < want:
            lo = mid
        else:
            hi = mid
    return drawn(hi)[0].astype(np.int32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", nargs="+", default=["nemotron", "glm"],
                    choices=sorted(CELLS))
    ap.add_argument("--load", nargs="+", default=["1", "1.9", "2.1", "worst"])
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--tokens-dtype", default=None,
                    help="of what the experts read, where it is not the "
                         "weights' (the Nemotron cell's latent is float32)")
    ap.add_argument("--recompute", type=int, default=1)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--inner", type=int, default=10)
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--describe", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/moe_ladder.jsonl")
    opts = ap.parse_args()

    if opts.describe:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        opts.platform = "cpu"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.base import RECOMPUTE_KEEP
    from mxnet_tpu.parallel import moe

    device = jax.devices()[0]
    if device.platform != opts.platform:
        raise SystemExit("moe_ladder: needs a %r device, jax found %r"
                         % (opts.platform, device.platform))
    place = None
    if opts.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        place = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    dtype = jnp.dtype(opts.dtype)
    tokens_dtype = jnp.dtype(opts.tokens_dtype or opts.dtype)
    keep = jax.checkpoint_policies.save_only_these_names(RECOMPUTE_KEEP)
    over_even = moe._SHORT_OVER_EVEN
    rungs = {"exact": 0, "sized": over_even}

    def worst(got, want):
        return max(float(jnp.abs(g.astype(jnp.float32)
                                 - w.astype(jnp.float32)).max()
                         / (jnp.abs(w.astype(jnp.float32)).max() + 1e-30))
                   for g, w in zip(got, want))

    lines = []
    for cell in opts.cell:
        n, d, f, k, h, experts, kind = CELLS[cell]
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(n, d), tokens_dtype)
        w_in = jnp.asarray(rng.randn(h, d, f * (2 if kind == "swiglu" else 1))
                           * d ** -0.5, dtype)
        w_down = jnp.asarray(rng.randn(h, f, d) * f ** -0.5, dtype)
        weights = jnp.asarray(rng.rand(n, k) / k, jnp.float32)
        out_weight = jnp.asarray(rng.randn(n, d), jnp.float32)

        def programs():
            # new functions a rung: jit remembers a function's trace, and
            # what a rung changes is not among its arguments
            def layer(x, weights, w_in, w_down, idx):
                return moe.held_expert_ffn(x, idx, weights, w_in, w_down,
                                           tuple(range(h)), experts, kind)

            def loss(fn):
                return lambda *a: (fn(*a)[0].astype(jnp.float32)
                                   * out_weight).sum()

            made = {"forward": jax.jit(layer),
                    "forward_backward": jax.jit(jax.value_and_grad(
                        loss(layer), argnums=(0, 1, 2, 3)))}
            if opts.recompute:
                made["recomputed"] = jax.jit(jax.value_and_grad(
                    loss(jax.checkpoint(layer, policy=keep)),
                    argnums=(0, 1, 2, 3)))
            return made

        drawn = {load: jnp.asarray(choices(
            rng, n, k, h, experts, None if load == "worst" else float(load)))
            for load in opts.load}
        want = {}
        for rung, over in rungs.items():
            moe._SHORT_OVER_EVEN = over
            about = {"cell": cell, "rung": rung,
                     "short_rows": moe.short_rows(n, k, h, experts),
                     "exact_rows": n * min(k, h)}
            args = (x, weights, w_in, w_down, drawn[opts.load[0]])
            abstract = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=place)
                        for a in args] if place else args
            compiled = {name: program.lower(*abstract).compile()
                        for name, program in programs().items()}
            for name, program in compiled.items():
                about[name + "_temp_bytes"] = \
                    program.memory_analysis().temp_size_in_bytes
            if opts.describe:
                lines.append(about)
                continue
            for load in opts.load:
                args = (x, weights, w_in, w_down, drawn[load])
                line = dict(about, load=load)
                for name, program in compiled.items():
                    out = jax.block_until_ready(program(*args))
                    times = []
                    for _ in range(opts.reps):
                        t0 = time.perf_counter()
                        for _ in range(opts.inner):
                            out = program(*args)
                        jax.block_until_ready(out)
                        times.append((time.perf_counter() - t0)
                                     / opts.inner * 1e3)
                    line[name + "_ms"] = statistics.median(times)
                    if name == "forward":
                        got = [out[0]]
                        line["here"] = float(out[1].sum())
                        line["exact_buffer_ran"] = float(out[3])
                    elif name == "forward_backward":
                        got += list(out[1])
                want.setdefault(load, got)
                line["err_y"] = worst(got[:1], want[load][:1])
                line["err_grads"] = worst(got[1:], want[load][1:])
                lines.append(line)
    moe._SHORT_OVER_EVEN = over_even
    os.makedirs(os.path.dirname(opts.out) or ".", exist_ok=True)
    with open(opts.out, "a") as f:
        for line in lines:
            line.update(dtype=opts.dtype, tokens_dtype=str(tokens_dtype),
                        device="described v5e"
                        if opts.describe else device.device_kind)
            print(json.dumps(line, sort_keys=True), flush=True)
            f.write(json.dumps(line, sort_keys=True) + "\n")


if __name__ == "__main__":
    main()
