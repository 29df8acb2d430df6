#!/usr/bin/env python3
"""One held-expert layer, forward and forward + backward, on the chip: the
microbenchmark behind `parallel/moe.py` (PERF.md section 6, PR 34 and PR
36 hold the ladders it gave).

    chiprun --chips 1 -- python3 tools/moe_ladder.py \\
        --cell nemotron glm --load 1 1.9 2.1 worst
    chiprun --chips 1 -- python3 tools/moe_ladder.py --route 1 \\
        --cell nemotron laguna glm --load 1

`--cell` names a benchmark cell's expert layer (its tokens, width, top-k,
held and published experts, expert kind); `--load` is the held experts'
load as a multiple of an even router's share (``worst``: every token's
min(k, H) held choices land here).  The choices are drawn on the host: k
distinct experts a token by random scores, the held experts' lifted until
the load is met.  Rungs, each at every load:

- ``exact``: one path over the exact no-drop buffer, what the layer was
  before it had two sizes;
- ``sized``: the layer as it is, the short buffer where the load fits it;
- ``ragged`` (`--products 1`): the layer with ``lax.ragged_dot`` in the
  place of the grouped kernels (ops/grouped.py), what it was before PR
  39; and ``sized@<rows>,<MiB>`` for every `--tiles` entry: the kernels at
  another row tile and another bound on their blocks' fast memory.

`--route 1` times the layer WITH its router (`token_choice_moe`: the
router's product over `ROUTER_WIDTH` lanes, the top-k, the chosen scores;
the held experts' selection correction lifted until the load is met).
Its rungs:

- ``gathered``: what the layer was before PR 36, kept here only - the
  chosen scores a ``take_along_axis`` of N * k scalars that a recomputed
  block runs again, an assignment's place among the held experts a lookup
  of N * k, their weights' cotangent a gather by `position` of N * k;
- ``as it is``: compares and sums over the expert axis, the chosen scores
  kept across a recomputation, the cotangent put by the load's rows.

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
import functools
import json
import os
import statistics
import sys
import time

# tokens, width, expert width, top-k, held, published experts, kind
CELLS = {
    "nemotron": (8192, 1024, 2688, 22, 8, 512, "relu2"),
    "glm": (8192, 2048, 1536, 4, 8, 64, "swiglu"),
    "laguna": (8192, 2048, 512, 8, 32, 256, "swiglu"),
    "tiny": (2048, 128, 64, 6, 2, 32, "relu2"),
}
# what the router reads, where it is not what the experts read
ROUTER_WIDTH = {"nemotron": 4096}


def choices(scores, k, held, load):
    """(N, k) int32 out of `scores` (N, E): k distinct experts a token,
    the first `held` taking `load` times an even router's share (None:
    all they can); and the lift of their scores that does it."""
    import numpy as np
    n, experts = scores.shape

    def drawn(lift):
        lifted = scores.copy()
        lifted[:, :held] += lift
        idx = np.argpartition(-lifted, k - 1, axis=1)[:, :k]
        return idx, int((idx < held).sum())

    if load is None:
        return drawn(2.0)[0].astype(np.int32), 2.0
    want = load * n * k * held / experts
    lo, hi = -1.0, 1.0
    for _ in range(40):
        mid = (lo + hi) / 2
        if drawn(mid)[1] < want:
            lo = mid
        else:
            hi = mid
    return drawn(hi)[0].astype(np.int32), hi


def gathered(moe, n_experts):
    """{dotted name in `moe`: the form it had before PR 36}: the three
    scalar gathers of N * k."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    def topk_route(x, router_w, bias, top_k, scale=1.0, norm_topk_prob=True):
        with jax.default_matmul_precision("highest"):
            logits = jnp.dot(x.astype(jnp.float32),
                             router_w.astype(jnp.float32).T)
        scores = jax.nn.sigmoid(logits)
        _, idx = lax.top_k(
            scores + lax.stop_gradient(bias.astype(jnp.float32)), top_k)
        idx = moe.recompute_keep(idx)
        chosen = jnp.take_along_axis(scores, idx, axis=-1)
        if norm_topk_prob:
            chosen = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
        return idx.astype(jnp.int32), chosen * scale

    def slot_of(experts, held):
        local_of = np.full((n_experts,), len(held), np.int32)
        local_of[np.asarray(held)] = np.arange(len(held))
        return jnp.asarray(local_of)[experts]

    def choices_of(self, rows):
        ok = (self.position < self.here)[:, None]
        return jnp.where(ok, rows[jnp.where(ok[:, 0], self.position, 0)],
                         0.0)

    return {"topk_route": topk_route, "_slot_of": slot_of,
            "_Short.choices_of": choices_of}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", nargs="+", default=["nemotron", "glm"],
                    choices=sorted(CELLS))
    ap.add_argument("--load", nargs="+", default=["1", "1.9", "2.1", "worst"])
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--tokens-dtype", default=None,
                    help="of what the experts read, where it is not the "
                         "weights' (default: the weights' - bfloat16 in "
                         "all three cells; the Nemotron cell's latent was "
                         "float32 until PR 38)")
    ap.add_argument("--route", type=int, default=0,
                    help="1: the layer with its router, rungs `gathered` "
                         "and `as it is`")
    ap.add_argument("--products", type=int, default=0,
                    help="1: rungs `ragged`, `sized` and one a `--tiles` "
                         "entry in the place of `exact` and `sized`")
    ap.add_argument("--tiles", nargs="*", default=[],
                    help="rows,MiB: the grouped kernels' row tile and the "
                         "fast memory their blocks may take")
    ap.add_argument("--recompute", type=int, default=1)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--inner", type=int, default=10)
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--describe", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/moe_ladder.jsonl")
    opts = ap.parse_args(argv)

    if opts.describe:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        opts.platform = "cpu"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
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
    def set_on(changes):
        """Set {dotted name in `moe`: value}; returns what stood there."""
        was = {}
        for name, value in changes.items():
            *path, attr = name.split(".")
            owner = functools.reduce(getattr, path, moe)
            was[name] = getattr(owner, attr)
            setattr(owner, attr, value)
        return was

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
        out_weight = jnp.asarray(rng.randn(n, d), jnp.float32)
        loads = {load: None if load == "worst" else float(load)
                 for load in opts.load}
        if opts.route:
            # a load is a correction: the held experts' scores lifted
            rungs = {"gathered": gathered(moe, experts), "as it is": {}}
            width = ROUTER_WIDTH.get(cell, d)
            tokens = jnp.asarray(rng.randn(n, width), dtype)
            router_w = jnp.asarray(rng.randn(experts, width) * width ** -0.5,
                                   jnp.float32)
            scores = 1 / (1 + np.exp(-np.asarray(tokens, np.float32)
                                     @ np.asarray(router_w).T))
            fixed = (x, tokens, router_w, w_in, w_down)
            drawn = {load: jnp.zeros((experts,), jnp.float32).at[:h].set(
                choices(scores, k, h, share)[1])
                for load, share in loads.items()}

            def layer(x, tokens, router_w, w_in, w_down, correction):
                return moe.token_choice_moe(
                    tokens, router_w, correction, w_in, w_down,
                    held=tuple(range(h)), top_k=k, activation=kind,
                    expert_input=x)
        else:
            # a load is the choices themselves, drawn on the host
            rungs = {"exact": {"_SHORT_OVER_EVEN": 0}, "sized": {}}
            if opts.products:
                rungs = {"ragged": {"grouped.grouped_product":
                                    lax.ragged_dot}, "sized": {}}
                for tiles in opts.tiles:
                    rows, mib = map(int, tiles.split(","))
                    rungs["sized@" + tiles] = {
                        "grouped._ROW_TILE": rows,
                        "grouped._BLOCK_BYTES": mib << 20}
            fixed = (x, jnp.asarray(rng.rand(n, k) / k, jnp.float32), w_in,
                     w_down)
            drawn = {load: jnp.asarray(choices(
                rng.rand(n, experts), k, h, share)[0])
                for load, share in loads.items()}

            def layer(x, weights, w_in, w_down, idx):
                return moe.held_expert_ffn(x, idx, weights, w_in, w_down,
                                           tuple(range(h)), experts, kind)

        def programs():
            # new functions a rung: jit remembers a function's trace, and
            # what a rung changes is not among its arguments
            def fresh(*a):
                return layer(*a)

            def loss(fn):
                return lambda *a: (fn(*a)[0].astype(jnp.float32)
                                   * out_weight).sum()

            by = tuple(range(len(fixed)))
            made = {"forward": jax.jit(fresh),
                    "forward_backward": jax.jit(jax.value_and_grad(
                        loss(fresh), argnums=by))}
            if opts.recompute:
                made["recomputed"] = jax.jit(jax.value_and_grad(
                    loss(jax.checkpoint(fresh, policy=keep)), argnums=by))
            return made

        want = {}
        for rung, changes in rungs.items():
            was = set_on(changes)
            about = {"cell": cell, "rung": rung,
                     "short_rows": moe.short_rows(n, k, h, experts),
                     "exact_rows": n * min(k, h)}
            args = fixed + (drawn[opts.load[0]],)
            abstract = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=place)
                        for a in args] if place else args
            compiled = {name: program.lower(*abstract).compile()
                        for name, program in programs().items()}
            set_on(was)
            for name, program in compiled.items():
                about[name + "_temp_bytes"] = \
                    program.memory_analysis().temp_size_in_bytes
            if opts.describe:
                lines.append(about)
                continue
            for load in opts.load:
                args = fixed + (drawn[load],)
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
    os.makedirs(os.path.dirname(opts.out) or ".", exist_ok=True)
    with open(opts.out, "a") as f:
        for line in lines:
            line.update(dtype=opts.dtype, tokens_dtype=str(tokens_dtype),
                        route=opts.route,
                        device="described v5e"
                        if opts.describe else device.device_kind)
            print(json.dumps(line, sort_keys=True), flush=True)
            f.write(json.dumps(line, sort_keys=True) + "\n")


if __name__ == "__main__":
    main()
