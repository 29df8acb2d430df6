"""Long-context LM training with ring-attention sequence parallelism
(SURVEY §5.7: long-context is first-class; reference has no equivalent —
this is the TPU-native design the rebuild adds on top of MXNet's surface).

A small causal transformer LM trains with its sequence axis SHARDED over
an 'sp' mesh axis: every attention layer runs mxnet_tpu.parallel.
ring_attention (K/V blocks rotate around the ring via ppermute, flash
kernel per hop), so activation memory per chip scales with L/sp while
the math stays EXACTLY the single-device attention (the parity suite
pins this).  dp × sp composes on one mesh.

    python examples/train_long_context.py [--seq-len 512] [--sp 2]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4, help="global batch")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--sp", type=int, default=0,
                    help="sequence-parallel degree (0 = all devices)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from functools import partial
    from jax.sharding import NamedSharding, PartitionSpec as P
    try:
        from jax import shard_map
    except ImportError:
        from jax.experimental.shard_map import shard_map
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import make_mesh, ring_attention

    devs = jax.devices()
    sp = args.sp or len(devs)
    assert args.seq_len % sp == 0, "seq-len must divide by sp"
    mesh = make_mesh(axes=("dp", "sp"), shape=(-1, sp), devices=devs)
    print("mesh:", dict(mesh.shape), "| L=%d (L/sp=%d per chip)"
          % (args.seq_len, args.seq_len // sp))

    D, H, V, L = args.d_model, args.heads, args.vocab, args.seq_len
    Dh = D // H
    rng = np.random.RandomState(0)

    def init_params():
        def g(*shape, s=0.02):
            return jnp.asarray(rng.randn(*shape) * s, jnp.float32)
        layers = []
        for _ in range(args.layers):
            layers.append({
                "wqkv": g(D, 3 * D), "wo": g(D, D),
                "w1": g(D, 4 * D), "w2": g(4 * D, D),
                "ln1": jnp.ones(D), "ln2": jnp.ones(D),
            })
        return {"emb": g(V, D), "layers": layers, "lnf": jnp.ones(D)}

    # ring attention over the sp axis: each shard holds L/sp of the
    # sequence; K/V rotate sp hops (causal masking handled per hop).
    # Batch is ALSO sharded (dp) — the ring's scan carry legitimately
    # varies over dp, so relax shard_map's varying-axis check where the
    # jax version enforces it.
    try:
        attn = shard_map(
            partial(ring_attention, axis_name="sp", causal=True),
            mesh=mesh,
            in_specs=(P("dp", "sp"), P("dp", "sp"), P("dp", "sp")),
            out_specs=P("dp", "sp"), check_vma=False)
    except TypeError:   # older jax: flag named check_rep
        attn = shard_map(
            partial(ring_attention, axis_name="sp", causal=True),
            mesh=mesh,
            in_specs=(P("dp", "sp"), P("dp", "sp"), P("dp", "sp")),
            out_specs=P("dp", "sp"), check_rep=False)

    def ln(x, gamma):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return gamma * (x - mu) / jnp.sqrt(var + 1e-5)

    def forward(params, tokens):
        B = tokens.shape[0]
        x = params["emb"][tokens]                       # (B, L, D)
        for lyr in params["layers"]:
            h = ln(x, lyr["ln1"])
            qkv = (h @ lyr["wqkv"]).reshape(B, L, 3, H, Dh)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            o = attn(q, k, v).reshape(B, L, D)
            x = x + o @ lyr["wo"]
            h = ln(x, lyr["ln2"])
            x = x + jax.nn.gelu(h @ lyr["w1"]) @ lyr["w2"]
        return ln(x, params["lnf"]) @ params["emb"].T   # tied head

    def loss_fn(params, tokens, targets):
        logits = forward(params, tokens)
        logp = jax.nn.log_softmax(logits, axis=-1)
        take = jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return -take.mean()

    @jax.jit
    def step(params, opt_m, opt_v, t, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets)
        # Adam, functional (the parallel path stays one jitted step)
        b1, b2, eps = 0.9, 0.999, 1e-8
        opt_m = jax.tree_util.tree_map(
            lambda m, g: b1 * m + (1 - b1) * g, opt_m, grads)
        opt_v = jax.tree_util.tree_map(
            lambda v, g: b2 * v + (1 - b2) * g * g, opt_v, grads)
        tt = t + 1
        params = jax.tree_util.tree_map(
            lambda p, m, v: p - args.lr * (m / (1 - b1 ** tt))
            / (jnp.sqrt(v / (1 - b2 ** tt)) + eps),
            params, opt_m, opt_v)
        return params, opt_m, opt_v, tt, loss

    # structured synthetic corpus: next token is a deterministic map of
    # the current one, so the LM has signal to model
    perm = rng.permutation(V)

    def batch():
        starts = rng.randint(0, V, args.batch)
        seq = np.zeros((args.batch, L + 1), np.int32)
        seq[:, 0] = starts
        for t in range(1, L + 1):
            seq[:, t] = perm[seq[:, t - 1]]
        return seq[:, :-1], seq[:, 1:]

    params = init_params()
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    opt_m, opt_v, t = zeros, jax.tree_util.tree_map(jnp.zeros_like,
                                                    params), 0
    shard = NamedSharding(mesh, P("dp", "sp"))
    losses = []
    for i in range(args.steps):
        x_np, y_np = batch()
        x = jax.device_put(jnp.asarray(x_np), shard)
        y = jax.device_put(jnp.asarray(y_np), shard)
        params, opt_m, opt_v, t, loss = step(params, opt_m, opt_v, t, x, y)
        losses.append(float(loss))
        if i % 10 == 0 or i == args.steps - 1:
            print("step %3d  loss %.4f" % (i, losses[-1]))
    assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])
    print("final loss %.4f (from %.4f) over L=%d with sp=%d"
          % (losses[-1], losses[0], L, sp))


if __name__ == "__main__":
    main()
