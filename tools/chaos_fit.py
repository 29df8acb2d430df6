#!/usr/bin/env python
"""chaos_fit.py — a tiny deterministic Module.fit job for supervisor chaos
runs (tests/test_supervisor.py, tools/chaos_smoke.sh).

Each rank trains the same seeded MLP on the same synthetic data with a
momentum optimizer and per-epoch checkpointing into a per-rank directory,
then dumps its final parameters to ``--out``.  Because everything is
seeded and the optimizer slot state rides the checkpoint sidecar, a rank
that is crashed (``--fault 'worker.step:crash:after=N'``), restarted by
``launch.py --restart on-failure`` and auto-resumed must land on exactly
the parameters of an uninterrupted run — which is what the callers
assert.
"""
import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("MX_FORCE_CPU", "1")

import numpy as np                                          # noqa: E402

import mxnet_tpu as mx                                      # noqa: E402
from mxnet_tpu import io as mio                             # noqa: E402
from mxnet_tpu.module import Module                         # noqa: E402


def _mlp():
    from mxnet_tpu import symbol as sym
    data = sym.Variable("data")
    h = sym.FullyConnected(data, sym.Variable("fc1_weight"),
                           sym.Variable("fc1_bias"), num_hidden=16)
    h = sym.Activation(h, act_type="relu")
    out = sym.FullyConnected(h, sym.Variable("fc2_weight"),
                             sym.Variable("fc2_bias"), num_hidden=3)
    return sym.SoftmaxOutput(out, sym.Variable("softmax_label"),
                             normalization="batch", name="softmax")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ckpt-dir", required=True,
                    help="checkpoint root; each rank uses <dir>/rank<r>")
    ap.add_argument("--out", default=None,
                    help="write final params to <out>.rank<r>.npz")
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batches", type=int, default=4)
    ap.add_argument("--batch-size", type=int, default=24)
    args = ap.parse_args()

    rank = os.environ.get("MX_PROCESS_ID", "0")
    rng = np.random.RandomState(0)
    n = args.batches * args.batch_size
    X = rng.randn(n, 8).astype(np.float32)
    Y = X[:, :3].argmax(axis=1).astype(np.float32)

    mx.random.seed(42)               # identical init across (re)starts
    mod = Module(_mlp(), context=mx.cpu())
    mod.fit(mio.NDArrayIter(X, Y, batch_size=args.batch_size),
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            num_epoch=args.epochs,
            checkpoint_dir=os.path.join(args.ckpt_dir, "rank%s" % rank))

    if args.out:
        arg, _aux = mod.get_params()
        np.savez("%s.rank%s.npz" % (args.out, rank),
                 **{k: v.asnumpy() for k, v in arg.items()})
    # warm-respawn receipts: the supervisor's chaos smoke greps these —
    # a rank respawned under launch.py --compile-cache must report hits
    # of jax's persistent cache
    from mxnet_tpu import compile_cache, programs
    cs = compile_cache.stats()
    summary = programs.program_summary()
    print("CHAOS_FIT_DONE rank %s cache_hits=%d cache_misses=%d "
          "compile_seconds=%.3f"
          % (rank, cs["xla_hits"], cs["xla_misses"],
             summary["compile_seconds_total"]), flush=True)


if __name__ == "__main__":
    main()
