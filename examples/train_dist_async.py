"""Async parameter-server training (reference: the dist_async mode of
example/image-classification/common/fit.py + tools/launch.py -s).

Each worker streams its own batches; the PS applies every push the
moment it arrives (server-side SGD), so fast workers never wait for slow
ones — the stale-tolerant tradeoff sync collectives cannot express.

Run (1 server + 2 workers on this host):

    python tools/launch.py -n 2 -s 1 --launcher local -- \\
        python examples/train_dist_async.py [--steps 50]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import autograd, gluon, kvstore, nd, optimizer  # noqa: E402


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--lr", type=float, default=0.05)
    args = p.parse_args()

    kv = kvstore.create("dist_async")
    rank, nworkers = kv.rank, kv.num_workers
    mx.random.seed(rank)                      # workers see different data

    # tiny regression net; weights live on the PS
    net = gluon.nn.Dense(1, in_units=8)
    net.initialize(mx.init.Xavier())
    params = list(net.collect_params().values())
    for i, param in enumerate(params):
        kv.init(i, param.data())
    kv.set_optimizer(optimizer.SGD(learning_rate=args.lr))
    for i, param in enumerate(params):        # start from server state
        kv.pull(i, out=param.data())

    rng = np.random.RandomState(100 + rank)
    w_true = np.arange(8, dtype=np.float32).reshape(8, 1) / 8.0
    for step in range(args.steps):
        X = nd.array(rng.randn(args.batch_size, 8).astype(np.float32))
        y = nd.array(X.asnumpy() @ w_true)
        with autograd.record():
            loss = ((net(X) - y) ** 2).mean()
        loss.backward()
        for i, param in enumerate(params):
            kv.push(i, param.grad())          # applied server-side NOW
            kv.pull(i, out=param.data())      # whatever is current
        if step % 10 == 0:
            print("rank %d step %d loss %.4f" % (rank, step,
                                                 float(loss.asnumpy())))
    kv._barrier()
    final = float(loss.asnumpy())
    print("rank %d FINAL loss %.4f (workers=%d)" % (rank, final, nworkers))


if __name__ == "__main__":
    main()
