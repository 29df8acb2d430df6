"""Gluon MNIST MLP (BASELINE config 0; reference:
example/gluon/mnist/mnist.py).

Runs on the real dataset when MX_DATA_DIR points at MNIST idx files,
otherwise on the synthetic stand-in so the script is runnable offline:

    python examples/train_mnist_gluon.py [--epochs 2] [--hybridize]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon


def get_data(batch_size):
    data_dir = os.environ.get("MX_DATA_DIR")
    from mxnet_tpu.gluon.data.vision import transforms as T
    to_tensor = T.ToTensor()
    if data_dir:
        from mxnet_tpu.gluon.data.vision import MNIST
        root = os.path.join(data_dir, "mnist")
        train = MNIST(root=root, train=True).transform_first(to_tensor)
        test = MNIST(root=root, train=False).transform_first(to_tensor)
    else:
        from mxnet_tpu.gluon.data.vision import SyntheticImageDataset
        train = SyntheticImageDataset(num_samples=2048, shape=(28, 28, 1),
                                      num_classes=10).transform_first(
                                          to_tensor)
        test = SyntheticImageDataset(num_samples=512, shape=(28, 28, 1),
                                     num_classes=10).transform_first(
                                         to_tensor)
    return (gluon.data.DataLoader(train, batch_size, shuffle=True),
            gluon.data.DataLoader(test, batch_size))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--hybridize", action="store_true")
    args = ap.parse_args()

    ctx = mx.tpu(0)
    train_loader, test_loader = get_data(args.batch_size)

    mx.random.seed(0)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(128, activation="relu"),
            gluon.nn.Dense(64, activation="relu"),
            gluon.nn.Dense(10))
    net.initialize(mx.init.Xavier(), ctx=ctx)
    if args.hybridize:
        net.hybridize()

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr, "momentum": 0.9})
    metric = mx.metric.Accuracy()
    for epoch in range(args.epochs):
        metric.reset()
        for x, y in train_loader:
            x, y = x.as_in_context(ctx), y.as_in_context(ctx)
            with autograd.record():
                out = net(x)
                loss = loss_fn(out, y)
            loss.backward()
            trainer.step(x.shape[0])
            metric.update([y], [out])
        name, acc = metric.get()
        print("epoch %d train %s=%.4f" % (epoch, name, acc))
    metric.reset()
    for x, y in test_loader:
        metric.update([y.as_in_context(ctx)],
                      [net(x.as_in_context(ctx))])
    print("final test %s=%.4f" % metric.get())


if __name__ == "__main__":
    main()
