"""Classic symbolic MNIST (reference:
example/image-classification/train_mnist.py).

The original v1.x workflow: compose a symbol with auto-created
parameter variables, wrap it in mx.mod.Module, and Module.fit drives
training with an NDArrayIter — no Gluon anywhere.  --network lenet
swaps the MLP for the conv net, exercising Convolution/Pooling through
the symbolic path.

    python examples/train_mnist_symbolic.py [--network mlp|lenet]
"""
import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402


def mlp_symbol():
    data = mx.sym.Variable("data")
    net = mx.sym.Flatten(data=data)
    net = mx.sym.FullyConnected(data=net, num_hidden=128, name="fc1")
    net = mx.sym.Activation(data=net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(data=net, num_hidden=64, name="fc2")
    net = mx.sym.Activation(data=net, act_type="relu", name="relu2")
    net = mx.sym.FullyConnected(data=net, num_hidden=10, name="fc3")
    return mx.sym.SoftmaxOutput(data=net, name="softmax")


def lenet_symbol():
    data = mx.sym.Variable("data")
    c1 = mx.sym.Convolution(data=data, kernel=(5, 5), num_filter=20,
                            name="conv1")
    a1 = mx.sym.Activation(data=c1, act_type="tanh")
    p1 = mx.sym.Pooling(data=a1, pool_type="max", kernel=(2, 2),
                        stride=(2, 2))
    c2 = mx.sym.Convolution(data=p1, kernel=(5, 5), num_filter=50,
                            name="conv2")
    a2 = mx.sym.Activation(data=c2, act_type="tanh")
    p2 = mx.sym.Pooling(data=a2, pool_type="max", kernel=(2, 2),
                        stride=(2, 2))
    f = mx.sym.Flatten(data=p2)
    fc1 = mx.sym.FullyConnected(data=f, num_hidden=500, name="fc1")
    a3 = mx.sym.Activation(data=fc1, act_type="tanh")
    fc2 = mx.sym.FullyConnected(data=a3, num_hidden=10, name="fc2")
    return mx.sym.SoftmaxOutput(data=fc2, name="softmax")


def get_iters(batch_size, flat):
    data_dir = os.environ.get("MX_DATA_DIR")
    if data_dir and os.path.isdir(os.path.join(data_dir, "mnist")):
        root = os.path.join(data_dir, "mnist")
        train = mx.io.MNISTIter(
            image=os.path.join(root, "train-images-idx3-ubyte"),
            label=os.path.join(root, "train-labels-idx1-ubyte"),
            batch_size=batch_size, flat=flat, shuffle=True)
        val = mx.io.MNISTIter(
            image=os.path.join(root, "t10k-images-idx3-ubyte"),
            label=os.path.join(root, "t10k-labels-idx1-ubyte"),
            batch_size=batch_size, flat=flat)
        return train, val
    # synthetic stand-in: class-dependent blobs so accuracy is learnable
    rng = np.random.RandomState(0)
    n = 2048
    y = rng.randint(0, 10, n)
    x = rng.rand(n, 1, 28, 28).astype(np.float32) * 0.1
    for i in range(n):
        cls = y[i]
        x[i, 0, 2 + (cls % 5) * 5:5 + (cls % 5) * 5,
          2 + (cls // 5) * 12:8 + (cls // 5) * 12] += 0.9
    if flat:
        x = x.reshape(n, 784)
    split = int(n * 0.9)
    train = mx.io.NDArrayIter(x[:split], y[:split], batch_size,
                              shuffle=True)
    val = mx.io.NDArrayIter(x[split:], y[split:], batch_size)
    return train, val


def main():
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(message)s")
    ap = argparse.ArgumentParser()
    ap.add_argument("--network", choices=("mlp", "lenet"), default="mlp")
    ap.add_argument("--num-epochs", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.1)
    args = ap.parse_args()

    flat = args.network == "mlp"
    sym = mlp_symbol() if flat else lenet_symbol()
    train, val = get_iters(args.batch_size, flat)

    model = mx.mod.Module(sym, context=mx.tpu(0))
    model.fit(
        train,
        eval_data=val,
        optimizer="sgd",
        optimizer_params={"learning_rate": args.lr, "momentum": 0.9},
        initializer=mx.init.Xavier(),
        eval_metric="acc",
        num_epoch=args.num_epochs,
        batch_end_callback=mx.callback.Speedometer(args.batch_size, 20),
    )
    acc = dict(model.score(val, mx.metric.Accuracy()))["accuracy"]
    print("final validation accuracy: %.4f" % acc)


if __name__ == "__main__":
    main()
