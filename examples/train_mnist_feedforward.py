"""The original pre-Module v1.x workflow, verbatim (reference:
example/image-classification/train_mnist.py at the FeedForward era /
python/mxnet/model.py class FeedForward): build a symbol, hand it to
mx.model.FeedForward with optimizer hyper-parameters as kwargs, call
fit/predict/score, save a prefix-epoch checkpoint and load it back.

    python examples/train_mnist_feedforward.py [--epochs N]
"""
import argparse
import logging
import os
import sys
import tempfile
import warnings

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402


def mlp_symbol():
    data = mx.sym.Variable("data")
    net = mx.sym.Flatten(data=data)
    net = mx.sym.FullyConnected(data=net, num_hidden=64, name="fc1")
    net = mx.sym.Activation(data=net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(data=net, num_hidden=10, name="fc2")
    return mx.sym.SoftmaxOutput(data=net, name="softmax")


def synthetic_mnist(n=2048, seed=0):
    """Offline stand-in with MNIST geometry: each digit class is a fixed
    28x28 prototype plus noise, so the fit generalizes to held-out data
    the way real MNIST does."""
    protos = np.random.RandomState(1234).randn(10, 784).astype(np.float32)
    rng = np.random.RandomState(seed)
    Y = rng.randint(0, 10, n)
    X = (protos[Y] + 2.0 * rng.randn(n, 784)).astype(np.float32)
    return X.reshape(n, 1, 28, 28), Y.astype(np.float32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=10)
    ap.add_argument("--lr", type=float, default=0.1)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)

    X, Y = synthetic_mnist()
    Xval, Yval = synthetic_mnist(512, seed=1)
    val_iter = mx.io.NDArrayIter(Xval, Yval, batch_size=128,
                                 label_name="softmax_label")

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        model = mx.model.FeedForward(
            symbol=mlp_symbol(), num_epoch=args.epochs,
            learning_rate=args.lr, momentum=0.9, numpy_batch_size=128,
            initializer=mx.init.Xavier())
    model.fit(X=X, y=Y, eval_data=(Xval, Yval),
              batch_end_callback=mx.callback.Speedometer(128, 8))

    acc = model.score(val_iter)
    print("final test accuracy %.4f" % acc)
    assert acc > 0.6, acc

    prefix = os.path.join(tempfile.mkdtemp(), "mnist-ff")
    model.save(prefix)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        loaded = mx.model.FeedForward.load(prefix, args.epochs)
    preds = loaded.predict(Xval)
    agree = float((preds.argmax(1) == model.predict(Xval).argmax(1)).mean())
    assert agree == 1.0, agree
    print("checkpoint roundtrip OK (%s-%04d.params)" % (prefix, args.epochs))


if __name__ == "__main__":
    main()
