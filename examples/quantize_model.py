"""Post-training INT8 quantization (reference: example/quantization/
imagenet_gen_qsym_onednn.py — the calibrate-then-deploy flow).

Train an fp32 model (hybridized for speed), run calibration batches
through contrib.quantization.quantize_net (naive min/max or KL-entropy
thresholds — quantize_net de-hybridizes, since the int8 rewrite is
python-dispatched), then compare fp32 vs INT8 accuracy and latency on
the validation set of a synthetic learnable dataset.

    python examples/quantize_model.py [--calib-mode naive|entropy]
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import autograd, gluon  # noqa: E402
from mxnet_tpu.contrib.quantization import quantize_net  # noqa: E402


def get_data(n=1024, batch=64, seed=0):
    rng = np.random.RandomState(seed)
    y = rng.randint(0, 10, n)
    x = rng.rand(n, 1, 16, 16).astype(np.float32) * 0.1
    for i in range(n):
        c = y[i]
        x[i, 0, (c % 4) * 4:(c % 4) * 4 + 3,
          (c // 4) * 5:(c // 4) * 5 + 4] += 0.9
    split = int(n * 0.8)
    train = gluon.data.DataLoader(
        gluon.data.ArrayDataset(mx.nd.array(x[:split]),
                                mx.nd.array(y[:split].astype(np.float32))),
        batch_size=batch, shuffle=True)
    val = gluon.data.DataLoader(
        gluon.data.ArrayDataset(mx.nd.array(x[split:]),
                                mx.nd.array(y[split:].astype(np.float32))),
        batch_size=batch)
    return train, val


def build_net():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(8, 3, padding=1, activation="relu"),
            gluon.nn.MaxPool2D(2),
            gluon.nn.Conv2D(16, 3, padding=1, activation="relu"),
            gluon.nn.MaxPool2D(2),
            gluon.nn.Flatten(),
            gluon.nn.Dense(32, activation="relu"),
            gluon.nn.Dense(10))
    return net


def accuracy(net, data):
    metric = mx.metric.Accuracy()
    for x, y in data:
        metric.update(y, net(x))
    return metric.get()[1]


def latency(net, data, iters=3):
    xs = [x for x, _ in data]
    for x in xs[:2]:
        net(x).wait_to_read()
    t0 = time.perf_counter()
    n = 0
    outs = []
    for _ in range(iters):
        for x in xs:
            outs.append(net(x))
            n += x.shape[0]
    for o in outs:      # async dispatch: the clock must cover ALL work
        o.wait_to_read()
    return n / (time.perf_counter() - t0)


def _quantized_layers(block):
    for child in block._children.values():
        if getattr(child, "_quantized", False):
            yield child
        yield from _quantized_layers(child)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calib-mode", choices=("naive", "entropy"),
                    default="naive")
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--calib-batches", type=int, default=4)
    args = ap.parse_args()

    # everything (data arrays AND the net) on one device: the batches
    # must live where the parameters live
    with mx.Context(mx.tpu(0)):
        train, val = get_data()
        net = build_net()
        net.initialize(mx.init.Xavier())
        net.hybridize()
        loss_f = gluon.loss.SoftmaxCrossEntropyLoss()
        trainer = gluon.Trainer(net.collect_params(), "adam",
                                {"learning_rate": 2e-3})
        for epoch in range(args.epochs):
            for x, y in train:
                with autograd.record():
                    loss = loss_f(net(x), y)
                loss.backward()
                trainer.step(x.shape[0])

        fp32_acc = accuracy(net, val)
        fp32_ips = latency(net, val)

        qnet = quantize_net(net, calib_data=train,
                            num_calib_batches=args.calib_batches,
                            calib_mode=args.calib_mode)
        n_q = sum(1 for _ in _quantized_layers(qnet))
        print("quantized layers: %d" % n_q)
        int8_acc = accuracy(qnet, val)
        int8_ips = latency(qnet, val)

    print("fp32:  acc %.4f  %.0f img/s" % (fp32_acc, fp32_ips))
    print("int8:  acc %.4f  %.0f img/s  (%s calibration)"
          % (int8_acc, int8_ips, args.calib_mode))
    drop = fp32_acc - int8_acc
    print("accuracy drop: %.4f" % drop)


if __name__ == "__main__":
    main()
