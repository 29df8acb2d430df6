"""End-to-end convergence tests (reference: tests/python/train/test_mlp.py —
'does SGD still converge' safety net; BASELINE config 0 gate: Gluon MLP
imperative + hybridized)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.data import DataLoader
from mxnet_tpu.gluon.data.vision import SyntheticImageDataset
from mxnet_tpu.gluon.data.vision.transforms import ToTensor, Compose


def _train_mlp(hybridize: bool, epochs=3):
    np.random.seed(7)
    mx.random.seed(7)
    train_set = SyntheticImageDataset(num_samples=512, shape=(8, 8, 1),
                                      num_classes=10, noise=0.25)
    test_set = SyntheticImageDataset(num_samples=256, shape=(8, 8, 1),
                                     num_classes=10, seed=99, noise=0.25)
    to_tensor = ToTensor()
    train_data = DataLoader(train_set.transform_first(to_tensor),
                            batch_size=64, shuffle=True)
    test_data = DataLoader(test_set.transform_first(to_tensor), batch_size=64)

    net = nn.HybridSequential()
    net.add(nn.Dense(64, activation="relu"), nn.Dense(10))
    net.initialize(mx.init.Xavier())
    if hybridize:
        net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.5, "momentum": 0.9})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    for _ in range(epochs):
        for data, label in train_data:
            with autograd.record():
                out = net(data)
                loss = loss_fn(out, label)
            loss.backward()
            trainer.step(data.shape[0])

    metric = mx.metric.Accuracy()
    for data, label in test_data:
        metric.update([label], [net(data)])
    return metric.get()[1]


def test_mlp_converges_imperative():
    acc = _train_mlp(hybridize=False)
    assert acc > 0.95, "imperative MLP failed to converge: acc=%s" % acc


def test_mlp_converges_hybridized():
    acc = _train_mlp(hybridize=True)
    assert acc > 0.95, "hybridized MLP failed to converge: acc=%s" % acc


def test_conv_net_trains():
    """Small CNN loss decreases (reference: tests/python/train/test_conv.py)."""
    np.random.seed(3)
    mx.random.seed(3)
    ds = SyntheticImageDataset(num_samples=128, shape=(8, 8, 1),
                               num_classes=4, noise=0.2)
    data = DataLoader(ds.transform_first(ToTensor()), batch_size=32,
                      shuffle=True)
    net = nn.HybridSequential()
    net.add(nn.Conv2D(8, 3, padding=1, activation="relu"),
            nn.MaxPool2D(),
            nn.Flatten(),
            nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.01})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    first_loss = last_loss = None
    for _ in range(4):
        for x, y in data:
            with autograd.record():
                loss = loss_fn(net(x), y).mean()
            loss.backward()
            trainer.step(1)
            val = float(loss.asscalar())
            if first_loss is None:
                first_loss = val
            last_loss = val
    # 16 Adam steps.  The trajectory equals an independent jax
    # implementation (lax conv + reduce_window + dense, textbook Adam) to
    # 1.2e-7 at every step, so the limit is set from readings, not the
    # code bent to it: last/first over seeds 0-7 on jax 0.9.0 is
    # 0.470-0.584 (this seed: 1.515 -> 0.830, 0.548).
    assert last_loss < first_loss * 0.65, (first_loss, last_loss)


def test_dataloader_shapes_and_shuffle():
    ds = SyntheticImageDataset(num_samples=100, shape=(4, 4, 1))
    dl = DataLoader(ds, batch_size=32, shuffle=True, last_batch="keep")
    batches = list(dl)
    assert len(batches) == 4
    assert batches[0][0].shape == (32, 4, 4, 1)
    assert batches[-1][0].shape == (4, 4, 4, 1)
    dl2 = DataLoader(ds, batch_size=32, last_batch="discard")
    assert len(list(dl2)) == 3


def test_dataloader_workers():
    ds = SyntheticImageDataset(num_samples=64, shape=(4, 4, 1))
    dl = DataLoader(ds, batch_size=16, num_workers=2)
    seen = 0
    for x, y in dl:
        seen += x.shape[0]
    assert seen == 64


def test_datasets_transform_chain():
    ds = SyntheticImageDataset(num_samples=10, shape=(8, 8, 1))
    tf = Compose([ToTensor()])
    out = ds.transform_first(tf)[0]
    x, y = out
    assert x.shape == (1, 8, 8)
    assert float(x.asnumpy().max()) <= 1.0
