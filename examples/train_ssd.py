"""SSD detection training (BASELINE config 4; reference:
example/ssd/train.py).  Real data: point --rec at an ImageDetIter .rec
pack (tools/im2rec.py --pack-label); offline it builds a synthetic
one-box dataset so the script runs anywhere.

    python examples/train_ssd.py [--epochs 2]
"""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd, recordio
from mxnet_tpu.gluon.model_zoo.ssd import SSDMultiBoxLoss, ssd_toy
from mxnet_tpu.image.detection import ImageDetIter
from mxnet_tpu.metric import VOC07MApMetric


def synthetic_rec(n=64, edge=64):
    rng = np.random.RandomState(0)
    d = tempfile.mkdtemp(prefix="ssd_rec_")
    prefix = os.path.join(d, "det")
    w = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    for i in range(n):
        img = np.full((edge, edge, 3), 30, np.uint8)
        bw = rng.randint(edge // 4, edge // 2)
        x0 = rng.randint(0, edge - bw)
        y0 = rng.randint(0, edge - bw)
        img[y0:y0 + bw, x0:x0 + bw] = 220
        label = np.concatenate(
            [[2, 5, 0], [x0 / edge, y0 / edge, (x0 + bw) / edge,
                         (y0 + bw) / edge]]).astype(np.float32)
        w.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, label, i, 0), img, quality=95))
    w.close()
    return prefix + ".rec"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rec", default=None, help=".rec with det labels")
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--data-shape", type=int, default=64)
    args = ap.parse_args()

    rec = args.rec or synthetic_rec()
    it = ImageDetIter(path_imgrec=rec,
                      data_shape=(3, args.data_shape, args.data_shape),
                      batch_size=args.batch_size, shuffle=True,
                      rand_mirror=True)

    mx.random.seed(0)
    net = ssd_toy(classes=1)
    net.initialize(mx.init.Xavier(), ctx=mx.tpu(0))
    loss_fn = SSDMultiBoxLoss()
    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.05, "momentum": 0.9})
    for epoch in range(args.epochs):
        it.reset()
        losses = []
        for batch in it:
            x = batch.data[0].as_in_context(mx.tpu(0)) / 255.0
            y = batch.label[0].as_in_context(mx.tpu(0))
            with autograd.record():
                anchors, cls_preds, box_preds = net(x)
                loc_t, loc_m, cls_t = net.targets(anchors, cls_preds, y)
                loss = loss_fn(cls_preds, box_preds, cls_t, loc_t, loc_m)
            loss.backward()
            trainer.step(x.shape[0])
            losses.append(float(loss.asnumpy().item()))
        print("epoch %d loss %.4f" % (epoch, sum(losses) / len(losses)))

    metric = VOC07MApMetric()
    it.reset()
    for batch in it:
        anchors, cls_preds, box_preds = net(
            batch.data[0].as_in_context(mx.tpu(0)) / 255.0)
        dets = net.detect(anchors, cls_preds, box_preds)
        n = batch.data[0].shape[0] - batch.pad   # drop wrap-around padding
        metric.update([batch.label[0][:n]], [dets[:n]])
    print("train-set %s=%.4f" % metric.get())


if __name__ == "__main__":
    main()
