"""ESPCN super-resolution (reference:
example/gluon/super_resolution/super_resolution.py).

Sub-pixel convolution: conv stack at low resolution, then
PixelShuffle2D rearranges channels into an upscale_factor-larger image
— the FLOPs stay at LR size, which maps well onto the MXU.  After
training, the net exports through mx.onnx (the reference uses this
exact model as its canonical ONNX-export demo).

    python examples/super_resolution.py [--epochs 1] [--upscale 3]
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import autograd, gluon  # noqa: E402
from mxnet_tpu.gluon import nn  # noqa: E402
from mxnet_tpu.gluon.contrib.nn import PixelShuffle2D  # noqa: E402


def build_net(upscale):
    net = nn.HybridSequential()
    net.add(nn.Conv2D(64, 5, padding=2, activation="relu"),
            nn.Conv2D(64, 3, padding=1, activation="relu"),
            nn.Conv2D(32, 3, padding=1, activation="relu"),
            nn.Conv2D(upscale * upscale, 3, padding=1),
            PixelShuffle2D((upscale, upscale)))
    return net


def get_data(batch_size, upscale, n=256, hr=48):
    """(LR, HR) luminance patch pairs (synthetic; LR = mean-pooled HR,
    the standard degradation model)."""
    lr = hr // upscale
    rng = np.random.RandomState(0)
    base = rng.uniform(0, 1, (n, 1, hr, hr)).astype(np.float32)
    hr_t = mx.nd.array(base)
    # LR = mean-pooled HR (the degradation model)
    lr_t = mx.nd.Pooling(hr_t, kernel=(upscale, upscale),
                         stride=(upscale, upscale), pool_type="avg")
    assert lr_t.shape[-1] == lr
    ds = gluon.data.ArrayDataset(lr_t, hr_t)
    return gluon.data.DataLoader(ds, batch_size=batch_size, shuffle=True,
                                 last_batch="discard")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--upscale", type=int, default=3)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--export", default="")
    ap.add_argument("--max-batches", type=int,
                    default=int(os.environ.get("MX_EX_MAX_BATCHES", 0)) or
                    None)
    args = ap.parse_args()

    ctx = mx.tpu(0)
    net = build_net(args.upscale)
    with mx.Context(ctx):
        net.initialize(mx.init.Xavier())
        net.hybridize()
        l2 = gluon.loss.L2Loss()
        trainer = gluon.Trainer(net.collect_params(), "adam",
                                {"learning_rate": args.lr})

        for epoch in range(args.epochs):
            t0, seen, lsum, n_b = time.time(), 0, 0.0, 0
            for i, (lo, hi) in enumerate(
                    get_data(args.batch_size, args.upscale)):
                if args.max_batches and i >= args.max_batches:
                    break
                n_b += 1
                lo = lo.as_in_context(ctx)
                hi = hi.as_in_context(ctx)
                with autograd.record():
                    out = net(lo)
                    loss = l2(out, hi)
                loss.backward()
                trainer.step(lo.shape[0])
                lsum += float(loss.mean().asnumpy())
                seen += lo.shape[0]
            if n_b == 0:
                raise SystemExit("no batches: --batch-size exceeds the "
                                 "dataset size")
            mse = lsum / n_b * 2.0                # L2Loss halves
            print("epoch %d: mse %.5f psnr %.2f dB (%.1f patch/s)"
                  % (epoch, mse, 10 * np.log10(1.0 / max(mse, 1e-9)),
                     seen / (time.time() - t0)))

        if args.export:
            # the reference's canonical ONNX-export path: hybridized net
            # -> symbol.json + .params -> onnx protobuf
            prefix = args.export.replace(".onnx", "")
            net.export(prefix)
            from mxnet_tpu import onnx as mx_onnx
            mx_onnx.export_model(prefix + "-symbol.json",
                                 prefix + "-0000.params",
                                 [(1, 1, 16, 16)], np.float32, args.export)
            print("exported ONNX ->", args.export)


if __name__ == "__main__":
    main()
