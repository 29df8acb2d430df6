"""DCGAN on small images (reference: example/gluon/dcgan/dcgan.py).

Shows the adversarial two-optimizer Gluon loop: a ConvTranspose
generator against a Conv discriminator, alternating updates from the
SAME autograd tape discipline the reference uses (train D on real+fake,
then train G through D's frozen weights).  Offline it runs on a
synthetic image set; point MX_DATA_DIR at an image folder for real data.

    python examples/dcgan.py [--epochs 1] [--batch-size 64] [--nz 100]
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


def build_generator(nz, ngf=32):
    net = nn.HybridSequential()
    # 1x1 -> 4x4 -> 8x8 -> 16x16 -> 32x32
    net.add(nn.Conv2DTranspose(ngf * 4, 4, 1, 0, use_bias=False,
                               in_channels=nz),
            nn.BatchNorm(), nn.Activation("relu"),
            nn.Conv2DTranspose(ngf * 2, 4, 2, 1, use_bias=False),
            nn.BatchNorm(), nn.Activation("relu"),
            nn.Conv2DTranspose(ngf, 4, 2, 1, use_bias=False),
            nn.BatchNorm(), nn.Activation("relu"),
            nn.Conv2DTranspose(3, 4, 2, 1, use_bias=False),
            nn.Activation("tanh"))
    return net


def build_discriminator(ndf=32):
    net = nn.HybridSequential()
    net.add(nn.Conv2D(ndf, 4, 2, 1, use_bias=False),
            nn.LeakyReLU(0.2),
            nn.Conv2D(ndf * 2, 4, 2, 1, use_bias=False),
            nn.BatchNorm(), nn.LeakyReLU(0.2),
            nn.Conv2D(ndf * 4, 4, 2, 1, use_bias=False),
            nn.BatchNorm(), nn.LeakyReLU(0.2),
            nn.Conv2D(1, 4, 1, 0, use_bias=False))
    return net


def get_data(batch_size, n=512):
    data_dir = os.environ.get("MX_DATA_DIR")
    if data_dir and os.path.isdir(os.path.join(data_dir, "images")):
        from mxnet_tpu.gluon.data.vision.datasets import ImageFolderDataset
        ds = ImageFolderDataset(os.path.join(data_dir, "images"))

        def tf(img, _label):
            img = mx.image.imresize(img, 32, 32)
            x = img.astype("float32").transpose((2, 0, 1)) / 127.5 - 1.0
            return x
        ds = ds.transform_first(lambda im: tf(im, 0))
    else:
        rng = np.random.RandomState(0)
        imgs = rng.uniform(-1, 1, (n, 3, 32, 32)).astype(np.float32)
        ds = gluon.data.ArrayDataset(mx.nd.array(imgs),
                                     mx.nd.zeros((n, 1)))
    return gluon.data.DataLoader(ds, batch_size=batch_size,
                                 shuffle=True, last_batch="discard")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--nz", type=int, default=100)
    ap.add_argument("--lr", type=float, default=2e-4)
    ap.add_argument("--max-batches", type=int,
                    default=int(os.environ.get("MX_EX_MAX_BATCHES", 0)) or
                    None)
    args = ap.parse_args()

    ctx = mx.tpu(0)
    netG, netD = build_generator(args.nz), build_discriminator()
    with mx.Context(ctx):
        netG.initialize(mx.init.Normal(0.02))
        netD.initialize(mx.init.Normal(0.02))
        netG.hybridize()
        netD.hybridize()

        loss_f = gluon.loss.SigmoidBinaryCrossEntropyLoss()
        trnG = gluon.Trainer(netG.collect_params(), "adam",
                             {"learning_rate": args.lr, "beta1": 0.5})
        trnD = gluon.Trainer(netD.collect_params(), "adam",
                             {"learning_rate": args.lr, "beta1": 0.5})

        for epoch in range(args.epochs):
            t0, seen, n_b = time.time(), 0, 0
            dsum = gsum = 0.0
            for i, (real, _) in enumerate(get_data(args.batch_size)):
                if args.max_batches and i >= args.max_batches:
                    break
                n_b += 1
                bs = real.shape[0]
                real = real.as_in_context(ctx)
                noise = mx.nd.random.normal(
                    shape=(bs, args.nz, 1, 1), ctx=ctx)
                ones = mx.nd.ones((bs,), ctx=ctx)
                zeros = mx.nd.zeros((bs,), ctx=ctx)

                # D step: real -> 1, G(z) -> 0 (fake detached from G)
                with autograd.record():
                    out_r = netD(real).reshape((-1,))
                    fake = netG(noise)
                    out_f = netD(fake.detach()).reshape((-1,))
                    errD = loss_f(out_r, ones) + loss_f(out_f, zeros)
                errD.backward()
                trnD.step(bs)

                # G step: fool D (D's params get grads too but only
                # trnG.step updates G — the reference's exact recipe)
                with autograd.record():
                    out = netD(fake).reshape((-1,))
                    errG = loss_f(out, ones)
                errG.backward()
                trnG.step(bs)

                dsum += float(errD.mean().asnumpy())
                gsum += float(errG.mean().asnumpy())
                seen += bs
            if n_b == 0:
                raise SystemExit("no batches: --batch-size exceeds the "
                                 "dataset size")
            print("epoch %d: lossD %.4f lossG %.4f (%.1f img/s)"
                  % (epoch, dsum / n_b, gsum / n_b,
                     seen / (time.time() - t0)))


if __name__ == "__main__":
    main()
