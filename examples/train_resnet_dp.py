"""Data-parallel ResNet training over a device mesh (BASELINE config 1
path; reference: example/image-classification/train_imagenet.py with
kvstore, rebuilt on the whole-step-jitted parallel.TrainStep).

Single host: uses every visible chip via a 1-axis dp mesh. Multi-host:
launch with tools/launch.py -n <N> and each worker feeds its batch shard.

    python examples/train_resnet_dp.py [--model resnet18_v1] [--steps 10]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet18_v1")
    ap.add_argument("--batch-size", type=int, default=32,
                    help="GLOBAL batch size")
    ap.add_argument("--image-size", type=int, default=64)
    ap.add_argument("--classes", type=int, default=100)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--lr", type=float, default=0.1)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel import init_process_group, make_mesh, TrainStep

    if os.environ.get("MX_NUM_PROCESSES"):
        init_process_group()

    mx.random.seed(0)
    with mx.Context("cpu"):
        net = getattr(vision, args.model)(classes=args.classes)
        net.initialize(mx.init.Xavier())
        net(mx.nd.zeros((1, 3, args.image_size, args.image_size)))

    def loss_fn(logits, labels):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        onehot = jax.nn.one_hot(labels, args.classes, dtype=logp.dtype)
        return -jnp.mean(jnp.sum(logp * onehot, axis=-1))

    mesh = make_mesh(axes=("dp",), devices=jax.devices())
    step = TrainStep(net, loss_fn, mesh, learning_rate=args.lr,
                     momentum=0.9)

    nproc = jax.process_count()
    local_bs = args.batch_size // nproc
    rng = np.random.RandomState(jax.process_index())
    for i in range(args.steps):
        x = rng.randn(local_bs, 3, args.image_size,
                      args.image_size).astype(np.float32)
        y = rng.randint(0, args.classes, local_bs).astype(np.int32)
        loss = step(x, y)
        if jax.process_index() == 0:
            val = float(np.asarray(jax.device_get(
                loss._jax if hasattr(loss, "_jax") else loss)))
            print("step %d loss %.4f" % (i, val))
    step.write_back(net)
    if jax.process_index() == 0:
        net.export("resnet_dp_trained")
        print("exported resnet_dp_trained-symbol.json / -0000.params")


if __name__ == "__main__":
    main()
