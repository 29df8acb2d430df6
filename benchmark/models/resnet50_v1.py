"""ResNet-50 v1 for the benchmark: the net through the repo's public API
(for training, and exported for serving), the plain float32 reference, and
the operations and bytes of one train step and one forward worked out from
the shapes.  Every size comes from the configuration file.
"""
import numpy as np


# -- the system under test --------------------------------------------------

def _net(config):
    from mxnet_tpu.gluon.model_zoo.vision.resnet import (BottleneckV1,
                                                         ResNetV1)
    assert config["block"] == "bottleneck_v1"
    return ResNetV1(BottleneckV1, config["layers"], config["channels"],
                    classes=config["classes"])


def build(config, ctx, seed):
    """The model zoo's ResNetV1 on `ctx`, cast, hybridized, deferred shapes
    resolved by a 2-row forward (imperative; see bert_base.build)."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    mx.random.seed(seed)
    net = _net(config)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net.cast(config["dtype"])
    net.hybridize()
    size = config["image_size"]
    net(nd.zeros((2, 3, size, size), ctx=ctx, dtype=config["dtype"]))
    return net


def export(config, seed, prefix):
    """The float32 artifact a user hands to ``python -m mxnet_tpu.serve
    --model``: initialised on the host from the seed, exported."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    mx.random.seed(seed)
    net = _net(config)
    net.initialize(mx.init.Xavier())
    net.hybridize()
    size = config["image_size"]
    net(nd.zeros((1, 3, size, size)))
    net.export(prefix)


def loss_fn():
    from mxnet_tpu import gluon
    sce = gluon.loss.SoftmaxCrossEntropyLoss()

    def ce_loss(out, label):
        return sce(out.astype("float32"), label)

    return ce_loss


def _images(rng, shape, dtype):
    """Decoded, normalised pixels: uint8 drawn from the seed, mapped to
    (p - 127.5) / 64 in `dtype` through a 256-entry table (a normal draw
    of 38 M floats a batch would be most of the set-up)."""
    table = ((np.arange(256, dtype=np.float32) - 127.5) / 64.0).astype(dtype)
    return table[rng.randint(0, 256, shape, dtype=np.uint8)]


def batches(config, traffic, seed):
    """The pool of host batches: ((images,), labels) each."""
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    b, size = traffic["batch"], config["image_size"]
    dtype = jnp.dtype(config["dtype"])
    pool = []
    for _ in range(traffic["pool"]):
        x = _images(rng, (b, 3, size, size), dtype)
        y = rng.randint(0, config["classes"], b).astype(np.float32)
        pool.append(((x,), y))
    return pool


def requests(config, rows, count, seed):
    """`count` float32 PREDICT payloads of `rows` rows each."""
    rng = np.random.RandomState(seed * 1000 + rows)
    size = config["image_size"]
    return [_images(rng, (rows, 3, size, size), np.float32)
            for _ in range(count)]


def example_shape(config):
    return "3,%d,%d" % (config["image_size"], config["image_size"])


def units_per_row(traffic):
    return 1


def check_inputs(config, traffic, seed):
    rng = np.random.RandomState(seed + 1)
    size = config["image_size"]
    return (_images(rng, (4, 3, size, size), np.float32),)


def logits(net, inputs, ctx):
    from mxnet_tpu import nd
    dtype = next(iter(net.collect_params().values())).dtype
    x = nd.array(inputs[0], ctx=ctx, dtype=dtype)
    return net(x)._jax.astype("float32")


# -- the plain reference ----------------------------------------------------

def reference(params, inputs, config):
    """ResNet v1 with bottleneck blocks as He et al. 2015 describe it
    (Table 1; the stride of a down-sampling block in its first 1x1
    convolution), inference-mode BatchNorm, in float32 ``jax.numpy`` at the
    highest precision: no Gluon, no kernels.  The model zoo's first and
    last 1x1 convolutions of a block carry a bias; the reference adds it
    where the parameters have one.  `params` maps the net's names to
    arrays."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def p(name):
        return jnp.asarray(params[name], jnp.float32)

    def conv(x, name, stride, pad):
        y = lax.conv_general_dilated(
            x, p(name + ".weight"), (stride, stride),
            [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        if name + ".bias" in params:
            y = y + p(name + ".bias")[None, :, None, None]
        return y

    def bn(x, name):
        scale = p(name + ".gamma") / jnp.sqrt(p(name + ".running_var")
                                              + 1e-5)
        shift = p(name + ".beta") - p(name + ".running_mean") * scale
        return x * scale[None, :, None, None] + shift[None, :, None, None]

    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(inputs[0], jnp.float32)
        x = jax.nn.relu(bn(conv(x, "features.0", 2, 3), "features.1"))
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2),
                              [(0, 0), (0, 0), (1, 1), (1, 1)])
        for stage, blocks in enumerate(config["layers"]):
            for b in range(blocks):
                name = "features.%d.%d." % (4 + stage, b)
                stride = 2 if (b == 0 and stage > 0) else 1
                y = jax.nn.relu(bn(conv(x, name + "body.0", stride, 0),
                                   name + "body.1"))
                y = jax.nn.relu(bn(conv(y, name + "body.3", 1, 1),
                                   name + "body.4"))
                y = bn(conv(y, name + "body.6", 1, 0), name + "body.7")
                if name + "downsample.0.weight" in params:
                    x = bn(conv(x, name + "downsample.0", stride, 0),
                           name + "downsample.1")
                x = jax.nn.relu(x + y)
        x = x.mean(axis=(2, 3))
        return x @ p("output.weight").T + p("output.bias")


# -- operations and bytes, from the shapes ----------------------------------

def forward_macs(config):
    """Multiply-accumulates of one image's forward pass, layer by layer:
    {layer name: MACs}.  Convolutions and the classifier only."""
    size = config["image_size"]
    ch = config["channels"]
    macs = {}
    hw = (size + 2 * 3 - 7) // 2 + 1                 # 7x7 stride 2 pad 3
    macs["conv1"] = hw * hw * ch[0] * 3 * 49
    hw = (hw + 2 - 3) // 2 + 1                       # 3x3 max-pool stride 2
    c_in = ch[0]
    for stage, blocks in enumerate(config["layers"]):
        c_out, mid = ch[stage + 1], ch[stage + 1] // 4
        for b in range(blocks):
            name = "stage%d.block%d" % (stage + 1, b)
            stride = 2 if (b == 0 and stage > 0) else 1
            out_hw = hw // stride                    # stride in the first 1x1
            macs[name + ".conv1x1a"] = out_hw * out_hw * mid * c_in
            macs[name + ".conv3x3"] = out_hw * out_hw * mid * mid * 9
            macs[name + ".conv1x1b"] = out_hw * out_hw * c_out * mid
            if b == 0 and c_in != c_out:
                macs[name + ".downsample"] = out_hw * out_hw * c_out * c_in
            hw, c_in = out_hw, c_out
    macs["classifier"] = ch[-1] * config["classes"]
    return macs


def n_params(config):
    """Parameters that train (weights, biases, BatchNorm scale and shift)
    and BatchNorm running statistics, counted from the shapes."""
    ch = config["channels"]
    train = 3 * 49 * ch[0] + 2 * ch[0]
    stats = 2 * ch[0]
    c_in = ch[0]
    for stage, blocks in enumerate(config["layers"]):
        c_out, mid = ch[stage + 1], ch[stage + 1] // 4
        for b in range(blocks):
            train += c_in * mid + mid + 9 * mid * mid + mid * c_out + c_out
            train += 2 * (mid + mid + c_out)
            stats += 2 * (mid + mid + c_out)
            if b == 0 and c_in != c_out:
                train += c_in * c_out + 2 * c_out
                stats += 2 * c_out
            c_in = c_out
    train += ch[-1] * config["classes"] + config["classes"]
    return train, stats


def ops_and_bytes(config, traffic):
    """Required floating-point operations and least HBM bytes of ONE train
    step of the batch: 2 per multiply-add, forward once and backward twice
    (by the input and by the weight) - except the first convolution, whose
    input is the image and needs no gradient.  BatchNorm, ReLU, pooling
    and the loss count 0.  Bytes: the batch in, every parameter with its
    float32 master copy and momentum read once and written once."""
    macs = forward_macs(config)
    per_image = sum(macs.values())
    b = traffic["batch"]
    forward = 2 * per_image * b
    flops = 3 * forward - 2 * macs["conv1"] * b
    train, stats = n_params(config)
    size = config["image_size"]
    state_bytes = train * (2 + 4 + 4) + stats * 2 * 4
    batch_bytes = b * (3 * size * size * 2 + 4)
    return {"flops": flops, "forward_flops": forward,
            "forward_macs_per_image": per_image,
            "bytes": 2 * state_bytes + batch_bytes,
            "n_params": train + stats, "detail": macs}
