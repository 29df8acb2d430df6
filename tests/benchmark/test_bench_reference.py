"""The plain references against the model-zoo nets with the same
parameters, at a tiny width on the CPU."""
import json
import os

import numpy as np
import pytest

from bench_helpers import DATA, REPO
from benchmark.run import Run

# float32 against float32 on the host: the same products in another order
# of summation.  A float32 sum of a few hundred terms is good to ~1e-6
# relative; 1e-5 of the logit scale leaves room and is still 100x under
# what a bf16 matmul (2^-9 a product) would show.
TOL = 1e-5


def _tiny(config_name, traffic_name):
    run = Run.__new__(Run)      # only what Run.model() needs: no cell
    run.root = REPO
    with open(os.path.join(DATA, config_name + ".json")) as f:
        run.config = json.load(f)
    with open(os.path.join(DATA, traffic_name + ".json")) as f:
        run.traffic = json.load(f)
    return run


@pytest.mark.parametrize("config,traffic", [("tiny_bert", "tiny-mlm"),
                                            ("tiny_resnet", "tiny-images")])
def test_reference_agrees_with_the_model_zoo_net(config, traffic):
    import mxnet_tpu as mx
    run = _tiny(config, traffic)
    model = run.model()
    ctx = mx.tpu(0)
    net = model.build(run.config, ctx, seed=5)
    params = {k: p.data().asnumpy() for k, p in net.collect_params().items()}
    if config == "tiny_resnet":
        # running statistics as a trained net has them, not 0 and 1
        rng = np.random.RandomState(0)
        for k in params:
            if k.endswith("running_mean"):
                params[k] = rng.randn(*params[k].shape).astype("float32")
            elif k.endswith("running_var"):
                params[k] = rng.uniform(0.5, 2, params[k].shape) \
                    .astype("float32")
        for k, p in net.collect_params().items():
            p.set_data(mx.nd.array(params[k], ctx=ctx))
    inputs = model.check_inputs(run.config, run.traffic, seed=5)
    got = np.asarray(model.logits(net, inputs, ctx))
    want = np.asarray(model.reference(params, inputs, run.config))
    assert got.shape == want.shape
    scale = np.abs(want).max()
    assert scale > 1e-3
    assert np.abs(got - want).max() / scale < TOL
    # and the comparison can fail: one layer's weight changed by 1 %
    name = next(k for k in params if k.endswith("ffn_1.weight")
                or k.endswith("body.3.weight"))
    bent = dict(params, **{name: params[name] * 1.01})
    off = np.asarray(model.reference(bent, inputs, run.config))
    assert np.abs(got - off).max() / scale > 10 * TOL


def test_parameter_count_of_ops_and_bytes_is_the_nets():
    import mxnet_tpu as mx
    for config, traffic in (("tiny_bert", "tiny-mlm"),
                            ("tiny_resnet", "tiny-images")):
        run = _tiny(config, traffic)
        model = run.model()
        net = model.build(run.config, mx.tpu(0), seed=1)
        n = sum(int(np.prod(p.shape))
                for p in net.collect_params().values())
        assert model.ops_and_bytes(run.config, run.traffic)["n_params"] == n
