"""ops_and_bytes of both configurations against numbers worked out by hand
here, from the published architectures."""
import pytest

from bench_helpers import REPO, manifest
from benchmark.run import Run


def _run(cell_name):
    m = manifest()
    cell = next(c for c in m["workloads"] if c["name"] == cell_name)
    return Run(REPO, m, cell, 0, 0, False)


def test_resnet50_forward_is_the_papers_3_8e9_multiply_adds():
    run = _run("resnet50-train-b256")
    model = run.model()
    macs = model.forward_macs(run.config)
    # He et al. 2015, Table 1, 50-layer column; v1: stride in the first 1x1.
    # conv1: 112*112 outputs x 64 channels x (3*7*7) = 118,013,952
    assert macs["conv1"] == 112 * 112 * 64 * 147 == 118013952
    # conv2_x, block 0 at 56x56 (input 64 channels, mid 64, out 256):
    #   1x1 64->64, 3x3 64->64, 1x1 64->256, projection 64->256
    hw = 56 * 56
    assert macs["stage1.block0.conv1x1a"] == hw * 64 * 64
    assert macs["stage1.block0.conv3x3"] == hw * 64 * 64 * 9
    assert macs["stage1.block0.conv1x1b"] == hw * 256 * 64
    assert macs["stage1.block0.downsample"] == hw * 256 * 64
    # conv3_x, block 0: the first 1x1 carries the stride, so it runs on the
    # 28x28 grid already (v1.5 would run it at 56x56: 4x these MACs)
    assert macs["stage2.block0.conv1x1a"] == 28 * 28 * 128 * 256
    assert macs["stage2.block0.conv3x3"] == 28 * 28 * 128 * 128 * 9
    # per stage: block 0 with projection + (n-1) blocks on c_out input
    def stage(hw, c_in, mid, c_out, n):
        first = hw * (c_in * mid + 9 * mid * mid + mid * c_out + c_in * c_out)
        rest = hw * (c_out * mid + 9 * mid * mid + mid * c_out)
        return first + (n - 1) * rest
    by_hand = (118013952
               + stage(56 * 56, 64, 64, 256, 3)
               + stage(28 * 28, 256, 128, 512, 4)
               + stage(14 * 14, 512, 256, 1024, 6)
               + stage(7 * 7, 1024, 512, 2048, 3)
               + 2048 * 1000)
    assert sum(macs.values()) == by_hand == 3857973248     # "3.8 x 10^9"
    ops = model.ops_and_bytes(run.config, run.traffic)
    assert ops["forward_macs_per_image"] == by_hand
    # one step of 256: forward 2/MAC, backward twice that, less the first
    # convolution's gradient by the image, which nothing needs
    assert ops["flops"] == 256 * (6 * by_hand - 2 * 118013952)
    assert ops["flops"] / 256 == pytest.approx(22.9e9, rel=5e-3)
    # parameters: 25,557,032 that train (torchvision's count + the model
    # zoo's 1x1 biases) - checked against the net itself in the
    # reference test; here: the bytes follow from them
    train, stats = model.n_params(run.config)
    assert stats == 2 * 26560 and train + stats == ops["n_params"]
    assert ops["bytes"] == 2 * (train * 10 + stats * 8) \
        + 256 * (3 * 224 * 224 * 2 + 4)


def test_bert_base_step_by_hand():
    run = _run("bert-base-train-s512")
    ops = run.model().ops_and_bytes(run.config, run.traffic)
    tokens, d, f, v, t = 32 * 512, 768, 3072, 30522, 512
    layer = (2 * tokens * d * 3 * d          # QKV projection
             + 2 * tokens * d * d            # output projection
             + 2 * 2 * tokens * d * f        # the two FFN products
             + 2 * 2 * 32 * t * t * d)       # QK^T and PV over 12 heads of 64
    assert sum(ops["detail"]["per_layer_forward"].values()) == layer
    head = 2 * tokens * d * d + 2 * tokens * d * v
    assert ops["forward_flops"] == 12 * layer + head == 3879815086080
    assert ops["flops"] == 3 * ops["forward_flops"]
    # 6*N*tokens with N = the matmul weights alone, plus attention
    matmul_weights = 12 * (4 * d * d + 2 * d * f) + d * d + d * v
    assert ops["flops"] == 6 * matmul_weights * tokens \
        + 3 * 12 * 4 * 32 * t * t * d
    # every parameter of the net (PR 22's chip run: 1,335.5 MB of
    # parameters + master copies + momentum = 10 bytes each)
    assert ops["n_params"] == 133545786
    assert ops["n_params"] * 10 == pytest.approx(1335.5e6, rel=1e-4)


def test_fsdp4_cell_is_four_times_the_work_on_the_same_state():
    one = _run("bert-base-train-s512")
    four = _run("bert-base-train-s512-fsdp4")
    a = one.model().ops_and_bytes(one.config, one.traffic)
    b = four.model().ops_and_bytes(four.config, four.traffic)
    assert four.traffic["batch"] == 4 * one.traffic["batch"]
    assert b["flops"] == 4 * a["flops"] and b["n_params"] == a["n_params"]


def test_step_roofline_bound_names_which_side_binds():
    run = _run("bert-base-train-s512")
    reader = run.load("benchmark/layer_metrics/step_roofline.py")
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert reader.bound({"flops": 1000.0, "bytes": 10.0}, peak, 1) \
        == (10.0, "compute")
    assert reader.bound({"flops": 10.0, "bytes": 100.0}, peak, 2) \
        == (5.0, "memory")
