"""The examples/ scripts must stay runnable offline (reference pattern:
example/ scripts are smoke-run in CI)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, timeout=600, cwd=None):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    # pin explicitly: in the MX_TEST_CTX=tpu lane the conftest does NOT
    # set these, and an unpinned example subprocess would try to claim
    # the chip the test process holds
    env["JAX_PLATFORMS"] = "cpu"
    env["MX_FORCE_CPU"] = "1"
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "examples", script), *args],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=cwd or REPO)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    return r.stdout


def test_mnist_example():
    out = _run("train_mnist_gluon.py", "--epochs", "1", "--hybridize")
    assert "final test accuracy" in out


def test_resnet_dp_example(tmp_path):
    out = _run("train_resnet_dp.py", "--steps", "2", "--batch-size", "8",
               "--image-size", "32", "--model", "resnet18_v1",
               cwd=str(tmp_path))
    assert "step 1 loss" in out
    for f in ("resnet_dp_trained-symbol.json",
              "resnet_dp_trained-0000.params"):
        assert os.path.exists(os.path.join(str(tmp_path), f))


def test_ssd_example():
    out = _run("train_ssd.py", "--epochs", "1")
    assert "mAP07" in out


def test_word_lm_example():
    """BASELINE config 3 example surface (reference example/rnn/word_lm):
    LSTM LM with truncated BPTT, perplexity + wps logging."""
    out = _run("word_lm.py", "--epochs", "1", "--max-batches", "8",
               "--batch-size", "8", "--bptt", "16", "--hidden", "32",
               "--embed", "16", "--vocab", "200")
    assert "Train-perplexity=" in out
    assert "final train perplexity" in out


def test_dist_async_example():
    """PS workflow example: 1 server + 2 workers converge async."""
    import subprocess
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "launch.py"),
                        "-n", "2", "-s", "1", "--launcher", "local", "--",
                        sys.executable,
                        os.path.join(REPO, "examples",
                                     "train_dist_async.py"),
                        "--steps", "25"],
                       capture_output=True, text=True, timeout=420, env=env)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    finals = [float(l.split("loss")[1].split("(")[0])
              for l in r.stdout.splitlines() if "FINAL" in l]
    assert len(finals) == 2
    assert all(v < 1.0 for v in finals), finals


def test_dcgan_example():
    """Adversarial two-Trainer loop (reference example/gluon/dcgan)."""
    out = _run("dcgan.py", "--epochs", "1", "--batch-size", "16",
               "--max-batches", "2")
    assert "lossD" in out and "lossG" in out


def test_super_resolution_example(tmp_path):
    """ESPCN + PixelShuffle + the canonical ONNX-export path
    (reference example/gluon/super_resolution)."""
    onnx_path = os.path.join(str(tmp_path), "sr.onnx")
    out = _run("super_resolution.py", "--epochs", "1", "--max-batches",
               "2", "--export", onnx_path, cwd=str(tmp_path))
    assert "psnr" in out
    assert os.path.exists(onnx_path) and os.path.getsize(onnx_path) > 1000


def test_lstm_bucketing_example():
    """Classic pre-Gluon stack: BucketSentenceIter + symbolic rnn cells +
    BucketingModule.fit (reference example/rnn/bucketing)."""
    out = _run("lstm_bucketing.py", "--num-epochs", "2", "--vocab", "80",
               "--num-hidden", "24", "--num-embed", "12",
               "--buckets", "10", "20", "30", "40", timeout=900)
    # epoch logs ride stderr (logging); stdout carries the final score.
    # Untrained-random scores ~110 on this config (uniform = vocab 80):
    # the bound must separate learning from a stall
    assert "final train perplexity" in out
    final = float(out.strip().splitlines()[-1].split(":")[1])
    assert final < 95, final


def test_symbolic_mnist_example():
    """Classic Module.fit workflow with auto-created symbol params
    (reference example/image-classification/train_mnist.py)."""
    out = _run("train_mnist_symbolic.py", "--num-epochs", "3",
               timeout=900)
    acc = float(out.strip().splitlines()[-1].split(":")[1])
    assert acc > 0.9, acc


def test_symbolic_lenet_example():
    """The conv branch: symbolic Convolution/Pooling auto-params."""
    out = _run("train_mnist_symbolic.py", "--network", "lenet",
               "--num-epochs", "1", timeout=900)
    acc = float(out.strip().splitlines()[-1].split(":")[1])
    assert acc > 0.9, acc


def test_quantize_model_example():
    """Post-training INT8 flow: train fp32 -> calibrate -> compare
    (reference example/quantization).  The quantized-layer count proves
    the rewrite actually engaged (a hybridize-cache bypass once made
    this comparison fp32-vs-fp32)."""
    out = _run("quantize_model.py", "--epochs", "2", timeout=900)
    lines = out.strip().splitlines()
    n_q = int([l for l in lines if l.startswith("quantized layers")][0]
              .split(":")[1])
    assert n_q == 4, out
    drop = float(lines[-1].split(":")[1])
    assert abs(drop) < 0.1, out


def test_feedforward_mnist_example():
    out = _run("train_mnist_feedforward.py", "--epochs", "4")
    assert "final test accuracy" in out
    assert "checkpoint roundtrip OK" in out


def test_long_context_example():
    out = _run("train_long_context.py", "--seq-len", "128", "--steps",
               "30", "--batch", "2", "--d-model", "32", "--heads", "2",
               "--layers", "1")
    assert "final loss" in out
    assert "sp=2" in out
