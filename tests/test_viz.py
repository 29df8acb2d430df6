"""mx.viz (print_summary / plot_network) + the opperf harness.

Reference: python/mxnet/visualization.py, benchmark/opperf/opperf.py.
"""
import json
import os
import subprocess
import sys

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import symbol as sym

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp_symbol():
    data = sym.Variable("data")
    h = sym.FullyConnected(data, sym.Variable("fc1_weight"),
                           sym.Variable("fc1_bias"), num_hidden=8,
                           name="fc1")
    h = sym.Activation(h, act_type="relu", name="relu1")
    out = sym.FullyConnected(h, sym.Variable("fc2_weight"),
                             sym.Variable("fc2_bias"), num_hidden=3,
                             name="fc2")
    return sym.SoftmaxOutput(out, sym.Variable("softmax_label"),
                             name="softmax")


def test_print_summary_counts_params(capsys):
    table = mx.viz.print_summary(_mlp_symbol(), shape={"data": (2, 4)})
    assert "fc1 (FullyConnected)" in table
    assert "fc2 (FullyConnected)" in table
    # fc1: 4*8+8 = 40; fc2: 8*3+3 = 27
    assert "Total params: 67" in table
    assert "67" in capsys.readouterr().out


def test_plot_network_dot_source(tmp_path):
    dot = mx.viz.plot_network(_mlp_symbol(), title="mlp")
    # the genuine graphviz package emits unquoted ids; the shim quotes —
    # normalize before asserting
    src = dot.source.replace('"', "")
    assert "digraph" in src
    assert "fc1 -> relu1" in src and "relu1 -> fc2" in src
    # weights hidden by default
    assert "fc1_weight" not in src
    full = mx.viz.plot_network(_mlp_symbol(), hide_weights=False)
    assert "fc1_weight" in full.source.replace('"', "")
    try:
        path = dot.render(str(tmp_path / "mlp"))
    except Exception:
        path = None  # graphviz package without the dot BINARY: fine
    if path:
        assert os.path.exists(path)


def test_opperf_harness_runs_subset():
    env = dict(os.environ, MX_FORCE_CPU="1", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "opperf.py"),
         "--ops", "relu,softmax,_plus_scalar", "--runs", "5"],
        capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr
    summary = json.loads(r.stdout.strip().splitlines()[-1])
    assert summary["num_ops"] == 3
    assert summary["num_errors"] == 0
    assert summary["median_eager_us"] > 0
    assert summary["median_dispatch_overhead_us"] is not None
