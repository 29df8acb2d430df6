"""Async double-buffered input pipeline (mxnet_tpu/io/prefetch.py).

* DevicePrefetcher: bit-parity loss trajectory, bounded queue, error
  transparency, clean shutdown, data_wait telemetry
* the env catalog holds its one knob (MX_PREFETCH_DEPTH) and none of the
  names that went with the executable store and the pre-ledger bench
* mxlint reinjection: a host sync in the prefetch handoff and disk I/O
  in the batcher loop both trip host-sync-in-hot-path
"""
import os
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import mxnet_tpu as mx                                       # noqa: E402
from mxnet_tpu import gluon, nd, telemetry                   # noqa: E402
from mxnet_tpu.base import environment                       # noqa: E402
from mxnet_tpu.io.prefetch import DevicePrefetcher           # noqa: E402


# ---------------------------------------------------------------------------
# DevicePrefetcher
# ---------------------------------------------------------------------------

def _mlp_loss_traj(use_prefetch, steps=6):
    from mxnet_tpu.gluon import nn
    mx.random.seed(0)
    np.random.seed(0)
    net = nn.Sequential()
    net.add(nn.Dense(16, in_units=8, activation="relu"))
    net.add(nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05, "momentum": 0.9})
    loss_fn = gluon.loss.L2Loss()
    rng = np.random.RandomState(7)
    batches = [(rng.randn(8, 8).astype(np.float32),
                rng.randn(8, 4).astype(np.float32))
               for _ in range(steps)]
    from mxnet_tpu import autograd

    def one(xb, yb):
        with autograd.record():
            loss = loss_fn(net(xb), yb)
        loss.backward()
        tr.step(batch_size=8)
        return float(loss.mean().asnumpy())

    if use_prefetch:
        with DevicePrefetcher(iter(batches)) as pf:
            return [one(nd.NDArray(xb), nd.NDArray(yb)) for xb, yb in pf]
    return [one(nd.array(xb), nd.array(yb)) for xb, yb in batches]


def test_prefetch_bit_parity_loss_trajectory():
    assert _mlp_loss_traj(False) == _mlp_loss_traj(True)


def test_prefetch_bounded_queue_and_order():
    produced = []

    def src():
        for i in range(50):
            produced.append(i)
            yield (np.full((2,), i, np.float32),)

    pf = DevicePrefetcher(src(), depth=2)
    first = next(pf)
    time.sleep(0.3)
    assert len(produced) <= 5           # depth + in-flight margin
    assert float(first[0][0]) == 0.0
    out = [float(b[0][0]) for b in pf]
    assert out == [float(i) for i in range(1, 50)]
    pf.close()


def test_prefetch_error_surfaces_on_consumer():
    def bad():
        yield (np.zeros((1,)),)
        raise RuntimeError("disk on fire")

    pf = DevicePrefetcher(bad())
    next(pf)
    with pytest.raises(mx.base.MXNetError, match="disk on fire"):
        next(pf)
    pf.close()


def test_prefetch_close_idempotent_and_bounded():
    def src():
        while True:
            yield (np.zeros((1,)),)

    pf = DevicePrefetcher(src(), depth=1)
    next(pf)
    t0 = time.monotonic()
    pf.close()
    pf.close()
    assert time.monotonic() - t0 < 5
    with pytest.raises(mx.base.MXNetError):
        next(pf)


def test_prefetch_data_wait_phase_observed():
    inst0 = telemetry.registry.find("step_phase_seconds",
                                    {"phase": "data_wait"})
    c0 = inst0.snapshot()["count"] if inst0 is not None else 0
    with environment("MX_TELEMETRY", "1"):
        with DevicePrefetcher([(np.zeros((1,)),)] * 3) as pf:
            for _ in pf:
                pass
    inst = telemetry.registry.find("step_phase_seconds",
                                   {"phase": "data_wait"})
    assert inst is not None
    assert inst.snapshot()["count"] >= c0 + 3


def test_prefetch_ndarray_leaves_roundtrip():
    x = nd.array(np.arange(6, dtype=np.float32).reshape(2, 3))
    with DevicePrefetcher([(x,)]) as pf:
        (out,) = next(pf)
    assert isinstance(out, nd.NDArray)
    np.testing.assert_array_equal(out.asnumpy(), x.asnumpy())


def test_prefetch_depth_env(tmp_path):
    with environment("MX_PREFETCH_DEPTH", "5"):
        from mxnet_tpu.io.prefetch import prefetch_depth
        assert prefetch_depth() == 5
    with environment("MX_PREFETCH_DEPTH", "0"):
        assert __import__(
            "mxnet_tpu.io.prefetch", fromlist=["prefetch_depth"]
        ).prefetch_depth() == 1


# ---------------------------------------------------------------------------
# env catalog + mxlint reinjection
# ---------------------------------------------------------------------------

def test_new_env_vars_cataloged():
    from mxnet_tpu.base import ENV_CATALOG
    # the prefetcher keeps its one knob; the four that went with the
    # executable store and the pre-ledger bench stay gone, and so do the
    # flash kernels' two block-unit knobs (a constant of ops/attention.py)
    assert [v for v in ENV_CATALOG if "PREFETCH" in v] == \
        ["MX_PREFETCH_DEPTH"]
    assert not [v for v in ENV_CATALOG
                if "COMPILE_CACHE" in v or "BENCH" in v]
    assert len(ENV_CATALOG) == 100


def _lint_source(code, path):
    from tools.mxlint import lint_source
    return lint_source(code, path)


def _rules_of(diags):
    return {d.rule for d in diags}


def test_reinjected_sync_in_prefetch_handoff_trips():
    p = os.path.join(REPO, "mxnet_tpu", "io", "prefetch.py")
    with open(p) as f:
        code = f.read()
    anchor = "_telemetry.observe_phase(\"data_wait\", " \
             "self._clock() - t0)"
    assert anchor in code, "prefetch handoff moved; update this test"
    bad = code.replace(
        anchor, anchor + "\n        _dbg = item[0].asnumpy()")
    diags = _lint_source(bad, "mxnet_tpu/io/prefetch.py")
    assert "host-sync-in-hot-path" in _rules_of(diags)


def test_reinjected_disk_io_in_batcher_loop_trips():
    # the satellite's contract verbatim: no disk I/O inside the batcher
    # loop — an open() reintroduced between dequeue and dispatch trips
    # host-sync-in-hot-path
    p = os.path.join(REPO, "mxnet_tpu", "serve", "batcher.py")
    with open(p) as f:
        code = f.read()
    anchor = "batch = self._collect()"
    assert anchor in code, "Batcher._loop moved; update this test"
    bad = code.replace(
        anchor,
        anchor + "\n            open('/tmp/spill', 'a').write('x')")
    diags = _lint_source(bad, "mxnet_tpu/serve/batcher.py")
    assert "host-sync-in-hot-path" in _rules_of(diags)
