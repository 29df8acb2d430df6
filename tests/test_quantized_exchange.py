"""ISSUE 5: quantized, overlap-scheduled gradient exchange.

Pins the tentpole layers:
  * int8 per-block quantize→dequantize error bounds (error <= scale/2
    per element, scale = max|block|/127) across block sizes;
  * error-feedback accumulation identity — over K steps the sum of
    dequantized payloads + the final residual equals the sum of true
    gradients (gradient mass is delayed, never lost) for int8 AND 2bit;
  * device/host packed-2bit wire-format bit parity;
  * the EQuARX-style dequant-sum-requant collective merge body;
  * the compact dist_async wire codec (QGRAD tuples) end-to-end over a
    real TCP server, server-side dequantize before the accumulator;
  * overlap scheduling — readiness planner unit closing, reverse-packed
    bucket order, hook firing order (late layers first), overlap ==
    serialized parity through a real 2-device Trainer fit, and the
    relaunch-on-rewrite guard;
  * loss-trajectory parity: int8/2bit-compressed DP training tracks the
    fp32 trajectory within documented tolerance.
"""
import socket
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.engine import engine
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import quantization as qops

import jax.numpy as jnp


# ---------------------------------------------------------------------------
# int8 kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block", [16, 64, 256])
@pytest.mark.parametrize("n", [16, 100, 1000])
def test_int8_roundtrip_error_bound_per_block(block, n):
    """|x - dequant(quant(x))| <= scale/2 per element, where scale is the
    per-block max|x|/127 — the symmetric-quantization bound."""
    rng = np.random.RandomState(block * 1000 + n)
    x = (rng.randn(n) * rng.uniform(0.1, 10)).astype(np.float32)
    q, scales, res = qops.quantize_int8_blocks(
        jnp.asarray(x), jnp.zeros((n,)), block)
    deq = np.asarray(qops.dequantize_int8_blocks(q, scales, n))
    nb = -(-n // block)
    assert np.asarray(q).shape == (nb * block,)
    assert np.asarray(scales).shape == (nb,)
    pad = np.zeros(nb * block, np.float32)
    pad[:n] = np.abs(x)
    per_block_scale = pad.reshape(nb, block).max(axis=1) / 127.0
    bound = np.repeat(per_block_scale, block)[:n] / 2 + 1e-7
    assert np.all(np.abs(deq - x) <= bound), np.abs(deq - x).max()
    # the residual is exactly the error (error feedback's carry)
    np.testing.assert_allclose(np.asarray(res), x - deq, atol=1e-6)


def test_int8_wire_bytes_accounting():
    # 1000 elems, block 256 -> 4 blocks: 1024 padded codes + 4 f32 scales
    assert qops.int8_wire_bytes(1000, 256) == 1024 + 16
    assert qops.two_bit_wire_bytes(50) == 4 * 4 + 4   # 4 words + threshold
    # the acceptance ratio: >= 3.5x fewer bytes than fp32 at default block
    n = 1 << 20
    assert 4 * n / qops.int8_wire_bytes(n, 256) > 3.5


@pytest.mark.parametrize("mode", ["int8", "2bit"])
def test_error_feedback_accumulation_identity(mode):
    """sum(dequantized payloads) + final residual == sum(true grads):
    quantization error is carried, never lost."""
    from mxnet_tpu.kvstore.gradient_compression import GradientCompression
    gc = GradientCompression(type=mode, threshold=0.5, block=16)
    rng = np.random.RandomState(7)
    n = 100
    grads = [(rng.randn(n) * 0.2).astype(np.float32) for _ in range(12)]
    emitted = np.zeros(n, np.float32)
    for g in grads:
        emitted += np.asarray(gc.quantize("k", jnp.asarray(g)))
    residual = np.asarray(gc._residuals["k"])
    np.testing.assert_allclose(emitted + residual, np.sum(grads, axis=0),
                               rtol=1e-4, atol=1e-4)


def test_residual_rolls_on_shape_change():
    from mxnet_tpu.kvstore.gradient_compression import GradientCompression
    gc = GradientCompression(type="int8", block=16)
    gc.quantize("k", jnp.ones((32,)))
    assert gc._residuals["k"].shape == (32,)
    gc.quantize("k", jnp.ones((16,)))    # layout change: fresh residual
    assert gc._residuals["k"].shape == (16,)


def test_dequant_sum_requant_merge():
    """The collective merge body: dequantize each worker's payload at its
    own scales, sum, requantize — result tracks the true sum within the
    merged scale's quantization step."""
    rng = np.random.RandomState(3)
    block, nb, w = 32, 4, 3
    xs = [(rng.randn(nb * block) * (i + 1)).astype(np.float32)
          for i in range(w)]
    qs, ss = [], []
    for x in xs:
        q, s, _ = qops.quantize_int8_blocks(jnp.asarray(x), jnp.zeros_like(
            jnp.asarray(x)), block)
        qs.append(np.asarray(q))
        ss.append(np.asarray(s))
    qo, so = qops.dequant_sum_requant_int8(
        jnp.asarray(np.stack(qs)), jnp.asarray(np.stack(ss)))
    merged = np.asarray(qops.dequantize_int8_blocks(qo, so, nb * block))
    true = np.sum(xs, axis=0)
    # two quantizations deep: per-worker error + requant error
    per_in = np.stack([np.repeat(s, block) for s in ss]).sum(axis=0) / 2
    bound = per_in + np.repeat(np.asarray(so), block) / 2 + 1e-6
    assert np.all(np.abs(merged - true) <= bound)


def test_pack_2bit_device_host_bit_parity():
    """ops.quantization.pack_2bit_words must emit the exact words the
    host-side pack_2bit does (the PS wire is decoded host-side)."""
    from mxnet_tpu.kvstore.gradient_compression import pack_2bit, unpack_2bit
    t = 0.25
    rng = np.random.RandomState(1)
    levels = rng.choice([-t, 0.0, t], size=53).astype(np.float32)
    dev = np.asarray(qops.pack_2bit_words(jnp.asarray(levels)))
    host = pack_2bit(levels, t)
    np.testing.assert_array_equal(dev, host)
    back_dev = np.asarray(qops.unpack_2bit_words(jnp.asarray(dev), t, 53))
    np.testing.assert_allclose(back_dev, levels)
    np.testing.assert_allclose(unpack_2bit(dev, 53, t), levels)


# ---------------------------------------------------------------------------
# compression config + wire codec
# ---------------------------------------------------------------------------

def test_set_gradient_compression_contract():
    from mxnet_tpu import kvstore
    kv = kvstore.create("local")
    with pytest.raises(ValueError, match="1bit"):
        kv.set_gradient_compression({"type": "1bit"})
    kv.set_gradient_compression({"type": "int8", "block": 64})
    assert kv._gc.type == "int8" and kv._gc.block == 64
    assert kv._gc.get_params()["block"] == 64
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.25})
    assert kv._gc.type == "2bit" and kv._gc.threshold == 0.25
    kv.set_gradient_compression({"type": "bf16"})
    assert kv._gc is None and kv._compress_bf16
    with pytest.raises(ValueError):
        from mxnet_tpu.kvstore.gradient_compression import \
            GradientCompression
        GradientCompression(type="bf16")     # cast path, not GC state


@pytest.mark.parametrize("mode", ["int8", "2bit"])
def test_wire_codec_roundtrip(mode):
    from mxnet_tpu.kvstore import gradient_compression as gcomp
    gc = gcomp.GradientCompression(type=mode, threshold=0.5, block=16)
    rng = np.random.RandomState(11)
    x = rng.randn(5, 7).astype(np.float32)
    wire = gc.encode("k", jnp.asarray(x))
    assert gcomp.is_wire_payload(wire)
    assert not gcomp.is_wire_payload(x)
    deq = gcomp.decode_wire(wire)
    assert deq.shape == (5, 7) and deq.dtype == np.float32
    # the decoded payload is the quantized view of x (error in residual)
    residual = np.asarray(gc._residuals["k"]).reshape(5, 7)
    np.testing.assert_allclose(deq + residual, x, rtol=1e-4, atol=1e-4)
    # compact: int8 ~1B/elem + scales; 2bit ~2 bits/elem
    payload = wire[5]
    nbytes = len(payload) if isinstance(payload, bytes) else payload.nbytes
    assert nbytes < x.size * 4


# ---------------------------------------------------------------------------
# collective (ici) quantized exchange
# ---------------------------------------------------------------------------

def test_ici_int8_bucketed_exchange_tracks_true_sum():
    """Single-process ici store, int8: the batched push/pull quantizes
    per bucket (one residual per bucket name) and the pulled values track
    the true per-key gradients within the block quantization error."""
    from mxnet_tpu import kvstore
    kv = kvstore.create("ici")
    kv.set_gradient_compression({"type": "int8", "block": 64})
    keys = list(range(6))
    shapes = [(16,), (8, 8), (32,), (4, 4), (64,), (2,)]
    for k, s in zip(keys, shapes):
        kv.init(k, nd.zeros(s))
    rng = np.random.RandomState(0)
    grads = [nd.array(rng.randn(*s).astype(np.float32)) for s in shapes]
    w0 = engine.wire_bytes
    kv.push(keys, [[g] for g in grads])
    outs = [nd.zeros(s) for s in shapes]
    kv.pull(keys, outs)
    wire = engine.wire_bytes - w0
    total = sum(int(np.prod(s)) for s in shapes)
    assert wire < total * 4, (wire, total * 4)     # compressed on the wire
    for g, o in zip(grads, outs):
        g = g.asnumpy()
        err = np.abs(o.asnumpy() - g)
        assert err.max() <= np.abs(g).max() / 127 + 1e-6, err.max()


def test_ici_2bit_exchange_emits_levels():
    from mxnet_tpu import kvstore
    kv = kvstore.create("ici")
    t = 0.5
    kv.set_gradient_compression({"type": "2bit", "threshold": t})
    kv.init("k", nd.zeros((8,)))
    g = nd.array(np.array([0.7, -0.7, 0.1, -0.1, 0.0, 2.0, -2.0, 0.4],
                          np.float32))
    kv.push("k", g)
    out = nd.zeros((8,))
    kv.pull("k", out=out)
    assert set(np.round(np.unique(out.asnumpy()), 5)) <= {-t, 0.0, t}


# ---------------------------------------------------------------------------
# dist_async compact wire over a real server
# ---------------------------------------------------------------------------

def _free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _start_server(port):
    from mxnet_tpu.kvstore.server import serve_forever
    t = threading.Thread(target=serve_forever,
                         kwargs=dict(port=port, num_workers=1), daemon=True)
    t.start()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port), timeout=0.2).close()
            return t
        except OSError:
            time.sleep(0.05)
    raise RuntimeError("server did not come up on %d" % port)


@pytest.fixture
def _dist_async_client(monkeypatch):
    from mxnet_tpu.kvstore.kvstore import KVStoreDistAsync
    monkeypatch.setenv("MX_KVSTORE_HEARTBEAT", "0")
    monkeypatch.delenv("MX_PS_ROOTS", raising=False)
    port = _free_port()
    _start_server(port)
    monkeypatch.setenv("MX_PS_ROOT", "127.0.0.1:%d" % port)
    kv = KVStoreDistAsync()
    yield kv
    kv.stop_server()


@pytest.mark.parametrize("mode", ["int8", "2bit"])
def test_dist_async_compressed_wire_roundtrip(_dist_async_client, mode):
    """PUSH ships the compact QGRAD tuple; the server dequantizes before
    its accumulator, so PULL returns full-width values tracking the true
    gradient within the mode's quantization error."""
    kv = _dist_async_client
    kv.set_gradient_compression({"type": mode, "threshold": 0.5,
                                 "block": 16})
    rng = np.random.RandomState(5)
    # 2bit emits at most +-threshold per push: keep |g| under the
    # threshold (the reference's tuning contract) so error feedback can
    # keep the cumulative sum in its +-(t + |g|max) band
    g = (rng.randn(6, 6) * 0.15).astype(np.float32)
    kv.init("w", nd.zeros((6, 6)))
    w0 = engine.wire_bytes
    kv.push("w", nd.array(g))
    wire = engine.wire_bytes - w0
    assert 0 < wire < g.nbytes                      # compact on the wire
    out = nd.zeros((6, 6))
    kv.pull("w", out=out)
    got = out.asnumpy()
    if mode == "int8":
        assert np.abs(got - g).max() <= np.abs(g).max() / 127 + 1e-6
    else:
        assert set(np.round(np.unique(got), 5)) <= {-0.5, 0.0, 0.5}
    # error feedback across pushes: the cumulative pulled sum stays in
    # the +-(threshold + |g|max) band of the true sum (2bit) / within
    # the accumulated block-quantization error (int8)
    for _ in range(10):
        kv.push("w", nd.array(g))
    kv.pull("w", out=out)
    total = out.asnumpy()
    atol = (0.5 + np.abs(g).max() if mode == "2bit"
            else np.abs(g).max() / 127 * 11) + 1e-5
    np.testing.assert_allclose(total, 11 * g, atol=atol)


def test_dist_async_bucketed_compressed_push(_dist_async_client,
                                             monkeypatch):
    """Fusion buckets + compression: ONE compact wire tuple per bucket."""
    monkeypatch.setenv("MX_KVSTORE_BUCKET_KB", "1")
    kv = _dist_async_client
    kv.set_gradient_compression({"type": "int8", "block": 16})
    keys = [0, 1, 2]
    shapes = [(8, 8), (16,), (8, 8)]
    for k, s in zip(keys, shapes):
        kv.init(k, nd.zeros(s))
    rng = np.random.RandomState(2)
    grads = [nd.array(rng.randn(*s).astype(np.float32)) for s in shapes]
    kv.push(keys, grads)
    assert kv._bucket_inited                        # buckets went out
    outs = [nd.zeros(s) for s in shapes]
    kv.pull(keys, outs)
    for g, o in zip(grads, outs):
        g = g.asnumpy()
        assert np.abs(o.asnumpy() - g).max() <= np.abs(g).max() / 127 + 1e-6


# ---------------------------------------------------------------------------
# overlap scheduling
# ---------------------------------------------------------------------------

def test_readiness_planner_reverse_buckets_close_in_production_order():
    from mxnet_tpu.kvstore.bucketing import ReadinessPlanner, plan_buckets
    keys = list(range(6))
    shapes = [(8,)] * 6
    buckets, solo = plan_buckets(keys, shapes, ["float32"] * 6, [4] * 6,
                                 ["default"] * 6, max_bytes=64,
                                 reverse=True)
    # reverse packing: bucket 0 holds the LAST params (backward's first)
    assert [sorted(b.positions) for b in buckets] == [[4, 5], [2, 3],
                                                      [0, 1]]
    planner = ReadinessPlanner(buckets, solo)
    closed = []
    for pos in reversed(keys):          # backward production order
        closed.extend(planner.note(pos))
    assert closed == [0, 1, 2]          # units close in launch order
    assert planner.pending() == []
    assert not planner.stale


def test_readiness_planner_copies_and_stale():
    from mxnet_tpu.kvstore.bucketing import Bucket, ReadinessPlanner
    b = Bucket(0, [0, 1], ["a", "b"], [4, 4], [(4,), (4,)], "float32")
    p = ReadinessPlanner([b], [2], copies=2)
    assert p.note(0, 0) == [] and p.note(0, 1) == []   # 1 of 2 members
    assert p.note(1, 0) == []
    assert p.note(1, 1) == [0]                         # bucket closes
    assert p.note(2, 0) == [] and p.note(2, 1) == [1]  # solo unit
    assert not p.stale
    assert p.note(0, 0) == [] and p.stale              # double event
    # unknown positions are ignored (params outside the exchange set)
    assert p.note(99) == []


def test_backward_fires_grad_hooks_late_layers_first():
    """Incremental leaf finalization: each grad hook fires exactly once,
    the grad is FINAL at hook time, and layers closer to the head
    finalize first — the order reverse-packed buckets rely on."""
    mx.random.seed(0)
    net = nn.Sequential()
    net.add(nn.Dense(8, in_units=4, activation="relu"))
    net.add(nn.Dense(8, in_units=8, activation="relu"))
    net.add(nn.Dense(2, in_units=8))
    net.initialize(mx.init.Xavier())
    params = list(net.collect_params().values())
    x = nd.array(np.random.RandomState(0).randn(4, 4).astype(np.float32))
    with autograd.record():
        loss = net(x).sum()
    fired = []
    for i, p in enumerate(params):
        g = p.list_grad()[0]
        g._grad_hook = (lambda i=i, g=g:
                        fired.append((i, np.asarray(g._jax).copy())))
    try:
        loss.backward()
    finally:
        for p in params:
            p.list_grad()[0]._grad_hook = None
    assert sorted(i for i, _ in fired) == list(range(len(params)))
    # grad value at hook time == final grad (finality)
    for i, snap in fired:
        np.testing.assert_array_equal(
            snap, np.asarray(params[i].list_grad()[0]._jax))
    # the LAST layer's params finalize before the first layer's
    order = [i for i, _ in fired]
    assert order.index(len(params) - 1) < order.index(0)


def _fit_two_device(compress=None, steps=4, rewrite_grads=False):
    mx.random.seed(0)
    ctxs = [mx.cpu(0), mx.cpu(1)]
    net = nn.Sequential()
    net.add(nn.Dense(16, in_units=8, activation="relu"))
    net.add(nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier(), ctx=ctxs)
    params = list(net.collect_params().values())
    tr = gluon.Trainer(params, "sgd", {"learning_rate": 0.05},
                       kvstore="device", compression_params=compress)
    loss_fn = gluon.loss.L2Loss()
    rng = np.random.RandomState(0)
    X = rng.randn(8, 8).astype(np.float32)
    Y = rng.randn(8, 4).astype(np.float32)
    losses = []
    for _ in range(steps):
        tot = 0.0
        with autograd.record():
            for ctx, sl in zip(ctxs, (slice(0, 4), slice(4, None))):
                loss = loss_fn(net(nd.array(X[sl], ctx=ctx)),
                               nd.array(Y[sl], ctx=ctx))
                loss.backward()
                tot += float(loss.mean().asnumpy())
        if rewrite_grads:
            # out-of-band mutation AFTER backward (and after any armed
            # overlap launches): halve every gradient
            for p in params:
                for g in p.list_grad():
                    g._set_jax(g._jax * 0.5)
        tr.step(batch_size=8)
        losses.append(tot)
    return losses, {k: v.data(ctxs[0]).asnumpy()
                    for k, v in net.collect_params().items()}


@pytest.mark.parametrize("compress", [None, {"type": "int8"}])
def test_overlap_matches_serialized_exchange(monkeypatch, compress):
    """MX_EXCHANGE_OVERLAP=1 is a pure scheduling change: params after a
    multi-step 2-device fit equal the serialized exchange bit-for-bit
    modulo fp accumulation order (same dispatches, earlier)."""
    monkeypatch.setenv("MX_EXCHANGE_OVERLAP", "0")
    _, base = _fit_two_device(compress=compress)
    monkeypatch.setenv("MX_EXCHANGE_OVERLAP", "1")
    _, overlapped = _fit_two_device(compress=compress)
    assert set(base) == set(overlapped)
    for k in base:
        np.testing.assert_allclose(overlapped[k], base[k],
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("compress", [None, {"type": "int8"},
                                      {"type": "2bit", "threshold": 0.05}])
def test_overlap_relaunches_on_grad_rewrite(monkeypatch, compress):
    """A gradient rewritten between backward and step() (manual grad
    scaling) invalidates the launched exchange: the snapshot guard
    relaunches the unit — and with compression on, the relaunch first
    ROLLS BACK the discarded launch's error-feedback step — so overlap
    matches the serialized result exactly."""
    monkeypatch.setenv("MX_EXCHANGE_OVERLAP", "0")
    _, base = _fit_two_device(compress=compress, rewrite_grads=True)
    monkeypatch.setenv("MX_EXCHANGE_OVERLAP", "1")
    _, overlapped = _fit_two_device(compress=compress, rewrite_grads=True)
    for k in base:
        np.testing.assert_allclose(overlapped[k], base[k],
                                   rtol=1e-5, atol=1e-6)


def test_session_relaunch_rolls_back_error_feedback():
    """Session-level EF rollback: launch a unit, rewrite its input,
    drain.  The relaunch must quantize the NEW value against the
    PRE-launch residual — the discarded payload's EF step un-happens, so
    the pulled value + residual account for exactly the committed
    gradient (no mass lost, no double-stepped residual)."""
    from mxnet_tpu import kvstore
    kv = kvstore.create("ici")
    kv.set_gradient_compression({"type": "int8", "block": 16})
    kv.init("k", nd.zeros((32,)))
    rng = np.random.RandomState(0)
    g = nd.array(rng.randn(32).astype(np.float32))
    sess = kv.begin_exchange(["k"], [[g]])
    sess.notify_key("k")                       # launches (consumes EF)
    true_committed = 0.5 * g.asnumpy()
    g._set_jax(g._jax * 0.5)                   # rewrite after launch
    sess.drain()                               # must rollback + relaunch
    out = nd.zeros((32,))
    kv.pull("k", out=out)
    residual = np.asarray(kv._gc._residuals["k"])
    np.testing.assert_allclose(out.asnumpy() + residual, true_committed,
                               rtol=1e-5, atol=1e-6)
    # donation resumes after commit (no pins left behind)
    assert not kv._gc._pinned


def test_overlap_residual_wire_keys_stable_across_steps(monkeypatch):
    """With overlap enabled, the first step's serialized fallback runs
    through the session machinery too, so every step quantizes under the
    SAME reverse-packed bucket names — no orphaned error-feedback
    residual (and no silently dropped compression error) at the
    serialized→overlapped transition."""
    from mxnet_tpu.kvstore import create as kv_create
    monkeypatch.setenv("MX_EXCHANGE_OVERLAP", "1")
    mx.random.seed(0)
    ctxs = [mx.cpu(0), mx.cpu(1)]
    net = nn.Sequential()
    net.add(nn.Dense(8, in_units=4, activation="relu"))
    net.add(nn.Dense(2, in_units=8))
    net.initialize(mx.init.Xavier(), ctx=ctxs)
    kv = kv_create("ici")
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05}, kvstore=kv,
                       compression_params={"type": "int8"})
    loss_fn = gluon.loss.L2Loss()
    rng = np.random.RandomState(0)
    X = rng.randn(8, 4).astype(np.float32)
    Y = rng.randn(8, 2).astype(np.float32)
    key_sets = []
    for _ in range(3):
        with autograd.record():
            for ctx, sl in zip(ctxs, (slice(0, 4), slice(4, None))):
                loss_fn(net(nd.array(X[sl], ctx=ctx)),
                        nd.array(Y[sl], ctx=ctx)).backward()
        tr.step(batch_size=8)
        key_sets.append(frozenset(kv._gc._residuals))
    assert key_sets[0] == key_sets[1] == key_sets[2], key_sets
    # the keys are bucket names (per-bucket residuals, not per-param)
    assert all(str(k).startswith("__fusedb")
               for k in key_sets[0]), key_sets[0]


def test_ici_sparse_push_survives_wire_accounting():
    """row_sparse payloads (no _jax, nnz-keyed) must pass through the
    ici store's wire accounting and int8 gates untouched — with and
    without compression installed (the supported sparse flow: a
    store-side updater applies the sparse gradient)."""
    from mxnet_tpu import kvstore
    from mxnet_tpu import optimizer as opt
    for compress in (None, {"type": "int8"}):
        kv = kvstore.create("ici")
        if compress:
            kv.set_gradient_compression(compress)
        kv.set_optimizer(opt.create("sgd", learning_rate=1.0))
        dense = nd.array(np.eye(4, 3, dtype=np.float32))
        w0 = np.ones((4, 3), np.float32)
        kv.init(0, nd.array(w0))
        r = dense.tostype("row_sparse")
        kv.push([0], [[r]])                  # must not crash
        out = nd.zeros((4, 3))
        kv.pull([0], [out])
        # sgd lr=1: w = w0 - grad
        np.testing.assert_allclose(out.asnumpy(),
                                   w0 - dense.asnumpy(), atol=1e-5)


def test_overlap_grad_req_flip_between_steps(monkeypatch):
    """Unfreezing a param between steps changes the exchange key set: the
    armed session no longer covers it, must be discarded (EF state rolled
    back), and the newly trainable param's gradients still exchange —
    params match the serialized path exactly."""
    def run(overlap):
        monkeypatch.setenv("MX_EXCHANGE_OVERLAP", overlap)
        mx.random.seed(0)
        ctxs = [mx.cpu(0), mx.cpu(1)]
        net = nn.Sequential()
        net.add(nn.Dense(8, in_units=4, activation="relu"))
        net.add(nn.Dense(2, in_units=8))
        net.initialize(mx.init.Xavier(), ctx=ctxs)
        params = list(net.collect_params().values())
        frozen = params[:2]                  # first layer starts frozen
        for p in frozen:
            p.grad_req = "null"
        tr = gluon.Trainer(params, "sgd", {"learning_rate": 0.05},
                           kvstore="device",
                           compression_params={"type": "int8"})
        loss_fn = gluon.loss.L2Loss()
        rng = np.random.RandomState(0)
        X = rng.randn(8, 4).astype(np.float32)
        Y = rng.randn(8, 2).astype(np.float32)
        for step in range(4):
            if step == 2:                    # unfreeze mid-training
                for p in frozen:
                    p.grad_req = "write"
            with autograd.record():
                for ctx, sl in zip(ctxs, (slice(0, 4), slice(4, None))):
                    loss_fn(net(nd.array(X[sl], ctx=ctx)),
                            nd.array(Y[sl], ctx=ctx)).backward()
            tr.step(batch_size=8)
        # every device copy identical (the unfrozen layer exchanged too)
        for p in params:
            ds = [d.asnumpy() for d in p.list_data()]
            for d in ds[1:]:
                np.testing.assert_array_equal(ds[0], d)
        return {k: v.data(ctxs[0]).asnumpy()
                for k, v in net.collect_params().items()}

    base = run("0")
    overlapped = run("1")
    for k in base:
        np.testing.assert_allclose(overlapped[k], base[k],
                                   rtol=1e-5, atol=1e-6)


def test_trainer_picks_up_env_default_compression(monkeypatch):
    monkeypatch.setenv("MX_GRAD_COMPRESS", "int8")
    net = nn.Dense(2, in_units=4)
    net.initialize(mx.init.Xavier(), ctx=[mx.cpu(0), mx.cpu(1)])
    tr = gluon.Trainer(net.collect_params(), "sgd", kvstore="device")
    assert tr._compression_params == {"type": "int8"}
    # explicit params always win over the env default
    tr2 = gluon.Trainer(net.collect_params(), "sgd", kvstore="device",
                        compression_params={"type": "bf16"})
    assert tr2._compression_params == {"type": "bf16"}


# ---------------------------------------------------------------------------
# loss-trajectory parity (dryrun_multichip-style)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("compress,tol", [
    ({"type": "int8"}, 0.02),
    # ±0.05 a step against gradients of order 1: the 2bit run lags by
    # ~6 % a step whatever the code does.  Both trajectories equal a
    # float64 numpy reference (quantize after the local reduce, one
    # residual a key) to 1e-6; the lag at step 6 on jax 0.9.0 is 0.267
    # for this seed (0) and 0.252-0.719 over seeds 0-7, so the limit is
    # this seed's reading and a margin, not a property of 2bit.
    ({"type": "2bit", "threshold": 0.05}, 0.30),
    ({"type": "bf16"}, 0.02),
])
def test_compressed_training_loss_parity(monkeypatch, compress, tol):
    """2-device DP training under compression tracks the fp32 loss
    trajectory: per-step relative divergence stays within the documented
    tolerance (int8/bf16 tight; 2bit coarser — its error feedback pays
    back over steps, not within one)."""
    monkeypatch.setenv("MX_EXCHANGE_OVERLAP", "1")
    base, _ = _fit_two_device(compress=None, steps=6)
    got, _ = _fit_two_device(compress=compress, steps=6)
    assert got[-1] < got[0]                     # it trains
    rel = [abs(a - b) / max(1e-6, abs(b)) for a, b in zip(got, base)]
    assert max(rel) <= tol, (rel, base, got)
