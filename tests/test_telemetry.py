"""Runtime telemetry (ISSUE 8): instrument registry semantics (incl.
under threads), JSON/Prometheus exposition, step-phase spans feeding the
profiler and the flight recorder, client<->server trace-ID propagation
over a real socket (retry + replay child events), crash dumps on a
virtual-clock watchdog trip and the NaN raise policy, heartbeat JSON
round-trip into the supervisor's fleet status table, and the mxlint
reinjection proving a host sync inside a span helper trips the hot-path
rule."""
import importlib.util
import json
import os
import socket
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from mxnet_tpu import fault, health, telemetry  # noqa: E402
from mxnet_tpu.telemetry import (Counter, Gauge, Histogram,  # noqa: E402
                                 Registry, registry)


def _load_launch():
    spec = importlib.util.spec_from_file_location(
        "mx_launch_telemetry_test", os.path.join(REPO, "tools", "launch.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def clean_telemetry(monkeypatch):
    """Isolated ring + trace buffer; MX_TELEMETRY forced on."""
    monkeypatch.setenv("MX_TELEMETRY", "1")
    telemetry.flight_recorder.clear()
    telemetry.clear_trace()
    yield
    telemetry.flight_recorder.clear()
    telemetry.clear_trace()


# ---------------------------------------------------------------------------
# instruments
# ---------------------------------------------------------------------------

def test_counter_gauge_semantics():
    r = Registry()
    c = r.counter("c", doc="d")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert r.counter("c") is c          # get-or-create
    c.set(0)
    assert c.value == 0
    g = r.gauge("g")
    g.set(7)
    g.dec(3)
    assert g.value == 4
    with pytest.raises(ValueError):
        r.gauge("c")                    # type mismatch on same name


def test_histogram_buckets_and_stats():
    r = Registry()
    h = r.histogram("lat", buckets=(0.001, 0.01, 0.1))
    for v in (0.0005, 0.005, 0.05, 5.0):
        h.observe(v)
    s = h.snapshot()
    assert s["count"] == 4
    assert s["buckets"] == {"0.001": 1, "0.01": 2, "0.1": 3, "+Inf": 4}
    assert s["min"] == 0.0005 and s["max"] == 5.0
    assert abs(s["avg"] - (0.0005 + 0.005 + 0.05 + 5.0) / 4) < 1e-9


def test_labeled_instruments_are_distinct():
    r = Registry()
    a = r.counter("reqs", labels={"cmd": "PUSH"})
    b = r.counter("reqs", labels={"cmd": "PULL"})
    assert a is not b
    a.inc(2)
    b.inc(3)
    snap = r.snapshot()
    assert snap["reqs{cmd=PUSH}"]["value"] == 2
    assert snap["reqs{cmd=PULL}"]["value"] == 3


def test_instruments_exact_under_threads():
    r = Registry()
    c = r.counter("n")
    h = r.histogram("h", buckets=(0.5,))

    def work():
        for _ in range(2000):
            c.inc()
            h.observe(0.25)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 16000
    assert h.snapshot()["count"] == 16000
    assert h.snapshot()["buckets"]["0.5"] == 16000


def test_prometheus_exposition_format():
    r = Registry()
    r.counter("engine.dispatch_count", doc="dispatches").inc(3)
    h = r.histogram("step_phase_seconds", labels={"phase": "forward"},
                    buckets=(0.01, 1.0))
    h.observe(0.005)
    h.observe(2.0)
    text = r.to_prometheus()
    assert "# TYPE mx_engine_dispatch_count counter" in text
    assert "mx_engine_dispatch_count 3" in text
    assert "# TYPE mx_step_phase_seconds histogram" in text
    assert 'mx_step_phase_seconds_bucket{phase="forward",le="0.01"} 1' \
        in text
    assert 'mx_step_phase_seconds_bucket{phase="forward",le="+Inf"} 2' \
        in text
    assert 'mx_step_phase_seconds_count{phase="forward"} 2' in text


def test_json_exposition_roundtrips():
    r = Registry()
    r.counter("a").inc(1)
    r.histogram("b").observe(0.2)
    blob = json.loads(r.to_json())
    assert blob["a"]["value"] == 1
    assert blob["b"]["count"] == 1


# ---------------------------------------------------------------------------
# engine counter fold-in (satellite: aliases keep working)
# ---------------------------------------------------------------------------

def test_engine_counters_are_registry_backed():
    from mxnet_tpu.engine import engine
    base = registry.value("engine.dispatch_count")
    assert engine.dispatch_count == base     # alias reads the registry
    engine.count_dispatch(2)
    assert engine.dispatch_count == base + 2
    assert registry.value("engine.dispatch_count") == base + 2
    # the tools' reset idiom writes through too
    w0 = engine.wire_bytes
    engine.count_wire_bytes(128)
    assert engine.wire_bytes == w0 + 128
    engine.wire_bytes = 0
    assert registry.value("engine.wire_bytes") == 0
    s0 = engine.compiled_steps
    engine.count_step_window(4, dispatches=2)
    assert engine.compiled_steps == s0 + 4


# ---------------------------------------------------------------------------
# phase spans + flight recorder
# ---------------------------------------------------------------------------

def test_phase_spans_accumulate_into_step_record(clean_telemetry):
    with telemetry.phase("forward"):
        pass
    with telemetry.phase("exchange"):
        pass
    rec = telemetry.note_step(steps=1, epoch=2, batch=5, batch_size=32)
    assert rec["epoch"] == 2 and rec["batch"] == 5
    assert set(rec["phases"]) >= {"forward", "exchange"}
    assert "dispatches" in rec and "wire_bytes" in rec
    ps = telemetry.phase_snapshot()
    assert ps["forward"]["count"] >= 1


def test_nested_same_phase_counts_once(clean_telemetry):
    h0 = telemetry.phase_snapshot().get("backward", {}).get("count", 0)
    with telemetry.phase("backward"):
        with telemetry.phase("backward"):      # Module->autograd nesting
            pass
    assert telemetry.phase_snapshot()["backward"]["count"] == h0 + 1
    rec = telemetry.note_step()
    assert rec["phases"]["backward"] > 0


def test_phase_disabled_is_noop(clean_telemetry, monkeypatch):
    monkeypatch.setenv("MX_TELEMETRY", "0")
    span = telemetry.phase("forward")
    with span:
        pass
    assert telemetry.note_step() is None
    assert telemetry.flight_recorder.records() == []


def test_ring_capacity_honors_env(clean_telemetry, monkeypatch):
    monkeypatch.setenv("MX_TELEMETRY_RING", "3")
    telemetry.flight_recorder.clear()        # re-size on next record
    for i in range(7):
        telemetry.note_step(batch=i)
    recs = telemetry.flight_recorder.records()
    assert len(recs) == 3
    assert [r["batch"] for r in recs] == [4, 5, 6]
    assert recs[-1]["step"] == 7             # total steps keep counting


def test_throughput_computed_between_steps(clean_telemetry):
    telemetry.note_step(batch_size=8)
    time.sleep(0.01)
    rec = telemetry.note_step(batch_size=8)
    assert rec["steps_per_sec"] > 0
    assert rec["throughput"] == pytest.approx(8 * rec["steps_per_sec"],
                                              rel=1e-3)


def test_trainer_step_records_flight_data(clean_telemetry):
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd

    net = gluon.nn.Dense(4, in_units=8)
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    loss_fn = gluon.loss.L2Loss()
    x = nd.array(np.random.RandomState(0).randn(4, 8).astype(np.float32))
    y = nd.array(np.zeros((4, 4), np.float32))
    for _ in range(2):
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(batch_size=4)
    recs = telemetry.flight_recorder.records()
    assert len(recs) == 2
    assert "backward" in recs[-1]["phases"]
    assert "optimizer_apply" in recs[-1]["phases"]
    assert recs[-1]["dispatches"] > 0


# ---------------------------------------------------------------------------
# profiler integration (satellite: compiled-step blind spot)
# ---------------------------------------------------------------------------

def test_phase_spans_land_in_profiler_dumps(clean_telemetry):
    from mxnet_tpu import profiler
    profiler.reset()
    profiler.set_state("run")
    try:
        with telemetry.phase("exchange"):
            pass
    finally:
        profiler.set_state("stop")
    agg = json.loads(profiler.dumps(format="json", reset=True))
    assert "phase.exchange" in agg


def test_compiled_step_dispatches_visible_in_profiler(clean_telemetry):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, profiler

    mx.random.seed(0)
    net = gluon.nn.Dense(3, in_units=6)
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05})
    step = trainer.make_compiled_step(net, gluon.loss.L2Loss())
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(4, 6).astype(np.float32))
    y = nd.array(rng.randn(4, 3).astype(np.float32))
    step.step(x, y)                      # deferred init + trace
    step.step(x, y)
    profiler.reset()
    profiler.set_state("run")
    try:
        step.step(x, y)
        Xw = nd.array(np.broadcast_to(np.asarray(x._jax),
                                      (4,) + tuple(x.shape)).copy())
        Yw = nd.array(np.broadcast_to(np.asarray(y._jax),
                                      (4,) + tuple(y.shape)).copy())
        step.run_window(Xw, Yw)
    finally:
        profiler.set_state("stop")
    assert step.compiled, step.fallback_reason
    agg = json.loads(profiler.dumps(format="json", reset=True))
    # single compiled steps and scan windows aggregate separately
    assert "phase.compiled_step" in agg
    assert "phase.compiled_window" in agg
    # and the window's flight record attributes every scanned step
    rec = telemetry.flight_recorder.last()
    assert rec["steps"] == 4 and rec.get("compiled") is True


# ---------------------------------------------------------------------------
# distributed trace propagation over a real socket
# ---------------------------------------------------------------------------

def _free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _start_server(port, num_workers=1):
    from mxnet_tpu.kvstore.server import serve_forever
    t = threading.Thread(target=serve_forever,
                         kwargs=dict(port=port, num_workers=num_workers),
                         daemon=True)
    t.start()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port),
                                     timeout=0.2).close()
            return t
        except OSError:
            time.sleep(0.05)
    raise RuntimeError("server did not come up on %d" % port)


def _stop_server(port, thread):
    from mxnet_tpu.kvstore.server import send_msg, recv_msg
    raw = socket.create_connection(("127.0.0.1", port), timeout=5)
    send_msg(raw, ("STOP", None))
    recv_msg(raw, timeout=5)
    raw.close()
    thread.join(timeout=10)


@pytest.fixture
def traced_client(clean_telemetry, monkeypatch):
    from mxnet_tpu.kvstore.kvstore import KVStoreDistAsync
    monkeypatch.setenv("MX_KVSTORE_RETRY_DEADLINE", "20")
    monkeypatch.setenv("MX_KVSTORE_RETRY_BASE", "0.05")
    monkeypatch.setenv("MX_KVSTORE_RETRY_MAX", "0.25")
    monkeypatch.setenv("MX_KVSTORE_HEARTBEAT", "0")
    monkeypatch.delenv("MX_PS_ROOTS", raising=False)
    port = _free_port()
    thread = _start_server(port)
    monkeypatch.setenv("MX_PS_ROOT", "127.0.0.1:%d" % port)
    telemetry.start_tracing()
    kv = KVStoreDistAsync()
    yield kv
    telemetry.stop_tracing()
    kv.close()
    _stop_server(port, thread)
    fault.clear()


def _spans(name):
    return [e for e in telemetry.trace_events()
            if e["name"] == name and e["ph"] == "X"]


def test_client_server_spans_share_trace_id(traced_client):
    from mxnet_tpu import nd
    kv = traced_client
    kv.init("w", nd.array(np.zeros(4, np.float32)))
    telemetry.clear_trace()
    kv.push("w", nd.array(np.ones(4, np.float32)))
    out = nd.zeros((4,))
    kv.pull("w", out=out)
    np.testing.assert_allclose(out.asnumpy(), np.ones(4))
    for cmd in ("PUSH", "PULL"):
        cli = _spans("kv.client.%s" % cmd)
        srv = _spans("kv.server.%s" % cmd)
        assert cli and srv, (cmd, [e["name"]
                                   for e in telemetry.trace_events()])
        assert srv[0]["args"]["trace_id"] == cli[0]["args"]["trace_id"]
        assert srv[0]["args"]["parent_id"] == cli[0]["args"]["span_id"]


def test_retry_and_replay_child_events(traced_client):
    """A reply lost after the server applied the PUSH: the client span
    gains a ``retry`` child event, the server's second handling answers
    from the exactly-once replay cache and gains a ``replay`` event —
    all under ONE trace id (the acceptance-criteria scenario)."""
    from mxnet_tpu import nd
    kv = traced_client
    kv.init("k", nd.array(np.zeros(2, np.float32)))
    telemetry.clear_trace()
    r0 = registry.value("kvstore.client_retries")
    p0 = registry.value("kvstore.server_replays")
    # drop the connection between send and recv: the PUSH is applied
    # server-side but the reply never lands -> reconnect + replay
    fault.inject("kvstore.recv", action="close", after=0, count=1)
    kv.push("k", nd.array(np.ones(2, np.float32)))
    out = nd.zeros((2,))
    kv.pull("k", out=out)
    np.testing.assert_allclose(out.asnumpy(), np.ones(2))  # exactly once
    assert registry.value("kvstore.client_retries") == r0 + 1
    assert registry.value("kvstore.server_replays") == p0 + 1
    cli = _spans("kv.client.PUSH")
    assert len(cli) == 1
    trace_id = cli[0]["args"]["trace_id"]
    retries = [e for e in telemetry.trace_events()
               if e["name"] == "retry" and e["ph"] == "i"]
    assert retries and retries[0]["args"]["trace_id"] == trace_id
    srv = _spans("kv.server.PUSH")
    assert len(srv) == 2                     # original + replayed handling
    assert all(s["args"]["trace_id"] == trace_id for s in srv)
    replays = [e for e in telemetry.trace_events()
               if e["name"] == "replay" and e["ph"] == "i"]
    assert replays and replays[0]["args"]["trace_id"] == trace_id


def test_plain_seq_envelope_still_handled():
    """4-tuple SEQ envelopes (no trace context) keep working — older
    tools and tests construct them directly."""
    from mxnet_tpu.kvstore.server import KVStoreServer
    srv = KVStoreServer(num_workers=1)
    ok, _ = srv.handle_request(
        ("SEQ", "r0:x", 1, ("INIT", "a", np.zeros(2))))
    assert ok
    ok, _ = srv.handle_request(
        ("SEQ", "r0:x", 2, ("PUSH", "a", np.ones(2))))
    assert ok
    ok, val = srv.handle_request(("SEQ", "r0:x", 3, ("PULL", "a")))
    assert ok and np.allclose(val, np.ones(2))


def test_trace_dump_and_merge(clean_telemetry, tmp_path):
    telemetry.start_tracing()
    try:
        with telemetry.Span("kv.client.PUSH", cat="rpc") as sp:
            ctx = sp.wire_context()
            with telemetry.rpc_span("kv.server.PUSH", trace_id=ctx[0],
                                    parent_id=ctx[1]):
                pass
    finally:
        telemetry.stop_tracing()
    p1 = telemetry.dump_trace(str(tmp_path / "a.trace.json"))
    blob = json.load(open(p1))
    assert blob["traceEvents"] and "metadata" in blob
    # second "process": same events, different file
    p2 = str(tmp_path / "b.trace.json")
    json.dump({"traceEvents": blob["traceEvents"],
               "metadata": {"pid": 999, "rank": "1", "role": "server"}},
              open(p2, "w"))
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import telemetry_dump
    merged, summary = telemetry_dump.merge([p1, p2])
    assert summary["distinct_trace_ids"] == 1      # one causal chain
    pids = {e["pid"] for e in merged["traceEvents"]}
    assert len(pids) == 2                          # one row per process
    names = {e["name"] for e in merged["traceEvents"]}
    assert "process_name" in names
    # CLI end-to-end
    out = str(tmp_path / "merged.json")
    rc = telemetry_dump.main(["--out", out, p1, p2])
    assert rc == 0 and os.path.exists(out)


# ---------------------------------------------------------------------------
# crash dumps: watchdog trip (virtual clock), NaN raise, fit death
# ---------------------------------------------------------------------------

def test_watchdog_trip_dumps_flight_recorder(clean_telemetry, monkeypatch,
                                             tmp_path, capsys):
    monkeypatch.setenv("MX_CRASH_DIR", str(tmp_path / "crash"))
    telemetry.note_step(epoch=0, batch=3)
    fired = []
    with fault.use_virtual_time() as clk:
        wd = health.Watchdog(timeout=5.0, on_timeout=lambda: fired.append(1))
        wd.pet()
        clk.advance(6.0)
        assert wd.check()
    assert fired == [1]
    dumps = os.listdir(str(tmp_path / "crash"))
    assert len(dumps) == 1, dumps
    blob = json.load(open(str(tmp_path / "crash" / dumps[0])))
    assert "watchdog" in blob["reason"]
    assert len(blob["records"]) >= 1
    assert blob["records"][-1]["batch"] == 3
    assert "engine.dispatch_count" in blob["counters"]


def test_nan_raise_policy_dumps_and_counts(clean_telemetry, monkeypatch,
                                           tmp_path):
    from mxnet_tpu import nd
    monkeypatch.setenv("MX_CRASH_DIR", str(tmp_path / "crash"))
    n0 = registry.value("health.nan_events")
    guard = health.GradientGuard("raise")
    poisoned = [("w", nd.array(np.array([1.0, np.nan], np.float32)))]
    from mxnet_tpu.base import MXNetError
    with pytest.raises(MXNetError):
        guard.allow_update(poisoned)
    assert registry.value("health.nan_events") == n0 + 1
    dumps = os.listdir(str(tmp_path / "crash"))
    assert dumps and "nan_policy_raise" in \
        json.load(open(str(tmp_path / "crash" / dumps[0])))["reason"]


def test_dump_crash_without_dir_is_none(clean_telemetry, monkeypatch):
    monkeypatch.delenv("MX_CRASH_DIR", raising=False)
    assert telemetry.dump_crash("whatever") is None


# ---------------------------------------------------------------------------
# heartbeat JSON round-trip -> supervisor fleet status table
# ---------------------------------------------------------------------------

def test_heartbeat_payload_roundtrip(clean_telemetry, tmp_path):
    telemetry.note_step(epoch=1, batch=2, batch_size=16)
    time.sleep(0.005)
    telemetry.note_step(epoch=1, batch=3, batch_size=16)
    hb = health.Heartbeat(str(tmp_path / "hb"))
    hb.beat(epoch=1, nbatch=3)
    launch = _load_launch()
    sp = launch.SupervisedProc("rank 0", ["true"], {},
                               heartbeat=str(tmp_path / "hb"))
    age, head, payload = launch.Supervisor._read_beat(sp)
    assert age is not None and age < 60
    assert head.split()[1:] == ["1", "3"]
    rec = telemetry.flight_recorder.last()
    assert payload["step"] == rec["step"]
    assert payload["throughput"] == rec["throughput"]
    assert payload["wire_bytes"] == rec["wire_bytes"]


def test_supervisor_status_table_renders(clean_telemetry, tmp_path):
    telemetry.note_step(epoch=0, batch=1, batch_size=8)
    time.sleep(0.005)
    telemetry.note_step(epoch=0, batch=2, batch_size=8)
    hb = health.Heartbeat(str(tmp_path / "hb"))
    hb.beat(epoch=0, nbatch=2)
    launch = _load_launch()
    sup = launch.Supervisor()
    sup.add("rank 0", ["true"], {}, heartbeat=str(tmp_path / "hb"))
    sup.add("server 0", ["true"], {}, role="server")
    table = sup.status_table()
    assert "fleet status:" in table
    assert "rank 0" in table and "server 0" in table
    rec = telemetry.flight_recorder.last()
    assert str(rec["step"]) in table          # step column populated
    assert "img/s" in table


def test_supervisor_crash_dump_written(clean_telemetry, monkeypatch,
                                       tmp_path):
    telemetry.note_step(epoch=0, batch=1)
    hb = health.Heartbeat(str(tmp_path / "hb"))
    hb.beat(epoch=0, nbatch=1)
    monkeypatch.setenv("MX_CRASH_DIR", str(tmp_path / "crash"))
    launch = _load_launch()
    sup = launch.Supervisor()
    sp = sup.add("rank 0", ["true"], {}, heartbeat=str(tmp_path / "hb"))
    path = sup._crash_dump(sp, 86, "exit 86 (watchdog)")
    blob = json.load(open(path))
    assert blob["rc"] == 86 and blob["proc"] == "rank 0"
    assert blob["heartbeat"].get("step") == \
        telemetry.flight_recorder.last()["step"]


# ---------------------------------------------------------------------------
# mxlint reinjection: spans must stay sync-free (hot-path rule roots)
# ---------------------------------------------------------------------------

def test_telemetry_is_hot_path_root():
    from tools.mxlint.rules import HOT_PATH_ROOTS
    roots = dict(HOT_PATH_ROOTS)
    assert "mxnet_tpu/telemetry.py" in roots
    quals = roots["mxnet_tpu/telemetry.py"]
    assert "phase" in quals and "note_step" in quals


def test_reinjected_sync_in_phase_span_trips_hot_path_rule():
    from tools.mxlint import lint_source
    from tools.mxlint.core import apply_baseline, load_baseline
    p = os.path.join(REPO, "mxnet_tpu", "telemetry.py")
    with open(p) as f:
        code = f.read()
    anchor = "        if self._on:"
    assert anchor in code, "_PhaseSpan.__exit__ moved; update this test"
    bad = code.replace(
        anchor, "        _dbg = exc[0].asnumpy()\n" + anchor, 1)
    diags = lint_source(bad, "mxnet_tpu/telemetry.py")
    rules = {d.rule for d in diags}
    assert "host-sync-in-hot-path" in rules, rules
    baseline = load_baseline(os.path.join(REPO, "tools", "mxlint",
                                          "baseline.json"))
    new, _, _ = apply_baseline(diags, baseline)
    assert "host-sync-in-hot-path" in {d.rule for d in new}


def test_shipped_telemetry_lints_clean():
    from tools.mxlint import lint_paths
    diags = lint_paths([os.path.join(REPO, "mxnet_tpu", "telemetry.py"),
                        os.path.join(REPO, "tools", "telemetry_dump.py")],
                       root=REPO)
    assert [d for d in diags] == [], diags
