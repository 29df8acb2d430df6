"""Program census (ISSUE 10): registry exactness on CPU (memory/cost
metadata matching jax's own AOT analysis, graceful None in light mode),
retrace-explainer diff correctness for shape/dtype/tree-structure
changes, the device-buffer census with owner attribution + leak
detector, crash-dump/flight-recorder wiring, the serve METRICS verb
over a real socket, engine.snapshot() consistency, specializing /
strict record semantics, and the mxlint reinjection proving a host sync
in the census hot path trips the rule."""
import json
import os
import socket
import subprocess
import sys
import threading
import time
import uuid

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402

import mxnet_tpu as mx                                  # noqa: E402
from mxnet_tpu import programs, telemetry               # noqa: E402


def _name(tag):
    """Unique program name per test run (records are process-global)."""
    return "test.%s.%s" % (tag, uuid.uuid4().hex[:8])


# ---------------------------------------------------------------------------
# registry exactness
# ---------------------------------------------------------------------------

def test_aot_program_records_compile_time_memory_and_cost():
    name = _name("aot")

    def fn(x, y):
        return x @ y + 1.0

    prog = programs.register_program(name, fn)
    a = jnp.ones((8, 16), jnp.float32)
    b = jnp.ones((16, 4), jnp.float32)
    out = prog(a, b)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jax.jit(fn)(a, b)))
    rec = programs.find_record(name)
    assert rec is not None
    snap = rec.snapshot()
    assert snap["compiles"] == 1
    assert snap["retraces"] == 0
    assert snap["compile_seconds"]["total"] > 0
    # exactness vs jax's own AOT analysis of the identical program
    ref = jax.jit(fn).lower(a, b).compile()
    ref_mem = ref.memory_analysis()
    if ref_mem is None:
        assert snap["memory"] is None       # graceful None
    else:
        assert snap["memory"]["argument_bytes"] == \
            int(ref_mem.argument_size_in_bytes)
        assert snap["memory"]["output_bytes"] == \
            int(ref_mem.output_size_in_bytes)
        assert snap["memory"]["temp_bytes"] == \
            int(ref_mem.temp_size_in_bytes)
    ca = ref.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    if isinstance(ca, dict) and "flops" in ca:
        assert snap["cost"]["flops"] == pytest.approx(float(ca["flops"]))
    # second identical call: cached executable, no new compile
    prog(a, b)
    assert programs.find_record(name).compiles == 1


def test_light_program_counts_traces_memory_explicitly_none():
    name = _name("light")
    prog = programs.register_program(name, lambda x: x * 2, mode="light")
    a = jnp.ones((4,), jnp.float32)
    prog(a)
    prog(a)                                 # cache hit: no new compile
    rec = programs.find_record(name)
    assert rec.compiles == 1
    assert rec.snapshot()["compile_seconds"]["total"] > 0
    assert rec.memory is None               # explicitly None in light mode
    assert rec.cost is None
    prog(jnp.ones((7,), jnp.float32))       # retrace
    assert rec.compiles == 2
    assert rec.retraces == 1


def test_register_but_never_dispatch_creates_no_record():
    name = _name("idle")
    programs.register_program(name, lambda x: x)
    assert programs.find_record(name) is None
    assert name not in programs.program_table()


def test_census_disabled_returns_plain_jit(monkeypatch):
    monkeypatch.setenv("MX_PROGRAM_CENSUS", "0")
    name = _name("off")
    prog = programs.register_program(name, lambda x: x + 1)
    out = prog(jnp.ones((2,), jnp.float32))
    np.testing.assert_allclose(np.asarray(out), [2.0, 2.0])
    assert not isinstance(prog, programs.Program)
    assert programs.find_record(name) is None


def test_donated_aot_program_dispatches():
    name = _name("donate")
    prog = programs.register_program(name, lambda x: x + 1,
                                     donate_argnums=(0,))
    out = prog(jnp.ones((4,), jnp.float32))
    np.testing.assert_allclose(np.asarray(out), 2.0 * np.ones(4))
    out2 = prog(jnp.asarray(np.asarray(out)))
    np.testing.assert_allclose(np.asarray(out2), 3.0 * np.ones(4))
    assert programs.find_record(name).compiles == 1


def test_aot_fallback_on_unlowerable_site_degrades_to_light():
    name = _name("fallback")
    calls = []

    def fn(x):
        calls.append(1)
        return x + 1

    prog = programs.register_program(name, fn)
    prog._aot = False                       # simulate a failed lowering
    out = prog(jnp.ones((3,), jnp.float32))
    np.testing.assert_allclose(np.asarray(out), 2.0 * np.ones(3))
    rec = programs.find_record(name)
    assert rec.compiles == 1                # probe-counted
    assert rec.memory is None


def test_aot_fallback_after_successful_compiles_counts_exactly():
    # AOT lowers bump the light-mode trace probe too; a later fallback
    # must not re-record those probe bumps as phantom compiles
    name = _name("fb2")
    prog = programs.register_program(name, lambda x: x + 1)
    prog(jnp.ones((2,), jnp.float32))           # real AOT compile
    rec = programs.find_record(name)
    assert rec.compiles == 1
    orig_jit = prog._jit

    class BoomLower:
        def lower(self, *a, **k):
            raise RuntimeError("boom")

        def __call__(self, *a, **k):
            return orig_jit(*a, **k)

    prog._jit = BoomLower()
    out = prog(jnp.ones((3,), jnp.float32))     # degrade to light
    np.testing.assert_allclose(np.asarray(out), 2.0 * np.ones(3))
    assert not prog._aot
    assert rec.compiles == 2, rec.compiles      # one light trace, no phantoms


# ---------------------------------------------------------------------------
# retrace explainer
# ---------------------------------------------------------------------------

def test_explainer_shape_change():
    name = _name("shape")
    prog = programs.register_program(name, lambda x: x.sum())
    prog(jnp.ones((4, 4), jnp.float32))
    prog(jnp.ones((8, 4), jnp.float32))
    rec = programs.find_record(name)
    assert rec.retraces == 1
    diff = rec.last_retrace["diff"]
    assert diff["kind"] == "leaves"
    (chg,) = diff["changed"]
    assert chg["change"] == "shape"
    assert chg["before"]["shape"] == (4, 4)
    assert chg["after"]["shape"] == (8, 4)


def test_explainer_dtype_change():
    name = _name("dtype")
    prog = programs.register_program(name, lambda x: x.sum())
    prog(jnp.ones((4,), jnp.float32))
    prog(jnp.ones((4,), jnp.bfloat16))
    diff = programs.find_record(name).last_retrace["diff"]
    (chg,) = diff["changed"]
    assert chg["change"] == "dtype"
    assert chg["before"]["dtype"] == "float32"
    assert chg["after"]["dtype"] == "bfloat16"


def test_explainer_tree_structure_change():
    name = _name("tree")
    prog = programs.register_program(
        name, lambda t: sum(jax.tree_util.tree_leaves(t)))
    a = jnp.ones((2,), jnp.float32)
    prog((a, a))
    prog({"x": a, "y": a})
    diff = programs.find_record(name).last_retrace["diff"]
    assert diff["kind"] == "tree_structure"
    assert diff["before"] != diff["after"]


def test_explainer_names_the_changed_arg_in_light_mode():
    name = _name("lightdiff")
    prog = programs.register_program(
        name, lambda x, y: x + y.sum(), mode="light")
    a = jnp.ones((2,), jnp.float32)
    prog(a, jnp.ones((3,), jnp.float32))
    prog(a, jnp.ones((5,), jnp.float32))
    diff = programs.find_record(name).last_retrace["diff"]
    (chg,) = diff["changed"]
    assert "[1]" in chg["arg"]              # second positional arg
    assert chg["change"] == "shape"


def test_explainer_sharding_change_same_shape_dtype():
    """ISSUE 11 satellite: a resharded argument — same shape, same
    dtype, different PartitionSpec — must diff as a 'sharding' change,
    not a generic leaf change.  This is the first explainer path FSDP
    (ROADMAP item 1) will exercise: flipping a parameter from
    replicated to fsdp-sharded retraces every program it feeds."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices("cpu")[:1]), ("fsdp",))
    x = jnp.ones((8, 4), jnp.float32)
    repl = jax.device_put(x, NamedSharding(mesh, P()))
    shard = jax.device_put(x, NamedSharding(mesh, P("fsdp")))
    old = programs.signature_of((repl,))
    new = programs.signature_of((shard,))
    diff = programs.diff_signatures(old, new)
    assert diff is not None and diff["kind"] == "leaves"
    (chg,) = diff["changed"]
    assert chg["change"] == "sharding"
    assert chg["before"]["shape"] == chg["after"]["shape"] == (8, 4)
    assert chg["before"]["dtype"] == chg["after"]["dtype"] == "float32"
    assert chg["before"]["device"] != chg["after"]["device"]
    # identical shardings stay cache hits (no spurious diff)
    assert programs.diff_signatures(
        old, programs.signature_of(
            (jax.device_put(x, NamedSharding(mesh, P())),))) is None


def test_explainer_sharding_change_through_dispatch():
    """End-to-end: dispatching an AOT program with a resharded
    (shape/dtype-identical) argument builds a second executable — the
    AOT cache keys on sharding, since an AOT executable rejects inputs
    laid out differently — and the record's explainer diff names the
    arg and the sharding change.  (Light mode defers to jax.jit's own
    cache, which may normalize single-device shardings; the AOT lane is
    the one serving/step programs use, so it is the one FSDP will
    retrace through.)"""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    name = _name("reshard")
    prog = programs.register_program(name, lambda x: x.sum())
    mesh = Mesh(np.array(jax.devices("cpu")[:1]), ("fsdp",))
    x = jnp.ones((8, 4), jnp.float32)
    prog(jax.device_put(x, NamedSharding(mesh, P())))
    prog(jax.device_put(x, NamedSharding(mesh, P("fsdp"))))
    rec = programs.find_record(name)
    assert rec.compiles == 2 and rec.retraces == 1
    (chg,) = rec.last_retrace["diff"]["changed"]
    assert chg["change"] == "sharding"
    assert "[0]" in chg["arg"]


def test_program_retrace_counter_in_telemetry():
    name = _name("metric")
    prog = programs.register_program(name, lambda x: x + 1)
    prog(jnp.ones((2,), jnp.float32))
    prog(jnp.ones((3,), jnp.float32))
    c = telemetry.registry.find("program_retraces", {"program": name})
    assert c is not None and c.value == 1
    prom = telemetry.registry.to_prometheus()
    assert "mx_program_compile_seconds" in prom
    assert "mx_program_retraces" in prom


# ---------------------------------------------------------------------------
# device-buffer census + leak detector
# ---------------------------------------------------------------------------

def test_census_attributes_params_and_optimizer_state():
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.gluon import nn
    net = nn.Dense(4, in_units=8)
    net.initialize(mx.init.Xavier())
    tr = gluon.Trainer(list(net.collect_params().values()), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    loss_fn = gluon.loss.L2Loss()
    x = nd.array(np.random.RandomState(0).randn(4, 8).astype(np.float32))
    y = nd.array(np.random.RandomState(1).randn(4, 4).astype(np.float32))
    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    tr.step(batch_size=4)
    census = programs.buffer_census()
    assert census["params"]["count"] >= 2           # weight+bias (+grads)
    assert census["params"]["bytes"] > 0
    assert census["optimizer_state"]["count"] >= 2  # momentum buffers
    assert census["total_bytes"] >= sum(
        v["bytes"] for k, v in census.items() if isinstance(v, dict))
    # the arrays stay counted once: total is consistent with the walk
    assert census["n_arrays"] >= census["params"]["count"]


def test_leak_detector_trips_on_retained_buffers(monkeypatch):
    monkeypatch.setenv("MX_LEAK_WARN_BYTES", "4096")
    det = programs.LeakDetector()
    det.check()                              # baseline
    retained = [jnp.ones((4096,), jnp.float32) for _ in range(3)]
    chk = det.check()
    assert chk["tripped"]
    assert chk["growth_bytes"] >= 4096
    g = telemetry.registry.find("census_leak_bytes")
    assert g is not None and g.value >= 4096
    # releasing the buffers shrinks the total: the streak resets
    del retained
    chk2 = det.check()
    assert not chk2["tripped"]
    assert chk2["growth_bytes"] == 0


def test_leak_detector_plateau_keeps_streak(monkeypatch):
    # a flat check between growth steps (allocator reuse) must NOT
    # reset the streak — only a shrink does
    monkeypatch.setenv("MX_LEAK_WARN_BYTES", str(450 * 1024))
    det = programs.LeakDetector()
    det.check()
    keep1 = [jnp.ones((64 * 1024,), jnp.float32)]      # +256KB
    assert not det.check()["tripped"]
    det.check()                                         # plateau
    keep2 = [jnp.ones((64 * 1024,), jnp.float32)]      # +256KB more
    chk = det.check()
    assert chk["tripped"], chk
    del keep1, keep2


def test_leak_detector_zero_threshold_never_trips(monkeypatch):
    monkeypatch.setenv("MX_LEAK_WARN_BYTES", "0")
    det = programs.LeakDetector()
    det.check()
    retained = [jnp.ones((1 << 16,), jnp.float32)]
    assert not det.check()["tripped"]
    del retained


def test_flight_recorder_step_records_carry_census(monkeypatch):
    monkeypatch.setenv("MX_TELEMETRY", "1")
    telemetry.flight_recorder.clear()
    for _ in range(17):                      # census rides every 16th
        telemetry.note_step(steps=1)
    recs = telemetry.flight_recorder.records()
    assert any("live_bytes" in r for r in recs), recs[-1]
    telemetry.flight_recorder.clear()


def test_crash_dump_carries_buffer_census_and_programs(tmp_path):
    name = _name("crash")
    prog = programs.register_program(name, lambda x: x * 3)
    prog(jnp.ones((2,), jnp.float32))
    path = telemetry.dump_crash("test", directory=str(tmp_path))
    blob = json.load(open(path))
    assert blob["buffer_census"]["total_bytes"] > 0
    assert name in blob["programs"]
    assert blob["programs"][name]["compile_seconds"]["total"] > 0


# ---------------------------------------------------------------------------
# serve: bucket table attribution + METRICS verb
# ---------------------------------------------------------------------------

@pytest.fixture
def serve_replica():
    from mxnet_tpu.serve import ServeServer, serve_forever, Servable
    from mxnet_tpu.serve.demo import demo_block, demo_example
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    state = ServeServer()
    sv = Servable(demo_block(), name="census-demo", version=1)
    state.host.deploy(sv, example=demo_example())
    stop = threading.Event()
    t = threading.Thread(target=serve_forever,
                         kwargs=dict(port=port, state=state,
                                     stop_event=stop), daemon=True)
    t.start()
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port),
                                     timeout=0.2).close()
            break
        except OSError:
            time.sleep(0.05)
    yield "127.0.0.1:%d" % port, sv
    stop.set()
    t.join(timeout=10)


def test_serve_bucket_table_fully_attributed(serve_replica):
    from mxnet_tpu.serve import ServeClient
    from mxnet_tpu.serve.demo import DEMO_IN
    addr, sv = serve_replica
    table = programs.program_table()
    for bucket in sv.buckets:
        key = "serve.census-demo.b%d" % bucket
        assert key in table, sorted(table)
        assert table[key]["compiles"] >= 1
        assert table[key]["compile_seconds"]["total"] > 0
        assert table[key]["retraces"] == 0
    # dispatching again stays retrace-free and the version's buffers
    # are attributed to the "serve" owner bucket
    cli = ServeClient([addr], timeout=30)
    cli.predict([np.zeros((2, DEMO_IN), np.float32)])
    after = programs.program_table()
    assert all(after["serve.census-demo.b%d" % b]["retraces"] == 0
               for b in sv.buckets)
    census = programs.buffer_census()
    assert census["serve"]["count"] >= 1
    assert census["serve"]["bytes"] > 0
    cli.close()


def test_metrics_verb_returns_prometheus_snapshot(serve_replica):
    from mxnet_tpu.serve import ServeClient
    addr, _sv = serve_replica
    cli = ServeClient([addr], timeout=30)
    text = cli.metrics()
    assert "# TYPE" in text
    assert "mx_serve_batches" in text or "mx_serve_requests" in text
    assert "mx_program_compile_seconds" in text
    blob = cli.metrics(fmt="json")
    parsed = json.loads(blob)
    assert any(k.startswith("program_compile_seconds") for k in parsed)
    cli.close()


def test_text_wire_codec_roundtrip():
    from mxnet_tpu.kvstore.wire_codec import (decode_text, encode_text,
                                              is_text_payload)
    payload = encode_text("mx_metric 1\n# ünïcode")
    assert is_text_payload(payload)
    assert decode_text(payload) == "mx_metric 1\n# ünïcode"
    with pytest.raises(ValueError):
        decode_text(("NOPE", b""))


def test_serve_load_cli_metrics_flag(serve_replica):
    addr, _sv = serve_replica
    env = dict(os.environ, JAX_PLATFORMS="cpu", MX_FORCE_CPU="1")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "serve_load.py"),
         "--addrs", addr, "--requests", "2", "--metrics"],
        capture_output=True, text=True, timeout=240, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "SERVE_LOAD_OK" in r.stdout
    assert "==== metrics: replica 0" in r.stdout
    assert "mx_program_compile_seconds" in r.stdout


# ---------------------------------------------------------------------------
# whole-step lane
# ---------------------------------------------------------------------------

def test_compiled_step_registers_program_and_explains_invalidation():
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.gluon import nn
    net = nn.Dense(4, in_units=8)
    net.initialize(mx.init.Xavier())
    tr = gluon.Trainer(list(net.collect_params().values()), "sgd",
                       {"learning_rate": 0.1})
    cstep = tr.make_compiled_step(net, gluon.loss.L2Loss())
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(4, 8).astype(np.float32))
    y = nd.array(rng.randn(4, 4).astype(np.float32))
    cstep.step(x, y)
    cstep.step(x, y)
    rec = programs.find_record("step.step")
    assert rec is not None
    assert rec.snapshot()["compile_seconds"]["total"] > 0
    before = rec.compiles
    # a batch-shape change is a CompiledStep invalidation: the census
    # explains it as a step.step retrace naming the data arg
    x2 = nd.array(rng.randn(6, 8).astype(np.float32))
    y2 = nd.array(rng.randn(6, 4).astype(np.float32))
    cstep.step(x2, y2)
    assert rec.compiles == before + 1
    assert rec.last_retrace is not None
    diff = rec.last_retrace["diff"]
    assert diff["kind"] == "leaves"
    assert any(c["change"] == "shape" for c in diff["changed"])


# ---------------------------------------------------------------------------
# engine snapshot + record semantics
# ---------------------------------------------------------------------------

def test_engine_snapshot_consistent_group():
    from mxnet_tpu.engine import engine
    s0 = engine.snapshot()
    for key in ("dispatches", "wire_bytes", "compiled_steps",
                "compiled_step_windows", "programs"):
        assert key in s0
    engine.count_step_window(5, dispatches=2)
    engine.count_wire_bytes(123)
    s1 = engine.snapshot()
    assert s1["dispatches"] - s0["dispatches"] == 2
    assert s1["compiled_steps"] - s0["compiled_steps"] == 5
    assert s1["compiled_step_windows"] - s0["compiled_step_windows"] == 1
    assert s1["wire_bytes"] - s0["wire_bytes"] == 123
    assert s1["programs"] >= 0


def test_ensure_compiled_builds_without_dispatch():
    name = _name("ensure")
    p = programs.register_program(name, lambda x: x * 2)
    x = jnp.ones((4,))
    assert p.ensure_compiled(x) == "compiled"
    assert p.ensure_compiled(x) == "ready"
    assert programs.find_record(name).compiles == 1
    np.testing.assert_array_equal(np.asarray(p(x)), 2 * np.ones(4))
    assert programs.find_record(name).compiles == 1   # no second build
    snap = programs.find_record(name).snapshot()
    assert "cache_hits" not in snap and "deserialize_seconds" not in snap
    light = programs.register_program(_name("ensure.light"),
                                      lambda x: x + 1, mode="light")
    assert light.ensure_compiled(x) is False


def test_specializing_record_semantics():
    name = _name("spec")
    p = programs.register_program(name, lambda x: x + 1, mode="light",
                                  specializing=True)
    p(jnp.ones((2,)))
    p(jnp.ones((3,)))           # fresh shape: specialization, NOT retrace
    rec = programs.find_record(name)
    assert rec.compiles == 2
    assert rec.retraces == 0
    assert rec.specializations == 1
    snap = rec.snapshot()
    assert snap["specializing"] and snap["specializations"] == 1


def test_strict_record_semantics_unchanged():
    name = _name("strict")
    p = programs.register_program(name, lambda x: x + 1, mode="light")
    p(jnp.ones((2,)))
    p(jnp.ones((3,)))
    rec = programs.find_record(name)
    assert rec.retraces == 1 and rec.specializations == 0


def test_hybridize_imperative_pass_builds_no_child_programs():
    # the deferred-init imperative pass of a hybridized parent must not
    # build per-child hybrid programs — the whole-net trace on the
    # SECOND call covers them
    from mxnet_tpu import nd
    from mxnet_tpu.gluon import nn
    before = set(programs.program_table())
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"))     # deferred in_units
    net.add(nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    x = nd.array(np.random.RandomState(0).randn(2, 6).astype(np.float32))
    net(x)                      # imperative pass (finishes deferred init)
    new = set(programs.program_table()) - before
    assert not any(n.startswith("hybrid.Dense") for n in new), new
    net(x)                      # whole-net trace
    new = set(programs.program_table()) - before
    assert any(n.startswith("hybrid.HybridSequential") for n in new), new


def test_env_catalog_covers_new_flags():
    from mxnet_tpu.base import ENV_CATALOG
    for var in ("MX_PROGRAM_CENSUS", "MX_LEAK_WARN_BYTES"):
        assert var in ENV_CATALOG


# ---------------------------------------------------------------------------
# mxlint reinjection: census helpers must stay sync-free
# ---------------------------------------------------------------------------

def test_reinjected_sync_in_census_call_path_trips_hot_path_rule():
    from tools.mxlint import lint_source
    from tools.mxlint.core import apply_baseline, load_baseline
    p = os.path.join(REPO, "mxnet_tpu", "programs.py")
    with open(p) as f:
        code = f.read()
    anchor = "        seq = self._seq\n"
    assert anchor in code, "Program.__call__ moved; update this test"
    bad = code.replace(
        anchor, "        _dbg = args[0].asnumpy()\n" + anchor, 1)
    diags = lint_source(bad, "mxnet_tpu/programs.py")
    rules = {d.rule for d in diags}
    assert "host-sync-in-hot-path" in rules, rules
    baseline = load_baseline(os.path.join(REPO, "tools", "mxlint",
                                          "baseline.json"))
    new, _, _ = apply_baseline(diags, baseline)
    assert "host-sync-in-hot-path" in {d.rule for d in new}


def test_shipped_programs_lints_clean():
    from tools.mxlint import lint_paths
    diags = lint_paths([os.path.join(REPO, "mxnet_tpu", "programs.py")],
                       root=REPO)
    assert [d for d in diags] == [], diags
