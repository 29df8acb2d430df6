"""The start-up timeline (ISSUE 37): jax's compile events as named spans,
the front end's ``import`` / ``initialize`` / ``forward`` phases, the
held first spans of a process, ``telemetry.startup_breakdown`` and the
first compiled step's INFO line."""
import json
import logging
import threading
import time

import pytest

from mxnet_tpu import compile_cache, gluon, nd, programs, telemetry

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"


@pytest.fixture
def timeline(monkeypatch):
    """An empty buffer with the whole start-up allowance, telemetry on,
    tracing off."""
    monkeypatch.setenv("MX_TELEMETRY", "1")
    monkeypatch.delenv("MX_TELEMETRY_TRACE", raising=False)
    monkeypatch.setattr(telemetry, "_startup_left",
                        [telemetry._STARTUP_SPANS])
    monkeypatch.setattr(telemetry, "_startup_dropped", [0])
    monkeypatch.setattr(telemetry, "_trace_forced", [0])
    telemetry.clear_trace()
    yield
    telemetry.clear_trace()


def _held(prefix=""):
    with telemetry._trace_lock:
        return [e for e in telemetry._trace_events
                if e["name"].startswith(prefix)]


def _names(prefix=""):
    return [(e["name"], e["args"].get("fun_name")) for e in _held(prefix)]


# -- the listener --------------------------------------------------------------

def _feed(events):
    """Replay (event, start, end, fun_name) tuples and bare hit markers
    through the listeners, as jax calls them: each at its END."""
    for ev in events:
        if ev == HIT:
            compile_cache._on_jax_event(HIT)
        else:
            name, start, end, fun = ev
            compile_cache._on_jax_span(name, start, end, fun_name=fun)


@pytest.mark.parametrize("hit, backend_name", [
    (False, "compile.backend"), (True, "compile.cache_load")])
def test_one_jit_gives_three_named_spans(timeline, hit, backend_name):
    now = time.time()
    _feed([(TRACE, now - 3.0, now - 2.0, "mx_step_step"),
           (LOWER, now - 2.0, now - 1.5, "jit(mx_step_step)")])
    if hit:
        compile_cache._last_hit.at = now - 1.0      # inside the span below
    _feed([(BACKEND, now - 1.5, now - 0.5, "jit(mx_step_step)")])
    assert _names("compile.") == [
        ("compile.trace", "mx_step_step"), ("compile.lower", "mx_step_step"),
        (backend_name, "mx_step_step")]
    trace, lower, backend = _held("compile.")
    assert trace["dur"] == pytest.approx(1.0e6)
    assert backend["ts"] == pytest.approx((now - 1.5) * 1e6, abs=1e3)
    assert {e["cat"] for e in _held()} == {"startup"}      # tracing is off


def test_a_nested_jits_hit_does_not_mark_the_outer_compile(timeline):
    """An operator that runs inside the step's trace loads from the cache;
    the step itself then compiles cold: the stale hit lies before its
    backend span."""
    now = time.time()
    compile_cache._last_hit.at = now - 8.5
    _feed([(TRACE, now - 9.0, now - 8.9, "mx_op_dot"),
           (LOWER, now - 8.9, now - 8.8, "jit(mx_op_dot)"),
           (BACKEND, now - 8.8, now - 8.0, "jit(mx_op_dot)"),
           (TRACE, now - 10.0, now - 7.0, "mx_step_step"),
           (LOWER, now - 7.0, now - 6.0, "jit(mx_step_step)"),
           (BACKEND, now - 6.0, now - 1.0, "jit(mx_step_step)")])
    assert _names("compile.")[2] == ("compile.cache_load", "mx_op_dot")
    assert _names("compile.")[5] == ("compile.backend", "mx_step_step")
    found = telemetry.startup_breakdown(
        telemetry.wall_to_perf(now - 10.0), telemetry.wall_to_perf(now))
    # the operator's second is its own, not the enclosing trace's
    assert found["by_program"]["mx_step_step"]["trace"] == pytest.approx(2.0)
    assert found["by_program"]["mx_op_dot"]["cache_load"] == \
        pytest.approx(0.8)
    assert found["trace"] == pytest.approx(2.1)


def test_another_threads_hit_is_not_this_threads(timeline):
    now = time.time()
    other = threading.Thread(target=compile_cache._on_jax_event, args=(HIT,))
    other.start()
    other.join(timeout=10)
    assert not other.is_alive()
    _feed([(BACKEND, now - 1.0, time.time() + 1.0, "jit(f)")])
    _feed([HIT, (BACKEND, now - 1.0, time.time() + 1.0, "jit(g)")])
    assert _names("compile.") == [("compile.backend", "f"),
                                  ("compile.cache_load", "g")]


def test_events_of_no_interest_are_not_spans(timeline):
    compile_cache._on_jax_span("/jax/some/other_duration", 1.0, 2.0,
                               fun_name="f")
    assert _held() == []


def test_a_fresh_jit_on_the_host_produces_all_three(timeline):
    import jax
    compile_cache.activate()

    def startup_test_fn(x):
        return x * 2 + 1

    jax.jit(startup_test_fn)(jax.numpy.arange(7.0)).block_until_ready()
    mine = [n for n, fun in _names("compile.") if fun == "startup_test_fn"]
    # no persistent cache under MX_FORCE_CPU=1: XLA compiled
    assert mine == ["compile.trace", "compile.lower", "compile.backend"]


def test_the_census_brackets_lower_and_compile_apart(timeline):
    fn = programs.register_program("startup.test_aot", lambda x: x + 1)
    import jax.numpy as jnp
    fn(jnp.ones((3,)))
    mine = [e for e in _held("compile.")
            if e["args"].get("program") == "startup.test_aot"]
    assert [e["name"] for e in mine] == ["compile.lower", "compile.backend"]
    assert {e["args"]["fun_name"] for e in mine} == {"mx_startup_test_aot"}
    inner = [n for n, fun in _names("compile.")
             if fun == "mx_startup_test_aot"]
    assert sorted(inner) == ["compile.backend", "compile.backend",
                             "compile.lower", "compile.lower",
                             "compile.trace"]
    # jax's spans lie inside the census's: nothing is counted twice
    found = telemetry.startup_breakdown()
    row = found["by_program"]["mx_startup_test_aot"]
    lower, backend = mine
    # (jax stamps by the wall clock: a millisecond's play at the edges)
    assert row["total"] == pytest.approx(
        (lower["dur"] + backend["dur"]) / 1e6, abs=5e-3)


def test_stats_gain_the_seconds(timeline):
    before = compile_cache.stats()
    now = time.time()
    _feed([(BACKEND, now - 3.0, now - 1.0, "jit(f)"), HIT,
           (BACKEND, now - 0.5, time.time() + 0.25, "jit(g)")])
    after = compile_cache.stats()
    assert after["xla_compile_seconds"] - before["xla_compile_seconds"] \
        == pytest.approx(2.0)
    assert after["xla_load_seconds"] - before["xla_load_seconds"] \
        == pytest.approx(0.75, abs=0.05)
    assert after["xla_hits"] - before["xla_hits"] == 1


# -- the clocks ----------------------------------------------------------------

def test_wall_stamps_land_on_the_perf_counter_axis():
    assert telemetry.wall_to_perf(time.time()) == \
        pytest.approx(time.perf_counter(), abs=0.05)
    # the process started before this module's import, not hours before
    assert 0 < time.perf_counter() - telemetry.process_start() < 6 * 3600


def test_import_is_the_timelines_first_span():
    """Held at import, before any fixture: the buffer may have been
    cleared since, the histogram has not."""
    h = telemetry.registry.find("step_phase_seconds", {"phase": "import"})
    assert h is not None and h.snapshot()["count"] == 1
    assert 0 < h.snapshot()["sum"] < 600


# -- the front end's phases ------------------------------------------------------

def _net():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(3))
    return net


def _count(phase):
    h = telemetry.registry.find("step_phase_seconds", {"phase": phase})
    return 0 if h is None else h.snapshot()["count"]


def test_forward_opens_once_at_the_top_level_eager_call(timeline):
    net = _net()
    net.initialize()
    before = _count("forward")
    net(nd.ones((2, 5)))                    # deferred shapes: eager pass
    assert len(_held("phase.forward")) == 1
    assert _count("forward") == before + 1
    # the deferred initialisation it finished lies inside, one per layer
    fwd, = _held("phase.forward")
    inits = _held("phase.initialize")[1:]           # [0]: net.initialize
    assert len(inits) == 2
    for e in inits:
        assert fwd["ts"] <= e["ts"] and \
            e["ts"] + e["dur"] <= fwd["ts"] + fwd["dur"] + 1


def test_forward_is_not_opened_inside_a_whole_program_trace(timeline):
    net = _net()
    net.initialize()
    x = nd.ones((2, 5))
    net(x)
    net.hybridize()
    telemetry.clear_trace()
    net(x)                                  # traces the children inside
    net(x)
    assert len(_held("phase.forward")) == 2         # the two top calls
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    step = trainer.make_compiled_step(net, gluon.loss.L2Loss())
    telemetry.clear_trace()
    step.step(x, nd.ones((2, 3)))
    assert step.compiled
    assert _held("phase.forward") == []
    assert [e["name"] for e in _held("phase.step")] == [
        "phase.step.prepare", "phase.step.write_back"]


@pytest.mark.parametrize("call", ["initialize", "cast"])
def test_initialize_counts_once_for_a_tree(timeline, call):
    net = _net()
    if call == "cast":
        net.initialize()
        net(nd.ones((2, 5)))
    before = _count("initialize")
    if call == "initialize":
        net.initialize()
    else:
        net.cast("float16")        # recursive: a phase a block, one owner
    assert _count("initialize") == before + 1


def test_optimizer_state_is_initialize(timeline):
    net = _net()
    net.initialize()
    x = nd.ones((2, 5))
    net(x)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    step = trainer.make_compiled_step(net, gluon.loss.L2Loss())
    telemetry.clear_trace()
    step.step(x, nd.ones((2, 3)))
    first = len(_held("phase.initialize"))
    assert first >= 2               # the plan's states, _own_state's copies
    step.step(x, nd.ones((2, 3)))
    assert len(_held("phase.initialize")) == first  # steady state: none


# -- the reduction ---------------------------------------------------------------

def _span(name, start, end, base, **args):
    telemetry.record_span(name, base + start, base + end, **args)


def test_breakdown_innermost_owns_and_the_kinds_sum(timeline):
    base = time.perf_counter()
    _span("phase.import", 1.0, 3.0, base)
    _span("phase.forward", 4.0, 10.0, base)
    _span("phase.initialize", 4.5, 5.5, base)               # in forward
    _span("compile.trace", 5.0, 5.25, base, fun_name="a")   # in initialize
    _span("compile.lower", 6.0, 6.5, base, fun_name="a")
    _span("compile.cache_load", 6.5, 7.0, base, fun_name="a")
    _span("compile.backend", 7.0, 9.0, base, fun_name="b")
    _span("phase.step.prepare", 10.0, 10.5, base)
    _span("phase.retrace", 10.0, 10.25, base)
    _span("phase.compiled_step", 10.5, 11.0, base)
    _span("rpc.push", 0.0, 12.0, base)                      # not a kind
    found = telemetry.startup_breakdown(base, base + 12.0)
    want = {"import": 2.0, "initialize": 0.75, "eager_forward": 2.0,
            "trace": 0.25, "lower": 0.5, "cache_load": 0.5,
            "cold_compile": 2.0, "step_host": 1.0, "unspanned": 3.0}
    assert set(telemetry.STARTUP_KINDS) == set(want)
    for kind, seconds in want.items():
        assert found[kind] == pytest.approx(seconds, abs=1e-6), kind
    assert sum(found[k] for k in telemetry.STARTUP_KINDS) == \
        pytest.approx(12.0, abs=1e-9)
    # largest first, each with its kinds
    assert list(found["by_program"]) == ["b", "a"]
    assert found["by_program"]["a"] == pytest.approx(
        {"trace": 0.25, "lower": 0.5, "cache_load": 0.5, "total": 1.25})
    assert found["dropped"] == 0


def test_breakdown_clips_to_its_window(timeline):
    base = time.perf_counter()
    _span("phase.forward", 0.0, 10.0, base)
    _span("compile.backend", 2.0, 6.0, base, fun_name="f")
    found = telemetry.startup_breakdown(base + 4.0, base + 12.0)
    assert found["cold_compile"] == pytest.approx(2.0)
    assert found["eager_forward"] == pytest.approx(4.0)
    assert found["unspanned"] == pytest.approx(2.0)
    empty = telemetry.startup_breakdown(base + 20.0, base + 21.0)
    assert empty["unspanned"] == pytest.approx(1.0)
    assert empty["by_program"] == {}


def test_gaps_over_half_a_second_name_their_neighbours(timeline):
    base = time.perf_counter()
    _span("phase.import", 1.0, 2.0, base)
    _span("compile.backend", 2.25, 3.0, base, fun_name="f")     # 0.25: none
    _span("phase.forward", 5.0, 6.0, base)
    found = telemetry.startup_breakdown(base, base + 7.0)
    assert found["gaps"] == [
        {"start": pytest.approx(0.0), "end": pytest.approx(1.0),
         "before": None, "after": "import"},
        {"start": pytest.approx(3.0), "end": pytest.approx(5.0),
         "before": "compile.backend f", "after": "forward"},
        {"start": pytest.approx(6.0), "end": pytest.approx(7.0),
         "before": "forward", "after": None}]
    assert found["unspanned"] == pytest.approx(4.25)


def test_default_window_is_process_start_to_now(timeline):
    found = telemetry.startup_breakdown()
    total = sum(found[k] for k in telemetry.STARTUP_KINDS)
    assert total == pytest.approx(
        time.perf_counter() - telemetry.process_start(), abs=0.5)


# -- what is held ----------------------------------------------------------------

def test_the_cap_drops_the_newest_and_counts(timeline, monkeypatch):
    monkeypatch.setattr(telemetry, "_startup_left", [3])
    base = time.perf_counter()
    for i in range(5):
        _span("compile.trace", i, i + 0.5, base, fun_name="f%d" % i)
    assert [fun for _, fun in _names()] == ["f0", "f1", "f2"]
    assert telemetry.registry.value("telemetry.startup_spans_dropped") == 2
    assert telemetry.startup_breakdown(base, base + 5.0)["dropped"] == 2
    with telemetry.phase("forward"):
        pass
    assert len(_held()) == 3
    assert telemetry.registry.value("telemetry.startup_spans_dropped") == 3


def test_telemetry_off_holds_nothing(timeline, monkeypatch):
    monkeypatch.setenv("MX_TELEMETRY", "0")
    assert not telemetry.holding_spans()
    now = time.time()
    _feed([(TRACE, now - 1.0, now, "f")])
    with telemetry.phase("initialize"):
        pass
    assert _held() == []
    assert telemetry.registry.value("telemetry.startup_spans_dropped") == 0


def test_trace_events_keeps_its_meaning(timeline):
    """Tracing off: the start-up spans are held under their own category
    and ``trace_events()`` stays empty; tracing on: spans carry their own
    category and are what it returns."""
    with telemetry.phase("initialize"):
        pass
    assert [e["cat"] for e in _held()] == ["startup"]
    assert telemetry.trace_events() == []
    telemetry.start_tracing()
    try:
        with telemetry.phase("initialize"):
            pass
        now = time.time()
        _feed([(LOWER, now - 1.0, now, "jit(f)")])
    finally:
        telemetry.stop_tracing()
    assert [(e["name"], e["cat"]) for e in telemetry.trace_events()] == [
        ("phase.initialize", "phase"), ("compile.lower", "compile")]
    assert len(_held()) == 3


def test_dump_trace_carries_the_startup_spans(timeline, tmp_path):
    now = time.time()
    _feed([(TRACE, now - 1.0, now, "mx_step_step")])
    with telemetry.phase("initialize"):
        pass
    path = telemetry.dump_trace(str(tmp_path / "t.trace.json"))
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    assert [(e["name"], e["ph"]) for e in events] == [
        ("compile.trace", "X"), ("phase.initialize", "X")]
    assert events[0]["args"] == {"fun_name": "mx_step_step"}


# -- the operator's line ---------------------------------------------------------

def test_the_first_dispatch_logs_one_line(timeline, caplog):
    net = _net()
    net.initialize()
    x, y = nd.ones((2, 5)), nd.ones((2, 3))
    net(x)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    step = trainer.make_compiled_step(net, gluon.loss.L2Loss())
    with caplog.at_level(logging.INFO, logger="mxnet_tpu.step"):
        for _ in range(3):
            step.step(x, y)
    lines = [r.getMessage() for r in caplog.records
             if r.name == "mxnet_tpu.step"]
    assert len(lines) == 1
    line, = lines
    assert line.startswith("first compiled step dispatched ")
    assert "s since process start" in line
    for kind in telemetry.STARTUP_KINDS:
        assert " %s " % kind in line
    assert "costliest programs: " in line and "mx_step_step" in line


def test_the_line_is_not_built_when_nobody_listens(timeline, monkeypatch):
    net = _net()
    net.initialize()
    x, y = nd.ones((2, 5)), nd.ones((2, 3))
    net(x)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    step = trainer.make_compiled_step(net, gluon.loss.L2Loss())
    monkeypatch.setattr(telemetry, "startup_line",
                        lambda *a, **k: pytest.fail("built"))
    logging.getLogger("mxnet_tpu.step").setLevel(logging.WARNING)
    try:
        step.step(x, y)
    finally:
        logging.getLogger("mxnet_tpu.step").setLevel(logging.NOTSET)
    assert step._announced
