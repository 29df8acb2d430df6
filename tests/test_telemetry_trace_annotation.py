"""ISSUE 25: the program's own host spans on jax's profiler clock.

Under a ``jax.profiler`` session on XLA:CPU the host plane of the trace
holds ``mx.step`` with ``mx.step.prepare``, ``mx.step.dispatch`` and
``mx.step.write_back`` nested in it in that order, ``mx.step.retrace``
inside the first prepare, and the prefetcher's ``mx.data_wait``;
``import mxnet_tpu.telemetry`` still pulls in no jax, and there the hook
is a no-op.
"""
import glob
import itertools
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, profiler, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.io.prefetch import DevicePrefetcher

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEPS = 4


def _host_spans(trace_dir):
    """[(name, start_ns, end_ns)] of the mx.* events of the host planes."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("mx."):
                    out.append((ev.name, int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


@pytest.fixture(scope="module")
def spans(tmp_path_factory):
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, in_units=8, activation="relu"))
    net.add(nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier(), ctx=mx.cpu(0))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9})
    step = trainer.make_compiled_step(net, gluon.loss.L2Loss())
    rng = np.random.RandomState(0)
    pool = [(rng.randn(16, 8).astype(np.float32),
             rng.randn(16, 4).astype(np.float32)) for _ in range(2)]
    feed = DevicePrefetcher(itertools.cycle(pool),
                            device=mx.cpu(0).jax_device)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        for _ in range(STEPS):
            x, y = next(feed)
            loss = step.step(nd.NDArray(x, ctx=mx.cpu(0)),
                             nd.NDArray(y, ctx=mx.cpu(0)))
        loss.wait_to_read()
    finally:
        jax.profiler.stop_trace()
        feed.close()
    assert step.compiled, step.fallback_reason
    return _host_spans(trace_dir)


def _named(spans, name):
    return [s for s in spans if s[0] == name]


@pytest.mark.parametrize("name, count", [
    ("mx.step", STEPS), ("mx.step.prepare", STEPS),
    ("mx.step.dispatch", STEPS), ("mx.step.write_back", STEPS),
    ("mx.step.retrace", 1), ("mx.data_wait", STEPS),
])
def test_every_span_of_the_taxonomy_is_on_the_host_plane(spans, name, count):
    assert len(_named(spans, name)) == count


def test_parts_nest_in_the_step_in_order(spans):
    steps = _named(spans, "mx.step")
    for lo, hi in ((s[1], s[2]) for s in steps):
        inside = [s for s in spans if s[0] != "mx.step"
                  and s[0].startswith("mx.step.") and lo <= s[1] < hi]
        assert all(s[2] <= hi for s in inside)
        order = [s[0] for s in inside if s[0] != "mx.step.retrace"]
        assert order == ["mx.step.prepare", "mx.step.dispatch",
                         "mx.step.write_back"]
        prepare, dispatch, write_back = (
            s for s in inside if s[0] != "mx.step.retrace")
        assert prepare[2] <= dispatch[1] and dispatch[2] <= write_back[1]
    # the one retrace lies inside the first step's prepare
    retrace, = _named(spans, "mx.step.retrace")
    first = _named(spans, "mx.step.prepare")[0]
    assert first[1] <= retrace[1] and retrace[2] <= first[2]


def test_data_wait_lies_outside_the_step(spans):
    steps = _named(spans, "mx.step")
    for wait in _named(spans, "mx.data_wait"):
        assert not any(s[1] < wait[2] and wait[1] < s[2] for s in steps)


def test_the_phases_keep_their_histograms(spans):
    """The new names are phases of the same taxonomy: the histograms the
    old names fed are still fed, and the new ones beside them."""
    for name in ("step.prepare", "step.write_back", "compiled_step",
                 "retrace", "data_wait"):
        hist = telemetry.registry.find("step_phase_seconds", {"phase": name})
        assert hist is not None and hist.snapshot()["count"] >= 1, name
    # an annotation's name is no phase of its own, and the whole step is
    # an annotation only (the step record has its time)
    for name in ("step.dispatch", "step"):
        assert telemetry.registry.find("step_phase_seconds",
                                       {"phase": name}) is None


def test_step_annotation_is_numbered_by_the_update_count():
    span = telemetry.phase("compiled_step", annotation="step.dispatch")
    assert span.annotation == "step.dispatch"
    assert telemetry.phase("retrace").annotation == "retrace"
    ann = profiler.host_span("step", step_num=7)
    assert isinstance(ann, jax.profiler.StepTraceAnnotation)
    assert isinstance(profiler.host_span("step.prepare"),
                      jax.profiler.TraceAnnotation)


def test_annotate_and_its_null_span_are_gone():
    assert not hasattr(profiler, "annotate")
    assert not hasattr(profiler, "_NULL_SPAN")
    assert "annotate" not in profiler.__all__


def test_telemetry_imports_no_jax_and_the_hook_is_a_noop_there():
    code = (
        "import sys, importlib.util, types, os\n"
        "pkg = types.ModuleType('mxnet_tpu')\n"
        "pkg.__path__ = [os.path.join(%r, 'mxnet_tpu')]\n"
        "sys.modules['mxnet_tpu'] = pkg\n"
        "import mxnet_tpu.telemetry as t, mxnet_tpu.profiler as p\n"
        "assert 'jax' not in sys.modules, 'telemetry imported jax'\n"
        "with p.host_span('step', step_num=3):\n"
        "    with t.phase('compiled_step', annotation='step.dispatch'):\n"
        "        pass\n"
        "with t.rpc_span('kv.push', trace_id='abc'):\n"
        "    pass\n"
        "with p.host_span('data_wait'):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'a span imported jax'\n"
        "h = t.registry.find('step_phase_seconds', {'phase': 'compiled_step'})\n"
        "assert h.snapshot()['count'] == 1\n"
        "print('ok')\n" % REPO)
    env = dict(os.environ, MX_TELEMETRY="1")
    done = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=60)
    assert done.returncode == 0, done.stderr
    assert done.stdout.strip() == "ok"
