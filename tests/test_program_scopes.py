"""ISSUE 25: a program's XLA module carries its census name, and the step
program says where each of its instructions comes from.

  * ``Program`` names the function it jits after the registry name, so
    the module of ``step.step`` is ``jit_mx_step_step``;
  * ``programs.program_scopes`` finds the scopes step.py, Block.__call__
    and attention_core open - ``forward``, a ``transpose(`` path (the
    backward pass), ``optimizer``, block names, ``attention_core`` - in the
    compiled text, on demand;
  * a fusion whose instructions lie under two scopes comes back ``mixed``;
  * the scopes change no numerics: the lowered StableHLO without
    locations is what it was without them.
"""
import contextlib

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, programs
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo.bert import MultiHeadAttention


class _Net(gluon.HybridBlock):
    def __init__(self):
        super().__init__()
        self.attention = MultiHeadAttention(16, 2)
        self.head = nn.Dense(4, flatten=False, in_units=16)

    def forward(self, x):
        return self.head(self.attention(x))


def _tiny_step():
    mx.random.seed(0)
    net = _Net()
    net.initialize(mx.init.Xavier(), ctx=mx.cpu(0))
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(2, 8, 16).astype(np.float32))
    y = nd.array(rng.randn(2, 8, 4).astype(np.float32))
    net(x)                                  # finishes deferred shapes
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9})
    return trainer.make_compiled_step(net, gluon.loss.L2Loss()), x, y


@pytest.fixture(scope="module")
def scopes():
    step, x, y = _tiny_step()
    step.step(x, y)
    assert step.compiled, step.fallback_reason
    return programs.program_scopes("step.step")


@pytest.mark.parametrize("census, function", [
    ("step.step", "mx_step_step"),
    ("op.broadcast_add", "mx_op_broadcast_add"),
    ("serve.predict[b=8]", "mx_serve_predict_b_8"),
])
def test_module_name_is_the_census_name_sanitised(census, function):
    assert programs.module_name(census) == function
    wrapped = programs.register_program(census, lambda a: a + 1)
    text = wrapped.lower(jax.ShapeDtypeStruct((2,), np.float32)).as_text()
    assert "module @jit_%s " % function in text


def test_step_module_is_named_after_the_census(scopes):
    assert scopes["module"] == "jit_" + programs.module_name("step.step") \
        == "jit_mx_step_step"
    assert scopes["instructions"]


@pytest.mark.parametrize("top, needle", [
    ("forward", "jvp(forward)"),
    ("backward", "transpose(jvp(forward))"),
    ("optimizer", "/optimizer/"),
])
def test_top_level_scopes_are_found(scopes, top, needle):
    found = [i for i in scopes["instructions"].values() if i["top"] == top]
    assert found
    assert all(needle in i["scope"] for i in found)
    if top == "forward":
        assert not any("transpose(" in i["scope"] for i in found)


def test_block_names_and_attention_core_are_on_the_path(scopes):
    paths = [i["scope"] for i in scopes["instructions"].values()]
    # root by class name, children by the attribute their parent holds
    # them under
    assert any("/_Net/attention/query_key_value/" in p for p in paths)
    assert any("/_Net/head/" in p for p in paths)
    assert any("/L2Loss/" in p for p in paths)
    core = [p for p in paths if "attention_core" in p]
    assert any("transpose(" in p for p in core)         # backward
    assert any("transpose(" not in p for p in core)     # forward
    assert all("/_Net/attention/" in p for p in core)


@pytest.mark.parametrize("path, top", [
    ("jit(mx_step_step)/jit(main)/jvp(forward)/Net/dot_general", "forward"),
    ("jit(mx_step_step)/transpose(jvp(forward))/Net/mul", "backward"),
    ("jit(mx_step_step)/forward/Net/add", "forward"),
    ("jit(mx_step_step)/optimizer/sub", "optimizer"),
    ("jit(mx_step_step)/exchange/all_reduce", "exchange"),
    ("jit(mx_step_step)/metric/add", "metric"),
    ("jit(mx_step_step)/reshape", None),
    ("", None),
])
def test_top_scope_of_a_path(path, top):
    assert programs.top_scope(path) == top


_HAND_WRITTEN = '''HloModule jit_mx_step_step, is_scheduled=true

%fused_two (p0: f32[4], p1: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %p1 = f32[4]{0} parameter(1)
  %g = f32[4]{0} multiply(%p0, %p1), metadata={op_name="jit(mx_step_step)/transpose(jvp(forward))/Net/mul"}
  ROOT %w = f32[4]{0} subtract(%p0, %g), metadata={op_name="jit(mx_step_step)/optimizer/sub"}
}

%fused_one (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %a = f32[4]{0} exponential(%p0), metadata={op_name="jit(mx_step_step)/jvp(forward)/Net/exp"}
  ROOT %b = f32[4]{0} negate(%a), metadata={op_name="jit(mx_step_step)/transpose(jvp(forward))/Net/neg"}
}

%body (c: (s32[], f32[4])) -> (s32[], f32[4]) {
  %c = (s32[], f32[4]{0}) parameter(0)
  %x = f32[4]{0} get-tuple-element(%c), index=1
  %inner = f32[4]{0} add(%x, %x), metadata={op_name="jit(mx_step_step)/while/body/jvp(forward)/Net/add"}
  ROOT %t = (s32[], f32[4]{0}) tuple(%c, %inner)
}

%cond (c: (s32[], f32[4])) -> pred[] {
  %c = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt = pred[] constant(false)
}

ENTRY %main.9 (Arg_0: f32[4], Arg_1: f32[4]) -> f32[4] {
  %Arg_0 = f32[4]{0} parameter(0), metadata={op_name="t_vals[0]"}
  %Arg_1 = f32[4]{0} parameter(1)
  %fusion.1 = f32[4]{0} fusion(%Arg_0, %Arg_1), kind=kLoop, calls=%fused_two, metadata={op_name="jit(mx_step_step)/optimizer/sub"}
  %fusion.2 = f32[4]{0} fusion(%Arg_0), kind=kLoop, calls=%fused_one
  %loop = (s32[], f32[4]{0}) while(%Arg_1), condition=%cond, body=%body
  ROOT %copy.3 = f32[4]{0} copy(%fusion.1)
}
'''


def test_hand_written_text_two_scope_fusion_is_mixed():
    got = programs._parse_scopes(_HAND_WRITTEN)
    assert got["module"] == "jit_mx_step_step"
    ins = got["instructions"]
    # the entry's instructions and the while body's, no fused one
    assert set(ins) == {"Arg_0", "Arg_1", "fusion.1", "fusion.2", "loop",
                        "copy.3", "c", "x", "inner", "t", "lt"}
    assert ins["fusion.1"] == {
        "scope": "jit(mx_step_step)/optimizer/sub", "top": "optimizer",
        "tops": ["backward", "optimizer"], "mixed": True}
    # forward with its own transpose is ONE scope; the root names it
    assert ins["fusion.2"]["mixed"] is False
    assert ins["fusion.2"]["top"] == "backward"
    assert ins["fusion.2"]["tops"] == ["backward", "forward"]
    assert ins["inner"]["top"] == "forward"     # a scan window's body
    assert ins["copy.3"] == {"scope": "", "top": None, "tops": [],
                             "mixed": False}
    assert ins["Arg_0"]["scope"] == "t_vals[0]" and ins["Arg_0"]["top"] is None


def test_program_scopes_of_an_unknown_or_light_program_is_none():
    assert programs.program_scopes("no.such.program") is None
    light = programs.register_program("scopes.light_probe",
                                      lambda a: a * 2, mode="light")
    light(np.ones(3, np.float32))
    assert programs.program_scopes("scopes.light_probe") is None


def test_scopes_leave_the_lowered_program_unchanged(monkeypatch):
    """StableHLO without locations, with the scopes and with
    jax.named_scope replaced by a no-op: the same text."""
    def lowered():
        programs.reset_records()
        step, x, y = _tiny_step()
        step.step(x, y)
        program = next(iter(step._cache.values()))
        plan = step._plan()
        from mxnet_tpu.step import _step_abstract_args
        args = _step_abstract_args(step, plan, 1)
        xs = (jax.ShapeDtypeStruct(x.shape, np.float32),)
        ys = jax.ShapeDtypeStruct(y.shape, np.float32)
        return program.lower(*args[:9], xs, ys).as_text()

    with_scopes = lowered()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = lowered()
    assert "loc(" not in with_scopes
    assert with_scopes == without
