"""The rotary kernel on the packed layout (ops/rotary.py) against the
``rotary_embedding`` operator's composition (ops/nn.py) and ``jax.grad``
of it, in interpret mode on the CPU: both Laguna parametrisations (every
lane of a 128-lane head; the first 64 with YaRN and its attention
factor), a 256-lane head whose last 64 lanes turn (GLM's query), bfloat16
and float32, queries on many heads and keys on few; the lanes that pass,
bit for bit; the rule; the counter; and the composition's program, which
is what it was.  What interpret mode cannot show (tiling, fast memory) is
tests/test_aot_compile.py's; times are a chip run's (PERF.md section 6,
PR 40).
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import telemetry
from mxnet_tpu.gluon.model_zoo import laguna
from mxnet_tpu.ops import attention, nn as ops_nn, rotary
from mxnet_tpu.ops.nn import yarn_inv_freq

T = 2 * rotary._ROWS
SLIDING, FULL = (laguna.rotary_keywords(laguna.ROPE_XS_2[kind], 128)
                 for kind in ("sliding_attention", "full_attention"))
# name: (lanes a head, the operator's keywords)
KINDS = {
    "every-lane-of-128": (128, SLIDING),
    "first-64-of-128-yarn": (128, FULL),
    "last-64-of-256": (256, dict(rotary_dim=64, theta=1e6)),
    "last-64-of-128-with-a-factor": (128, dict(rotary_dim=64,
                                               attention_factor=0.5)),
    "every-lane-of-256": (256, dict(rotary_dim=256)),
}
DEFAULTS = dict(theta=10000.0, first=False, yarn=None, attention_factor=1.0)


def _composition(x, heads, keywords):
    """The operator off the chip, jitted: XLA:CPU folds the angles'
    power differently in an eager call, by more than a rounding."""
    return jax.jit(lambda x: ops_nn._rotary_embedding(
        x, num_heads=heads, **keywords))(x)


def _kernel(x, heads, keywords):
    return rotary.turn(x, heads, **dict(DEFAULTS, **keywords))


def _data(heads, d, dtype, seed=0, batch=2):
    return jnp.asarray(np.random.RandomState(seed).randn(batch, T, heads * d),
                       jnp.dtype(dtype))


def _close(got, want, dtype):
    """Equal to one rounding of the data's dtype (an FMA may move a
    float32 sum's last bit, and with it a bfloat16 result's)."""
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    ulp = 2.0 ** -7 if dtype == "bfloat16" else 2.0 ** -21
    assert np.isfinite(got).all()
    assert (np.abs(got - want) <= ulp * np.maximum(np.abs(want), 1.0)).all()
    # ... and where a float32 sum's last bit is rounded away, nearly
    # everywhere to the bit
    assert dtype == "float32" or (got == want).mean() > 0.99


@pytest.mark.parametrize("heads", [8, 2], ids=["q-8-heads", "k-2-heads"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_kernel_is_the_composition(kind, dtype, heads):
    d, keywords = KINDS[kind]
    assert rotary.rotary_rule(T, d, keywords["rotary_dim"], dtype)
    x = _data(heads, d, dtype)
    got = _kernel(x, heads, keywords)
    assert got.dtype == x.dtype and got.shape == x.shape
    _close(got, _composition(x, heads, keywords), dtype)
    # the lanes that pass are the data's, bit for bit; the others turn
    r = keywords["rotary_dim"]
    passing = np.arange(d) >= r if keywords.get("first") \
        else np.arange(d) < d - r
    by_head = lambda v: np.asarray(v, np.float32).reshape(-1, T, heads, d)
    np.testing.assert_array_equal(by_head(got)[..., passing],
                                  by_head(x)[..., passing])
    assert (by_head(got)[:, 1:][..., ~passing]
            != by_head(x)[:, 1:][..., ~passing]).mean() > 0.5


@pytest.mark.parametrize("heads", [8, 2], ids=["q-8-heads", "k-2-heads"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_cotangent_is_the_compositions(kind, dtype, heads):
    """``jax.grad`` through the ``custom_vjp`` - the same kernel on the
    negative angle - against ``jax.grad`` of the composition, under a
    loss whose cotangent differs lane by lane."""
    d, keywords = KINDS[kind]
    x = _data(heads, d, dtype)
    weight = _data(heads, d, dtype, seed=1).astype(jnp.float32)

    def grad(turn):
        return jax.jit(jax.grad(lambda x: (
            turn(x, heads, keywords).astype(jnp.float32) * weight).sum()))(x)

    got = grad(_kernel)
    assert got.dtype == x.dtype
    _close(got, grad(lambda x, heads, keywords: ops_nn._rotary_embedding(
        x, num_heads=heads, **keywords)), dtype)


def test_a_turn_and_its_cotangent_undo_each_other():
    """A turn is `attention_factor` times an orthogonal map: the backward
    kernel of the forward's result is the data times the factor squared."""
    factor = FULL["attention_factor"]
    x = _data(4, 128, "float32")
    _, back = jax.vjp(lambda x: _kernel(x, 4, FULL), x)
    again = np.asarray(back(_kernel(x, 4, FULL))[0]).reshape(2, T, 4, 128)
    want = np.asarray(x).reshape(2, T, 4, 128)
    np.testing.assert_allclose(again[..., :64], want[..., :64] * factor ** 2,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(again[..., 64:], want[..., 64:])


def test_what_passes_is_copied_whatever_its_partner_holds():
    """The lanes that pass go through a select, not ``x * 1 + partner *
    0``: an infinity or a NaN beside them, and a negative zero in them,
    change nothing."""
    x = np.array(_data(2, 128, "float32")).reshape(2, T, 2, 128)
    x[..., :64] = np.inf
    x[0, ..., 64:] = -0.0
    x[1, :, 0, :64] = np.nan
    got = np.asarray(_kernel(jnp.asarray(x.reshape(2, T, 256)), 2, FULL))
    assert np.array_equal(got.reshape(x.shape)[..., 64:].view(np.uint32),
                          x[..., 64:].view(np.uint32))


def test_the_tables_are_the_operators_angles():
    """cos on both lanes of a pair, -sin on its first and +sin on its
    second, the attention factor on both, 1 and 0 where a lane passes;
    backward the signs exchanged."""
    d, r = 128, FULL["rotary_dim"]
    keywords = dict(DEFAULTS, **FULL)
    c, s = rotary.tables(T, d, back=False, **keywords)
    assert c.shape == s.shape == (T, d) and c.dtype == s.dtype == jnp.float32
    inv = np.asarray(yarn_inv_freq(r, FULL["theta"], *FULL["yarn"]),
                     np.float32)
    angle = np.arange(T, dtype=np.float32)[:, None] * inv
    factor = np.float32(FULL["attention_factor"])
    np.testing.assert_allclose(c[:, :32], np.cos(angle) * factor, atol=2e-6)
    np.testing.assert_array_equal(c[:, 32:64], c[:, :32])
    np.testing.assert_allclose(s[:, 32:64], np.sin(angle) * factor,
                               atol=2e-6)
    np.testing.assert_array_equal(s[:, :32], -s[:, 32:64])
    assert (np.asarray(c[:, 64:]) == 1).all() and not np.asarray(
        s[:, 64:]).any()
    c_back, s_back = rotary.tables(T, d, back=True, **keywords)
    np.testing.assert_array_equal(c_back, c)
    np.testing.assert_array_equal(s_back, -np.asarray(s))


# (positions, lanes a head, lanes that turn, dtype) -> inside the rule?
RULE = {
    "laguna-sliding": ((8192, 128, 128, "bfloat16"), True),
    "laguna-full": ((8192, 128, 64, "bfloat16"), True),
    "glm-query-256-lanes": ((4096, 256, 64, "bfloat16"), True),
    "float32": ((T, 128, 128, "float32"), True),
    "a-head-of-192-lanes": ((4096, 192, 64, "bfloat16"), False),
    "glm-key-one-head-of-64": ((4096, 64, 64, "bfloat16"), False),
    "a-narrow-head": ((T, 16, 8, "float32"), False),
    "an-odd-length": ((T + 8, 128, 128, "bfloat16"), False),
    "an-odd-rotary-part": ((T, 128, 63, "bfloat16"), False),
    "more-lanes-than-the-head": ((T, 128, 256, "bfloat16"), False),
    "float16": ((T, 128, 128, "float16"), False),
    "a-head-wider-than-a-block": ((T, 2048, 64, "bfloat16"), False),
}


@pytest.mark.parametrize("call", sorted(RULE))
def test_the_rule_reads_shapes_and_dtypes_alone(call):
    args, inside = RULE[call]
    assert rotary.rotary_rule(*args) is inside


def _parent_composition(data, num_heads=1, rotary_dim=None, theta=10000.0,
                        first=False, yarn=None, attention_factor=1.0):
    """`ops/nn.py:_rotary_embedding` as PR 39 left it, line for line."""
    b, t, hd = data.shape
    d = hd // num_heads
    r = d if rotary_dim is None else rotary_dim
    half = r // 2
    if yarn is None:
        inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / r))
    else:
        inv = jnp.asarray(yarn_inv_freq(r, theta, *yarn), jnp.float32)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    if attention_factor != 1.0:
        cos, sin = cos * attention_factor, sin * attention_factor
    x = data.reshape(b, t, num_heads, d)
    rope = (x[..., :r] if first else x[..., d - r:]).astype(jnp.float32)
    x1, x2 = rope[..., :half], rope[..., half:]
    turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                             axis=-1).astype(data.dtype)
    parts = [turned, x[..., r:]] if first else [x[..., :d - r], turned]
    return jnp.concatenate(parts, axis=-1).reshape(b, t, hd)


def _program(fn, x):
    # a new function a call: a trace is remembered by function, and what
    # the caller patches is not among the arguments
    return str(jax.make_jaxpr(lambda x: fn(x))(x))


# heads, lanes a head, keywords: inside the rule (off the chip) and not
COMPOSED = {
    "laguna-sliding-off-the-chip": (4, 128, SLIDING, False),
    "laguna-full-off-the-chip": (4, 128, FULL, False),
    "glm-query-off-the-chip": (4, 256, dict(rotary_dim=64, theta=1e6),
                               False),
    "glm-key-on-the-chip": (1, 64, dict(rotary_dim=64, theta=1e6), True),
    "a-head-of-192-lanes-on-the-chip": (4, 192, dict(rotary_dim=64), True),
    "a-narrow-head-on-the-chip": (2, 16, dict(rotary_dim=8, first=True),
                                  True),
}


@pytest.mark.parametrize("call", sorted(COMPOSED))
def test_the_compositions_program_is_what_it_was(monkeypatch, call):
    """Off the chip, and on it for a call outside the rule, the operator
    traces the parent's composition primitive for primitive, forward and
    backward, and no kernel."""
    heads, d, keywords, on_the_chip = COMPOSED[call]
    monkeypatch.setattr(attention, "_on_tpu", lambda: on_the_chip)
    x = _data(heads, d, "bfloat16")
    for of in (lambda f: f, lambda f: jax.grad(
            lambda x: f(x).astype(jnp.float32).sum())):
        got = _program(of(lambda x: ops_nn._rotary_embedding(
            x, num_heads=heads, **keywords)), x)
        assert "pallas_call" not in got
        assert got == _program(of(lambda x: _parent_composition(
            x, num_heads=heads, **keywords)), x)


def test_on_the_chip_and_in_the_rule_the_kernel_runs(monkeypatch):
    """One kernel forward, one backward, nothing of the data kept, and no
    array of four axes."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    x = _data(4, 128, "bfloat16")

    def turn(x):
        return ops_nn._rotary_embedding(x, num_heads=4, **FULL)

    forward = _program(turn, x)
    assert forward.count("pallas_call") == 1
    backward = _program(jax.grad(
        lambda x: (turn(x).astype(jnp.float32) ** 2).sum()), x)
    assert backward.count("pallas_call") == 2
    for text in (forward, backward):
        assert "[2,%d,4,128]" % T not in text and "reshape" not in text
    assert not jax.tree_util.tree_leaves(
        jax.eval_shape(lambda x: jax.vjp(turn, x)[1], x))


def _calls():
    return {path: telemetry.registry.value("rotary_calls", {"path": path})
            for path in ("kernel", "composition")}


def test_the_counter_counts_both_paths(monkeypatch):
    """``rotary_calls{path}`` grows when the operator is TRACED: once a
    call of a jitted program however often the program runs."""
    x = _data(2, 128, "bfloat16")
    narrow = _data(2, 16, "bfloat16")
    before = _calls()
    fn = jax.jit(lambda x: ops_nn._rotary_embedding(x, num_heads=2))
    fn(x), fn(x)
    assert _calls() == dict(before, composition=before["composition"] + 1)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    jax.make_jaxpr(lambda x, n: (
        ops_nn._rotary_embedding(x, num_heads=2),
        ops_nn._rotary_embedding(x, num_heads=1, **FULL),
        ops_nn._rotary_embedding(n, num_heads=2)))(x, narrow)
    assert _calls() == {"kernel": before["kernel"] + 2,
                        "composition": before["composition"] + 2}


@pytest.mark.parametrize("split", ["batch-over-data-fsdp", "heads-over-tp"])
def test_under_a_layout_every_chip_turns_its_own_share(split):
    """Inside `attention_partition_scope` the kernel runs under the
    ``shard_map`` the flash kernels use - rows and heads are independent,
    so any split is exact: the result is the unsharded call's, bit for
    bit."""
    from jax.sharding import Mesh
    from mxnet_tpu.parallel import SpecLayout
    axes = ("data", "fsdp") if split.startswith("batch") else ("data", "tp")
    layout = SpecLayout.infer(Mesh(
        np.array(jax.devices()[:4]).reshape(2, 2), axes))
    x = _data(4, 128, "bfloat16", batch=4)
    want = _kernel(x, 4, FULL)
    with attention.attention_partition_scope(layout):
        fn = jax.jit(lambda x: _kernel(x, 4, FULL))
        text = str(jax.make_jaxpr(lambda x: _kernel(x, 4, FULL))(x))
        got = fn(x)
    assert "shard_map" in text
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_the_ladder_rehearses_on_the_cpu(tmp_path):
    """tools/rotary_ladder.py end to end at its tiny shape in interpret
    mode: a row for the copy, one for the composition, one a block shape,
    and the kernel's results are the composition's."""
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "rotary_ladder.py")
    spec = importlib.util.spec_from_file_location("_rotary_ladder", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    out = tmp_path / "ladder.jsonl"
    was = rotary._ROWS, rotary._BLOCK_LANES
    try:
        lines = tool.main(["--platform", "cpu", "--shape", "tiny",
                           "--blocks", "256,1024", "128,128", "--reps", "1",
                           "--inner", "1", "--out", str(out)])
    finally:
        rotary._ROWS, rotary._BLOCK_LANES = was
    assert [line["rung"] for line in lines] == [
        "copy", "composition", "kernel@256,1024", "kernel@128,128"]
    assert lines == [json.loads(line) for line in out.read_text().split("\n")
                     if line]
    for line in lines[2:]:
        assert line["forward_err"] < 2.0 ** -8
        assert line["backward_err"] < 2.0 ** -8
        assert line["device"] == "cpu"
