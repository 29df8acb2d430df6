"""BatchNorm's batch statistics from one read of the data (`BatchNorm`,
ops/nn.py): float32 means of ``d`` and ``d²`` for ``d = x - K``, ``K``
the moving mean, where the operator took ``jnp.mean`` and then
``jnp.var``.  Against a float64 two-pass NumPy reference: ``out``,
``new_mean`` and ``new_var`` for bfloat16 and float32 data, the channels
on axis 1 and on the last axis, ``fix_gamma`` either way, in the worst
cancellation (the batch mean 30 times the batch's deviation, the moving
mean 0: a first step) and with the moving mean near the batch's.  The
gradients against ``jax.grad`` of the two-pass operator kept below as it
was; the moving statistics' branch, bit for bit; the Gluon layer's
counter ``batch_norm_calls{stats}``.  That the two statistics come out of the
producer's fusion is tests/test_aot_compile.py's."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu import autograd, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops import registry

U = 2.0 ** -24          # float32's unit roundoff
EPS = 1e-5
MOMENTUM = 0.9


def _two_pass(data, gamma, beta, moving_mean, moving_var, eps=EPS,
              momentum=MOMENTUM, fix_gamma=True, use_global_stats=False,
              axis=1, training=True):
    """The operator as it was: ``jnp.mean``, then ``jnp.var``."""
    ax = axis % data.ndim
    red = tuple(i for i in range(data.ndim) if i != ax)
    bshape = [1] * data.ndim
    bshape[ax] = data.shape[ax]
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    if training and not use_global_stats:
        x32 = data.astype(jnp.float32)
        mean = jnp.mean(x32, axis=red)
        var = jnp.var(x32, axis=red)
        new_mean = momentum * moving_mean + (1.0 - momentum) * mean
        new_var = momentum * moving_var + (1.0 - momentum) * var
    else:
        mean, var = moving_mean, moving_var
        new_mean, new_var = moving_mean, moving_var
    inv = lax.rsqrt(var + eps).astype(data.dtype)
    out = (data - mean.reshape(bshape).astype(data.dtype)) * \
        (inv * g.astype(data.dtype)).reshape(bshape) + \
        beta.astype(data.dtype).reshape(bshape)
    return out, new_mean, new_var


_one_pass = registry.get_op("BatchNorm").fn


# case: (the batch mean in batch deviations, where the moving mean lies:
#        at 0, or 0.05 deviations from the batch mean)
CASES = {"mean-30-deviations-moving-0": (30.0, "zero"),
         "moving-mean-near-the-batch-mean": (30.0, "near"),
         "mean-1-deviation-moving-0": (1.0, "zero")}
SHAPES = {1: (8, 4, 8, 8), -1: (8, 8, 8, 4)}


def _every_case(test):
    """Each case, for bfloat16 and float32 data, the channels on axis 1
    and on the last axis, ``fix_gamma`` either way."""
    for name, values in (("fix_gamma", [True, False]), ("axis", [1, -1]),
                         ("dtype", ["bfloat16", "float32"]),
                         ("case", sorted(CASES))):
        test = pytest.mark.parametrize(name, values)(test)
    return test


def _inputs(case, dtype, axis, seed=0):
    """Data of `dtype` whose channels have the case's mean over
    deviation, gamma, beta, and the moving statistics; the float64
    reference's mean and biased variance of the data as rounded."""
    rng = np.random.RandomState(seed)
    shape = SHAPES[axis]
    c = shape[axis]
    bshape = [1] * len(shape)
    bshape[axis] = c
    ratio, moving = CASES[case]
    dev = rng.uniform(0.5, 2.0, c)
    mu = ratio * dev * rng.choice([-1.0, 1.0], c)
    x = rng.randn(*shape) * dev.reshape(bshape) + mu.reshape(bshape)
    x = jnp.asarray(x, jnp.dtype(dtype))
    x64 = np.asarray(x.astype(jnp.float32), np.float64)
    red = tuple(i for i in range(len(shape)) if i != axis % len(shape))
    mean, var = x64.mean(red), x64.var(red)
    mm = np.zeros(c) if moving == "zero" else \
        mean + 0.05 * np.sqrt(var) * rng.choice([-1.0, 1.0], c)
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    args = (x, f32(rng.uniform(0.5, 1.5, c)), f32(rng.uniform(-1, 1, c)),
            f32(mm), f32(rng.uniform(0.5, 2.0, c)))
    return args, x64, mean, var


def _bounds(x64, args, mean, var, axis):
    """Float32 rounding's worst case for the two statistics (recursive
    summation, any order: a sum of n terms is off by at most (n - 1)·u
    of the sum of their magnitudes).  With d = x - K, m1 = E[d] and
    kappa = m1² / var: the mean within u·|mean| + n·u·E|d|, the variance
    within n·u·(var + 3·m1²) - relative n·u·(1 + 3·kappa)."""
    k = np.asarray(args[3], np.float64)
    bshape = [1] * x64.ndim
    bshape[axis] = -1
    d = x64 - k.reshape(bshape)
    red = tuple(i for i in range(x64.ndim) if i != axis % x64.ndim)
    n = x64.size // x64.shape[axis]
    m1 = mean - k
    mean_err = U * np.abs(mean) + n * U * np.abs(d).mean(red)
    var_rel = n * U * (1.0 + 3.0 * m1 * m1 / var)
    return mean_err, var_rel


def _run(fn, args, **keywords):
    return jax.jit(lambda *a: fn(*a, **keywords))(*args)


@_every_case
def test_the_statistics_match_a_float64_two_pass_reference(case, dtype,
                                                           axis, fix_gamma):
    """``new_mean`` and ``new_var`` (momentum 0.9, the variance biased as
    it was) within float32 rounding of the float64 reference's update:
    the variance within 1e-5 relative wherever the moving mean lies
    near the batch's or the mean is a deviation from 0, and within the
    summation bound ``n·u·(1 + 3·kappa)`` in the worst case."""
    args, x64, mean, var = _inputs(case, dtype, axis)
    mean_err, var_rel = _bounds(x64, args, mean, var, axis)
    _, new_mean, new_var = _run(_one_pass, args, axis=axis,
                                fix_gamma=fix_gamma)
    mm, mv = (np.asarray(a, np.float64) for a in args[3:])
    want_mean = MOMENTUM * mm + (1 - MOMENTUM) * mean
    want_var = MOMENTUM * mv + (1 - MOMENTUM) * var
    # the update's own two products and sum: 3 roundings of its terms
    upd = lambda a, b: 3 * U * (MOMENTUM * np.abs(a) + (1 - MOMENTUM)
                                * np.abs(b))
    assert new_mean.dtype == new_var.dtype == jnp.float32
    assert np.all(np.abs(np.asarray(new_mean) - want_mean)
                  <= (1 - MOMENTUM) * mean_err + upd(mm, mean))
    assert np.all(np.abs(np.asarray(new_var) - want_var)
                  <= (1 - MOMENTUM) * var_rel * var + upd(mv, var))
    if CASES[case] != (30.0, "zero"):
        got_var = (np.asarray(new_var, np.float64) - MOMENTUM * mv) \
            / (1 - MOMENTUM)
        assert np.all(np.abs(got_var - var) <= 1e-5 * var)


def _ulp(v, dtype):
    """One unit in the last place of |v| in `dtype` (normal numbers)."""
    v = np.maximum(np.abs(np.asarray(v, np.float64)), 2.0 ** -100)
    return 2.0 ** (np.floor(np.log2(v)) - (7 if dtype == "bfloat16" else 23))


@_every_case
def test_the_output_is_the_reference_s_rounding(case, dtype, axis,
                                                fix_gamma):
    """``out`` is the normalisation it was, in the data's dtype, over the
    statistics the operator computed (bit for bit); against the same
    expression over the float64 reference's statistics it lies within
    one bfloat16 ulp of the larger of the result and the normalised term
    for bfloat16 data, and for float32 data within what the statistics'
    bounds carry through it and a few roundings."""
    args, x64, mean, var = _inputs(case, dtype, axis)
    mean_err, var_rel = _bounds(x64, args, mean, var, axis)
    keywords = dict(axis=axis, fix_gamma=fix_gamma)
    out, got_mean, got_var = _run(_one_pass, args, momentum=0.0, **keywords)
    same, _, _ = _run(_two_pass, args[:3] + (got_mean, got_var),
                      use_global_stats=True, **keywords)
    assert out.dtype == args[0].dtype
    assert np.array_equal(np.asarray(out, np.float32),
                          np.asarray(same, np.float32))
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    want, _, _ = _run(_two_pass, args[:3] + (f32(mean), f32(var)),
                      use_global_stats=True, **keywords)
    bshape = [1] * x64.ndim
    bshape[axis] = -1
    scale = 1.0 / np.sqrt(var + EPS)
    if not fix_gamma:
        scale = scale * np.asarray(args[1], np.float64)
    term = np.abs(x64 - mean.reshape(bshape)) * np.abs(scale).reshape(bshape)
    want = np.asarray(want, np.float64)
    ulp = _ulp(np.maximum(np.abs(want), term), dtype)
    if dtype == "float32":
        ulp = 4 * ulp + term * (var_rel / 2).reshape(bshape) + \
            (np.abs(scale) * (mean_err + U * np.abs(mean))).reshape(bshape)
    assert np.all(np.abs(np.asarray(out, np.float64) - want) <= ulp)


@_every_case
def test_the_gradients_match_the_two_pass_operator(case, dtype, axis,
                                                   fix_gamma):
    """``jax.grad`` with respect to data, gamma and beta against that of
    the two-pass operator, relative to each gradient's largest entry:
    within one bfloat16 ulp for bfloat16 data, and within the variance's
    summation bound for float32 data (the gradient of the normalisation
    reads the variance through its inverse root)."""
    args, x64, mean, var = _inputs(case, dtype, axis)
    _, var_rel = _bounds(x64, args, mean, var, axis)
    weight = jnp.asarray(np.random.RandomState(1).randn(*args[0].shape),
                         jnp.float32)

    def grads(fn):
        def loss(x, gamma, beta):
            out, _, _ = fn(x, gamma, beta, *args[3:], axis=axis,
                           fix_gamma=fix_gamma)
            return (out.astype(jnp.float32) * weight).sum()
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(*args[:3])

    rtol = 2.0 ** -8 if dtype == "bfloat16" else float(var_rel.max())
    for got, want in zip(grads(_one_pass), grads(_two_pass)):
        assert got.dtype == want.dtype
        got, want = (np.asarray(v, np.float64) for v in (got, want))
        assert np.all(np.abs(got - want) <= rtol * np.abs(want).max())


@pytest.mark.parametrize("axis", [1, -1])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("branch", ["use_global_stats", "inference"])
def test_the_moving_statistics_branch_is_what_it_was(branch, dtype, axis):
    """``use_global_stats`` and inference (``training=False``: serving's
    PREDICT programs) normalise with the moving statistics as before,
    bit for bit, and hand them on unchanged."""
    args, *_ = _inputs("moving-mean-near-the-batch-mean", dtype, axis)
    keywords = dict(axis=axis, fix_gamma=False)
    keywords.update({"use_global_stats": True} if branch == "use_global_stats"
                    else {"training": False})
    for got, want in zip(_run(_one_pass, args, **keywords),
                         _run(_two_pass, args, **keywords)):
        assert got.dtype == want.dtype
        assert np.array_equal(np.asarray(got, np.float32),
                              np.asarray(want, np.float32))


def _calls():
    return {stats: telemetry.registry.value("batch_norm_calls",
                                            {"stats": stats})
            for stats in ("batch", "moving")}


def test_a_hybridized_net_counts_each_layer_once_a_mode():
    """``batch_norm_calls{stats}`` grows where a layer is called: a
    hybridized net traced under `autograd.record` counts each of its
    layers once as ``batch`` - three layers of one width are three, where
    the operator is traced once for them - and once more outside it as
    ``moving``, however often each program runs; a layer that keeps
    ``use_global_stats`` counts ``moving`` under `autograd.record` too."""
    net = nn.HybridSequential()
    for fixed in (False, False, True):
        net.add(nn.BatchNorm(in_channels=4, use_global_stats=fixed))
    net.initialize()
    net.hybridize()
    x = mx.nd.array(np.random.RandomState(0).randn(2, 4, 3, 3))
    before = _calls()
    for _ in range(2):
        with autograd.record():
            net(x)
        net(x)
    assert _calls() == {"batch": before["batch"] + 2,
                        "moving": before["moving"] + 4}


def test_an_imperative_layer_counts_every_call():
    """Not hybridized, a layer's forward runs at every call: each counts."""
    layer = nn.BatchNorm(in_channels=4)
    layer.initialize()
    x = mx.nd.array(np.random.RandomState(0).randn(2, 4, 3, 3))
    before = _calls()
    with autograd.record():
        layer(x), layer(x)
    layer(x)
    assert _calls() == {"batch": before["batch"] + 2,
                        "moving": before["moving"] + 1}
