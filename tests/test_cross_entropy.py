"""The sparse-label softmax cross-entropy (`sparse_softmax_cross_entropy`,
ops/nn.py): logsumexp less the picked logit, in float32, against the
composition it replaced in `gluon.loss.SoftmaxCrossEntropyLoss` and
`glm_moe_lite.NextTokenLoss` - `log_softmax`, then `pick` - kept below
as those call sites had it.  Values and gradients, float32 and bfloat16
logits, labels at the edges and outside the classes (clipped, as `pick`
clips), the classes on another axis, sample weights; the gradient holds
no scatter; the counter ``cross_entropy_calls{path}``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd, telemetry
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.gluon.model_zoo import glm_moe_lite
from mxnet_tpu.ndarray.ndarray import invoke
from mxnet_tpu.ops import registry


def _op(name):
    return registry.get_op(name).fn


def _fused(pred, label, axis=-1):
    return _op("sparse_softmax_cross_entropy")(pred, label, axis=axis)


def _composition(pred, label, axis=-1):
    """SoftmaxCrossEntropyLoss's sparse-label lines before the operator:
    ``pred.log_softmax(axis)`` and ``-pick(pred, label, axis)``."""
    pred = _op("log_softmax")(pred, axis=axis)
    return -_op("pick")(pred, label, axis=axis, keepdims=False)


def _composition_term(logits, ids, ahead):
    """NextTokenLoss._term before the operator."""
    nll = -invoke("pick", logits.astype("float32").log_softmax(axis=-1),
                  invoke("roll", ids, shift=-ahead, axis=1), axis=-1)
    return invoke("slice_axis", nll, axis=1, begin=0,
                  end=ids.shape[1] - ahead).mean(axis=1)


# (logits' shape, the classes' axis, labels: drawn, the two edges, or
# outside the classes on both sides)
CASES = {
    "rows": ((6, 13), -1, "drawn"),
    "rows-edges": ((6, 13), -1, "edges"),
    "rows-outside": ((6, 13), -1, "outside"),
    "tokens": ((2, 5, 17), -1, "drawn"),
    "tokens-outside": ((2, 5, 17), 2, "outside"),
    "nct": ((3, 11, 4), 1, "drawn"),
    "nct-edges": ((3, 11, 4), 1, "edges"),
}


def _draw(case, dtype, seed=0):
    shape, axis, labels = CASES[case]
    rng = np.random.RandomState(seed)
    classes = shape[axis]
    rest = tuple(n for i, n in enumerate(shape) if i != axis % len(shape))
    x = (rng.standard_normal(shape) * 3).astype(np.float32)
    if labels == "drawn":
        y = rng.randint(0, classes, rest)
    elif labels == "edges":
        y = np.where(rng.rand(*rest) < 0.5, 0, classes - 1)
    else:
        y = np.where(rng.rand(*rest) < 0.5, -3, classes + 5)
    return jnp.asarray(x, dtype), jnp.asarray(y, jnp.int32), axis


def _tolerance(dtype):
    return dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32 \
        else dict(rtol=2e-2, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_operator_matches_the_composition(case, dtype):
    """Values and ``jax.grad`` against the composition on the same
    logits in float32 (what the decoder's loss and the benchmark's BERT
    loss ran); the operator's result is float32 whatever the logits."""
    x, y, axis = _draw(case, dtype)
    weights = jnp.linspace(0.5, 1.5, y.size).reshape(y.shape)
    out = _fused(x, y, axis)
    assert out.dtype == jnp.float32 and out.shape == y.shape
    want = _composition(x.astype(jnp.float32), y, axis)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)

    def total(loss):
        return jax.grad(lambda x: (loss(x, y, axis) * weights).sum())

    got = total(_fused)(x)
    assert got.dtype == x.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(total(lambda x, y, axis: _composition(
            x.astype(jnp.float32), y, axis))(x), np.float32),
        **_tolerance(dtype))


def test_bfloat16_logits_are_summed_in_float32():
    """On bfloat16 logits the composition rounds the log-probabilities
    to bfloat16; the operator does not: it stays at the float32
    reading, the composition strays from it."""
    x, y, axis = _draw("tokens", jnp.bfloat16)
    exact = _composition(x.astype(jnp.float32), y, axis)
    assert float(jnp.abs(_fused(x, y, axis) - exact).max()) < 1e-5
    assert float(jnp.abs(_composition(x, y, axis).astype(jnp.float32)
                         - exact).max()) > 1e-3


@pytest.mark.parametrize("form,scatters", [(_fused, False),
                                          (_composition, True)],
                         ids=["operator", "composition"])
def test_the_gradient_holds_no_scatter(form, scatters):
    """The pick's transpose: a select for the operator, the gather's
    scatter-add into a zero tensor of the logits' size for the
    composition."""
    x, y, axis = _draw("tokens", jnp.float32)
    text = str(jax.make_jaxpr(jax.grad(
        lambda x: form(x, y, axis).sum()))(x))
    assert ("scatter-add" in text) is scatters


@pytest.mark.parametrize("sparse_label,from_logits", [
    (True, False), (True, True), (False, False)])
@pytest.mark.parametrize("weighted", [False, True],
                         ids=["unweighted", "sample_weight"])
def test_the_gluon_loss_matches_its_old_lines(sparse_label, from_logits,
                                              weighted):
    """SoftmaxCrossEntropyLoss on the (N, C, T) layout with axis=1: the
    sparse-label loss on logits takes the operator, the other two keep
    the composition; loss and gradient as before either way."""
    x, y, axis = _draw("nct", jnp.float32)
    pred = nd.array(np.asarray(x))
    label = nd.array(np.asarray(y)) if sparse_label else \
        nd.array(np.asarray(jax.nn.one_hot(y, x.shape[1], axis=1)))
    sw = np.linspace(0.5, 1.5, 3, dtype=np.float32).reshape(3, 1)

    def want(x):
        if sparse_label and not from_logits:
            per = _composition(x, y, 1)
        elif sparse_label:
            per = -_op("pick")(x, y, axis=1)
        else:
            per = -(jax.nn.log_softmax(x, axis=1)
                    * jax.nn.one_hot(y, x.shape[1], axis=1)).sum(1)
        return (per * sw if weighted else per).mean(axis=1)

    pred.attach_grad()
    with autograd.record():
        got = gloss.SoftmaxCrossEntropyLoss(
            axis=1, sparse_label=sparse_label, from_logits=from_logits)(
                pred, label, nd.array(sw) if weighted else None)
    got.backward()
    np.testing.assert_allclose(got.asnumpy(), want(x), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(pred.grad.asnumpy(),
                               jax.grad(lambda x: want(x).sum())(x),
                               rtol=1e-5, atol=1e-6)


def _tiny_heads(mtp, seed=3):
    rng = np.random.RandomState(seed)
    heads = [nd.array(rng.standard_normal((2, 6, 19)) * 2,
                      dtype="bfloat16") for _ in range(2 if mtp else 1)]
    ids = nd.array(rng.randint(0, 19, (2, 6)), dtype="int32")
    return heads, ids


@pytest.mark.parametrize("mtp", [False, True], ids=["main", "mtp"])
def test_next_token_loss_matches_its_old_term(mtp, monkeypatch):
    """NextTokenLoss on bfloat16 heads, with and without the MTP head:
    the loss and both heads' gradients as the old term gave them."""
    results = []
    for term in (glm_moe_lite.NextTokenLoss._term, _composition_term):
        monkeypatch.setattr(glm_moe_lite.NextTokenLoss, "_term",
                            staticmethod(term))
        heads, ids = _tiny_heads(mtp)
        for h in heads:
            h.attach_grad()
        with autograd.record():
            loss = glm_moe_lite.NextTokenLoss(0.3)(
                tuple(heads) if mtp else heads[0], ids)
        loss.backward()
        results.append((loss.asnumpy(),
                        [h.grad.asnumpy().astype(np.float32)
                         for h in heads]))
    (loss, grads), (want, want_grads) = results
    assert loss.dtype == np.float32 and loss.shape == (2,)
    np.testing.assert_allclose(loss, want, rtol=1e-5, atol=1e-6)
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(g, w, rtol=2e-2, atol=1e-4)


def _calls():
    return {path: telemetry.registry.value("cross_entropy_calls",
                                           {"path": path})
            for path in ("fused", "composition")}


def test_the_counter_counts_both_paths():
    """``cross_entropy_calls{path}`` grows where a loss is called: once
    for a hybridized loss however often its program runs, once a head for
    `NextTokenLoss`; the composition's forms of the Gluon loss count
    ``composition``."""
    before = _calls()
    pred, label = nd.array(np.zeros((3, 23), np.float32)), nd.zeros((3,))
    loss = gloss.SoftmaxCrossEntropyLoss()
    loss.hybridize()
    loss(pred, label), loss(pred, label)
    assert _calls() == dict(before, fused=before["fused"] + 1)
    gloss.SoftmaxCrossEntropyLoss(from_logits=True)(pred, label)
    gloss.SoftmaxCrossEntropyLoss(sparse_label=False)(pred, nd.zeros((3, 23)))
    heads, ids = _tiny_heads(mtp=True)
    glm_moe_lite.NextTokenLoss(0.3)(tuple(heads), ids)
    assert _calls() == dict(before, fused=before["fused"] + 3,
                            composition=before["composition"] + 2)


def test_the_operator_is_wide_under_amp():
    """AMP's float32 list holds the operator beside `log_softmax`."""
    from mxnet_tpu.amp import lists
    assert "sparse_softmax_cross_entropy" in lists.FP32_OPS
    assert mx.nd.sparse_softmax_cross_entropy is not None
