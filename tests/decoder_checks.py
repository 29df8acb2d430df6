"""What the decoder test files (test_glm_moe_lite.py, test_nemotron_h.py,
test_laguna.py) hold a Gluon net to: the plain float32 reference of the
benchmark's model file, the net's own readings through the tape, and the
errors between the two that each file's limits judge.

The reference is read under one ``jax.jit`` for each configuration it is
read in: eagerly every primitive compiles its own program, and a model
file's reference took hundreds of such compiles a reading.  Parameters,
ids and the followed router choices are arguments of the program; the
configuration, the rounded operand dtype and what is left out are its
key.  Not collected by pytest: no ``test_`` prefix."""
import importlib.util
import os

import numpy as np

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def model_file(name):
    """benchmark/models/<name>.py, loaded as a module of its own."""
    spec = importlib.util.spec_from_file_location(
        "_" + name, os.path.join(REPO, "benchmark", "models", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def kind(name):
    """A parameter's kind: its name without the layer numbers."""
    return ".".join(p for p in name.split(".") if not p.isdigit())


def net_readings(net, ids, loss_fn, heads):
    """(logits, routing, loss, {name: grad}) of the Gluon net through the
    tape; the logits are (heads, B, T, V) where the net has more than one
    head (the net's outputs: the heads' logits, then the routing), else
    (B, T, V)."""
    x = nd.array(ids, ctx=mx.cpu(), dtype="int32")
    with autograd.record():
        outs = net(x)
        logits = outs[:heads] if heads > 1 else outs[0]
        loss = loss_fn(logits, x).mean()        # the reference's: over rows
    loss.backward()
    grads = {name: np.asarray(p.grad()._jax, np.float32)
             for name, p in net.collect_params().items()
             if p.grad_req != "null"}
    logits = np.stack([np.asarray(o._jax, np.float32) for o in outs[:heads]]) \
        if heads > 1 else np.asarray(outs[0]._jax, np.float32)
    return logits, np.asarray(outs[heads]._jax), \
        float(np.asarray(loss._jax, np.float32).mean()), grads


def _frozen(value):
    """A configuration as a hashable key: dicts and lists become tuples."""
    if isinstance(value, dict):
        return tuple(sorted((k, _frozen(v)) for k, v in value.items()))
    if isinstance(value, (list, tuple)):
        return tuple(_frozen(v) for v in value)
    return value


_JITTED = {}      # (what, the configuration's key, ...) -> jitted function


def _reader(model, config, operand, without, gradients, follows):
    """The jitted reading of `model`'s reference in one configuration:
    (params, ids[, given]) -> (logits, routing, loss, grads, gap)."""
    key = (model, _frozen(config), operand, tuple(without), gradients,
           follows)
    if key in _JITTED:
        return _JITTED[key]
    config = dict(config)
    leave_out = (tuple(without),) if without else ()

    def read(params, *inputs):
        *logits, routing, _, gap = model._forward(params, inputs, config,
                                                  operand, *leave_out)
        main = logits[0]
        if gradients:
            loss, grads = jax.value_and_grad(lambda ps: model.reference_loss(
                ps, inputs, config, operand))(params)
        else:   # the loss of the logits at hand, whatever made them wrong
            logp = jax.nn.log_softmax(main[:, :-1], axis=-1)
            loss, grads = -jnp.take_along_axis(
                logp, inputs[0][:, 1:, None], axis=-1).mean(), {}
        return (jnp.stack(logits) if len(logits) > 1 else main), routing, \
            loss, grads, gap

    _JITTED[key] = jax.jit(read)
    return _JITTED[key]


def jitted(fn, config):
    """A model file's `fn(params, inputs, config)` (`_forward`,
    `reference`) under one ``jax.jit`` a configuration, called as
    ``(params, *inputs)``."""
    key = (fn, _frozen(config))
    if key not in _JITTED:
        config = dict(config)
        _JITTED[key] = jax.jit(lambda params, *inputs: fn(params, inputs,
                                                          config))
    return _JITTED[key]


def reference_readings(model, params, ids, config, operand=None, given=None,
                       without=(), gradients=True):
    """The reference's four readings - logits, routing, loss, gradients -
    with its own router choices or following `given`, and the worst gap
    of a followed choice.
    `operand` rounds every product's operands to that dtype; `without`
    leaves terms out (a model file whose `_forward` takes them);
    `gradients` False gives the loss of the logits at hand and no
    gradients."""
    inputs = (ids,) if given is None else (ids, given)
    read = _reader(model, config, operand, without, gradients,
                   given is not None)
    logits, routing, loss, grads, gap = read(
        params, *(jnp.asarray(a) for a in inputs))
    return (np.asarray(logits), np.asarray(routing), float(loss),
            {k: np.asarray(v, np.float32) for k, v in grads.items()}), \
        float(np.asarray(gap).max())


def errors(got, want):
    """(logits error over the logit scale, relative loss error, {kind:
    gradient error over that kind's gradient norm})."""
    g_logits, _, g_loss, g_grads = got
    w_logits, _, w_loss, w_grads = want
    kinds = {}
    for name, g in g_grads.items():
        err = np.linalg.norm(g - w_grads[name])
        scale = np.linalg.norm(w_grads[name]) + 1e-12
        kinds[kind(name)] = max(kinds.get(kind(name), 0.0), err / scale)
    return (np.abs(g_logits - w_logits).max() / np.abs(w_logits).max(),
            abs(g_loss - w_loss) / abs(w_loss), kinds)


def fails(tol, errors, gap):
    """Whether readings of these errors and this gap fail any of `tol`'s
    limits (no gradients read: none of theirs)."""
    logits, loss, kinds = errors
    return bool(logits > tol["logits"] or loss > tol["loss"]
                or max(kinds.values(), default=0.0) > tol["grads"]
                or gap > tol["gap"])
