"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the training and serving main paths once, through the entry points
a user calls, at the published width of ResNet-50 v1 and BERT-base, on one
TPU chip; checks what comes out; exits non-zero on the first failed phase.
Times printed here are set-up and step times of 3 steps: a smoke, not a
measurement.

    python chip_smoke.py             # one chip, every phase
    python chip_smoke.py --chips 4   # only the data,fsdp=4 BERT-base step
                                     # and the one-chip step it is compared to

The parent imports nothing but the standard library (``import mxnet_tpu``
imports jax, and a parent that has touched jax holds the chip): every
phase runs in a child of its own, one after another, and the device facts
of the last line come from a child's output.  Each phase prints one JSON
line; the last line of a passing run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""
import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SELF = [sys.executable, os.path.abspath(__file__)]
ARTIFACTS = os.path.join(HERE, ".smoke_artifacts")   # exported model, ports
PLATFORM = "tpu"          # main() refuses anything else
PHASE_TIMEOUT_S = 1000    # one child; the whole run has 1200 s

# Full-width sizes.  ResNet-50 batch: the size the ahead-of-time compile
# for a v5e chip fits in 16 GB of HBM (see CHANGES.md, PR 22).
SIZES = {
    "train_resnet50": dict(batch=256, image=224, classes=1000,
                           layers=None, channels=None, lr=0.02),
    "train_bert_base": dict(layers=12, units=768, heads=12, vocab=30522,
                            batch=16, seq=512, lr=0.5),
    "eager": dict(rows=256, cols=8, hidden=32),
    # long causal at head size 128; BERT-base's own: 32 rows a chip of
    # 512 tokens, 12 heads of 64, no mask
    "pallas": dict(cases=(((2, 8, 2048, 128), True),
                          ((32, 12, 512, 64), False),
                          ((2, 20, 4096, 256), True))),
    "serve_resnet50": dict(model="resnet50_v1", image=224, classes=1000,
                           rows=(1, 3, 8, 2, 16, 5, 4, 1)),
    "serve_decode": dict(prompts=((3, 1, 4, 1, 5), (9, 2, 6),
                                  (5, 3, 5, 8, 9, 7, 9, 3, 2, 3, 8, 4),
                                  (2, 7, 1, 8)),
                         max_tokens=24),
    "fsdp4": dict(layers=12, units=768, heads=12, vocab=30522,
                  batch=16, seq=512, lr=0.5),
}
SIZES["decode_reference"] = SIZES["serve_decode"]
# Phases whose process is the CPU-pinned CLIENT of a replica it starts:
# the replica child holds the chip, so the client must stay off it.
CLIENT_PHASES = ("serve_resnet50", "serve_decode")


# ---------------------------------------------------------------------------
# helpers shared by the phase functions (child side: these import jax)
# ---------------------------------------------------------------------------

def _device_facts():
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _platforms(arrays):
    return sorted({d.platform for a in arrays for d in a.devices()})


class _LoweringCount:
    """Counts jax lowerings (every in-memory jit-cache miss lowers, whether
    or not the persistent cache then supplies the executable) and
    persistent-cache hits, from jax's own monitoring events."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        from jax import monitoring
        self.lowerings = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def close(self):
        from jax import monitoring
        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)

    def _on_duration(self, name, _secs, **_kw):
        if name == self.LOWER:
            self.lowerings += 1

    def _on_event(self, name, **_kw):
        if name == self.HIT:
            self.cache_hits += 1


def _train(net, data, label, loss_fn, lr, platform, steps=3, layout=None):
    """gluon.Trainer (SGD, momentum) -> Trainer.make_compiled_step; one
    warm-up step, then `steps` counted steps on the same batch.  Returns
    the facts the train phases check."""
    import jax
    from mxnet_tpu import gluon, programs
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": lr, "momentum": 0.9,
                             "multi_precision": True})
    step = trainer.make_compiled_step(net, loss_fn, layout=layout)
    count = _LoweringCount()
    t0 = time.perf_counter()
    losses = [step.step(data, label)]
    jax.block_until_ready(losses[0]._jax)
    warm_s = time.perf_counter() - t0
    if not step.compiled:
        raise AssertionError("compiled step fell back to eager: %s"
                             % step.fallback_reason)
    lowered0 = count.lowerings
    compiles0 = programs.program_summary()["compiles"]
    t0 = time.perf_counter()
    for _ in range(steps):
        losses.append(step.step(data, label))
    jax.block_until_ready(losses[-1]._jax)
    steps_s = time.perf_counter() - t0
    count.close()
    values = [float(l.asnumpy().mean()) for l in losses]
    params = [p.data()._jax for p in net.collect_params().values()]
    states = [s._jax for s in _state_leaves(trainer)]
    facts = {
        "losses": values,
        "warmup_seconds": round(warm_s, 2),
        "seconds_per_step_smoke": round(steps_s / steps, 4),
        "compiles_in_steps": programs.program_summary()["compiles"]
        - compiles0,
        "lowerings_in_steps": count.lowerings - lowered0,
        "xla_cache_hits": count.cache_hits,
        "param_platforms": _platforms(params),
        "state_platforms": _platforms(states),
        "loss_platforms": _platforms([losses[-1]._jax]),
        "n_params": int(sum(p.size for p in params)),
    }
    for v in values:
        if not v == v or abs(v) == float("inf"):
            raise AssertionError("non-finite loss: %r" % (values,))
    if not values[-1] < values[0]:
        raise AssertionError("loss did not fall: %r" % (values,))
    for key in ("param_platforms", "state_platforms", "loss_platforms"):
        if facts[key] != [platform]:
            raise AssertionError("%s = %r, expected [%r]"
                                 % (key, facts[key], platform))
    if facts["compiles_in_steps"] or facts["lowerings_in_steps"]:
        raise AssertionError("compiled inside the counted steps: %r" % facts)
    return facts, step, params, states


def _state_leaves(trainer):
    """Every optimizer-state NDArray of the trainer's first updater
    (momentum, and the float32 master weights of bf16 parameters)."""
    from mxnet_tpu.ndarray.ndarray import NDArray
    out = []

    def walk(s):
        if isinstance(s, NDArray):
            out.append(s)
        elif isinstance(s, (list, tuple)):
            for x in s:
                walk(x)

    for state in trainer._updaters[0].states.values():
        walk(state)
    return out


def _seeded(seed):
    import numpy as np
    import mxnet_tpu as mx
    mx.random.seed(seed)
    return np.random.RandomState(seed)


# ---------------------------------------------------------------------------
# phases (each returns a JSON-able dict; raising fails the run)
# ---------------------------------------------------------------------------

def train_resnet50(batch, image, classes, layers, channels, lr,
                   platform=PLATFORM, seed=0):
    """Model-zoo ResNet-50 v1 (bf16, float32 master weights), the compiled
    train step, 1 + 3 steps.  `layers`/`channels` exist for the CPU test,
    which cannot afford 50 layers; None means the model-zoo resnet50_v1."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.gluon.model_zoo import vision
    rng = _seeded(seed)
    ctx = mx.tpu(0)
    if layers is None:
        net = vision.resnet50_v1(classes=classes)
    else:
        from mxnet_tpu.gluon.model_zoo.vision.resnet import (BottleneckV1,
                                                             ResNetV1)
        net = ResNetV1(BottleneckV1, layers, channels, classes=classes)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net.cast("bfloat16")
    net.hybridize()
    # deferred shapes resolve on the first (imperative) forward: 2 rows
    net(nd.zeros((2, 3, image, image), ctx=ctx, dtype="bfloat16"))
    x = nd.array(rng.randn(batch, 3, image, image).astype("float32"),
                 ctx=ctx, dtype="bfloat16")
    y = nd.array(rng.randint(0, classes, batch).astype("float32"), ctx=ctx)
    sce = gluon.loss.SoftmaxCrossEntropyLoss()

    def loss_fn(out, label):
        return sce(out.astype("float32"), label)

    facts, _, _, _ = _train(net, x, y, loss_fn, lr, platform)
    facts.update(batch=batch, image=image, learning_rate=lr)
    return facts


def _bert(layers, units, heads, vocab, seq, ctx):
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo import bert
    net = bert.get_bert(num_layers=layers, units=units, num_heads=heads,
                        vocab_size=vocab, max_length=seq, dropout=0.0,
                        use_classifier=False)
    net.initialize(mx.init.Normal(0.02), ctx=ctx)
    net.cast("bfloat16")
    net.hybridize()
    # deferred shapes resolve on the first (imperative) forward: 2 rows
    two = nd.zeros((2, seq), ctx=ctx, dtype="int32")
    net(two, two)
    return net


def _bert_batch(rng, vocab, batch, seq, ctx):
    from mxnet_tpu import nd
    tok = nd.array(rng.randint(0, vocab, (batch, seq)), ctx=ctx,
                   dtype="int32")
    seg = nd.zeros((batch, seq), ctx=ctx, dtype="int32")
    lab = nd.array(rng.randint(0, vocab, (batch, seq)).astype("float32"),
                   ctx=ctx)
    return tok, seg, lab


def _mlm_loss():
    from mxnet_tpu import gluon
    sce = gluon.loss.SoftmaxCrossEntropyLoss()

    def loss_fn(outs, label):
        return sce(outs[-1].astype("float32"), label)   # (B, T, vocab)

    return loss_fn


def _attention_impl(heads, units, seq):
    """Which implementation ops.attention.attention_heads picks for this
    model's (B, T, H*D): the Pallas flash kernels or the jnp composition
    XLA compiles.  Read off the lowering, not off the rule."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import attention_heads
    q = jax.ShapeDtypeStruct((2, seq, units), jnp.bfloat16)
    text = jax.jit(lambda q, k, v: attention_heads(q, k, v, heads)) \
        .lower(q, q, q).as_text()
    return "flash" if "tpu_custom_call" in text else "xla"


def train_bert_base(layers, units, heads, vocab, batch, seq, lr,
                    platform=PLATFORM, seed=0):
    """model_zoo.bert.get_bert (bf16, float32 master weights, dropout 0),
    MLM cross-entropy, the compiled train step, 1 + 3 steps."""
    import mxnet_tpu as mx
    rng = _seeded(seed)
    ctx = mx.tpu(0)
    net = _bert(layers, units, heads, vocab, seq, ctx)
    tok, seg, lab = _bert_batch(rng, vocab, batch, seq, ctx)
    facts, _, _, _ = _train(net, (tok, seg), lab, _mlm_loss(), lr, platform)
    facts.update(batch=batch, seq=seq, learning_rate=lr,
                 attention_impl=_attention_impl(heads, units, seq))
    return facts


def eager(rows, cols, hidden, platform=PLATFORM, seed=0):
    """The imperative path: NDArray ops under autograd.record with an
    in-place update through the chunk, then 3 eager Trainer.step calls
    (the donated fused optimizer) and a read through a view of a weight
    taken BEFORE the steps — it must show the updated values, and no
    donated buffer may be read after it was deleted."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    rng = _seeded(seed)
    ctx = mx.tpu(0)
    X = nd.array(rng.randn(rows, cols).astype("float32"), ctx=ctx)
    w_true = rng.randn(cols, 1).astype("float32")
    Y = nd.array(X.asnumpy() @ w_true, ctx=ctx)
    w = nd.zeros((cols, 1), ctx=ctx)
    w.attach_grad()
    first = last = None
    for _ in range(20):
        with autograd.record():
            loss = ((nd.dot(X, w) - Y) ** 2).mean()
        loss.backward()
        head = w[0:2]                  # a view of the root chunk
        w -= 0.1 * w.grad              # in-place update through the chunk
        head *= 1.0                    # in-place write through the view
        last = float(loss.asnumpy())
        first = last if first is None else first
    if not last < 0.5 * first:
        raise AssertionError("imperative loop did not converge: %r -> %r"
                             % (first, last))
    if _platforms([w._jax, loss._jax]) != [platform]:
        raise AssertionError("imperative arrays on %r"
                             % _platforms([w._jax, loss._jax]))

    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(hidden, in_units=cols, activation="relu"))
    net.add(gluon.nn.Dense(1, in_units=hidden))
    net.initialize(mx.init.Xavier(), ctx=ctx)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9})
    weight = net[0].weight.data()
    view = weight[0:2]                 # taken BEFORE the donated steps
    before = view.asnumpy().copy()
    l2 = gluon.loss.L2Loss()
    losses = []
    for _ in range(3):
        with autograd.record():
            loss = l2(net(X), Y)
        loss.backward()
        trainer.step(rows)
        losses.append(float(loss.asnumpy().mean()))
    after_view = view.asnumpy()        # raises if it reads a deleted buffer
    after_root = net[0].weight.data().asnumpy()[0:2]
    if not np.array_equal(after_view, after_root):
        raise AssertionError("view of a weight is stale after eager steps")
    if np.array_equal(after_view, before):
        raise AssertionError("eager Trainer.step did not change the weight")
    if not losses[-1] < losses[0]:
        raise AssertionError("eager losses did not fall: %r" % (losses,))
    return {"imperative_loss": [first, last], "trainer_losses": losses,
            "param_platforms": _platforms(
                [p.data()._jax for p in net.collect_params().values()])}


def pallas(cases, platform=PLATFORM, seed=0):
    """ops.attention.attention_core with the flash path forced, bf16,
    forward and gradient, against _attention_jnp on the same inputs, for
    each (shape, causal) of `cases`.  On the TPU the lowered text must
    hold the Mosaic kernels (tpu_custom_call); anywhere else Pallas
    interprets and it must not."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.ops import attention as att
    rng = np.random.RandomState(seed)

    def rel(a, b):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-6))

    def one(shape, causal):
        q, k, v = (jnp.asarray(rng.randn(*shape), jnp.bfloat16)
                   for _ in range(3))
        scale = 1.0 / shape[-1] ** 0.5

        def flash(q, k, v):
            with att.attention_impl_scope("pallas"):
                return att.attention_core(q, k, v, causal=causal)

        def ref(q, k, v):
            return att._attention_jnp(q, k, v, scale, causal)

        def grads(f):
            return jax.jit(jax.grad(
                lambda q, k, v: f(q, k, v).astype(jnp.float32).sum(),
                argnums=(0, 1, 2)))

        fwd_text = jax.jit(flash).lower(q, k, v).as_text()
        bwd_text = grads(flash).lower(q, k, v).as_text()
        calls = {"forward": fwd_text.count("tpu_custom_call"),
                 "backward": bwd_text.count("tpu_custom_call")}
        want = platform == "tpu"
        if (calls["forward"] > 0) != want or (calls["backward"] > 0) != want:
            raise AssertionError("tpu_custom_call counts %r on %r"
                                 % (calls, platform))
        out, want_out = jax.jit(flash)(q, k, v), jax.jit(ref)(q, k, v)
        g, want_g = grads(flash)(q, k, v), grads(ref)(q, k, v)
        # bf16 has 8 mantissa bits (2^-8 = 3.9e-3); the kernel and the
        # reference round at different points of a 2048-term softmax sum
        errs = {"out": rel(out, want_out),
                "dq": rel(g[0], want_g[0]), "dk": rel(g[1], want_g[1]),
                "dv": rel(g[2], want_g[2])}
        if not all(e == e and e < 5e-2 for e in errs.values()):
            raise AssertionError("flash vs jnp relative errors %r at %r"
                                 % (errs, shape))
        return {"tpu_custom_calls": calls, "max_rel_err": errs,
                "shape": list(shape), "causal": causal}

    return {"cases": [one(tuple(shape), causal) for shape, causal in cases]}


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class _Replica:
    """A real ``python -m mxnet_tpu.serve`` child on the chip."""

    def __init__(self, args, env, extra_env=None, ready_timeout=900):
        os.makedirs(ARTIFACTS, exist_ok=True)
        self.port = _free_port()
        self._ready = ready = os.path.join(ARTIFACTS,
                                           "ready-%d" % self.port)
        if os.path.exists(ready):
            os.remove(ready)
        env = dict(env, **(extra_env or {}))
        env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
        t0 = time.perf_counter()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "mxnet_tpu.serve", "--port",
             str(self.port), "--ready-file", ready] + list(args),
            env=env, cwd=HERE, stdout=sys.stderr)
        while not os.path.exists(ready):
            if self.proc.poll() is not None:
                raise AssertionError("replica exited %d before it was "
                                     "ready" % self.proc.returncode)
            if time.perf_counter() - t0 > ready_timeout:
                self.kill()
                raise AssertionError("replica not ready in %d s"
                                     % ready_timeout)
            time.sleep(0.2)
        self.ready_seconds = round(time.perf_counter() - t0, 2)
        self.addr = "127.0.0.1:%d" % self.port

    def drive(self, requests):
        """requests(client) against the replica, then STOP.  Returns
        (answers, HEALTH before, HEALTH after, the replica's exit code);
        the replica is gone afterwards whatever happened."""
        from mxnet_tpu.serve.client import ServeClient
        try:
            client = ServeClient([self.addr], timeout=120)
            health = client.health()
            answers = requests(client)
            after = client.health()
            client.stop()
            client.close()
            try:
                rc = self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                raise AssertionError("replica did not exit after STOP")
        finally:
            self.kill()
        return answers, health, after, rc

    def kill(self):
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        if os.path.exists(self._ready):
            os.remove(self._ready)


def serve_resnet50(model, image, classes, rows, server_env,
                   platform=PLATFORM, seed=0):
    """Export the seeded model (net.export), start a real replica on it,
    send predict requests of mixed row counts from this CPU-pinned client,
    and compare with a float32 host forward of the same weights."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.gluon.model_zoo import vision
    rng = _seeded(seed)
    os.makedirs(ARTIFACTS, exist_ok=True)
    prefix = os.path.join(ARTIFACTS, model)
    net = getattr(vision, model)(classes=classes)
    net.initialize(mx.init.Xavier())           # host: this process is pinned
    net.hybridize()
    net(nd.zeros((1, 3, image, image)))        # deferred shapes
    net.export(prefix)
    requests = [rng.randn(n, 3, image, image).astype("float32")
                for n in rows]
    # the reference: float32, on the host, from the exported artifact
    host = gluon.SymbolBlock.imports(prefix + "-symbol.json", ["data"],
                                     prefix + "-0000.params")
    host.hybridize()
    want = host(nd.array(np.concatenate(requests))).asnumpy()

    replica = _Replica(["--model", prefix, "--example-shape",
                        "3,%d,%d" % (image, image)], server_env)
    answers, health, after, rc = replica.drive(
        lambda client: [client.predict([r])[1][0] for r in requests])
    got = np.concatenate(answers)
    # Tolerance (finding 7): the reference multiplies in float32; the TPU's
    # default precision rounds float32 matmul/conv operands to bf16 (8
    # mantissa bits, 2^-8 = 3.9e-3 per product) and the error compounds
    # through 53 convolutions.  Measured against the logits' own scale:
    # 4.3e-3 on a v5e (PR 22's chip run); the bound leaves 5x for other
    # seeds.  Host against host, only summation order differs.
    scale = float(np.abs(want).max())
    err = float(np.abs(got - want).max()) / scale
    tol = 2e-2 if platform == "tpu" else 1e-4
    facts = {"replica_param_platform": health.get("param_platform"),
             "ready_seconds": replica.ready_seconds,
             "requests": len(requests), "rows": list(rows),
             "max_abs_err_over_logit_scale": err, "tolerance": tol,
             "top1_agreement": float((got.argmax(1) == want.argmax(1))
                                     .mean()),
             "retraces_while_serving": after["retraces"]
             - health["retraces"],
             "replica_exit_code": rc}
    if facts["replica_param_platform"] != platform:
        raise AssertionError("replica parameters on %r"
                             % facts["replica_param_platform"])
    if got.shape != want.shape or not np.isfinite(got).all():
        raise AssertionError("bad answers: shape %r" % (got.shape,))
    if not err <= tol:
        raise AssertionError("answers differ from the host reference: "
                             "%g > %g" % (err, tol))
    if facts["retraces_while_serving"] or rc != 0:
        raise AssertionError("replica: %r" % facts)
    return facts


def decode_reference(prompts, max_tokens, platform=PLATFORM):
    """decode.reference_generate for each prompt, on this process's
    default device."""
    from mxnet_tpu.serve import decode
    return {"tokens": [decode.reference_generate(list(p), max_tokens)
                       for p in prompts]}


def serve_decode(prompts, max_tokens, server_env, platform=PLATFORM):
    """``python -m mxnet_tpu.serve --decode`` with MX_SERVE_KV_PAGES set
    (the paged engine), generate calls from this CPU-pinned client,
    token-identical to decode.reference_generate.  The built-in LM is
    tiny: this shows the engine's programs and donated pools work on the
    chip, not a width.  The reference runs on the replica's device, in a
    child of its own after the replica has released the chip: greedy
    argmax over float32 logits is not stable across the TPU's bf16
    matmul passes and the host's float32 ones (finding 7), so the host's
    tokens are reported, not required."""
    replica = _Replica(["--decode"], server_env,
                       extra_env={"MX_SERVE_KV_PAGES": "40"})
    got, health, after, rc = replica.drive(
        lambda client: [client.generate(list(p), max_tokens=max_tokens)[1]
                        for p in prompts])
    want = _run_child("decode_reference", server_env)["tokens"]
    host = decode_reference(prompts, max_tokens)["tokens"]
    dec = health["decode"]
    facts = {"engine": dec["engine"],
             "replica_param_platform": dec.get("param_platform"),
             "ready_seconds": replica.ready_seconds,
             "generations": len(got), "tokens": sum(len(t) for t in got),
             "identical_to_reference": got == want,
             "identical_to_host_float32": sum(
                 g == h for g, h in zip(got, host)),
             "retraces_while_serving": after["decode"]["retraces"]
             - dec["retraces"],
             "replica_exit_code": rc}
    if facts["engine"] != "paged" or \
            facts["replica_param_platform"] != platform:
        raise AssertionError("replica: %r" % facts)
    if got != want:
        raise AssertionError("tokens differ from reference_generate: "
                             "%r vs %r" % (got, want))
    if facts["retraces_while_serving"] or rc != 0:
        raise AssertionError("replica: %r" % facts)
    return facts


def fsdp4(layers, units, heads, vocab, batch, seq, lr, platform=PLATFORM,
          seed=0, chips=4):
    """BERT-base, same seed and global batch, through
    make_compiled_step(layout=SpecLayout) on a data,fsdp=4 mesh over the
    four chips in one process, and the one-chip step it is compared to."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import SpecLayout, make_mesh
    ctx = mx.tpu(0)
    runs = {}
    for name in ("one_chip", "fsdp"):
        rng = _seeded(seed)
        net = _bert(layers, units, heads, vocab, seq, ctx)
        tok, seg, lab = _bert_batch(rng, vocab, batch, seq, ctx)
        layout = None
        if name == "fsdp":
            mesh = make_mesh(axes=("data", "fsdp"), shape=(-1, chips),
                             devices=jax.devices()[:chips])
            layout = SpecLayout.infer(mesh)
        facts, step, params, states = _train(
            net, (tok, seg), lab, _mlm_loss(), lr, platform, layout=layout)
        runs[name] = facts
        if name == "fsdp":
            per_device = {}
            for a in params + states:
                for sh in a.addressable_shards:
                    per_device[sh.device.id] = \
                        per_device.get(sh.device.id, 0) + sh.data.nbytes
            total = sum(a.nbytes for a in params + states)
            text = _step_program_text(step)
    out = {"one_chip_losses": runs["one_chip"]["losses"],
           "fsdp_losses": runs["fsdp"]["losses"],
           "param_and_state_bytes": total,
           "bytes_per_device": [per_device[k] for k in sorted(per_device)],
           "share_per_device": [round(per_device[k] / total, 3)
                                for k in sorted(per_device)],
           "collectives": {op: text.count(op) for op in
                           ("all-gather", "reduce-scatter", "all-reduce")},
           "seconds_per_step_smoke": {
               k: v["seconds_per_step_smoke"] for k, v in runs.items()}}
    # same math, different reduction order across four shards of the
    # batch, in bf16 activations: agree to 2% of the loss
    for a, b in zip(out["one_chip_losses"], out["fsdp_losses"]):
        if not abs(a - b) <= 2e-2 * abs(a):
            raise AssertionError("one-chip and fsdp losses differ: %r" % out)
    # a quarter each when every array divides; what does not divide by
    # the fsdp axis replicates (BERT-base's 30522-row word embedding)
    held = sorted(per_device.values())
    if len(held) != chips or held[-1] > 0.5 * total or \
            held[-1] > 1.1 * held[0]:
        raise AssertionError("state is not spread over the chips: %r" % out)
    if not out["collectives"]["all-gather"] or not (
            out["collectives"]["reduce-scatter"]
            or out["collectives"]["all-reduce"]):
        raise AssertionError("no collectives in the step: %r" % out)
    return out


def _step_program_text(step):
    """Compiled text of the step's one registered program (the census
    wrapper keeps its executables per signature)."""
    program = next(iter(step._cache.values()))
    compiled = next(iter(program._cache.values()))
    return compiled.as_text()


PHASE_FUNCS = {f.__name__: f for f in (
    train_resnet50, train_bert_base, eager, pallas, serve_resnet50,
    serve_decode, decode_reference, fsdp4)}


# ---------------------------------------------------------------------------
# child and parent
# ---------------------------------------------------------------------------

def _child(name):
    """Run one phase in this process and print its JSON line."""
    sys.path.insert(0, HERE)
    kwargs = dict(SIZES[name])
    t0 = time.perf_counter()
    result = {"phase": name}
    if name in CLIENT_PHASES:
        kwargs["server_env"] = dict(os.environ)   # the replica's: unpinned
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["MX_FORCE_CPU"] = "1"
    else:
        result["device"] = _device_facts()
        if result["device"]["platform"] != PLATFORM:
            raise SystemExit("chip_smoke: %s needs a %r device, jax found "
                             "%r" % (name, PLATFORM, result["device"]))
    result.update(PHASE_FUNCS[name](platform=PLATFORM, **kwargs))
    result["seconds"] = round(time.perf_counter() - t0, 1)
    result["ok"] = True
    print(json.dumps(result), flush=True)


def _run_child(name, env):
    """Start one phase child, pass its output through, return the JSON
    object of its last line.  A child that fails fails the run."""
    # a session of its own: at the time limit the child goes together
    # with any replica it started
    proc = subprocess.Popen(SELF + ["--phase", name], env=env, cwd=HERE,
                            stdout=subprocess.PIPE, start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=PHASE_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait()
        raise SystemExit("chip_smoke: phase %s passed its %d s"
                         % (name, PHASE_TIMEOUT_S))
    out = out.decode(errors="replace")
    sys.stdout.write(out)
    sys.stdout.flush()
    if proc.returncode != 0:
        raise SystemExit("chip_smoke: phase %s failed (exit %d)"
                         % (name, proc.returncode))
    result = json.loads(out.strip().splitlines()[-1])
    if result.get("ok") is not True or result.get("phase") != name:
        raise SystemExit("chip_smoke: phase %s printed no result" % name)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the data,fsdp=4 BERT-base step and "
                         "the one-chip step it is compared to")
    ap.add_argument("--phase", choices=sorted(PHASE_FUNCS),
                    help=argparse.SUPPRESS)     # the child's entry
    args = ap.parse_args(argv)
    if args.phase:
        _child(args.phase)
        return 0
    env = dict(os.environ)
    if args.chips == 4:
        results = [_run_child("fsdp4", env)]
    else:
        results = [_run_child(name, env) for name in (
            "train_resnet50", "train_bert_base", "eager", "pallas",
            "serve_resnet50", "serve_decode")]
        # the persistent cache: BERT-base again, in a fresh child
        again = _run_child("train_bert_base", env)
        if not again["xla_cache_hits"] > 0:
            raise SystemExit("chip_smoke: the second BERT-base child found "
                             "nothing in the persistent cache")
        print(json.dumps({"phase": "cache", "ok": True,
                          "xla_cache_hits": again["xla_cache_hits"],
                          "warmup_seconds": {
                              "cold": results[1]["warmup_seconds"],
                              "warm": again["warmup_seconds"]}}),
              flush=True)
    device = next(r["device"] for r in results if "device" in r)
    if device["platform"] != PLATFORM or device["count"] != args.chips:
        raise SystemExit("chip_smoke: ran on %r, wanted %d %s chip(s)"
                         % (device, args.chips, PLATFORM))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
