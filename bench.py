"""Headline benchmark: ResNet-50 training throughput, images/sec/chip.

BASELINE.json primary metric: "ResNet-50 ImageNet images/sec/chip".
The driver runs this on the real chip each round (BENCH_r{N}.json).

One full training step (fwd + loss + bwd + SGD-momentum update) compiled
into a single XLA program via parallel.TrainStep on a 1-device mesh —
the steady-state Gluon hybridize+Trainer path collapsed to its compute.
bf16 compute (MXU-native) with fp32 master math in BN, synthetic data
(the reference's benchmark_score.py / train_imagenet.py --benchmark 1
pattern: measure compute throughput, not input pipeline).

The training and inference lanes (default, --bert, --score, --eager) run
in this process on the TPU and nowhere else: with no chip the exit code
is non-zero and nothing is printed.  A chip belongs to one process at a
time, so nothing here probes for it from a child.

vs_baseline: MXNet-CUDA's classic published ResNet-50 throughput on one
V100-era GPU; BASELINE.json `published` is empty so we use 1000 img/s as
the nominal single-accelerator reference.
"""
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASELINE_IMG_PER_SEC = 1000.0  # nominal MXNet-CUDA 1-GPU reference

# ISSUE 13 retrace chase: strict retraces the --eager lane may report.
# Measured 2 after the imperative-pass fix + specializing-site census
# split (was 79); the budget only ever goes DOWN — bench_compare exits
# non-zero on an over-budget report.
EAGER_RETRACE_BUDGET = 4

# Per-chip bf16 peak TFLOP/s by device kind (public cloud.google.com/tpu
# numbers); the MFU gate must use the actual device, not a flat constant.
# ORDERED: specific kinds first — v5p reports device_kind "TPU v5", while
# v5e reports "TPU v5 lite"/"TPU v5e", so the bare "v5" entry (459, v5p)
# must come after every lite spelling.
_TPU_PEAK_TFLOPS = [
    ("v5 lite", 197.0), ("v5litepod", 197.0), ("v5e", 197.0),
    ("v5p", 459.0), ("v5", 459.0),
    ("v6 lite", 918.0), ("v6e", 918.0),
    ("v4", 275.0), ("v3", 123.0), ("v2", 45.0),
]


def _device_peak_tflops():
    """bf16 peak for jax.devices()[0], keyed on device_kind.  A kind that
    is not in the table is an error, not a default."""
    import jax
    kind = jax.devices()[0].device_kind
    for key, peak in _TPU_PEAK_TFLOPS:
        if key in kind.lower():
            return peak
    raise KeyError("bench.py: no bf16 peak known for device_kind %r; add "
                   "it to _TPU_PEAK_TFLOPS with its source" % kind)


def _census_report(max_programs=40):
    """Program-census block every bench lane embeds (ISSUE 10): the
    roll-up the regression sentinel gates on (total compile seconds,
    peak temp bytes, retrace count) plus the per-program table, largest
    compile first.  ISSUE 13: the persistent compile-cache roll-up
    (hits/misses/bytes per layer) rides along — the warm-restart
    acceptance reads it."""
    from mxnet_tpu import compile_cache, programs
    table = programs.program_table()
    ranked = sorted(table.values(),
                    key=lambda t: -t["compile_seconds"]["total"])
    dropped = max(0, len(ranked) - max_programs)
    out = {"summary": programs.program_summary(),
           "compile_cache": compile_cache.stats(),
           "programs": {t["name"]: t for t in ranked[:max_programs]}}
    if dropped:
        out["programs_truncated"] = dropped
    return out


def _timed_steps(step, scan, warmup, iters, dev_batch, host_batch):
    """Measure `iters` steps; per-step dispatch loop by default, ONE
    k-step jit (TrainStep.run_steps) with --scan.  In scan mode the first
    timed call absorbs the k-step compile and is discarded (no separate
    warmup executable); returns (loss, dt)."""
    import time as _t
    import jax

    def _sync(x):
        jax.block_until_ready(x._jax if hasattr(x, "_jax") else x)

    if scan:
        loss = step.run_steps(iters, *host_batch)   # compile + warm
        _sync(loss)
        t0 = _t.perf_counter()
        loss = step.run_steps(iters, *host_batch)
        _sync(loss)
        return loss, _t.perf_counter() - t0
    for _ in range(warmup):
        loss = step(*dev_batch)
    _sync(loss)
    t0 = _t.perf_counter()
    for _ in range(iters):
        loss = step(*dev_batch)
    _sync(loss)
    return loss, _t.perf_counter() - t0


def run_bench():
    """The headline lane: ResNet-50 bf16 train step, batch 256."""
    import jax
    import numpy as np
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel import make_mesh, TrainStep

    batch, warmup, iters = 256, 5, 20

    mx.random.seed(0)
    np.random.seed(0)

    with mx.Context("cpu"):
        net = vision.resnet50_v1(classes=1000)
        net.initialize(mx.init.Xavier())
        net.cast("bfloat16")
        net(mx.nd.zeros((1, 3, 224, 224), dtype="bfloat16"))  # deferred init

    def loss_fn(logits, labels):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        onehot = jax.nn.one_hot(labels, 1000, dtype=logp.dtype)
        return -jnp.mean(jnp.sum(logp * onehot, axis=-1))

    mesh = make_mesh(axes=("dp",), devices=jax.devices()[:1])
    step = TrainStep(net, loss_fn, mesh, learning_rate=0.1, momentum=0.9)

    x = jnp.asarray(np.random.randn(batch, 3, 224, 224), jnp.bfloat16)
    y = jnp.asarray(np.random.randint(0, 1000, batch), jnp.int32)
    xs, ys = step.shard_batch(x, y)

    scan = os.environ.get("MX_BENCH_SCAN") == "1"
    loss, dt = _timed_steps(step, scan, warmup, iters, (xs, ys), (x, y))

    img_per_sec = batch * iters / dt
    # MFU diagnostic: ResNet-50 fwd+bwd ~= 3x 3.87 GFLOP/img at 224x224.
    tflops = img_per_sec * 3 * 3.87e9 / 1e12
    print(json.dumps({
        "metric": "resnet50_train_images_per_sec_per_chip"
                  + ("_scan" if scan else ""),
        "value": round(img_per_sec, 2),
        "unit": "images/sec",
        "vs_baseline": round(img_per_sec / BASELINE_IMG_PER_SEC, 4),
        # the denominator is a NOMINAL 1000 img/s (BASELINE.json shipped
        # no published numbers; replace when the reference harness runs)
        "baseline_nominal": True,
        "device": jax.default_backend(),
        "batch": batch,
        "tflops": round(tflops, 2),
    }))


def run_bert_bench():
    """--bert: BERT-base pretraining-style step, tokens/sec/chip (the
    second north-star metric, BASELINE.json).  MLM cross-entropy over a
    whole-step-jitted TrainStep; bf16 activations; seq len 512."""
    import jax
    import numpy as np
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import bert as bert_mod
    from mxnet_tpu.parallel import make_mesh, TrainStep

    batch, seq, layers, units, heads = 16, 512, 12, 768, 12
    warmup, iters = 3, 10
    vocab = 30522

    mx.random.seed(0)
    np.random.seed(0)
    with mx.Context("cpu"):
        net = bert_mod.get_bert(num_layers=layers, units=units,
                                num_heads=heads, vocab_size=vocab,
                                max_length=seq, dropout=0.0,
                                use_classifier=False)
        net.cast("bfloat16")
        net.initialize(mx.init.Normal(0.02))
        net(mx.nd.zeros((1, seq), dtype="int32"),
            mx.nd.zeros((1, seq), dtype="int32"))

    def loss_fn(outputs, labels):
        mlm = outputs[-1]                    # (B, T, vocab)
        logp = jax.nn.log_softmax(mlm.astype(jnp.float32), axis=-1)
        onehot = jax.nn.one_hot(labels, vocab, dtype=logp.dtype)
        return -jnp.mean(jnp.sum(logp * onehot, axis=-1))

    mesh = make_mesh(axes=("dp",), devices=jax.devices()[:1])
    step = TrainStep(net, loss_fn, mesh, learning_rate=1e-3)
    tok = jnp.asarray(np.random.randint(0, vocab, (batch, seq)), jnp.int32)
    seg = jnp.zeros((batch, seq), jnp.int32)
    lab = jnp.asarray(np.random.randint(0, vocab, (batch, seq)), jnp.int32)
    tok, seg, lab = step.shard_batch(tok, seg, lab)

    scan = os.environ.get("MX_BENCH_SCAN") == "1"
    host = tuple(np.asarray(jax.device_get(a)) for a in (tok, seg, lab))
    loss, dt = _timed_steps(step, scan, warmup, iters,
                            (tok, seg, lab), host)

    tokens_per_sec = batch * seq * iters / dt
    # MEASURED param count (not the 110M folklore number): sum over the
    # block's parameter tree.
    n_params = float(sum(
        int(np.prod(p.shape)) for p in net.collect_params().values()
        if p.shape is not None))
    # fwd+bwd FLOPs/token ≈ 6*N (dense matmuls) + 12*L*s*d (attention
    # scores+apply, quadratic term) — the standard training-FLOPs formula.
    flops_per_token = 6.0 * n_params + 12.0 * layers * seq * units
    tflops = tokens_per_sec * flops_per_token / 1e12
    mfu = tflops / _device_peak_tflops()
    print(json.dumps({
        "metric": "bert_base_pretrain_tokens_per_sec_per_chip"
                  + ("_scan" if scan else ""),
        "value": round(tokens_per_sec, 1), "unit": "tokens/sec",
        "vs_baseline": round(mfu / 0.5, 4),   # 1.0 == the 50% MFU target
        "device": jax.default_backend(), "batch": batch, "seq": seq,
        "n_params": int(n_params), "peak_tflops": _device_peak_tflops(),
        "tflops": round(tflops, 2), "mfu": round(mfu, 4),
    }))


def run_eager_bench():
    """--eager: Gluon eager-Trainer step throughput, images/sec/chip.

    The steady-state path ISSUE 3 optimized: per-op forward/backward, ONE
    fused optimizer dispatch per step (multi-tensor pytree apply), device-
    side metric accumulation.  Reported next to the TrainStep numbers so
    BENCH rounds can watch the eager-vs-whole-step-jit gap shrink; the
    dispatch counts per step ride along as diagnostics.
    """
    import jax
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.engine import engine
    from mxnet_tpu.gluon.model_zoo import vision

    batch, warmup, iters = 64, 3, 10

    mx.random.seed(0)
    np.random.seed(0)
    net = vision.resnet18_v1(classes=1000)
    net.initialize(mx.init.Xavier())
    net.hybridize()
    params = list(net.collect_params().values())
    trainer = gluon.Trainer(params, "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    metric = mx.metric.Accuracy()
    x_np = np.random.randn(batch, 3, 224, 224).astype(np.float32)
    y_np = np.random.randint(0, 1000, batch).astype(np.float32)
    x = nd.array(x_np)
    y = nd.array(y_np)

    def step(xb, yb):
        with autograd.record():
            out = net(xb)
            loss = loss_fn(out, yb)
        loss.backward()
        trainer.step(batch_size=batch)
        metric.update([yb], [out])
        return loss

    def sync():
        # the loss alone doesn't depend on the step's optimizer update or
        # the metric accumulate — block on those too, or the last step's
        # device work leaks out of the timed window
        jax.block_until_ready(loss._jax)
        jax.block_until_ready(params[0].data()._jax)
        if metric._dev_sum is not None:
            jax.block_until_ready(metric._dev_sum)

    for _ in range(warmup):
        loss = step(x, y)
    sync()

    # ISSUE 13: the timed loop consumes a REAL input stream — fresh
    # host batches crossing to the device each step — so data_wait is a
    # measured phase, not structurally zero.  With MX_PREFETCH (default
    # on) the DevicePrefetcher device_puts one batch ahead off its own
    # thread; with it off the transfer runs synchronously in the loop,
    # observed under the same phase for an honest on/off comparison.
    from mxnet_tpu import telemetry as _tel
    from mxnet_tpu.io.prefetch import DevicePrefetcher, prefetch_enabled

    def batch_stream():
        for _ in range(iters):
            yield (x_np, y_np)

    use_prefetch = prefetch_enabled()

    def _dw_total():
        inst = _tel.registry.find("step_phase_seconds",
                                  {"phase": "data_wait"})
        return inst.snapshot()["sum"] if inst is not None else 0.0

    # ISSUE 10: ONE consistent counter read (snapshot), not racy
    # property-by-property reads mid-step
    snap0 = engine.snapshot()
    dw0 = _dw_total()
    if use_prefetch:
        with DevicePrefetcher(batch_stream()) as pf:
            t0 = time.perf_counter()
            for xb, yb in pf:
                loss = step(nd.NDArray(xb), nd.NDArray(yb))
            sync()
            dt = time.perf_counter() - t0
    else:
        t0 = time.perf_counter()
        for xb_np, yb_np in batch_stream():
            t_dw = time.perf_counter()
            xb = nd.NDArray(jax.device_put(xb_np))
            yb = nd.NDArray(jax.device_put(yb_np))
            # block on BOTH transfers: an in-flight label copy would
            # escape data_wait and overlap the step, flattering the
            # synchronous baseline
            jax.block_until_ready(xb._jax)
            jax.block_until_ready(yb._jax)
            _tel.observe_phase("data_wait", time.perf_counter() - t_dw)
            loss = step(xb, yb)
        sync()
        dt = time.perf_counter() - t0
    data_wait_s = _dw_total() - dw0
    prefetch_report = {
        "enabled": use_prefetch,
        "data_wait_total_ms": round(data_wait_s * 1e3, 3),
        "data_wait_share_pct": round(100.0 * data_wait_s / dt, 3),
        "gate_pct": 5.0,
        "within_gate": bool(100.0 * data_wait_s / dt < 5.0),
    }
    dispatches = (engine.snapshot()["dispatches"]
                  - snap0["dispatches"]) / iters
    img_per_sec = batch * iters / dt

    # ISSUE 8: telemetry snapshot + measured overhead.  The loop above
    # ran with telemetry ON (the default), so the per-phase histograms
    # already hold this bench's step breakdown; the overhead probe runs
    # separately on the fast MLP eager step (seconds-long CPU resnet
    # steps drown the microsecond-scale span cost in scheduler noise).
    from mxnet_tpu import telemetry
    # snapshot the resnet loop's record BEFORE the overhead probe runs
    # its own MLP steps through the recorder
    last_record = telemetry.flight_recorder.last()
    telemetry_report = {
        "enabled": telemetry.enabled(),
        "phases": telemetry.phase_snapshot(),
        "last_step_record": last_record,
        "overhead": _telemetry_overhead(),
    }

    # ISSUE 7 comparison lane: the SAME workload through the whole-step
    # compiled path (one donated jit per step; lax.scan window amortizes
    # the remaining host round-trip) — BENCH rounds watch this ratio as
    # the eager pipeline's dispatch overhead gets compiled away.
    mx.random.seed(0)
    np.random.seed(0)
    net_c = vision.resnet18_v1(classes=1000)
    net_c.initialize(mx.init.Xavier())
    trainer_c = gluon.Trainer(list(net_c.collect_params().values()), "sgd",
                              {"learning_rate": 0.1, "momentum": 0.9})
    from mxnet_tpu.step import scan_window
    cstep = trainer_c.make_compiled_step(net_c, loss_fn,
                                         metric=mx.metric.Accuracy())
    scan_n = scan_window() or 16

    def timed(fn, n):
        # two warm calls: the first finishes deferred init (eager
        # fallback), the second traces + compiles; the third is steady
        # state
        fn()
        fn()
        t0 = time.perf_counter()
        loss = fn()
        jax.block_until_ready(loss._jax)
        return n / (time.perf_counter() - t0)

    compiled_ips = timed(lambda: cstep.step(x, y), batch)
    xw = nd.array(np.broadcast_to(np.asarray(x._jax),
                                  (scan_n,) + tuple(x.shape)).copy())
    yw = nd.array(np.broadcast_to(np.asarray(y._jax),
                                  (scan_n,) + tuple(y.shape)).copy())
    scan_ips = timed(lambda: cstep.run_window(xw, yw), batch * scan_n)

    # ISSUE 13 retrace budget: the eager lane's STRICT retrace count
    # (specializing sites count their expected shape specializations
    # separately) can only go down.  Over-budget poisons the report —
    # tools/bench_compare.py exits non-zero on it.
    census = _census_report()
    retraces = census["summary"]["retraces"]

    # ISSUE 14: --mesh lane — the SpecLayout-sharded step's per-chip
    # params+optimizer bytes (buffer census) and throughput; gated by
    # tools/bench_compare.py as the mesh-class-keyed
    # params_bytes_per_chip series
    sharded = _sharded_lane()

    print(json.dumps({
        "metric": "resnet18_eager_trainer_images_per_sec_per_chip",
        "value": round(img_per_sec, 2),
        "unit": "images/sec",
        "vs_baseline": round(img_per_sec / BASELINE_IMG_PER_SEC, 4),
        "baseline_nominal": True,
        "device": jax.default_backend(),
        "batch": batch,
        "dispatches_per_step": round(dispatches, 1),
        "n_params": len(params),
        # eager-vs-compiled, same model/batch (ISSUE 7 acceptance lane)
        "compiled_images_per_sec": round(compiled_ips, 2),
        "compiled_scan_images_per_sec": round(scan_ips, 2),
        "scan_window": scan_n,
        "speedup_compiled_vs_eager": round(compiled_ips / img_per_sec, 2),
        "speedup_scan_vs_eager": round(scan_ips / img_per_sec, 2),
        "dispatch_bound": _dispatch_bound_compare(),
        # ISSUE 13: async input pipeline — data_wait share of the timed
        # eager loop (acceptance < 5% with prefetch on)
        "prefetch": prefetch_report,
        "retrace_budget": EAGER_RETRACE_BUDGET,
        "retraces_over_budget": bool(retraces > EAGER_RETRACE_BUDGET),
        # ISSUE 8: per-phase step breakdown + measured span overhead
        "telemetry": telemetry_report,
        # ISSUE 10: per-program compile-cost/memory table + the roll-up
        # tools/bench_compare.py appends to BENCH_HISTORY.jsonl and gates
        "census": census,
        # ISSUE 14: sharded-training lane (None unless --mesh/MX_MESH_AXES)
        "sharded": sharded,
    }))


def _sharded_lane(layers=4, hidden=256, batch=32, steps=3):
    """The --mesh lane (ISSUE 14): a SpecLayout-sharded CompiledStep on
    the MX_BENCH_MESH mesh vs its replicated twin — reporting the
    buffer-census per-chip params+optimizer bytes (the series
    tools/bench_compare.py gates, keyed by mesh class) and the sharded
    step's throughput.  Returns None when no mesh is configured (the
    default eager lane is unchanged)."""
    import gc as _gc
    mesh_text = os.environ.get("MX_BENCH_MESH") or ""
    if not mesh_text:
        return None
    import jax
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, programs
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import SpecLayout, make_mesh
    from mxnet_tpu.parallel.speclayout import parse_mesh_axes

    axes, sizes = parse_mesh_axes(mesh_text)
    mesh = make_mesh(axes=axes, shape=sizes, devices=jax.devices())
    layout = SpecLayout.infer(mesh)
    mesh_class = ",".join("%s=%d" % (a, s)
                          for a, s in dict(mesh.shape).items())

    rng = np.random.RandomState(0)
    X = rng.randn(batch, 64).astype(np.float32)
    Y = rng.randn(batch, 8).astype(np.float32)
    loss_fn = gluon.loss.L2Loss()

    def _census_delta(lay):
        """(per-chip params+optimizer bytes, images/sec) of one fresh
        trainer under `lay`, as a buffer-census delta around its
        lifetime — the same attribution buffer_census() reports."""
        _gc.collect()
        before = programs.buffer_census()
        mx.random.seed(0)
        net = nn.Sequential()
        in_units = 64
        for _ in range(layers):
            net.add(nn.Dense(hidden, in_units=in_units,
                             activation="relu"))
            in_units = hidden
        net.add(nn.Dense(8, in_units=in_units))
        net.initialize(mx.init.Xavier())
        tr = gluon.Trainer(list(net.collect_params().values()), "sgd",
                           {"learning_rate": 0.05, "momentum": 0.9})
        step = tr.make_compiled_step(net, loss_fn, layout=lay)
        step.step(nd.array(X), nd.array(Y), batch_size=batch)   # trace
        step.step(nd.array(X), nd.array(Y), batch_size=batch)
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step.step(nd.array(X), nd.array(Y), batch_size=batch)
        jax.block_until_ready(loss._jax)
        ips = batch * steps / (time.perf_counter() - t0)
        _gc.collect()
        after = programs.buffer_census()

        def delta(owner):
            return max(0, after[owner]["bytes_per_chip"]
                       - before[owner]["bytes_per_chip"])
        return delta("params"), delta("optimizer_state"), ips

    repl_params, repl_opt, repl_ips = _census_delta(None)
    sh_params, sh_opt, sh_ips = _census_delta(layout)
    fsdp = layout.fsdp
    measured = (repl_params + repl_opt) / max(1, sh_params + sh_opt)
    return {
        "mesh": mesh_text,
        "mesh_class": mesh_class,
        "fsdp": fsdp,
        "params_bytes_per_chip": sh_params,
        "optimizer_bytes_per_chip": sh_opt,
        "replicated_params_bytes": repl_params,
        "replicated_optimizer_bytes": repl_opt,
        # per-chip state must drop ~linearly with the fsdp axis
        "ideal_ratio": fsdp,
        "measured_ratio": round(measured, 3),
        "within_15pct_of_ideal": bool(measured >= 0.85 * fsdp),
        "images_per_sec": round(sh_ips, 2),
        "replicated_images_per_sec": round(repl_ips, 2),
    }


def _telemetry_overhead(layers=8, hidden=64, batch=16, pairs=12):
    """Measured cost of the telemetry span/record layer on an eager
    training step (ISSUE 8 acceptance: <= 5%).

    Alternating off/on step pairs with best-of-N per mode: the layer's
    cost is deterministic (a few dict ops + leaf-lock bumps per phase),
    so it survives the min, while interleaving cancels clock-speed and
    scheduler drift that a two-block comparison would misread as
    overhead.  The probe uses a fast MLP step — on CPU a resnet step
    takes seconds and its run-to-run noise alone dwarfs the microsecond
    span cost being measured."""
    import jax
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.base import set_env
    from mxnet_tpu.gluon import nn

    mx.random.seed(0)
    net = nn.Sequential()
    in_units = 32
    for _ in range(layers):
        net.add(nn.Dense(hidden, in_units=in_units, activation="relu"))
        in_units = hidden
    net.add(nn.Dense(8, in_units=in_units))
    net.initialize(mx.init.Xavier())
    tr = gluon.Trainer(list(net.collect_params().values()), "sgd",
                       {"learning_rate": 0.05, "momentum": 0.9})
    loss_fn = gluon.loss.L2Loss()
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(batch, 32).astype(np.float32))
    y = nd.array(rng.randn(batch, 8).astype(np.float32))

    def one_step():
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        tr.step(batch_size=batch)
        jax.block_until_ready(loss._jax)

    one_step()
    one_step()                          # warm: compile + state creation
    times = {"0": [], "1": []}
    prev = os.environ.get("MX_TELEMETRY")
    try:
        for _ in range(pairs):
            for mode in ("0", "1"):
                set_env("MX_TELEMETRY", mode)
                t0 = time.perf_counter()
                one_step()
                times[mode].append(time.perf_counter() - t0)
    finally:
        set_env("MX_TELEMETRY", prev if prev is not None else "1")
    # per-pair differences cancel slow machine drift; their median is
    # robust to the occasional preempted step either side
    deltas = sorted(on - off for on, off in zip(times["1"], times["0"]))
    med_delta = deltas[len(deltas) // 2]
    t_off, t_on = min(times["0"]), min(times["1"])
    return {
        "workload": "mlp%dx%d_eager_step" % (layers, hidden),
        "pairs": pairs,
        "step_ms_telemetry_off": round(t_off * 1e3, 4),
        "step_ms_telemetry_on": round(t_on * 1e3, 4),
        "overhead_pct": round(max(0.0, med_delta / t_off * 100.0), 2),
    }


def _dispatch_bound_compare(layers=24, hidden=64, batch=16, steps=8):
    """The step-time win whole-step compilation buys where per-dispatch
    host overhead dominates (deep narrow MLP, per-op eager forward — the
    non-hybridized Gluon debug pipeline — vs ONE scanned window)."""
    import jax
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.gluon import nn

    def build():
        mx.random.seed(0)
        net = nn.Sequential()
        in_units = 32
        for _ in range(layers):
            net.add(nn.Dense(hidden, in_units=in_units, activation="relu"))
            in_units = hidden
        net.add(nn.Dense(8, in_units=in_units))
        net.initialize(mx.init.Xavier())
        tr = gluon.Trainer(list(net.collect_params().values()), "sgd",
                           {"learning_rate": 0.05, "momentum": 0.9})
        return net, tr

    rng = np.random.RandomState(0)
    X = rng.randn(batch, 32).astype(np.float32)
    Y = rng.randn(batch, 8).astype(np.float32)
    loss_fn = gluon.loss.L2Loss()

    net_e, tr_e = build()
    x, y = nd.array(X), nd.array(Y)

    def eager_step():
        with autograd.record():
            loss = loss_fn(net_e(x), y)
        loss.backward()
        tr_e.step(batch_size=batch)
        return loss
    eager_step()
    jax.block_until_ready(eager_step()._jax)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = eager_step()
    jax.block_until_ready(loss._jax)
    eager_sps = steps / (time.perf_counter() - t0)

    net_c, tr_c = build()
    cstep = tr_c.make_compiled_step(net_c, loss_fn)
    Xw = np.broadcast_to(X, (steps,) + X.shape).copy()
    Yw = np.broadcast_to(Y, (steps,) + Y.shape).copy()
    cstep.run_window(Xw, Yw)            # warm: trace + compile
    t0 = time.perf_counter()
    loss = cstep.run_window(Xw, Yw)
    jax.block_until_ready(loss._jax)
    compiled_sps = steps / (time.perf_counter() - t0)
    return {
        "model": "mlp%dx%d" % (layers, hidden),
        "eager_steps_per_sec": round(eager_sps, 2),
        "compiled_steps_per_sec": round(compiled_sps, 2),
        "scan_window": steps,
        "speedup_compiled_vs_eager": round(compiled_sps / eager_sps, 2),
    }


def run_exchange_bench():
    """--exchange: bucketed gradient-exchange micro-bench (ISSUE 5).

    Times one batched push+pull of a ResNet-ish key set (many small dense
    tensors + a few large ones) through the collective store per wire
    mode — fp32, bf16 cast, int8 per-block quantized, 2-bit — and reports
    ms/step plus the measured wire bytes (engine.wire_bytes deltas).  On
    one process the collective is local, so this isolates the quantize/
    bucketing overhead the compression pays for its bandwidth win; on a
    real pod the same harness times the ICI/DCN exchange itself.
    """
    import jax
    import numpy as np
    from mxnet_tpu import kvstore, nd
    from mxnet_tpu.engine import engine

    on_cpu = jax.default_backend() == "cpu"
    iters = 3 if on_cpu else 20
    rng = np.random.RandomState(0)
    # conv-net-like: many small params, a few big FC/embedding-scale ones
    sizes = [256] * 40 + [16 * 1024] * 12 + [256 * 1024] * 4 + [2 << 20]
    grads = [nd.array(rng.randn(n).astype(np.float32)) for n in sizes]
    keys = list(range(len(sizes)))
    total_mb = sum(sizes) * 4 / (1 << 20)

    per_mode = {}
    for mode in ("fp32", "bf16", "int8", "2bit"):
        kv = kvstore.create("ici")
        if mode != "fp32":
            kv.set_gradient_compression({"type": mode})
        for k, g in zip(keys, grads):
            kv.init(k, nd.zeros((g.size,)))
        vlists = [[g] for g in grads]
        kv.push(keys, vlists)                       # warm (compile)
        kv.pull(keys, vlists)
        grads[0].wait_to_read()
        w0 = engine.snapshot()["wire_bytes"]
        t0 = time.perf_counter()
        for _ in range(iters):
            kv.push(keys, vlists)
            kv.pull(keys, vlists)
        grads[0].wait_to_read()
        dt = time.perf_counter() - t0
        wire_mb = (engine.snapshot()["wire_bytes"] - w0) / iters / (1 << 20)
        per_mode[mode] = {"ms_per_step": round(dt / iters * 1e3, 2),
                          "wire_mb_per_step": round(wire_mb, 3)}
    fp32_mb = per_mode["fp32"]["wire_mb_per_step"]
    for mode, rec in per_mode.items():
        rec["wire_reduction_vs_fp32"] = round(
            fp32_mb / max(1e-9, rec["wire_mb_per_step"]), 2)
    print(json.dumps({
        "metric": "gradient_exchange_wire_reduction_int8",
        "value": per_mode["int8"]["wire_reduction_vs_fp32"],
        "unit": "x_fewer_bytes",
        "device": jax.default_backend(),
        "keys": len(sizes),
        "payload_mb": round(total_mb, 1),
        "iters": iters,
        "per_mode": per_mode,
    }))


def run_score_bench():
    """--score: model-zoo INFERENCE throughput vs batch size (reference:
    example/image-classification/benchmark_score.py).  Hybridized forward
    (one executable per shape), bf16."""
    import jax
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision

    # compute must actually LIVE on the accelerator: default ctx is cpu(0),
    # which would silently benchmark XLA:CPU under a TPU label
    ctx = mx.tpu(0)
    models = ["resnet18_v1", "resnet50_v1", "mobilenet1_0"]
    batches = [1, 8, 32, 128]
    size = 224
    iters = 20
    results = {}
    mx.random.seed(0)
    np.random.seed(0)
    for name in models:
        net = getattr(vision, name)(classes=1000)
        net.initialize(mx.init.Xavier(), ctx=ctx)
        net.cast("bfloat16")
        net.hybridize(static_alloc=True)
        per_batch = {}
        for b in batches:
            x = mx.nd.array(np.random.rand(b, 3, size, size),
                            dtype="bfloat16", ctx=ctx)
            net(x).wait_to_read()                  # compile
            t0 = time.perf_counter()
            for _ in range(iters):
                out = net(x)
            out.wait_to_read()
            per_batch[b] = round(b * iters /
                                 (time.perf_counter() - t0), 2)
        results[name] = per_batch
    top = results[models[0]][batches[-1]]
    print(json.dumps({
        "metric": "model_zoo_inference_images_per_sec",
        "value": top, "unit": "images/sec",
        "vs_baseline": 0.0, "device": jax.default_backend(),
        "per_model": results,
    }))


def _pctile(sorted_vals, p):
    """p-th percentile of an ascending list (truncation-indexed — the
    convention both serve lanes share); 0.0 on empty."""
    if not sorted_vals:
        return 0.0
    return sorted_vals[min(len(sorted_vals) - 1,
                           int(p / 100.0 * len(sorted_vals)))]


def run_serve_bench(rate=None, duration=None, senders=12,
                    routed=False):
    """--serve: open-loop load against a REAL local serving replica
    (ISSUE 9 acceptance lane).  ``routed=True`` (``--serve --routed``,
    ISSUE 17) appends a paired direct-vs-through-the-router probe
    against the SAME warm replica — interleaved closed-loop lanes,
    medians gated at p50/p99 within 10%.

    A synthetic Poisson arrival process (configurable rate/duration;
    open-loop: the schedule never slows down for the server, so queueing
    shows up as latency, not as a lower offered rate) drives PREDICT
    RPCs over a real socket through the SEQ envelope into the
    micro-batcher.  Reports p50/p99 end-to-end latency (measured from
    the SCHEDULED arrival, so sender lateness counts — the
    coordinated-omission-safe convention), achieved throughput, the
    batch-occupancy histogram, the rejection rate, and the serve-time
    retrace count after warmup (must be 0: every dispatch must hit the
    AOT bucket table).
    """
    import socket as _socket
    import threading
    import numpy as np
    from mxnet_tpu import telemetry
    from mxnet_tpu.serve import (Overloaded, ServeClient, ServeServer,
                                 Servable, serve_forever)
    from mxnet_tpu.serve.demo import DEMO_IN, demo_block, demo_example

    rate = float(rate or os.environ.get("MX_BENCH_SERVE_RATE", 250.0))
    duration = float(duration or
                     os.environ.get("MX_BENCH_SERVE_DURATION", 2.0))

    s = _socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    state = ServeServer()
    state.host.deploy(Servable(demo_block(), name="demo-mlp", version=1),
                      example=demo_example())
    stop_ev = threading.Event()
    threading.Thread(target=serve_forever,
                     kwargs=dict(port=port, state=state,
                                 stop_event=stop_ev),
                     daemon=True).start()
    addr = "127.0.0.1:%d" % port
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        try:
            _socket.create_connection(("127.0.0.1", port),
                                      timeout=0.2).close()
            break
        except OSError:
            time.sleep(0.05)

    # a couple of warm round-trips (client connect, codec, first batch)
    warm_cli = ServeClient([addr], timeout=30)
    xw = np.zeros((1, DEMO_IN), np.float32)
    for _ in range(3):
        warm_cli.predict([xw])
    warm_cli.close()
    sv = state.host.active()
    retraces_before = sv.retraces
    reg = telemetry.registry
    rej0 = reg.value("serve.rejected")
    batches0 = reg.value("serve.batches")
    occ_inst = reg.find("serve.batch_occupancy")
    occ0 = occ_inst.snapshot() if occ_inst is not None else None

    # open-loop schedule: Poisson arrivals, single-row requests
    rng = np.random.RandomState(7)
    arrivals = np.cumsum(rng.exponential(1.0 / rate,
                                         int(rate * duration) + 1))
    arrivals = arrivals[arrivals < duration]
    payloads = [rng.randn(1, DEMO_IN).astype(np.float32)
                for _ in range(len(arrivals))]
    latencies, rejected, errors = [], [0], [0]
    lat_lock = threading.Lock()
    next_i = [0]
    idx_lock = threading.Lock()
    t0 = time.perf_counter()

    def sender():
        cli = ServeClient([addr], timeout=30)
        while True:
            with idx_lock:
                i = next_i[0]
                if i >= len(arrivals):
                    break
                next_i[0] += 1
            due = t0 + arrivals[i]
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            try:
                cli.predict([payloads[i]])
                lat = time.perf_counter() - due
                with lat_lock:
                    latencies.append(lat)
            except Overloaded:
                with lat_lock:
                    rejected[0] += 1
            except Exception:
                with lat_lock:
                    errors[0] += 1
        cli.close()

    threads = [threading.Thread(target=sender, daemon=True)
               for _ in range(senders)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=duration + 60)
    wall = time.perf_counter() - t0

    # report the LOAD's occupancy only — the warm-up round-trips above
    # also dispatched (single-row) batches; deltas keep them out, like
    # rejected_counter / retraces_after_warmup below
    occupancy = {}
    inst = reg.find("serve.batch_occupancy")
    if inst is not None:
        snap = inst.snapshot()
        count = snap["count"] - (occ0["count"] if occ0 else 0)
        total = snap["sum"] - (occ0["sum"] if occ0 else 0.0)
        occupancy = {
            "count": count,
            "avg_rows": round(total / count, 2) if count else 0.0,
            "max_rows": snap["max"],
            "buckets": {le: c - (occ0["buckets"].get(le, 0)
                                 if occ0 else 0)
                        for le, c in snap["buckets"].items()}}
    lat_ms = sorted(l * 1e3 for l in latencies)

    def pct(p):
        return round(_pctile(lat_ms, p), 3)

    n_ok = len(latencies)
    report = {
        "metric": "serve_demo_requests_per_sec",
        "value": round(n_ok / wall, 2),
        "unit": "requests/sec",
        "device": "cpu" if os.environ.get("MX_FORCE_CPU") else "default",
        "offered_rate": rate,
        "duration_s": duration,
        "requests": len(arrivals),
        "completed": n_ok,
        "rejected": rejected[0],
        "errors": errors[0],
        "rejection_rate": round(rejected[0] / max(1, len(arrivals)), 4),
        "latency_ms": {"p50": pct(50), "p90": pct(90), "p99": pct(99),
                       "max": round(lat_ms[-1], 3) if lat_ms else 0.0},
        "batch_occupancy": occupancy,
        "batches": reg.value("serve.batches") - batches0,
        "retraces_after_warmup": sv.retraces - retraces_before,
        "zero_serve_time_retraces": sv.retraces == retraces_before,
        "rejected_counter": reg.value("serve.rejected") - rej0,
        "phases": {k: v for k, v in telemetry.phase_snapshot().items()
                   if k in ("queue_wait", "pad", "serve_dispatch",
                            "scatter")},
        # ISSUE 10: the serve lane's program census — every bucket
        # program with compile time and (where the backend provides it)
        # memory/cost metadata
        "census": _census_report(),
    }

    # fleet-collector overhead probe (ISSUE 12 acceptance): INTERLEAVED
    # paired closed-loop lanes against the SAME warm replica — each
    # cycle runs a collector-off lane then a collector-on lane, and the
    # gate compares the MEDIANS (interleaving cancels box drift, the
    # median kills one-off scheduler spikes).  Expected <=2%, gate <=5%
    # on BOTH throughput and p99.  The collector runs in a SUBPROCESS,
    # matching the production topology (the supervisor hosts it): what
    # lands on the replica is exactly the per-scrape METRICS handling
    # (registry snapshot + one socket round-trip), not the collector's
    # own merge loop stealing the GIL.
    from mxnet_tpu.base import get_env as _get_env

    def _probe_load(nreq, rate_, target=addr):
        cli = ServeClient([target], timeout=30)
        lat = []
        sched = np.cumsum(rng.exponential(1.0 / rate_, nreq))
        t0p = time.perf_counter()
        for i in range(nreq):
            due = t0p + sched[i]
            d = due - time.perf_counter()
            if d > 0:
                time.sleep(d)
            try:
                cli.predict([payloads[i % len(payloads)]])
                lat.append(time.perf_counter() - due)
            except Exception:
                pass        # shed/failed probes just shrink the sample
        cli.close()
        wallp = time.perf_counter() - t0p
        lat.sort()
        p50_ = lat[min(len(lat) - 1, int(0.50 * len(lat)))] * 1e3 \
            if lat else 0.0
        p99_ = lat[min(len(lat) - 1, int(0.99 * len(lat)))] * 1e3 \
            if lat else 0.0
        # plain floats: the latencies are contaminated with np.float64
        # via the np.cumsum schedule, and a np.bool_ gate comparison
        # would fail json.dumps
        return float(len(lat) / wallp), float(p50_), float(p99_)

    probe_rate = max(50.0, rate / 2.0)
    fleet_interval = _get_env("MX_FLEET_INTERVAL", 2.0, float) or 2.0
    # span >= 3 scrape rounds per lane so the paired delta actually
    # contains scrapes (bounded so the probe stays a bench, not a soak)
    probe_n = int(os.environ.get(
        "MX_BENCH_FLEET_PROBE",
        max(200, int(probe_rate * min(3.0 * fleet_interval, 8.0)))))

    # (a) deterministic per-scrape cost: time the METRICS round-trip
    # the collector performs; the replica-side duty cycle it implies
    # (scrape_ms / interval_ms, an upper bound — it charges the whole
    # round-trip as stolen replica CPU) is the gated number, because
    # sub-5% paired deltas sit below a shared box's noise floor.
    from mxnet_tpu import fleet as _fleet
    scrape_ms = []
    for _ in range(5):
        t0s = time.perf_counter()
        _fleet.fetch_metrics(addr, fmt="json")
        scrape_ms.append((time.perf_counter() - t0s) * 1e3)
    scrape_ms = sorted(scrape_ms)[len(scrape_ms) // 2]
    modeled_pct = 100.0 * (scrape_ms / 1e3) / fleet_interval

    # (b) interleaved paired lanes at the CONFIGURED MX_FLEET_INTERVAL;
    # the collector subprocess is spawned fresh per on-lane so the
    # adjacent off-lane is genuinely collector-free
    probe_src = (
        "import sys, time\n"
        "sys.path.insert(0, %r)\n"
        "from mxnet_tpu import fleet\n"
        "c = fleet.FleetCollector([fleet.FleetMember('serve', 0, "
        "addr=%r)], interval=%r)\n"
        "c.scrape_once()\n"
        "print('SCRAPING', flush=True)\n"
        "c.start()\n"
        "time.sleep(600)\n" % (os.path.dirname(os.path.abspath(__file__)),
                               addr, fleet_interval))
    cycles = int(os.environ.get("MX_BENCH_FLEET_CYCLES", 3))
    off_tps, off_p99s, on_tps, on_p99s = [], [], [], []
    for _cycle in range(cycles):
        tp_, _p50, p99_ = _probe_load(probe_n, probe_rate)
        off_tps.append(tp_)
        off_p99s.append(p99_)
        proc = subprocess.Popen([sys.executable, "-c", probe_src],
                                stdout=subprocess.PIPE, text=True)
        try:
            line = proc.stdout.readline()   # first scrape completed
            if "SCRAPING" not in line:
                raise RuntimeError(
                    "fleet probe collector failed to start")
            # settle past the subprocess's interpreter+import CPU burst:
            # production collectors start ONCE — the steady state under
            # measurement is scraping, not python startup sharing the
            # box with the replica for the lane's first second
            time.sleep(0.75)
            tp_, _p50, p99_ = _probe_load(probe_n, probe_rate)
            on_tps.append(tp_)
            on_p99s.append(p99_)
        finally:
            proc.kill()
            proc.wait()

    def _median(vs):
        return sorted(vs)[len(vs) // 2] if vs else 0.0

    off_tp, off_p99 = _median(off_tps), _median(off_p99s)
    on_tp, on_p99 = _median(on_tps), _median(on_p99s)
    tp_overhead = 100.0 * (off_tp - on_tp) / off_tp if off_tp else 0.0
    p99_overhead = 100.0 * (on_p99 - off_p99) / off_p99 if off_p99 \
        else 0.0
    report["fleet_collector"] = {
        "scrape_interval_s": fleet_interval,
        "collector": "subprocess (supervisor topology)",
        "cycles": cycles,
        "scrape_roundtrip_ms": round(scrape_ms, 3),
        "modeled_overhead_pct": round(modeled_pct, 3),
        "throughput_off_rps": round(off_tp, 2),
        "throughput_on_rps": round(on_tp, 2),
        "p99_off_ms": round(off_p99, 3),
        "p99_on_ms": round(on_p99, 3),
        "throughput_overhead_pct": round(tp_overhead, 2),
        "p99_overhead_pct": round(p99_overhead, 2),
        "gate_pct": 5.0,
        # the ISSUE acceptance gate, measured: median-of-interleaved
        # throughput AND p99 deltas <=5% (negative deltas = noise
        # favoring the collector-on lanes); the modeled duty cycle
        # rides along as the deterministic cross-check
        "within_gate": tp_overhead <= 5.0 and p99_overhead <= 5.0,
    }

    if routed:
        # ISSUE 17 acceptance: the session router's forwarding tax.
        # A SUBPROCESS router fronts the SAME warm replica — the
        # production topology (the supervisor runs the router as its
        # own process), and the same reasoning as the collector probe
        # above: in-process it would fight the replica's batcher for
        # the GIL and charge scheduler contention to forwarding.
        # Interleaved paired closed-loop lanes (direct, then through
        # the router) per cycle cancel box drift, medians kill
        # scheduler spikes.  Gate: routed p50 AND p99 within 10% of
        # direct, with an ABSOLUTE ms floor — on a fast box a 10%
        # relative delta of a small p50 is two-loopback-hop noise, so
        # the gate also passes when the ADDED latency is under the
        # floor flat.
        rs = _socket.socket()
        rs.bind(("", 0))
        rport_ = rs.getsockname()[1]
        rs.close()
        renv = dict(os.environ, JAX_PLATFORMS="cpu", MX_FORCE_CPU="1")
        renv["PYTHONPATH"] = (
            os.path.dirname(os.path.abspath(__file__))
            + os.pathsep + renv.get("PYTHONPATH", ""))
        rproc = subprocess.Popen(
            [sys.executable, "-m", "mxnet_tpu.serve.router",
             "--port", str(rport_), "--replicas", addr],
            env=renv, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)
        raddr = "127.0.0.1:%d" % rport_
        rdeadline = time.monotonic() + 30
        while time.monotonic() < rdeadline:
            try:
                _socket.create_connection(("127.0.0.1", rport_),
                                          timeout=0.2).close()
                break
            except OSError:
                time.sleep(0.05)
        warm_r = ServeClient([raddr], timeout=30)
        for _ in range(3):
            warm_r.predict([xw])
        warm_r.close()
        # probe BELOW the queueing knee: at the fleet probe's rate the
        # single closed-loop sender sits near 50% utilization, where
        # open-loop lateness cascades amplify ANY per-request delta
        # into the tail — that measures the queue, not the router.  At
        # ~5x the service time between arrivals the latency IS the
        # path: replica service + (routed) two loopback hops.
        routed_rate = min(probe_rate, 50.0)
        routed_n = max(120, int(3.0 * routed_rate))
        d_tps, d_p50s, d_p99s = [], [], []
        r_tps, r_p50s, r_p99s = [], [], []
        for _cycle in range(cycles):
            tp_, p50_, p99_ = _probe_load(routed_n, routed_rate)
            d_tps.append(tp_)
            d_p50s.append(p50_)
            d_p99s.append(p99_)
            tp_, p50_, p99_ = _probe_load(routed_n, routed_rate,
                                          target=raddr)
            r_tps.append(tp_)
            r_p50s.append(p50_)
            r_p99s.append(p99_)
        rproc.kill()
        rproc.wait()
        d_p50, d_p99 = _median(d_p50s), _median(d_p99s)
        r_p50, r_p99 = _median(r_p50s), _median(r_p99s)
        p50_pct = 100.0 * (r_p50 - d_p50) / d_p50 if d_p50 else 0.0
        p99_pct = 100.0 * (r_p99 - d_p99) / d_p99 if d_p99 else 0.0
        gate_pct, floor_ms = 10.0, 1.0
        report["routed"] = {
            "cycles": cycles,
            "probe_rate": routed_rate,
            "probe_requests": routed_n,
            "throughput_direct_rps": round(_median(d_tps), 2),
            "throughput_routed_rps": round(_median(r_tps), 2),
            "p50_direct_ms": round(d_p50, 3),
            "p50_routed_ms": round(r_p50, 3),
            "p99_direct_ms": round(d_p99, 3),
            "p99_routed_ms": round(r_p99, 3),
            "p50_overhead_pct": round(p50_pct, 2),
            "p99_overhead_pct": round(p99_pct, 2),
            "gate_pct": gate_pct,
            "floor_ms": floor_ms,
            "within_gate": bool(
                (p50_pct <= gate_pct or r_p50 - d_p50 <= floor_ms)
                and (p99_pct <= gate_pct or r_p99 - d_p99 <= floor_ms)),
        }
    stop_ev.set()
    print(json.dumps(report))


def run_decode_bench(n_gens=None, rate=None):
    """--serve --decode: the autoregressive decode lane (ISSUE 15
    acceptance).

    A mixed-length Poisson workload (70% short generations, 30% long
    ones — the regime where request-level batching starves) drives the
    continuous-batching decode engine in-process, then the IDENTICAL
    workload replays against ``mode="request"`` (admit a batch, run it
    to completion — the classic strawman).  The offered rate
    deliberately exceeds single-replica capacity so a backlog forms:
    throughput then measures the ENGINE's batching discipline, not the
    arrival process (an underloaded engine drains any schedule at the
    offered rate and the comparison degenerates to 1x).  Reports
    tokens/sec,
    per-token p50/p99 (first token = submit→harvest including queue +
    prefill; then inter-token gaps), slot occupancy, the
    continuous-vs-request speedup (acceptance >= 2x), zero serve-time
    retraces and FLAT KV-pool bytes across the whole run (the pool is
    donated through every step — any growth is a leak).

    ISSUE 18 adds three PAGED lanes on top:

    * ``paged`` — the identical mixed workload through the paged
      engine (same geometry, auto ``kv_pages`` == the flat pool's
      HBM): tokens must match the flat continuous lane ELEMENT-WISE,
      the page heap must stay flat, and warm retraces must stay zero;
    * ``shared_prefix`` — N sessions over K long shared prompts:
      flat re-prefills every repeat, the paged engine answers it with
      a CoW fork + ONE replay chunk, so repeat first-token p50 must
      drop >= 5x (same tokens out of both engines);
    * ``admission`` — the census-pinned equal-HBM capacity story:
      at byte-identical KV pools the paged heap runs the mixed-length
      admission >= 4x as wide as flat slots allow.

    ISSUE 20 adds the SPECULATIVE lane: the draft-friendly demo LM
    (a deep target whose step is KV-gather-bound over a long paged
    extent, plus its 1-layer draft prefix) decodes the identical
    closed-loop workload through the plain paged engine and through
    the speculative engine (k draft dispatches + ONE k+1-position
    verify dispatch per window).  Request-level tokens/sec must come
    out >= 2x, tokens must match the plain paged lane ELEMENT-WISE
    (speculative greedy output is bit-identical by construction), the
    page heap must stay flat, and warm retraces must stay zero.
    """
    import numpy as np
    from mxnet_tpu import telemetry
    from mxnet_tpu.serve.decode import (DecodeBatcher, DecodeConfig,
                                        DecodeServable,
                                        PagedDecodeBatcher,
                                        PagedDecodeServable,
                                        reference_generate)

    n_gens = int(n_gens or os.environ.get("MX_BENCH_DECODE_GENS", 200))
    rate = float(rate or os.environ.get("MX_BENCH_DECODE_RATE", 2500.0))
    short_new, long_new, long_frac = 2, 48, 0.3

    # the demo LM is sized so a decode step is DISPATCH-overhead-bound
    # (per-step cost ~flat in the active count) — the regime a real TPU
    # decode step lives in (weight-load-bandwidth-bound, equally
    # batch-size-invariant), where tokens-per-step translates directly
    # to throughput.  A compute-bound toy would under-credit continuous
    # batching for an artifact of the CPU bench box.
    cfg = DecodeConfig(dim=8, heads=1, layers=6, slots=8, max_tokens=48,
                       prompt_buckets=(4, 8))
    rng = np.random.RandomState(11)
    prompts = [list(map(int, rng.randint(2, cfg.vocab,
                                         size=rng.randint(2, 8))))
               for _ in range(n_gens)]
    max_news = [long_new if rng.rand() < long_frac else short_new
                for _ in range(n_gens)]
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_gens))
    reg = telemetry.registry

    def pct(sorted_secs, p):
        return round(_pctile(sorted_secs, p) * 1e3, 3)

    def run_lane(mode, paged=False):
        if paged:
            sv = PagedDecodeServable(config=cfg)
            eng = PagedDecodeBatcher(sv, queue_cap=n_gens + 64,
                                     mode=mode)
        else:
            sv = DecodeServable(config=cfg)
            eng = DecodeBatcher(sv, queue_cap=n_gens + 64, mode=mode)
        # untimed pre-burst: each lane measures its STEADY state, not
        # the process's first-touch costs (XLA autotune, allocator
        # warm, CPU boost ramp) — without this the lane that happens to
        # run first eats them and the comparison drifts run to run
        pre = [eng.submit([3, 4], max_new=12) for _ in range(24)]
        for g in pre:
            g.result(timeout=120)
        kv0 = sv.kv_state_bytes()
        retr0 = sv.retraces
        steps0 = reg.value("serve.decode.steps")
        gens = []
        t0 = time.perf_counter()
        # open-loop Poisson arrivals: the schedule never slows down for
        # the engine, so queueing shows up as latency
        for i in range(n_gens):
            due = t0 + arrivals[i]
            d = due - time.perf_counter()
            if d > 0:
                time.sleep(d)
            gens.append(eng.submit(prompts[i], max_new=max_news[i]))
        outs = [g.result(timeout=300) for g in gens]
        wall = time.perf_counter() - t0
        tokens = sum(len(o) for o in outs)
        steps = reg.value("serve.decode.steps") - steps0
        decode_tokens = tokens - n_gens     # first tokens = prefill
        token_lats = sorted(t for g in gens for t in g.token_times[1:])
        first_lats = sorted(g.token_times[0] for g in gens
                            if g.token_times)
        kv_flat = sv.kv_state_bytes() == kv0
        lane = {
            "mode": "paged" if paged else mode,
            "generations": n_gens,
            "tokens": tokens,
            "wall_s": round(wall, 3),
            "tokens_per_sec": round(tokens / wall, 2),
            "decode_steps": steps,
            "mean_occupancy": round(decode_tokens / steps, 2)
            if steps else 0.0,
            "token_latency_ms": {"p50": pct(token_lats, 50),
                                 "p99": pct(token_lats, 99)},
            "first_token_ms": {"p50": pct(first_lats, 50),
                               "p99": pct(first_lats, 99)},
            "retraces_after_warmup": sv.retraces - retr0,
            "kv_pool_bytes": sv.kv_state_bytes(),
            "kv_pool_flat": kv_flat,
        }
        if paged:
            lane["page_stats"] = eng.page_stats()
        eng.close()
        return lane, outs

    def run_shared_prefix_bench():
        # the prefix-reuse headline: K long shared prompts, repeats
        # must come back with ONE replay chunk under the paged engine
        # while flat pays the whole monolithic prefill again.  Model
        # sized so the prompt prefill is COMPUTE-bound (a chunk is
        # ~1/16th of it) — a dispatch-bound toy would hide the win.
        sp_len = int(os.environ.get("MX_BENCH_SHARED_LEN", 1024))
        sp_k = int(os.environ.get("MX_BENCH_SHARED_PROMPTS", 4))
        sp_reqs = int(os.environ.get("MX_BENCH_SHARED_REQS", 24))
        sp_new = 8
        base = dict(dim=32, heads=2, layers=6, slots=4, max_tokens=16,
                    prompt_buckets=(8, sp_len))
        srng = np.random.RandomState(18)
        probe = DecodeConfig(**base)
        bases = [[int(t) for t in srng.randint(2, probe.vocab,
                                               size=sp_len)]
                 for _ in range(sp_k)]
        warm_p = [int(t) for t in srng.randint(2, probe.vocab,
                                               size=sp_len)]

        def lane(paged):
            if paged:
                scfg = DecodeConfig(kv_page_len=64, prefill_chunk=64,
                                    kv_pages=128, **base)
                sv = PagedDecodeServable(config=scfg)
                eng = PagedDecodeBatcher(sv)
            else:
                sv = DecodeServable(config=DecodeConfig(**base))
                eng = DecodeBatcher(sv)
            # untimed warm generation off a DISTINCT full-length
            # prompt: compile + first-touch costs never land in the
            # first measured cold request
            eng.submit(warm_p, max_new=2).result(timeout=600)
            firsts = {"cold": [], "shared": []}
            outs, seen = [], set()
            for i in range(sp_reqs):
                k = i % sp_k
                bucket = "shared" if k in seen else "cold"
                seen.add(k)
                g = eng.submit(bases[k], max_new=sp_new)
                outs.append(g.result(timeout=600))
                firsts[bucket].append(g.token_times[0])
            stats = eng.page_stats()
            eng.close()
            ms = {b: {"p50": pct(sorted(v), 50),
                      "p99": pct(sorted(v), 99), "n": len(v)}
                  for b, v in firsts.items() if v}
            return ms, outs, stats

        flat_ms, flat_outs, _ = lane(paged=False)
        paged_ms, paged_outs, pstats = lane(paged=True)
        sp_speed = (flat_ms["shared"]["p50"]
                    / max(1e-9, paged_ms["shared"]["p50"]))
        return {
            "prompt_len": sp_len,
            "prompts": sp_k,
            "requests": sp_reqs,
            "flat_first_token_ms": flat_ms,
            "paged_first_token_ms": paged_ms,
            "shared_page_hits": pstats["shared_hits"],
            "parity": bool(paged_outs == flat_outs),
            "first_token_speedup": round(sp_speed, 2),
            "speedup_ok": bool(sp_speed >= 5.0
                               and paged_outs == flat_outs),
        }

    def run_admission_bench():
        # census-pinned equal-HBM capacity: flat slots=2 admits 2,
        # the byte-identical page heap runs the mixed-length set 6x
        # as wide (short sessions hold 1 page, not a flat extent)
        abase = dict(dim=8, heads=1, layers=1, max_tokens=16,
                     prompt_buckets=(4, 64))
        flat_cfg = DecodeConfig(slots=2, **abase)
        paged_cfg = DecodeConfig(slots=12, kv_page_len=16, kv_pages=18,
                                 **abase)
        sv = PagedDecodeServable(config=paged_cfg)
        flat_pool = (flat_cfg.layers * 2 * (flat_cfg.slots + 1)
                     * flat_cfg.max_len * flat_cfg.dim * 4)
        paged_pool = sv.page_bytes() * paged_cfg.kv_pages
        eng = PagedDecodeBatcher(sv, autostart=False)
        long_p = [int(t) for t in np.arange(64) % 7 + 1]
        work = [(long_p, 16)] + [([1 + i % 5, 2, 3, 4], 2)
                                 for i in range(11)]
        gens = [eng.submit(p, max_new=n) for p, n in work]
        eng.step_sync()                  # admission is one boundary
        concurrent = eng.active_count()
        eng.drain_sync()
        correct = all(
            g.tokens_so_far() == reference_generate(
                p, n, params=sv.params, config=paged_cfg)
            for g, (p, n) in zip(gens, work))
        eng.close()
        ratio = concurrent / float(flat_cfg.slots)
        return {
            "flat_slots": flat_cfg.slots,
            "paged_sessions": concurrent,
            "capacity_ratio": round(ratio, 2),
            "kv_pool_bytes_flat": flat_pool,
            "kv_pool_bytes_paged": paged_pool,
            "equal_hbm": bool(flat_pool == paged_pool),
            "tokens_correct": bool(correct),
            "ok": bool(ratio >= 4.0 and flat_pool == paged_pool
                       and correct),
        }

    def run_speculative_bench():
        # the speculative headline (ISSUE 20): the target is sized so
        # a decode step is KV-GATHER-bound — a deep model over a long
        # paged extent, the regime a real memory-bandwidth-bound TPU
        # decode step lives in — so the 1-layer draft costs ~1/24th of
        # a target step and the k+1-position verify costs ~one step
        # (the per-lane page gather is shared across window positions):
        # k committed tokens for ~2 target-steps' worth of HBM traffic.
        from mxnet_tpu.serve.decode import (DraftDecodeServable,
                                            SpeculativeDecodeBatcher,
                                            demo_spec_pair)
        sk = int(os.environ.get("MX_BENCH_SPEC_K", 8))
        s_gens = int(os.environ.get("MX_BENCH_SPEC_GENS", 8))
        s_new = int(os.environ.get("MX_BENCH_SPEC_NEW", 72))
        scfg = DecodeConfig(dim=64, heads=4, layers=24, slots=4,
                            max_tokens=1024, prompt_buckets=(8, 16),
                            kv_page_len=64, kv_pages=96,
                            prefill_chunk=16, spec_k=sk)
        tparams, dcfg, dparams = demo_spec_pair(scfg, draft_layers=1)
        srng = np.random.RandomState(20)
        sprompts = [[int(t) for t in srng.randint(2, scfg.vocab,
                                                  size=12)]
                    for _ in range(s_gens)]

        def lane(spec):
            sv = PagedDecodeServable(params=tparams, config=scfg)
            if spec:
                draft = DraftDecodeServable(params=dparams, config=dcfg,
                                            name="demo-lm-draft")
                eng = SpeculativeDecodeBatcher(sv, draft,
                                               queue_cap=s_gens + 8)
            else:
                eng = PagedDecodeBatcher(sv, queue_cap=s_gens + 8)
            for g in [eng.submit([3, 4, 5], max_new=8)
                      for _ in range(4)]:
                g.result(timeout=600)
            kv0 = sv.kv_state_bytes()
            retr0 = sv.retraces + (eng.draft.retraces if spec else 0)
            w0 = reg.value("serve.decode.spec_windows")
            d0 = reg.value("serve.decode.draft_steps")
            # closed-loop request-level throughput, min wall of two
            # measured passes: greedy decode is deterministic so both
            # passes emit identical tokens — the min isolates engine
            # cost from bench-box scheduling noise
            best, outs = None, None
            for _ in range(2):
                t0 = time.perf_counter()
                gens = [eng.submit(p, max_new=s_new) for p in sprompts]
                pass_outs = [g.result(timeout=600) for g in gens]
                wall = time.perf_counter() - t0
                if best is None or wall < best:
                    best, outs = wall, pass_outs
            tokens = sum(len(o) for o in outs)
            lane_rec = {
                "tokens": tokens,
                "wall_s": round(best, 3),
                "tokens_per_sec": round(tokens / best, 2),
                "kv_pool_flat": bool(sv.kv_state_bytes() == kv0),
                "retraces_after_warmup":
                    sv.retraces + (eng.draft.retraces if spec else 0)
                    - retr0,
            }
            if spec:
                windows = reg.value("serve.decode.spec_windows") - w0
                lane_rec["spec_windows"] = windows
                lane_rec["draft_steps"] = \
                    reg.value("serve.decode.draft_steps") - d0
                st = eng.page_stats()
                lane_rec["engine"] = st["engine"]
                lane_rec["draft_model"] = st["draft_model"]
            eng.close()
            return lane_rec, outs

        base_rec, base_outs = lane(spec=False)
        spec_rec, spec_outs = lane(spec=True)
        ratio = (spec_rec["tokens_per_sec"]
                 / max(1e-9, base_rec["tokens_per_sec"]))
        parity = bool(spec_outs == base_outs)
        return {
            "spec_k": sk,
            "target_layers": scfg.layers,
            "draft_layers": dcfg.layers,
            "kv_extent_tokens": scfg.max_tokens,
            "generations": s_gens,
            "max_new": s_new,
            "paged_baseline": base_rec,
            "speculative": spec_rec,
            "request_speedup": round(ratio, 2),
            "parity": parity,
            "kv_pool_flat": bool(base_rec["kv_pool_flat"]
                                 and spec_rec["kv_pool_flat"]),
            "zero_retraces": bool(
                base_rec["retraces_after_warmup"] == 0
                and spec_rec["retraces_after_warmup"] == 0),
            "speedup_ok": bool(ratio >= 2.0 and parity),
        }

    cont, cont_outs = run_lane("continuous")
    req, _ = run_lane("request")
    paged_lane, paged_outs = run_lane("continuous", paged=True)
    shared = run_shared_prefix_bench()
    admission = run_admission_bench()
    speculative = run_speculative_bench()
    speedup = cont["tokens_per_sec"] / max(1e-9, req["tokens_per_sec"])
    report = {
        "metric": "serve_decode_tokens_per_sec",
        "value": cont["tokens_per_sec"],
        "unit": "tokens/sec",
        "device": "cpu" if os.environ.get("MX_FORCE_CPU") else "default",
        "decode": {
            "offered_rate": rate,
            "slots": cfg.slots,
            "mix": {"short_tokens": short_new, "long_tokens": long_new,
                    "long_fraction": long_frac},
            "continuous": cont,
            "request_level": req,
            "continuous_speedup": round(speedup, 2),
            "speedup_ok": bool(speedup >= 2.0),
            "kv_pool_flat": bool(cont["kv_pool_flat"]),
            "zero_serve_time_retraces": bool(
                cont["retraces_after_warmup"] == 0
                and req["retraces_after_warmup"] == 0),
            "paged": {
                "lane": paged_lane,
                "parity_with_flat": bool(paged_outs == cont_outs),
                "kv_pool_flat": bool(paged_lane["kv_pool_flat"]),
                "zero_retraces": bool(
                    paged_lane["retraces_after_warmup"] == 0),
            },
            "shared_prefix": shared,
            "admission": admission,
            "speculative": speculative,
        },
        "phases": {k: v for k, v in telemetry.phase_snapshot().items()
                   if k in ("prefill", "decode_step", "kv_evict")},
        "census": _census_report(),
    }
    print(json.dumps(report))


def run_warm_spawn_bench():
    """--warm-spawn: serve replica ready-to-traffic time, cold vs warm
    (ISSUE 13 acceptance lane).

    Spawns the compile-heavy conv demo replica (resnet18 @ 64x64 — the
    compile-bound regime a TPU replica lives in) twice against one
    persistent compile-cache directory: the COLD spawn pays every
    bucket program's trace+XLA compile and populates the store; the
    WARM spawn deserializes the same executables.  Ready-to-traffic is
    measured spawn → first successful PREDICT over a real socket, so
    interpreter+jax import, model build, bucket warm-up and server
    bind all count.  The replica's compile-cache counters and census
    are scraped over the METRICS verb — the warm spawn must report
    cache hits == its bucket count and warm compile seconds ~0.
    """
    import shutil
    import socket as _socket
    import tempfile
    import numpy as np
    from mxnet_tpu import fleet
    from mxnet_tpu.serve import ServeClient
    from mxnet_tpu.serve.demo import DEMO_CONV_SHAPE

    cache_dir = tempfile.mkdtemp(prefix="mx_warm_spawn_cache_")
    buckets = os.environ.get("MX_BENCH_WARM_BUCKETS", "1,2,4,8,16,32,64")
    spawn_timeout = float(os.environ.get("MX_BENCH_WARM_TIMEOUT", 300))

    def _free_port():
        s = _socket.socket()
        s.bind(("", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def spawn_and_measure(tag):
        port = _free_port()
        env = dict(os.environ,
                   JAX_PLATFORMS="cpu", MX_FORCE_CPU="1",
                   MX_COMPILE_CACHE=cache_dir,
                   MX_SERVE_BUCKETS=buckets,
                   PYTHONPATH=os.path.dirname(os.path.abspath(__file__))
                   + os.pathsep + os.environ.get("PYTHONPATH", ""))
        t0 = time.perf_counter()
        # stderr goes to a FILE, not a pipe: a chatty cold compile
        # could fill a pipe buffer and deadlock the replica before it
        # ever binds — the file is read back only on failure
        err_path = os.path.join(cache_dir, "replica-%s.stderr" % tag)
        err_f = open(err_path, "wb")
        try:
            proc = subprocess.Popen(
                [sys.executable, "-m", "mxnet_tpu.serve", "--demo-conv",
                 "--port", str(port)],
                env=env, stdout=subprocess.DEVNULL, stderr=err_f)
        finally:
            err_f.close()
        addr = "127.0.0.1:%d" % port
        x = np.zeros((1,) + DEMO_CONV_SHAPE, np.float32)
        ready_s = None
        deadline = time.monotonic() + spawn_timeout
        while time.monotonic() < deadline and proc.poll() is None:
            try:
                cli = ServeClient([addr], timeout=10)
                cli.predict([x])
                ready_s = time.perf_counter() - t0
                cli.close()
                break
            except Exception:
                time.sleep(0.05)
        if ready_s is None:
            proc.kill()
            proc.wait()
            try:
                with open(err_path, "rb") as f:
                    err = f.read()
            except OSError:
                err = b""
            raise RuntimeError("warm-spawn %s replica never became "
                               "ready: %s" % (tag,
                                              err.decode(errors="replace")
                                              [-2000:]))
        # the replica's own receipts, over the wire it serves on
        snap = fleet.fetch_metrics(addr, fmt="json")

        def _val(name):
            total = 0
            for entry in snap.values():
                if isinstance(entry, dict) and entry.get("name") == name:
                    total += int(entry.get("value", 0))
            return total

        compile_s = 0.0
        for entry in snap.values():
            if isinstance(entry, dict) and \
                    entry.get("name") == "program_compile_seconds" and \
                    entry.get("type") == "histogram":
                compile_s += float(entry.get("sum", 0.0))
        stats = {
            "ready_to_traffic_s": round(ready_s, 3),
            "cache_hits": _val("compile_cache.hits"),
            "cache_misses": _val("compile_cache.misses"),
            "cache_writes": _val("compile_cache.writes"),
            "compile_seconds_total": round(compile_s, 3),
        }
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
        return stats

    try:
        cold = spawn_and_measure("cold")
        warm = spawn_and_measure("warm")
    finally:
        if not os.environ.get("MX_BENCH_WARM_KEEP"):
            shutil.rmtree(cache_dir, ignore_errors=True)
    n_buckets = len([b for b in buckets.split(",") if b.strip()])
    speedup = cold["ready_to_traffic_s"] / max(1e-9,
                                               warm["ready_to_traffic_s"])
    print(json.dumps({
        "metric": "serve_warm_spawn_speedup",
        "value": round(speedup, 2),
        "unit": "x_faster_ready_to_traffic",
        "device": "cpu",
        "buckets": buckets,
        "cold": cold,
        "warm": warm,
        "warm_spawn_seconds": warm["ready_to_traffic_s"],
        "cold_spawn_seconds": cold["ready_to_traffic_s"],
        "gate": 5.0,
        "within_gate": bool(speedup >= 5.0),
        "warm_hits_cover_buckets": bool(warm["cache_hits"] >= n_buckets),
        "warm_compile_under_1s": bool(
            warm["compile_seconds_total"] < 1.0),
    }))


def run_real_data_bench():
    """--real-data: prove the input pipeline (.rec → JPEG decode → augment →
    NCHW batch) sustains the compute rate (SURVEY hard part 7: ~3k img/s
    decode behind a saturated MXU).  Builds a synthetic ImageNet-shaped
    .rec pack, then measures ImageRecordIter throughput standalone."""
    import tempfile
    import numpy as np
    from mxnet_tpu import recordio
    from mxnet_tpu.io import ImageRecordIter

    n_img, edge, batch = 512, 256, 64
    d = tempfile.mkdtemp(prefix="mxbench_rec_")
    prefix = os.path.join(d, "synth")
    rng = np.random.RandomState(0)
    w = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    # JPEG-realistic content: smooth low-freq fields, not raw noise
    base = rng.rand(8, edge, edge, 3)
    for i in range(n_img):
        img = (base[i % 8] * (120 + (i % 100)) % 255).astype(np.uint8)
        w.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, float(i % 1000), i, 0), img, quality=90))
    w.close()

    it = ImageRecordIter(path_imgrec=prefix + ".rec",
                         data_shape=(3, 224, 224), batch_size=batch,
                         shuffle=True, rand_crop=True, rand_mirror=True,
                         preprocess_threads=os.cpu_count() or 8)
    for _ in range(2):  # warm the pool
        next(it)
    it.reset()
    t0 = time.perf_counter()
    n = 0
    for b in it:
        n += b.data[0].shape[0]
    dt = time.perf_counter() - t0
    iter_ips = round(n / dt, 2)

    # DataLoader worker-model comparison on the same decode+augment work:
    # serial vs GIL-bound threads vs the reference-style spawned processes
    # (gluon/data/dataloader.py _MultiWorkerIter equivalent).
    from mxnet_tpu.gluon.data import DataLoader
    from mxnet_tpu.gluon.data.vision import ImageRecordDataset

    ds = ImageRecordDataset(prefix + ".rec",
                            transform=_ingest_decode_transform)
    n_workers = min(4, os.cpu_count() or 4)
    loader_ips = {}
    for mode, kw in (("serial", {"num_workers": 0}),
                     ("threads", {"num_workers": n_workers,
                                  "thread_pool": True}),
                     ("processes", {"num_workers": n_workers})):
        dl = DataLoader(ds, batch_size=batch, **kw)
        it2 = iter(dl)
        next(it2)           # warm pool / first-spawn cost (NOT counted)
        t0 = time.perf_counter()
        seen = 0
        for b in it2:
            seen += b[0].shape[0]
        loader_ips[mode] = round(seen / (time.perf_counter() - t0), 2)
        if hasattr(dl, "_shutdown_pool"):
            dl._shutdown_pool()
    print(json.dumps({
        "metric": "image_record_iter_images_per_sec",
        "value": iter_ips, "unit": "images/sec",
        "vs_baseline": round(iter_ips / 3000.0, 4),  # ref decode target
        "threads": os.cpu_count() or 8, "batch": batch,
        "dataloader_images_per_sec": loader_ips,
        "workers": n_workers,
        # on a 1-CPU host the process pool CANNOT win (no parallel
        # hardware); judge the threads-vs-processes delta only when
        # host_cpus > workers
        "host_cpus": os.cpu_count() or 1,
    }))


def _ingest_decode_transform(img, label):
    """Decode-bound worker transform: resize + mirror + normalize, pure
    numpy/PIL (top level: must pickle into spawned workers)."""
    import numpy as np
    from PIL import Image
    a = np.asarray(img)
    im = Image.fromarray(a).resize((224, 224))
    out = np.asarray(im, np.float32)[:, ::-1].transpose(2, 0, 1) / 255.0
    return out, np.float32(label)


def main():
    if "--real-data" in sys.argv:
        run_real_data_bench()
        return
    if "--exchange" in sys.argv:
        run_exchange_bench()
        return
    if "--serve" in sys.argv:
        # CPU-friendly like --exchange: the serving engine's value on a
        # bench box is the batching/latency behavior, not model FLOPs
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault("MX_FORCE_CPU", "1")
        if "--decode" in sys.argv:
            # ISSUE 15: continuous-vs-request-level decode comparison
            run_decode_bench()
            return
        run_serve_bench(routed="--routed" in sys.argv)
        return
    if "--warm-spawn" in sys.argv:
        # CPU-friendly: the lane measures spawn→first-PREDICT time of
        # subprocess replicas, which pin themselves to cpu
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault("MX_FORCE_CPU", "1")
        run_warm_spawn_bench()
        return
    mode = "bert" if "--bert" in sys.argv else \
        ("score" if "--score" in sys.argv else
         ("eager" if "--eager" in sys.argv else "resnet"))
    if "--scan" in sys.argv:
        # diagnostic: run the measured iterations inside ONE jit (lax scan
        # over the step) — the delta vs the default per-step dispatch loop
        # is the per-step host overhead
        os.environ["MX_BENCH_SCAN"] = "1"
    if "--mesh" in sys.argv:
        # ISSUE 14: --mesh data,fsdp[=N][,tp=N] arms the sharded lane of
        # --eager over the visible chips
        at = sys.argv.index("--mesh")
        if at + 1 >= len(sys.argv):
            sys.stderr.write("bench.py: --mesh expects an axes argument "
                             "(e.g. --mesh data,fsdp=2)\n")
            sys.exit(2)
        os.environ["MX_BENCH_MESH"] = sys.argv[at + 1]
    import jax
    if jax.default_backend() != "tpu":
        sys.stderr.write("bench.py: the %s lane runs on a TPU; jax found "
                         "%r (JAX_PLATFORMS=%r)\n"
                         % (mode, jax.devices(),
                            os.environ.get("JAX_PLATFORMS", "")))
        sys.exit(1)
    {"bert": run_bert_bench, "score": run_score_bench,
     "eager": run_eager_bench, "resnet": run_bench}[mode]()


if __name__ == "__main__":
    main()
