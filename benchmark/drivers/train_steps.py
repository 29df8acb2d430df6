"""Traffic of the kind ``train_steps``: a closed loop of compiled train
steps through the user path ``gluon.Trainer.make_compiled_step`` ->
``step.CompiledStep``, fed by ``io.DevicePrefetcher`` from a pool of
seeded host batches.

The run's own process holds the chip(s).  Set-up is everything before the
first counted dispatch: imports, build, the correctness check against the
configuration's plain reference, the batch pool, two warm-up steps (the
first compiles or loads from jax's cache).  The window then dispatches
steps back to back, syncs on the loss every ``sync_every`` steps and ends
at the first sync past ``--seconds``.  Traced, the last ``trace_seconds``
of the window run under ``jax.profiler``; host-clock numbers then come
from the part before.
"""
import itertools
import os
import time

import numpy as np

from benchmark.harness import compiles, device, profiler, trace_reduce


def _state_leaves(trainer):
    """Every optimizer-state NDArray of the trainer's first updater
    (momentum and float32 master weights); reaches into the trainer as
    ``chip_smoke._state_leaves`` does - there is no public accessor."""
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


def _step_program_text(step):
    """Compiled text of the step's one program (``chip_smoke``'s way)."""
    program = next(iter(step._cache.values()))
    compiled = next(iter(program._cache.values()))
    return compiled.as_text()


def _data_wait_s():
    """What the prefetcher's consumer has waited so far, in all."""
    from mxnet_tpu import telemetry
    return telemetry.registry.find(
        "step_phase_seconds", {"phase": "data_wait"}).snapshot()["sum"]


def _check_reference(run, model, net, ctx):
    import jax
    import jax.numpy as jnp
    inputs = model.check_inputs(run.config, run.traffic, run.seed)
    got = model.logits(net, inputs, ctx)
    params = {name: p.data()._jax
              for name, p in net.collect_params().items()}
    # parameters and rows are ARGUMENTS: closed over, they would be
    # constants of the program and every seed would compile it anew
    ref = jax.jit(lambda ps, xs: model.reference(ps, xs, run.config))
    want = ref(params, inputs)
    scale = float(jnp.abs(want).max())
    err = float(jnp.abs(got - want).max()) / scale
    tol = run.config["check_tolerance"]
    if not want.shape == got.shape or not err <= tol:
        run.wrong("logits differ from the plain reference: %g of the "
                  "logit scale > %g" % (err, tol))
    run.facts["reference_err"] = err
    run.note(reference_err=err, tolerance=tol, logit_scale=scale)


def _verify(run, net, trainer, step, layout, losses):
    """What the run must have been, to count; returns the loss values."""
    facts = run.facts
    chips = run.cell["chips"]
    values = [float(np.asarray(l._jax, np.float32).mean()) for l in losses]
    facts["attempted"] = len(values)
    facts["failed"] = sum(1 for v in values if not np.isfinite(v))
    if facts["failed"]:
        run.wrong("%d non-finite losses" % facts["failed"])
    if len(values) >= 16 and not np.mean(values[-8:]) < np.mean(values[:8]):
        run.wrong("loss did not fall: first 8 mean %g, last 8 mean %g"
                  % (np.mean(values[:8]), np.mean(values[-8:])))
    if facts["compiles_in_window"] or facts["lowerings_in_window"]:
        run.wrong("compiled inside the window: %d compiles, %d lowerings"
                  % (facts["compiles_in_window"],
                     facts["lowerings_in_window"]))
    params = [p.data()._jax for p in net.collect_params().values()]
    states = [s._jax for s in _state_leaves(trainer)]
    placed = sorted({d.platform for a in params + states + [losses[-1]._jax]
                     for d in a.devices()})
    if placed != [facts["device"]["platform"]]:
        run.wrong("parameters, optimizer state or loss on %r" % placed)
    if layout is not None:
        per_device = {}
        for a in params + states:
            for sh in a.addressable_shards:
                per_device[sh.device.id] = \
                    per_device.get(sh.device.id, 0) + sh.data.nbytes
        total = sum(a.nbytes for a in params + states)
        facts["state_share"] = max(per_device.values()) / total
        text = _step_program_text(step)
        found = {k: text.count(k) for k in
                 ("all-gather", "reduce-scatter", "all-reduce")}
        facts["collectives_in_text"] = found
        if len(per_device) != chips or facts["state_share"] > 0.5:
            run.wrong("state is not spread over the chips: %r" % per_device)
        if not found["all-gather"] or not (found["reduce-scatter"]
                                           or found["all-reduce"]):
            run.wrong("no collectives in the step: %r" % found)
    return values


def run(run):
    facts = run.facts
    chips = run.cell["chips"]
    facts["device"] = device.require(chips)
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, programs
    from mxnet_tpu.io.prefetch import DevicePrefetcher
    from mxnet_tpu.ndarray.ndarray import NDArray
    traffic, config = run.traffic, run.config
    model = run.model()
    ctx = mx.tpu(0)
    count = compiles.CompileCount()

    t0 = time.perf_counter()
    net = model.build(config, ctx, run.seed)
    facts["build_s"] = time.perf_counter() - t0
    _check_reference(run, model, net, ctx)

    opt = dict(config["optimizer"])
    trainer = gluon.Trainer(net.collect_params(), opt.pop("name"), opt)
    layout = sharding = None
    if traffic.get("mesh"):
        from mxnet_tpu.parallel import SpecLayout, make_mesh
        mesh = make_mesh(axes=tuple(traffic["mesh"]["axes"]),
                         shape=tuple(traffic["mesh"]["shape"]),
                         devices=jax.devices()[:chips])
        layout = SpecLayout.infer(mesh)
        sharding = layout.batch_sharding()
    step = trainer.make_compiled_step(net, model.loss_fn(), layout=layout)

    pool = model.batches(config, traffic, run.seed)
    rows = traffic["batch"]
    feed = DevicePrefetcher(itertools.cycle(pool),
                            device=sharding or ctx.jax_device)

    def next_batch():
        data, label = next(feed)
        return (tuple(NDArray(a, ctx=ctx) for a in data),
                NDArray(label, ctx=ctx))

    losses = []
    try:
        for _ in range(2):                      # warm-up: compile or load
            data, label = next_batch()
            losses.append(step.step(data, label))
        jax.block_until_ready(losses[-1]._jax)
        if not step.compiled:
            raise SystemExit("benchmark: the compiled step fell back to "
                             "eager: %s" % step.fallback_reason)
        warm = len(losses)
        facts["compile_s"] = \
            programs.program_summary()["compile_seconds_total"]
        facts["cache_hits"] = count.cache_hits
        lowered0 = count.lowerings
        compiles0 = programs.program_summary()["compiles"]
        wait0 = _data_wait_s()
        trace_dir = os.path.join(run.cache_dir, "trace", run.cell["name"])
        trace_at = run.seconds - traffic["trace_seconds"] \
            if run.trace else None
        call_s = []
        window_span = None                      # set once tracing
        untraced = None             # (steps, seconds, wait, call times)

        facts["setup_s"] = time.perf_counter() - run.t_process
        t_first = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.data_next"):
                data, label = next_batch()
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.step_call"):
                losses.append(step.step(data, label))
            call_s.append(time.perf_counter() - t)
            if (len(losses) - warm) % traffic["sync_every"]:
                continue
            with jax.profiler.TraceAnnotation("bench.sync"):
                jax.block_until_ready(losses[-1]._jax)
            elapsed = time.perf_counter() - t_first
            if elapsed >= run.seconds:
                break
            if trace_at is not None and elapsed >= trace_at \
                    and window_span is None:
                untraced = (len(losses) - warm, elapsed,
                            _data_wait_s() - wait0, list(call_s))
                profiler.start(trace_dir)
                window_span = jax.profiler.TraceAnnotation(
                    "bench.trace_window")
                window_span.__enter__()
        t_last = time.perf_counter()
        if window_span is not None:
            window_span.__exit__(None, None, None)
            profiler.stop()
    finally:
        feed.close()

    steps = len(losses) - warm
    if untraced is None:
        untraced = (steps, t_last - t_first, _data_wait_s() - wait0, call_s)
    facts["steps"], facts["window_s"] = steps, t_last - t_first
    facts["host_steps"], facts["host_window_s"], facts["data_wait_s"], \
        facts["step_call_s"] = untraced
    facts["rows_per_step"] = rows
    facts["units_per_row"] = model.units_per_row(traffic)
    facts["ops"] = model.ops_and_bytes(config, traffic)
    facts["lowerings_in_window"] = count.lowerings - lowered0
    facts["compiles_in_window"] = \
        programs.program_summary()["compiles"] - compiles0
    count.close()
    facts["memory_peak_bytes"] = device.memory_peak_bytes(chips)
    facts["device"]["memory_peak_bytes"] = facts["memory_peak_bytes"]

    values = _verify(run, net, trainer, step, layout, losses[warm:])
    run.note(steps=steps, window_s=facts["window_s"],
             loss_first=values[0], loss_at_step_8=values[min(7, steps - 1)],
             loss_last=values[-1], setup_s=facts["setup_s"],
             build_s=facts["build_s"], cache_hits=facts["cache_hits"],
             memory_peak_bytes=facts["memory_peak_bytes"],
             memory_stats=device.memory_stats())

    if window_span is not None:
        reduced = trace_reduce.read_into(facts, trace_dir)
        if reduced is not None:
            run.note(trace={k: reduced[k] for k in (
                "window_s", "busy_s", "idle_share", "step_module",
                "step_events", "step_busy_s", "collective_s",
                "collective_exposed_s")},
                kinds=dict(list(reduced["kinds"].items())[:8]))
        elif not device.rehearsing():       # XLA:CPU has no device plane
            run.wrong("the traced window holds no device operation")
