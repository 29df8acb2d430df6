"""The traced window read from inside the program: device time by the
scopes the step program carries, host time by the program's own ``mx.*``
spans.

``read(run)`` opens the run's trace again with the program's span names
(``trace_reduce.load_xplane(..., host_prefixes=("mx.", window))``), asks
the program where each instruction of its step comes from
(``mxnet_tpu.programs.program_scopes("step.step")``: instruction name ->
``op_name`` path, parsed from the compiled text, so nothing depends on
what the trace's events carry) and reduces both with ``reduce``.  The
result is cached on ``run.facts`` so that the nine readers under
``layer_metrics/`` share one pass; one earlier output line carries the
table by block path and the idle gaps by ``mx.*`` span.

``reduce`` works on plain dicts only (the recorded sample under
``tests/benchmark/data/`` has the same form).  Definitions:

- *window*: the host span ``bench.trace_window``, else everything.
- *step events*: the "XLA Modules" events named as the step program's
  module that lie wholly inside the window; an op belongs to the module
  event that contains its start.  Every per-step number is a sum over the
  ops of the step events (self times, as ``trace_reduce.self_times``)
  divided by their count, mean over the devices.
- an op's *scope* is its instruction's entry in ``program_scopes``:
  ``top`` one of forward / backward / exchange / optimizer / metric (an
  op under ``forward`` whose path passes through ``transpose(`` is
  backward), ``mixed`` for a fusion whose instructions lie under more
  than one of the scopes step.py opens.  *Unattributed*: mixed fusions
  and ops with no top-level scope (or no entry at all).
- *block path*: the scope's components after the top-level one up to the
  first ``jit(...)`` wrapper (an eager op's own program) - the blocks'
  structural names, as in ``forward/BERTModel/encoder/*/attention``: a
  number (a child of a Sequential) is written ``*``, so the same block of
  twelve layers is one row.
- *operator*: the name of the first eager op's own program on the path
  (``jit(mx_op_BatchNorm)`` -> ``BatchNorm``): what tells a Sequential's
  numbered children apart, summed under ``<top>/<operator>``.
- *other programs*: ops inside module events that are not the step's, by
  module name (a program's module is ``jit_`` + its census name with
  ``mx_`` in front and dots as underscores).
- *host spans*: median duration of each ``mx.*`` span that lies wholly
  inside the window.
- *idle gaps*: ``trace_reduce.reduce`` on the same trace with the ``mx.*``
  spans cut to the innermost one at each moment.

Where the program has no scopes (a checkout from before they existed, or
an executable handed over by a cache that ignores them) every scope
number is None and one line says why; where it has no ``mx.*`` spans the
span numbers are None.
"""
import bisect
import os

from benchmark.harness import stats, trace_reduce

WINDOW = "bench.trace_window"
STEP_PROGRAM = "step.step"


def block_path(scope):
    """'BERTModel/encoder/*/attention' of an op_name path."""
    parts = [p for p in scope.split("/") if p]
    while parts and parts[0].startswith(("jit(", "pjit(")):
        parts.pop(0)
    if not parts:
        return ""
    out = []
    for p in parts[1:]:
        if p.startswith(("jit(", "pjit(")):
            break
        out.append(p)
    else:
        out = out[:-1]          # no eager-op wrapper: the last is the primitive
    return "/".join("*" if p.isdigit() else p for p in out)


def operator_of(scope):
    """'BatchNorm' of '.../features/1/jit(mx_op_BatchNorm)/reduce_sum',
    '' where no eager op's program is on the path."""
    at = scope.find("jit(mx_op_")
    return scope[at + 10:scope.index(")", at)] if at >= 0 else ""


def innermost(spans):
    """`spans` ([name, start, dur], any nesting or overlap) cut into
    pieces that do not overlap, each named by the covering span that
    started last."""
    edges = sorted({s[1] for s in spans} | {s[1] + s[2] for s in spans})
    by_start = sorted(spans, key=lambda s: s[1])
    out, active, i = [], [], 0
    for lo, hi in zip(edges, edges[1:]):
        while i < len(by_start) and by_start[i][1] <= lo:
            active.append(by_start[i])
            i += 1
        active = [s for s in active if s[1] + s[2] > lo]
        if not active:
            continue
        name = active[-1][0]
        if out and out[-1][0] == name and out[-1][1] + out[-1][2] == lo:
            out[-1][2] += hi - lo
        else:
            out.append([name, lo, hi - lo])
    return out


def _window(trace):
    windows = [s for s in trace.get("host", ()) if s[0] == WINDOW]
    if windows:
        return (min(w[1] for w in windows),
                max(w[1] + w[2] for w in windows))
    every = [(op[2], op[2] + op[3])
             for d in (trace.get("devices") or {}).values()
             for op in d["ops"]]
    every += [(s[1], s[1] + s[2]) for s in trace.get("host", ())]
    if not every:
        return 0, 0
    return min(e[0] for e in every), max(e[1] for e in every)


def host_spans(trace):
    """{span name: median ms} of the mx.* spans inside the window."""
    lo, hi = _window(trace)
    durs = {}
    for name, start, dur in trace.get("host", ()):
        if name.startswith("mx.") and start >= lo and start + dur <= hi:
            durs.setdefault(name, []).append(dur / 1e6)
    return {k: stats.median(v) for k, v in sorted(durs.items())}


def reduce(trace, scopes, step_module=None):
    """See the module's docstring.  `scopes` is ``program_scopes``'s
    result or None; `step_module` names the step's module where `scopes`
    does not.  Milliseconds per step throughout."""
    out = {"spans_ms": host_spans(trace), "scopes": None, "why": None,
           "steps": 0}
    devices = trace.get("devices") or {}
    if not devices:
        out["why"] = "the trace holds no device op"
        return out
    lo, hi = _window(trace)
    instructions = (scopes or {}).get("instructions") or {}
    step_module = (scopes or {}).get("module") or step_module
    named = any(i.get("top") == "forward" for i in instructions.values())
    n = len(devices)
    tops, blocks, operators, modules, mixes = {}, {}, {}, {}, {}
    attention = unattributed = step_total = 0.0
    steps = 0
    for dev in devices.values():
        events = sorted((m for m in dev["modules"]
                         if m[1] >= lo and m[1] + m[2] <= hi),
                        key=lambda m: m[1])
        if step_module is None and events:
            # as trace_reduce: the module with the most device time
            total = {}
            for m in events:
                total[m[0]] = total.get(m[0], 0) + m[2]
            step_module = max(total, key=total.get)
        starts = [m[1] for m in events]
        steps += sum(1 for m in events if m[0] == step_module)
        ops = [op for op in dev["ops"] if op[2] >= lo and op[2] + op[3] <= hi]
        for op, own in zip(ops, trace_reduce.self_times(ops)):
            i = bisect.bisect_right(starts, op[2]) - 1
            if i < 0 or op[2] >= events[i][1] + events[i][2]:
                continue                    # inside no whole module event
            ms = own / 1e6
            if events[i][0] != step_module:
                modules[events[i][0]] = modules.get(events[i][0], 0.0) + ms
                continue
            step_total += ms
            where = instructions.get(op[0])
            top = where and where["top"]
            if where is None or top is None or where["mixed"]:
                unattributed += ms
                what = "+".join(where["tops"]) if where and where["tops"] \
                    else "no scope"
                mixes[what] = mixes.get(what, 0.0) + ms
                continue
            tops[top] = tops.get(top, 0.0) + ms
            if "attention_core" in where["scope"]:
                attention += ms
            label = "/".join(p for p in (top, block_path(where["scope"]))
                             if p)
            blocks[label] = blocks.get(label, 0.0) + ms
            label = "/".join(p for p in (top, operator_of(where["scope"]))
                             if p)
            operators[label] = operators.get(label, 0.0) + ms
    out["steps"] = steps // n
    per = float(steps) or 1.0       # events of all devices: sums ÷ this
    out["step_module"] = step_module
    out["other_programs_ms"] = sum(modules.values()) / per if steps else None
    out["other_programs"] = {k: v / per for k, v in
                             sorted(modules.items(), key=lambda kv: -kv[1])}
    out["step_ms"] = step_total / per
    if not named or not steps:
        out["why"] = "no step event of module %r in the window" \
            % step_module if not steps else \
            "no instruction of %s carries a `forward` scope (%d " \
            "instructions known)" % (STEP_PROGRAM, len(instructions))
        return out
    ranked = sorted(blocks.items(), key=lambda kv: -kv[1])
    out["scopes"] = {
        "forward_ms": tops.get("forward", 0.0) / per,
        "backward_ms": tops.get("backward", 0.0) / per,
        "optimizer_ms": tops.get("optimizer", 0.0) / per,
        "exchange_ms": tops.get("exchange", 0.0) / per,
        "metric_ms": tops.get("metric", 0.0) / per,
        "attention_ms": attention / per,
        "unattributed_ms": unattributed / per,
        "scope_unattributed": 100.0 * unattributed / step_total
        if step_total else None,
        "unattributed_by": {k: v / per for k, v in
                            sorted(mixes.items(), key=lambda kv: -kv[1])},
        "blocks_ms": {k: v / per for k, v in ranked},
        "operators_ms": {k: v / per for k, v in
                         sorted(operators.items(), key=lambda kv: -kv[1])},
    }
    return out


def _program_scopes():
    """The live program's scopes, or None where the program has none to
    give (a checkout from before ``programs.program_scopes``)."""
    try:
        from mxnet_tpu import programs
    except ImportError:
        return None
    ask = getattr(programs, "program_scopes", None)
    return ask(STEP_PROGRAM) if ask is not None else None


def idle_gaps(trace):
    """Idle gaps of the window named by the innermost mx.* span, seconds."""
    cut = dict(trace, host=innermost(
        [s for s in trace.get("host", ()) if s[0] != WINDOW])
        + [s for s in trace.get("host", ()) if s[0] == WINDOW])
    reduced = trace_reduce.reduce(cut, window_span=WINDOW)
    return reduced["idle_gaps"] if reduced else None


def read(run):
    """The reduction of this run's trace (cached on ``run.facts``), or
    None for a run that was not traced or left no trace."""
    if "program_trace" in run.facts:
        return run.facts["program_trace"]
    out = None
    path = trace_reduce.find_xplane(
        os.path.join(run.cache_dir, "trace", run.cell["name"])) \
        if run.trace else None
    if path is not None:
        trace = trace_reduce.load_xplane(path, host_prefixes=("mx.", WINDOW))
        out = reduce(trace, _program_scopes())
        seen = out["scopes"]
        if seen is None:
            run.note(scopes="absent", why=out["why"])
        else:
            run.note(scopes={k: seen[k] for k in (
                "forward_ms", "backward_ms", "optimizer_ms", "exchange_ms",
                "metric_ms", "attention_ms", "unattributed_ms")},
                step_ms=out["step_ms"], steps=out["steps"],
                blocks_ms=dict(list(seen["blocks_ms"].items())[:12]),
                operators_ms=dict(list(seen["operators_ms"].items())[:8]),
                unattributed_by=dict(
                    list(seen["unattributed_by"].items())[:6]))
        more = {"spans_ms": out["spans_ms"]}
        if trace.get("devices"):
            more.update(step_module=out["step_module"],
                        other_programs_ms=dict(
                            list(out["other_programs"].items())[:8]),
                        idle_gaps_s=idle_gaps(trace))
        run.note(**more)
    run.facts["program_trace"] = out
    return out


def scope(run, key):
    """One scope number of the run, or None."""
    got = read(run)
    return None if not got or got["scopes"] is None else got["scopes"][key]


def span(run, name):
    """Median ms of one mx.* span in the traced window, or None."""
    got = read(run)
    return None if not got else got["spans_ms"].get(name)
