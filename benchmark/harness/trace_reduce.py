"""From a device trace to numbers.

``load_xplane`` reads the profiler's ``.xplane.pb`` with
``jax.profiler.ProfileData`` and nothing else, into a plain dict (the same
form the recorded sample under ``tests/benchmark/data/`` has):

    {"devices": {"/device:TPU:0": {"ops":     [[name, group, start_ns, dur_ns], ...],
                                   "async":   [[name, group, start_ns, dur_ns], ...],
                                   "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}          # the benchmark's own spans

``ops`` is the trace's "XLA Ops" line (what the core executes, one after
another), ``async`` its "Async XLA Ops" line (transfers and collectives in
flight from their start to their done), ``modules`` its "XLA Modules" line
(one event per program run).

``reduce`` works on that dict only.  Definitions:

- *window*: the host span named `window_span` (the driver puts it around
  the traced part), else first event start to last event end.  Events are
  clipped to it.
- *busy* of a device: the union of its op intervals; *idle share* is
  1 − busy ÷ window, averaged over the devices.
- *group* of an op: the trace's own ``hlo_category`` stat where present
  (the v5e's trace of PR 23 carries none).  Else, from the HLO text the
  event is named by: a collective by its kind; a custom call by its
  target (a Pallas kernel's name); any other op by its opcode, its fusion
  kind and the shapes it produces without their layouts, as in
  ``fusion:kOutput bf16[32,512,3072]`` - so the same op of twelve layers
  is one group, and a reader can tell the MLM head from the attention.
  A group's time is the *self* time of its ops (an op that contains
  others, as a ``while`` does, is charged only what its children leave),
  averaged over the devices.  ``kinds`` adds the groups up by their first
  word (``fusion:kLoop``: elementwise and reductions, bound by memory;
  ``fusion:kOutput``: fusions around a convolution or matrix product).
- *collective*: the union of the collective events of both lines of a
  device; *exposed* is the part of it during which no other op of the
  "XLA Ops" line runs on that device.
- *idle gaps*: the complement of busy inside the window.  A gap inside a
  running program (a module event) is ``device.between_ops``; any other is
  named by the host span that covers most of it, else `host_default`.
"""
import bisect
import glob
import os
import re

COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce", "all-to-all",
               "collective-permute", "collective-broadcast")
_DIGITS = re.compile(r"[.\-_]?\d+$")
_LINES = {"XLA Ops": "ops", "Async XLA Ops": "async"}
_MODULE_LINES = ("XLA Modules",)
_FINGERPRINT = re.compile(r"\(\d+\)$")


_HLO = re.compile(r"^%?(?P<name>[^ ]+) = (?P<out>.*?) ?(?P<op>[a-z][a-z0-9\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_KIND = re.compile(r"kind=(k[A-Za-z]+)")


def parse_hlo(text):
    """(instruction name, opcode, output shapes without layouts) of the
    HLO text a TPU trace names its op events by; (text, None, "") for a
    plain name."""
    m = _HLO.match(text)
    if not m:
        return text.lstrip("%"), None, ""
    out = _LAYOUT.sub("", m.group("out"))
    return m.group("name"), m.group("op"), out


def collective_kind(name):
    """'all-gather' for 'all-gather-start.12' or its HLO text, None for a
    non-collective."""
    short, op, _ = parse_hlo(name)
    low = (op or short).lower()
    for kind in COLLECTIVES:
        if low.startswith(kind):
            return kind
    return None


def group_of(name, stats):
    kind = collective_kind(name)
    if kind:
        return kind
    if stats.get("hlo_category"):
        return str(stats["hlo_category"])
    short, op, out = parse_hlo(name)
    if op is None:
        base = short
        while True:
            cut = _DIGITS.sub("", base)
            if cut == base or not cut:
                return base
            base = cut
    if op == "custom-call":
        target = _TARGET.search(name)
        return "custom-call:%s" % (target.group(1) if target else out)[:120]
    fusion = _KIND.search(name) if op == "fusion" else None
    label = "%s:%s" % (op, fusion.group(1)) if fusion else op
    return ("%s %s" % (label, out))[:120]


def find_xplane(trace_dir):
    """The newest .xplane.pb under `trace_dir`, or None."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def load_xplane(path, host_prefixes=("bench.", "replica.")):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "async": [], "modules": []}
            for line in plane.lines:
                if line.name in _LINES:
                    rows = dev[_LINES[line.name]]
                    for ev in line.events:
                        stats = {k: v for k, v in ev.stats
                                 if k == "hlo_category"}
                        rows.append(
                            [parse_hlo(ev.name)[0], group_of(ev.name, stats),
                             int(ev.start_ns), int(ev.duration_ns)])
                elif line.name in _MODULE_LINES:
                    for ev in line.events:
                        dev["modules"].append(
                            [_FINGERPRINT.sub("", ev.name),
                             int(ev.start_ns), int(ev.duration_ns)])
            if dev["ops"]:
                out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(tuple(host_prefixes)):
                        out["host"].append([ev.name, int(ev.start_ns),
                                            int(ev.duration_ns)])
    return out


# -- interval arithmetic (half-open [start, end) in ns) ---------------------

def union(intervals):
    """Sorted, merged copy of `intervals`."""
    merged = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """The part of merged `a` not covered by merged `b`."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def self_times(ops):
    """Self time of each op (ns): its duration less that of the ops
    directly nested in it.  `ops` rows are [name, group, start, dur]."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][2], -ops[i][3]))
    own = [op[3] for op in ops]
    stack = []
    for i in order:
        start, end = ops[i][2], ops[i][2] + ops[i][3]
        while stack and ops[stack[-1]][2] + ops[stack[-1]][3] <= start:
            stack.pop()
        if stack and end <= ops[stack[-1]][2] + ops[stack[-1]][3]:
            own[stack[-1]] -= ops[i][3]
        stack.append(i)
    return own


def _covering(spans, starts, gap):
    """Name of the span that covers most of `gap`, or None."""
    s, e = gap
    best, best_ov = None, 0
    i = bisect.bisect_right(starts, s) - 1
    # spans of different threads may overlap: look back a bounded way
    i = max(0, i - 8)
    while i < len(spans) and spans[i][1] < e:
        ov = min(e, spans[i][1] + spans[i][2]) - max(s, spans[i][1])
        if ov > best_ov:
            best, best_ov = spans[i][0], ov
        i += 1
    return best


def reduce(trace, window_span="bench.trace_window",
           host_default="host.untraced"):
    """See the module's docstring.  Seconds throughout; None where the
    trace holds no device op."""
    devices = trace.get("devices") or {}
    if not devices:
        return None
    spans = sorted((s for s in trace.get("host", ())
                    if s[0] != window_span), key=lambda s: s[1])
    starts = [s[1] for s in spans]
    windows = [s for s in trace.get("host", ()) if s[0] == window_span]
    if windows:
        lo = min(w[1] for w in windows)
        hi = max(w[1] + w[2] for w in windows)
    else:
        lo = min(op[2] for d in devices.values() for op in d["ops"])
        hi = max(op[2] + op[3] for d in devices.values() for op in d["ops"])
    window = hi - lo
    n = len(devices)
    busy_s = idle = coll = exposed = 0.0
    groups, gaps = {}, {}
    step_durs, step_name = [], None
    for dev in devices.values():
        ops = [op for op in dev["ops"]
               if op[2] < hi and op[2] + op[3] > lo]
        busy = union(clip([[op[2], op[2] + op[3]] for op in ops], lo, hi))
        busy_s += total(busy) / 1e9 / n
        idle += (1.0 - total(busy) / window) / n
        own_ns = self_times(ops)
        for op, own in zip(ops, own_ns):
            groups[op[1]] = groups.get(op[1], 0.0) + own / 1e9 / n
        c = union(clip([[op[2], op[2] + op[3]]
                        for op in ops + list(dev.get("async", ()))
                        if op[1] in COLLECTIVES], lo, hi))
        # "other" = self-time-bearing ops that are no collectives and do
        # not merely contain one
        others = union(clip(
            [[op[2], op[2] + op[3]] for op, own in zip(ops, own_ns)
             if op[1] not in COLLECTIVES and own == op[3]], lo, hi))
        coll += total(c) / 1e9 / n
        exposed += total(subtract(c, others)) / 1e9 / n
        modules = union(clip([[m[1], m[1] + m[2]] for m in dev["modules"]],
                             lo, hi))
        for gap in subtract([[lo, hi]], busy):
            inside = total(clip(modules, gap[0], gap[1]))
            if inside * 2 > gap[1] - gap[0]:
                name = "device.between_ops"
            else:
                name = _covering(spans, starts, gap) or host_default
            gaps[name] = gaps.get(name, 0.0) + (gap[1] - gap[0]) / 1e9 / n
        # the step program: the module with the most device time
        per_module = {}
        for m in dev["modules"]:
            if m[1] >= lo and m[1] + m[2] <= hi:
                per_module.setdefault(m[0], []).append(m[2])
        if per_module:
            name = max(per_module, key=lambda k: sum(per_module[k]))
            if step_name in (None, name):
                step_name = name
                step_durs.extend(d / 1e9 for d in per_module[name])
    top = sorted(groups.items(), key=lambda kv: -kv[1])
    kinds = {}
    for name, seconds in top:           # 'fusion:kLoop', 'copy-done', ...
        kind = name.split(" ")[0]
        kinds[kind] = kinds.get(kind, 0.0) + seconds
    return {
        "window_s": window / 1e9, "busy_s": busy_s, "idle_share": idle,
        "devices": n, "groups": dict(top),
        "kinds": dict(sorted(kinds.items(), key=lambda kv: -kv[1])),
        "collective_s": coll, "collective_exposed_s": exposed,
        "idle_gaps": dict(sorted(gaps.items(), key=lambda kv: -kv[1])),
        "step_module": step_name,
        "step_events": len(step_durs) // max(1, n),
        "step_busy_s": sorted(step_durs)[len(step_durs) // 2]
        if step_durs else None,
    }


def read_into(facts, trace_dir, **how):
    """Reduce the newest trace under `trace_dir` (``how`` goes to
    ``reduce``) into a driver's facts: ``trace``, the device's ``busy_s``
    and ``window_s``, ``breakdown``.  Returns the reduction, or None when
    the trace holds no device op (XLA:CPU's never does)."""
    path = find_xplane(trace_dir)
    reduced = reduce(load_xplane(path), **how) if path else None
    if reduced is not None:
        facts["trace"] = reduced
        facts["device"]["busy_s"] = reduced["busy_s"]
        facts["device"]["window_s"] = reduced["window_s"]
        facts["breakdown"] = breakdown(reduced)
    return reduced


def breakdown(reduced, top=10):
    """The ledger's ``breakdown``: most device time, longest idle."""
    return {"device_ops": [[k, v] for k, v in
                           list(reduced["groups"].items())[:top]],
            "idle_gaps": [[k, v] for k, v in
                          list(reduced["idle_gaps"].items())[:top]]}
