"""Device time a step under the named scopes a model's blocks and
operators open inside the step program (``jax.named_scope``: a Gluon
block's attribute name, an operator's own sub-scope).

``read(run)`` opens the run's trace as ``program_trace.read`` does, asks
the program for its scopes and sums, for each entry of ``SCOPES``, the
self time of the step's ops whose ``op_name`` path holds the entry's
components in that order (whole components: ``moe`` matches
``.../blocks/1/moe/route/...`` and not ``.../moe_x/...``), forward,
backward and recomputed alike, per step event, mean over devices - the
window, step events and self times of ``program_trace.reduce``.  As there,
``mixed`` fusions (XLA fuses a weight's gradient product with its
optimizer update) are left out: they are `scope_unattributed`'s, and one
earlier line says how much of each scope they would have added by their
root's path.  One exception to the paths: XLA:TPU expands
``lax.ragged_dot`` into kernels of its own whose ``op_name`` is just
``ragged-dot-none`` / ``ragged-dot-metadata`` - the scope is lost in the
expansion - and only the expert layer's grouped products call it, so an
instruction so named is counted under ``moe`` and ``moe/experts``
(`scope_unattributed`'s reader, which knows no model, counts it as
unattributed; it cannot be told forward from backward, recomputed or
not, or the MTP module's layer from the trunk's).
The result is cached on ``run.facts``; None where the run
was not traced, the program has no scopes, or no step event lies in the
window.  An entry no instruction matches reads None: that program has no
such scope (a checkout from before it existed).
"""
import bisect
import os

from benchmark.harness import program_trace, trace_reduce

SCOPES = {
    "mla": ("mla",),
    "attention_core": ("attention_core",),
    "moe": ("moe",),
    "moe/route": ("moe", "route"),
    "moe/dispatch": ("moe", "dispatch"),
    "moe/experts": ("moe", "experts"),
    "moe/combine": ("moe", "combine"),
    "moe/shared": ("moe", "shared"),
    "mtp": ("mtp",),
    "lm_head": ("lm_head",),
    "recompute": ("rematted_computation",),
}


RAGGED_DOT = "ragged-dot"       # XLA:TPU's own op_name for its expansion
RAGGED_DOT_KEYS = ("moe", "moe/experts")


def under(path, names):
    """Does the op_name `path` hold `names` as whole components, in order?"""
    parts = iter(p for p in path.split("/") if p)
    return all(any(p == name for p in parts) for name in names)


def keys_under(where, wanted):
    """The entries of `wanted` an instruction's time goes to."""
    if where["scope"].startswith(RAGGED_DOT):
        return [k for k in RAGGED_DOT_KEYS if k in wanted]
    return [k for k, names in wanted.items() if under(where["scope"], names)]


def per_step(trace, scopes, wanted=None):
    """{key: ms a step or None} of `wanted` (default SCOPES), plus
    ``"mixed"``: {key: ms} of the mixed fusions whose root lies there."""
    wanted = wanted or SCOPES
    devices = trace.get("devices") or {}
    instructions = (scopes or {}).get("instructions") or {}
    module = (scopes or {}).get("module")
    if not devices or not instructions or module is None:
        return None
    keys_of = {name: keys_under(where, wanted)
               for name, where in instructions.items()}
    seen = {k for ks in keys_of.values() for k in ks}
    lo, hi = program_trace._window(trace)
    sums = {k: 0.0 for k in wanted}
    mixed = {k: 0.0 for k in wanted}
    steps = 0
    for dev in devices.values():
        events = sorted((m for m in dev["modules"]
                         if m[0] == module and m[1] >= lo
                         and m[1] + m[2] <= hi), key=lambda m: m[1])
        starts = [m[1] for m in events]
        steps += len(events)
        ops = [op for op in dev["ops"] if op[2] >= lo and op[2] + op[3] <= hi]
        for op, own in zip(ops, trace_reduce.self_times(ops)):
            i = bisect.bisect_right(starts, op[2]) - 1
            if i < 0 or op[2] >= events[i][1] + events[i][2]:
                continue
            where = instructions.get(op[0])
            if where is None:
                continue
            lost = where["mixed"] or (
                where["top"] is None
                and not where["scope"].startswith(RAGGED_DOT))
            into = mixed if lost else sums
            for k in keys_of[op[0]]:
                into[k] += own / 1e6
    if not steps:
        return None
    out = {k: (v / steps if k in seen else None) for k, v in sums.items()}
    out["mixed"] = {k: v / steps for k, v in mixed.items() if v}
    return out


def read(run):
    if "scope_time" in run.facts:
        return run.facts["scope_time"]
    out = None
    path = trace_reduce.find_xplane(
        os.path.join(run.cache_dir, "trace", run.cell["name"])) \
        if run.trace else None
    if path is not None:
        trace = trace_reduce.load_xplane(
            path, host_prefixes=("mx.", program_trace.WINDOW))
        out = per_step(trace, program_trace._program_scopes())
        if out is not None:
            run.note(scope_time_ms={k: v for k, v in out.items()
                                    if k != "mixed" and v is not None},
                     scope_time_mixed_ms=out["mixed"])
    run.facts["scope_time"] = out
    return out


def ms(run, key):
    """Milliseconds a step under SCOPES[key], or None."""
    got = read(run)
    return None if got is None else got.get(key)
