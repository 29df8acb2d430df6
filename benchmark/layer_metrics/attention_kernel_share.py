"""Kernels: how much of attention_ms runs in kernels of the program's own
(Pallas: `custom-call` ops) - the counter that says the flash path
engages.  Device time a step of the custom-call ops whose `op_name` path
holds `attention_core`, over attention_ms; the rest is what XLA puts
around the calls (layout copies, the backward's delta).  0 where the
composition runs.

The run's trace is opened as program_trace.read does and reduced a second
time with the step's scopes narrowed to its custom-call instructions
(the trace's op rows carry the group `custom-call:<target>`): that
reduction's attention_ms is the kernels' time, by the same window, step
events and self times as the whole."""
import os

from benchmark.harness import program_trace, trace_reduce


def kernels_ms(trace, scopes):
    """attention_ms of `trace` counting custom-call ops only; 0.0 where
    the step holds none under a scope, None where nothing can be read."""
    if not scopes or not trace.get("devices"):
        return None
    calls = {op[0] for dev in trace["devices"].values() for op in dev["ops"]
             if op[1].startswith("custom-call")}
    narrowed = dict(scopes, instructions={
        name: where for name, where in scopes["instructions"].items()
        if name in calls})
    got = program_trace.reduce(trace, narrowed)
    if got["scopes"] is None:       # no custom call under `forward`
        return 0.0 if got["steps"] else None
    return got["scopes"]["attention_ms"]


def read(run):
    whole = program_trace.scope(run, "attention_ms")
    if not whole:
        return None
    path = trace_reduce.find_xplane(
        os.path.join(run.cache_dir, "trace", run.cell["name"]))
    if path is None:
        return None
    trace = trace_reduce.load_xplane(
        path, host_prefixes=("mx.", program_trace.WINDOW))
    kernels = kernels_ms(trace, program_trace._program_scopes())
    if kernels is None:
        return None
    run.note(attention_kernels_ms=kernels, attention_ms=whole)
    return 100.0 * kernels / whole
