"""Device time a step under the scopes a decoder with window and full
attention in one stack opens beside those of ``scope_time.SCOPES`` (which
is not edited: the accepted readers hold it as it is): a layer's attention
by its kind, ``attention_window`` and ``attention_full`` (a block's
attribute name: projections, rotary, the core, the gate), the core inside
each (``attention_core``, the operator's own scope: the flash kernels and
what XLA puts around them), and ``rotary`` and ``head_gate`` of both kinds
together.

``read(run)`` opens the run's trace as ``scope_time.read`` does and hands
``scope_time.per_step`` these scopes as `wanted`: the same window, step
events, self times and treatment of mixed fusions.  The result is cached
on ``run.facts``; None where the run was not traced, the program has no
scopes or no step event lies in the window.  An entry no instruction
matches reads None: that program has no such scope (a checkout from
before it existed).
"""
import os

from benchmark.harness import program_trace, scope_time, trace_reduce

SCOPES = {
    "attention_window": ("attention_window",),
    "attention_full": ("attention_full",),
    "attention_window/attention_core": ("attention_window",
                                        "attention_core"),
    "attention_full/attention_core": ("attention_full", "attention_core"),
    "rotary": ("rotary",),
    "head_gate": ("head_gate",),
}


def reduce(trace, scopes):
    """{key: ms a step or None} of SCOPES, or None."""
    return scope_time.per_step(trace, scopes, wanted=SCOPES)


def read(run):
    if "scope_time_swa" in run.facts:
        return run.facts["scope_time_swa"]
    out = None
    path = trace_reduce.find_xplane(
        os.path.join(run.cache_dir, "trace", run.cell["name"])) \
        if run.trace else None
    if path is not None:
        trace = trace_reduce.load_xplane(
            path, host_prefixes=("mx.", program_trace.WINDOW))
        out = reduce(trace, program_trace._program_scopes())
        if out is not None:
            run.note(scope_time_swa_ms={k: v for k, v in out.items()
                                        if k != "mixed" and v is not None},
                     scope_time_swa_mixed_ms=out["mixed"])
    run.facts["scope_time_swa"] = out
    return out


def ms(run, key):
    """Milliseconds a step under SCOPES[key], or None."""
    got = read(run)
    return None if got is None else got.get(key)
