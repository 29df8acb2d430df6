"""Device time a step under the scopes a hybrid state-space decoder opens
beside those of ``scope_time.SCOPES`` (which is not edited: the accepted
readers hold it as it is): the Mamba-2 mixer ``ssm`` (a block's attribute
name), its convolution ``ssm/conv`` and its scan ``ssm/scan`` (the
operators' own sub-scopes, ``ops/ssm.py``), and the latent expert layer's
two projections ``moe/latent``.

``read(run)`` opens the run's trace as ``scope_time.read`` does and hands
``scope_time.per_step`` these scopes as `wanted`: the same window, step
events, self times and treatment of mixed fusions.  A second reduction
with the step's scopes narrowed to its ``custom-call`` instructions (as
``attention_kernel_share`` does) gives the part of ``ssm/scan`` that runs
in kernels of the program's own: 0.0 where the scan is a composition.
The result is cached on ``run.facts``; None where the run was not traced,
the program has no scopes or no step event lies in the window.  An entry
no instruction matches reads None: that program has no such scope (a
checkout from before it existed).
"""
import os

from benchmark.harness import program_trace, scope_time, trace_reduce

SCOPES = {
    "ssm": ("ssm",),
    "ssm/conv": ("ssm", "conv"),
    "ssm/scan": ("ssm", "scan"),
    "moe/latent": ("moe", "latent"),
}
KERNELS = "ssm/scan:kernels"


def reduce(trace, scopes):
    """{key: ms a step or None} of SCOPES plus KERNELS, or None."""
    out = scope_time.per_step(trace, scopes, wanted=SCOPES)
    if out is None:
        return None
    calls = {op[0] for dev in trace["devices"].values() for op in dev["ops"]
             if op[1].startswith("custom-call")}
    narrowed = dict(scopes, instructions={
        name: where for name, where in scopes["instructions"].items()
        if name in calls})
    kernels = scope_time.per_step(
        trace, narrowed, wanted={"ssm/scan": SCOPES["ssm/scan"]}) \
        if narrowed["instructions"] else None
    out[KERNELS] = None if out["ssm/scan"] is None \
        else ((kernels or {}).get("ssm/scan") or 0.0)
    return out


def read(run):
    if "scope_time_ssm" in run.facts:
        return run.facts["scope_time_ssm"]
    out = None
    path = trace_reduce.find_xplane(
        os.path.join(run.cache_dir, "trace", run.cell["name"])) \
        if run.trace else None
    if path is not None:
        trace = trace_reduce.load_xplane(
            path, host_prefixes=("mx.", program_trace.WINDOW))
        out = reduce(trace, program_trace._program_scopes())
        if out is not None:
            run.note(scope_time_ssm_ms={k: v for k, v in out.items()
                                        if k != "mixed" and v is not None},
                     scope_time_ssm_mixed_ms=out["mixed"])
    run.facts["scope_time_ssm"] = out
    return out


def ms(run, key):
    """Milliseconds a step under SCOPES[key] (or KERNELS), or None."""
    got = read(run)
    return None if got is None else got.get(key)
