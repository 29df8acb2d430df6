"""Kernels: what the block size costs a banded call.  The (query, key)
pairs in the blocks the windowed flash kernels visit over the pairs inside
the band, forward: `attention_pairs_visited{kind=window}` over
`attention_pairs_needed{kind=window}`, counters the attention operator
adds to when a causal kernel call is traced (ops/attention.py).  1.0 is a
kernel that touches the band alone; 512-row blocks on a 512-key band
visit two blocks a query block.  None where the program has no such
counters (a checkout from before the kernels took a window) or traced no
windowed call."""


def read(run):
    try:
        from mxnet_tpu import telemetry
    except ImportError:
        return None
    kind = {"kind": "window"}
    needed = telemetry.registry.value("attention_pairs_needed", kind)
    visited = telemetry.registry.value("attention_pairs_visited", kind)
    if not needed:
        return None
    run.note(attention_pairs={
        k: {"needed": telemetry.registry.value("attention_pairs_needed",
                                               {"kind": k}),
            "visited": telemetry.registry.value("attention_pairs_visited",
                                                {"kind": k})}
        for k in ("window", "full")})
    return visited / needed
