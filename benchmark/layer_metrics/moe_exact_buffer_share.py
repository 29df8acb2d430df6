"""Kernels: the share of the expert layers' calls in which the held
experts' load passed the short buffer and the exact no-drop buffer ran
(parallel/moe.py:held_expert_ffn), every expert layer together:
`moe_exact_buffer_calls{layer}` over `moe_layer_calls{layer}`, device
buffers the step accumulates and the registry fetches when read - here,
after the window, the warm-up steps included.  0 where every call fitted
twice an even router's share; None where the program has no such
counters (a checkout from before the layer had two sizes)."""


def read(run):
    try:
        from mxnet_tpu import telemetry
    except ImportError:
        return None
    exact = calls = 0.0
    for inst in telemetry.registry.instruments():
        if inst.name == "moe_exact_buffer_calls":
            exact += float(inst.value)
        elif inst.name == "moe_layer_calls":
            calls += float(inst.value)
    return 100.0 * exact / calls if calls else None
