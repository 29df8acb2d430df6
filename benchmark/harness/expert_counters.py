"""The expert layers' routing counters, read from the program's telemetry
registry: `moe_assignments{layer, expert}` and
`moe_assignments_elsewhere{layer}` are device buffers the step program
accumulates (gluon.nn.TokenChoiceMoE) and the registry fetches only when
an instrument's value is read - here, after the window.  They count since
the first train step, the two warm-up steps included."""


def assignments():
    """({layer: {expert: count}}, {layer: count routed elsewhere}); both
    empty where the program has no such counters."""
    try:
        from mxnet_tpu import telemetry
    except ImportError:
        return {}, {}
    here, away = {}, {}
    for inst in telemetry.registry.instruments():
        if inst.name == "moe_assignments":
            here.setdefault(inst.labels["layer"], {})[
                inst.labels["expert"]] = float(inst.value)
        elif inst.name == "moe_assignments_elsewhere":
            away[inst.labels["layer"]] = float(inst.value)
    return here, away
