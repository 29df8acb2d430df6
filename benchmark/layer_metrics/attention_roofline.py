"""Kernels: the attention products' share of their roofline.  The least
time the chips could take for the REQUIRED operations of the attention
of one step - `attention_scores` + `attention_values` of
ops_and_bytes(...)["detail"], every layer, forward once and backward
twice, nothing recomputed, over chips x the bf16 peak as
step_roofline.bound divides (the T x T products are compute-bound once
the scores stay on the chip) - over attention_ms, the device time a step
under the scope `attention_core`.  It counts the same work whatever
implements it, so the jnp composition reads it as the kernels do."""
from benchmark.harness import peaks, program_trace


def least_ms(ops, layers, peak, chips):
    """Least milliseconds of one step's attention products on `chips`."""
    forward = ops["detail"]["per_layer_forward"]
    flops = 3 * layers * (forward["attention_scores"]
                          + forward["attention_values"])
    return 1e3 * flops / (chips * peak["bf16_flops_per_s"])


def read(run):
    f = run.facts
    took = program_trace.scope(run, "attention_ms")
    products = f.get("ops", {}).get("detail", {}).get("per_layer_forward", {})
    if not took or "attention_scores" not in products:
        return None              # not traced, or a model without attention
    least = least_ms(f["ops"], run.config["num_hidden_layers"],
                     peaks.peaks_for(f["device"]["kind"]), run.cell["chips"])
    run.note(attention_least_ms=least, attention_ms=took)
    return 100.0 * least / took
