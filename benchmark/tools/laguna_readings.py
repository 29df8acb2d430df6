"""Readings that place the limits of the Laguna-XS.2 cell's check, taken on
the chip at the published widths, by hand, outside the timed path:

    chiprun --chips 1 -- python3 benchmark/tools/laguna_readings.py --seed 7

The net against the float32 reference at the timed sizes, as the driver's
check compares them (the reference follows the net's router choices):
error over the logit scale, share of choices that differ, worst gap.  Then
the reference MADE WRONG in the ways the limits must catch, each held to
the float32 reference the same way:

(a) float8_e4m3 operands - the nearest precision below the
    configuration's bfloat16: has to read above `check_tolerance`;
(b) no selection correction in the router: above `check_routing_gap`;
(c) the band left out of the sliding layers (plain causal): above
    `check_tolerance`;
(d) YaRN left out of the full layers (plain theta-500000 rotary): above
    `check_tolerance`.

Last, loss and gradient norms by parameter kind of the net (through the
tape, blocks recomputed) against ``jax.grad`` of `reference_loss`, on
`--grad-rows` x `--grad-seq` ids.  One JSON line each.  Nothing here is
read by run.py.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CONFIG = "benchmark/configs/laguna_xs_2.json"
TRAFFIC = "benchmark/traffic/clm-s8192-b1-ep8.json"


def _kind(name):
    return ".".join(p for p in name.split(".") if not p.isdigit())


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--grad-rows", type=int, default=1)
    ap.add_argument("--grad-seq", type=int, default=1024)
    args = ap.parse_args(argv)
    import numpy as np
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, nd
    from benchmark.run import Run
    with open(os.path.join(ROOT, CONFIG)) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, TRAFFIC)) as f:
        traffic = json.load(f)
    run = Run.__new__(Run)
    run.root = ROOT
    model = run.load(config["model_file"])
    ctx = mx.tpu(0)
    net = model.build(config, ctx, args.seed)
    params = {n: p.data()._jax for n, p in net.collect_params().items()}

    def say(**facts):
        print(json.dumps(facts, sort_keys=True), flush=True)

    inputs = model.check_inputs(config, traffic, args.seed)
    got = model.logits(net, inputs, ctx)
    forward = jax.jit(lambda ps, xs: model._forward(ps, xs, config))

    def held_to_reference(logits, routing):
        """How the driver's check would read `logits` made with `routing`."""
        want, _, differs, gap = forward(params, (inputs[0], routing))
        return {"err": float(jnp.abs(logits - want).max()
                             / jnp.abs(want).max()),
                "logit_scale": float(jnp.abs(want).max()),
                "choices_differ_share": float(differs.mean()),
                "worst_gap": float(gap.max())}

    say(reading="net", **held_to_reference(got, inputs[1]))
    del got
    for name, operand, without in (
            ("a_reference_float8_e4m3", jnp.float8_e4m3fn, ()),
            ("b_reference_without_router_correction", None, ("correction",)),
            ("c_reference_without_band", None, ("band",)),
            ("d_reference_without_yarn", None, ("yarn",))):
        wrong = jax.jit(lambda ps, xs, operand=operand, without=without:
                        model._forward(ps, xs, config, operand, without))(
            params, inputs[:1])
        say(reading=name, **held_to_reference(wrong[0], wrong[1]))
        del wrong

    rng = np.random.RandomState(args.seed + 2)
    ids = rng.randint(0, config["vocab_size"],
                      (args.grad_rows, args.grad_seq)).astype(np.int32)
    x = nd.array(ids, ctx=ctx, dtype="int32")
    with autograd.record():
        outs = net(x)
        loss = model.loss_fn()(outs[0], x).mean()
    loss.backward()
    routing = np.asarray(outs[1]._jax)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda ps, xs: model.reference_loss(ps, xs, config)))(
        params, (ids, routing))
    kinds = {}
    for name, p in net.collect_params().items():
        if p.grad_req == "null":
            continue
        g = np.asarray(p.grad()._jax, np.float32)
        w = np.asarray(want[name], np.float32)
        k = kinds.setdefault(_kind(name), {"norm": 0.0, "err": 0.0})
        rel = float(np.linalg.norm(g - w) / (np.linalg.norm(w) + 1e-30))
        if rel >= k["err"]:
            k.update(norm=float(np.linalg.norm(w)), err=rel)
    say(reading="gradients", rows=args.grad_rows, seq=args.grad_seq,
        loss_net=float(np.asarray(loss._jax, np.float32)),
        loss_reference=float(want_loss),
        worst=max(kinds, key=lambda k: kinds[k]["err"]),
        worst_err=max(k["err"] for k in kinds.values()), by_kind=kinds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
