"""Readings that place the limits of the Nemotron-3-Super cell's check,
taken on the chip at the published widths, by hand, outside the timed
path:

    chiprun --chips 1 -- python3 benchmark/tools/nemotron_readings.py --seed 7

1. the net against the float32 reference at the timed sizes, as the
   driver's check compares them (the reference follows the net's router
   choices): error over the logit scale, share of choices that differ,
   worst gap;
2. the reference with float8_e4m3 operands - the nearest precision below
   the configuration's bfloat16 - held to the float32 reference the same
   way: it has to read above `check_tolerance`;
3. the reference without the router's selection correction, the same way:
   it has to read above `check_routing_gap`;
4. the reference with the scan's state kept in bfloat16 between positions
   (everything else float32), the same way;
5. loss and gradient norms by parameter kind of the net (through the tape,
   layers recomputed) against ``jax.grad`` of `reference_loss`, on
   `--grad-rows` x `--grad-seq` ids.

One JSON line each.  Nothing here is read by run.py.  Readings 1-3 and 5
are `glm_readings.py`'s, whose code this file shares through its
``--config`` and ``--traffic``; reading 4 is this model's own.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CONFIG = "benchmark/configs/nemotron_3_super.json"
TRAFFIC = "benchmark/traffic/clm-s8192-b1.json"


def scan_state_reading(seed):
    """Reading 4, one JSON line."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from benchmark.run import Run
    with open(os.path.join(ROOT, CONFIG)) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, TRAFFIC)) as f:
        traffic = json.load(f)
    run = Run.__new__(Run)
    run.root = ROOT
    model = run.load(config["model_file"])
    net = model.build(config, mx.tpu(0), seed)
    params = {n: p.data()._jax for n, p in net.collect_params().items()}
    ids = model.check_inputs(config, traffic, seed)[:1]
    want = jax.jit(lambda ps, xs: model._forward(ps, xs, config))(params, ids)
    low = jax.jit(lambda ps, xs: model._forward(
        ps, xs, config, state=jnp.bfloat16))(params, (ids[0], want[2]))
    got, ref = jnp.stack(low[:2]), jnp.stack(want[:2])
    print(json.dumps({
        "reading": "reference_with_bfloat16_scan_state",
        "err": float(jnp.abs(got - ref).max() / jnp.abs(ref).max()),
        "choices_differ_share": float(low[3].mean()),
        "worst_gap": float(low[4].max())}, sort_keys=True), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--grad-rows", type=int, default=1)
    ap.add_argument("--grad-seq", type=int, default=1024)
    args = ap.parse_args(argv)
    scan_state_reading(args.seed)
    from benchmark.tools import glm_readings
    return glm_readings.main([
        "--seed", str(args.seed), "--grad-rows", str(args.grad_rows),
        "--grad-seq", str(args.grad_seq), "--config", CONFIG,
        "--traffic", TRAFFIC])


if __name__ == "__main__":
    sys.exit(main())
