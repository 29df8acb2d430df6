"""Compile one train cell's step program at its real size for a described
v5e chip, without a chip (on-chip-measurement guide, section 2, third
rehearsal), and print what the TPU compiler says it needs.  Run by hand,
here, before the cell's first chip run; never by the driver:

    python3 benchmark/tools/aot_check.py --workload bert-base-train-s512

The net is built on the host at full width through the same model file
the cell uses; the step's program is lowered from shapes with every
argument placed on ``topo.devices[0]``.  A compile that passes is not a
chip run.  One chip only: a sharded step places its own parameters on the
mesh it is given, which a described device cannot hold.
"""
import argparse
import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["MX_FORCE_CPU"] = "1"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int, default=None,
                    help="try another batch than the traffic file's")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from benchmark.run import Run
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next(c for c in manifest["workloads"]
                if c["name"] == args.workload)
    run = Run(ROOT, manifest, cell, 0, 0, False)
    if args.batch:
        run.traffic["batch"] = args.batch
    model = run.model()
    ctx = mx.tpu(0)
    t0 = time.perf_counter()
    net = model.build(run.config, ctx, 0)
    opt = dict(run.config["optimizer"])
    trainer = gluon.Trainer(net.collect_params(), opt.pop("name"), opt)
    cs = trainer.make_compiled_step(net, model.loss_fn())
    plan = cs._plan()
    if plan is None:
        raise SystemExit("no plan: %s" % cs.fallback_reason)
    batch = run.traffic["batch"]
    rescale, wds, lr_rows, decay_rows = cs._lr_rows(plan, 1, batch)
    fn = cs._build_fn(plan, 1, 1, rescale, wds, decay_rows is not None,
                      None, False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a),
                                           jnp.result_type(a),
                                           sharding=chip), tree)

    (data, label), = model.batches(run.config,
                                   dict(run.traffic, pool=1), 0)
    from mxnet_tpu.ops import random as ops_random
    state = described(cs._gather_state(plan))
    call = state + (described(lr_rows),
                    None if decay_rows is None else described(decay_rows),
                    described(ops_random.next_key()),
                    tuple(described(a) for a in data), described(label))
    print("built in %.1f s; compiling for %s ..."
          % (time.perf_counter() - t0, topo.devices[0].device_kind),
          flush=True)
    t0 = time.perf_counter()
    compiled = fn.lower(*call).compile()
    mem = compiled.memory_analysis()
    gb = 1e9
    out = {"workload": args.workload, "batch": batch,
           "compile_seconds_here": round(time.perf_counter() - t0, 1),
           "argument_gb": mem.argument_size_in_bytes / gb,
           "output_gb": mem.output_size_in_bytes / gb,
           "alias_gb": mem.alias_size_in_bytes / gb,
           "temp_gb": mem.temp_size_in_bytes / gb,
           "arguments_plus_temp_gb": (mem.argument_size_in_bytes
                                      + mem.temp_size_in_bytes) / gb,
           "tpu_custom_calls": compiled.as_text().count("tpu_custom_call")}
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
