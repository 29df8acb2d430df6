"""Per-operator benchmark harness.

Reference: benchmark/opperf/opperf.py (run_op_benchmarks — per-op fwd/bwd
latency over standard shapes) and benchmark/python/ffi/benchmark_ffi.py
(per-call eager-dispatch overhead, SURVEY hard part 2).

Reuses the test battery's per-op input specs (tests/test_operator.py
SPECS) so every benchmarked op runs on the same shapes its correctness
test pins.  Two numbers per op:
  * ``eager_us``  — wall time through the FULL eager dispatch path
    (NDArray wrap, registry lookup, per-op jit cache) — the FFI-overhead
    benchmark's role;
  * ``fwd_us``    — wall time of the cached XLA executable alone.
Plus ``dispatch_overhead_us`` = eager - fwd aggregated at the end.

Usage:  python tools/opperf.py [--ops op1,op2] [--runs 50] [-o out.json]
"""
import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))


def bench_op(opname, spec, runs):
    import jax
    from mxnet_tpu import nd
    from mxnet_tpu.ndarray.ndarray import invoke
    from mxnet_tpu.ops import registry

    np_inputs = spec.inputs()
    nd_inputs = [nd.array(x) for x in np_inputs]
    op = registry.get_op(opname)

    def once():
        return invoke(opname, *nd_inputs, **spec.params)

    def sync(res):
        outs = res if isinstance(res, (list, tuple)) else [res]
        for o in outs:
            if hasattr(o, "_jax"):
                jax.block_until_ready(o._jax)

    try:
        sync(once())  # compile + warm
        sync(once())
    except Exception as e:  # keep the sweep going: record the failure
        return {"op": opname, "error": "%s: %s" % (type(e).__name__, e)}

    t0 = time.perf_counter()
    for _ in range(runs):
        res = once()
    sync(res)
    eager_us = (time.perf_counter() - t0) / runs * 1e6

    rec = {"op": opname, "eager_us": round(eager_us, 2),
           "shapes": [list(x.shape) for x in np_inputs]}
    if not op.no_jit and not op.needs_rng:
        # time the cached executable alone (no dispatch wrapping)
        from mxnet_tpu.ops.registry import cached_jit
        fn = cached_jit(op.name, spec.params)
        jax_in = [x._jax for x in nd_inputs]
        jax.block_until_ready(jax.tree_util.tree_leaves(fn(*jax_in)))
        t0 = time.perf_counter()
        for _ in range(runs):
            out = fn(*jax_in)
        jax.block_until_ready(jax.tree_util.tree_leaves(out))
        fwd_us = (time.perf_counter() - t0) / runs * 1e6
        rec["fwd_us"] = round(fwd_us, 2)
        rec["dispatch_overhead_us"] = round(eager_us - fwd_us, 2)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", default=None,
                    help="comma-separated op subset (default: all specs)")
    ap.add_argument("--runs", type=int, default=50)
    ap.add_argument("-o", "--output", default=None)
    args = ap.parse_args()

    import jax
    import test_operator as batt  # tests/ on sys.path

    ops = sorted(batt.SPECS)
    if args.ops:
        ops = [o for o in args.ops.split(",") if o in batt.SPECS]
    results = []
    for opname in ops:
        rec = bench_op(opname, batt.SPECS[opname], args.runs)
        results.append(rec)
        sys.stderr.write("%-40s %s\n" % (
            opname, rec.get("eager_us", rec.get("error"))))
    ok = [r for r in results if "eager_us" in r]
    overhead = [r["dispatch_overhead_us"] for r in ok
                if "dispatch_overhead_us" in r]
    summary = {
        "device": jax.default_backend(),
        "num_ops": len(ok),
        "num_errors": len(results) - len(ok),
        "median_eager_us": round(sorted(
            r["eager_us"] for r in ok)[len(ok) // 2], 2) if ok else None,
        "median_dispatch_overhead_us": round(sorted(overhead)[
            len(overhead) // 2], 2) if overhead else None,
        "results": results,
    }
    out = json.dumps(summary)
    if args.output:
        with open(args.output, "w") as f:
            f.write(out)
    # one-line summary on stdout (driver-friendly), full payload in -o
    print(json.dumps({k: v for k, v in summary.items() if k != "results"}))


if __name__ == "__main__":
    main()
