"""kvstore push/pull bandwidth harness.

Reference: ``tools/bandwidth/measure.py`` — times repeated
``push``+``pull`` of large arrays through a kvstore and reports GB/s per
store type.  Here the interesting axes are the collective stores (one
jitted reduce; ICI on real hardware, host RAM on the fake mesh) and the
dist_async TCP parameter server.

ISSUE 5 adds *wire accounting*: every exchange notes the bytes its payload
occupies in its wire representation (compressed int8/2-bit codes+scales,
bf16 cast, or full width) on ``engine.wire_bytes``; this harness reports
the measured bytes-per-step and — with ``--compare-compress`` — the
reduction factor vs an uncompressed fp32 baseline run in the same process
(the ISSUE 5 acceptance gate: int8 must move >= 3.5x fewer bytes).

ISSUE 16 adds ``--hierarchical``: a self-contained flat-vs-two-tier
comparison of the dist_async CROSS-SLICE leg.  It spawns an in-process
parameter server, then measures the same int8-pushed payload twice —
flat (int8 push + full-width fp32 pull, the one-tier exchange's return
leg) and two-tier (int8 push + PULLQ int8 pull, the promoted
cross-slice leg of the hierarchical exchange) — and asserts the
two-tier run moves fewer wire bytes per step.  Pull-leg bytes come from
the ``kvstore.pull_wire_bytes`` telemetry counter; push-leg bytes stay
on ``engine.wire_bytes`` as before.

Run:  python tools/bandwidth.py [--store local|device|ici] [--mb 64]
      [--iters 10] [--compress 2bit|int8|bf16] [--compare-compress]
      [--hierarchical]
(dist_async needs `tools/launch.py -n W -s 1 -- python tools/bandwidth.py
 --store dist_async`; --hierarchical brings its own server.)
"""
import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _measure(store, compress, mb, iters, key="x"):
    """One timed pushpull loop; returns
    (kv, GiB/s, push wire bytes per step, pull wire bytes per step)."""
    import numpy as np
    from mxnet_tpu import nd, kvstore
    from mxnet_tpu import telemetry
    from mxnet_tpu.engine import engine

    kv = kvstore.create(store)
    if compress:
        params = {"type": compress}
        if compress == "2bit":
            params["threshold"] = 0.5
        kv.set_gradient_compression(params)
    n = int(mb * (1 << 20) / 4)
    payload = nd.array(np.random.RandomState(0).rand(n).astype(np.float32))
    out = nd.zeros((n,))
    kv.init(key, nd.zeros((n,)))
    kv.pushpull(key, payload, out=out)          # warm (compile/connect)
    out.wait_to_read()
    w0 = engine.snapshot()["wire_bytes"]        # one consistent read
    p0 = telemetry.registry.value("kvstore.pull_wire_bytes")
    t0 = time.perf_counter()
    for _ in range(iters):
        kv.pushpull(key, payload, out=out)
    out.wait_to_read()
    dt = time.perf_counter() - t0
    wire_per_step = (engine.snapshot()["wire_bytes"] - w0) / iters
    pull_per_step = (telemetry.registry.value("kvstore.pull_wire_bytes")
                     - p0) / iters
    moved = 2 * mb * iters / 1024.0              # push + pull, GiB
    return kv, round(moved / dt, 3), int(wire_per_step), int(pull_per_step)


def _hierarchical_main(args):
    """--hierarchical (ISSUE 16): flat vs two-tier dist_async exchange,
    self-contained — spawns an in-process parameter server (the
    cross-slice tier), runs the same int8-pushed payload through the
    flat return leg (full-width fp32 pull) and the two-tier one (PULLQ
    int8 pull), and asserts the two-tier run moves fewer cross-slice
    wire bytes per step.  Exits nonzero when it does not."""
    import socket as _socket
    import threading

    os.environ.setdefault("MX_FORCE_CPU", "1")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import mxnet_tpu as mx   # noqa: F401  (backend init)
    from mxnet_tpu.kvstore import server as ps_server

    s = _socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    threading.Thread(target=ps_server.serve_forever,
                     kwargs=dict(port=port, num_workers=1),
                     daemon=True).start()
    addr = "127.0.0.1:%d" % port
    os.environ["MX_PS_ROOT"] = addr
    os.environ["MX_PS_ROOTS"] = addr
    os.environ["DMLC_NUM_SERVER"] = "1"
    os.environ["DMLC_NUM_WORKER"] = "1"
    deadline = time.time() + 10.0
    while time.time() < deadline:
        try:
            _socket.create_connection(("127.0.0.1", port),
                                      timeout=0.2).close()
            break
        except OSError:
            time.sleep(0.05)

    mb = 8.0 if args.mb == 64.0 else args.mb   # a single-server compare
                                               # needs no 64 MB payload
    os.environ["MX_EXCHANGE_HIERARCHICAL"] = "0"
    kv_f, flat_gbps, flat_push, flat_pull = _measure(
        "dist_async", "int8", mb, args.iters, key="h_flat")
    os.environ["MX_EXCHANGE_HIERARCHICAL"] = "1"
    kv_h, hier_gbps, hier_push, hier_pull = _measure(
        "dist_async", "int8", mb, args.iters, key="h_tier")
    kv_h.close()
    kv_f.close()
    flat_total = flat_push + flat_pull
    hier_total = hier_push + hier_pull
    report = {
        "metric": "kvstore_hierarchical_cross_slice_bytes",
        "store": "dist_async", "mb_per_tensor": mb, "iters": args.iters,
        "compression": "int8",
        "flat": {"push_wire_bytes": flat_push,
                 "pull_wire_bytes": flat_pull,
                 "total_wire_bytes": flat_total,
                 "gb_per_sec": flat_gbps},
        "hierarchical": {"push_wire_bytes": hier_push,
                         "pull_wire_bytes": hier_pull,
                         "total_wire_bytes": hier_total,
                         "gb_per_sec": hier_gbps},
        "cross_slice_reduction": round(flat_total / max(1, hier_total), 3),
        "ok": hier_total < flat_total,
    }
    print(json.dumps(report))
    if not report["ok"]:
        print("bandwidth.py: FAIL - hierarchical exchange moved %d "
              "wire bytes/step, flat moved %d (expected fewer)"
              % (hier_total, flat_total), file=sys.stderr)
        return 1
    return 0


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--store", default="local")
    p.add_argument("--mb", type=float, default=64.0)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--compress", default=None)
    p.add_argument("--compare-compress", action="store_true",
                   help="also run an uncompressed fp32 baseline and "
                   "report the measured wire-bytes reduction factor")
    p.add_argument("--hierarchical", action="store_true",
                   help="self-contained flat-vs-two-tier dist_async "
                   "comparison (in-process server); asserts the "
                   "two-tier exchange moves fewer cross-slice wire "
                   "bytes per step than the flat int8 exchange")
    p.add_argument("--cpu", action="store_true",
                   help="pin the CPU backend (no TPU probe)")
    args = p.parse_args()
    if args.cpu:
        os.environ.setdefault("MX_FORCE_CPU", "1")
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if args.hierarchical:
        sys.exit(_hierarchical_main(args))
    import mxnet_tpu as mx   # noqa: F401  (backend init)

    kv, gbps, wire, _pull = _measure(args.store, args.compress, args.mb,
                                     args.iters)
    report = {
        "metric": "kvstore_pushpull_bandwidth_gb_per_sec",
        "value": gbps, "unit": "GiB/s",
        "store": kv.type, "mb_per_tensor": args.mb, "iters": args.iters,
        "compression": args.compress,
        "wire_bytes_per_step": wire,
        "num_workers": kv.num_workers,
    }
    if args.compare_compress:
        # fresh store + key: independent residual state, same payload
        _, base_gbps, base_wire, _bp = _measure(args.store, None, args.mb,
                                                args.iters, key="x_fp32")
        report["fp32_wire_bytes_per_step"] = base_wire
        report["fp32_gb_per_sec"] = base_gbps
        report["wire_reduction_vs_fp32"] = round(
            base_wire / max(1, wire), 3)
    print(json.dumps(report))


if __name__ == "__main__":
    main()
