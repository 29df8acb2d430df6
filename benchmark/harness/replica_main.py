"""The serve cell's replica: ``python -m mxnet_tpu.serve`` unchanged, plus
what only the process that holds the chip can do.

    python3 benchmark/harness/replica_main.py --facts F [--trace-dir D
        --trigger T --trace-seconds S] -- <arguments of mxnet_tpu.serve>

- refuses to start off a TPU (or, under MX_FORCE_CPU=1, off the host):
  ``harness.device.require``;
- writes the device facts to F at start and, after STOP, again with
  ``memory_peak_bytes``;
- when T appears, traces S seconds with jax's profiler into D (the parent
  is a CPU-pinned client and cannot see the chip) and writes ``T.done``.

Goes when the replica has a trace switch of its own (PERF.md, Open
questions).
"""
import argparse
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _write(path, facts):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(facts, f)
    os.replace(tmp, path)


def _trace_on_trigger(trigger, trace_dir, seconds, stop):
    import jax
    from benchmark.harness import profiler
    while not os.path.exists(trigger):
        if stop.wait(0.02):
            return
    profiler.start(trace_dir)
    with jax.profiler.TraceAnnotation("replica.trace_window"):
        time.sleep(seconds)
    profiler.stop()
    _write(trigger + ".done", {"seconds": seconds})


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    split = argv.index("--")
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--facts", required=True)
    ap.add_argument("--trace-dir", default=None)
    ap.add_argument("--trigger", default=None)
    ap.add_argument("--trace-seconds", type=float, default=3.0)
    args = ap.parse_args(argv[:split])
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark.harness import device
    facts = device.require(1)
    _write(args.facts, facts)
    stop = threading.Event()
    tracer = None
    if args.trace_dir:
        tracer = threading.Thread(
            target=_trace_on_trigger, name="replica-trace", daemon=True,
            args=(args.trigger, args.trace_dir, args.trace_seconds, stop))
        tracer.start()
    from mxnet_tpu.serve.__main__ import main as serve_main
    try:
        rc = serve_main(argv[split + 1:])
    finally:
        stop.set()
        if tracer is not None:
            tracer.join(timeout=30)
    facts["memory_peak_bytes"] = device.memory_peak_bytes(1)
    facts["memory_stats"] = device.memory_stats()
    _write(args.facts, facts)
    return rc


if __name__ == "__main__":
    sys.exit(main())
