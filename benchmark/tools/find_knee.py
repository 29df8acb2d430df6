"""Find the highest rate a serve cell's replica sustains - once, by hand,
on the chip; never by the driver:

    chiprun -- python3 benchmark/tools/find_knee.py --config resnet50_v1 --traffic predict-steady

(by configuration and traffic mix, so that it also serves a cell that is
not in BENCHMARK.json yet)

One replica, started as the cell starts it.  A doubling-then-bisecting
sweep of `--seconds` (20) second open-loop runs of the cell's own mix; a
rate *holds* when at least 99 % of its requests answer within `--limit-ms`
(100), ``serve.queue_rows`` is no higher after the run than 16, nothing
was refused or failed, and the generator's p99 lateness stays under
`--late-ms` (1).  Prints one JSON line per run and the knee; the traffic
file's ``rate_per_s`` is then set by hand to 0.8 x it, with the table in
PERF.md.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--start", type=float, default=50.0)
    ap.add_argument("--limit-ms", type=float, default=100.0)
    ap.add_argument("--late-ms", type=float, default=1.0)
    ap.add_argument("--bisections", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--root", default=ROOT, help="for a rehearsal")
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root)
    from benchmark import run as bench_run
    from benchmark.harness import loadgen
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = {"name": "%s.%s" % (args.config, args.traffic), "chips": 1,
            "config": args.config, "traffic": args.traffic}
    if os.environ.get("MX_FORCE_CPU") != "1":
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
            root, "benchmark", ".cache", "xla"))
    run = bench_run.Run(root, manifest, cell, args.seed, args.seconds, False)
    driver = run.load("benchmark/drivers/%s.py" % run.traffic["driver"])
    live = {}
    driver.run(run, keep=live)
    print("replica ready in %.1f s, correct=%s" % (
        run.facts["replica_ready_s"], run.correct), flush=True)
    table = []

    def holds(rate, k):
        due, rows = loadgen.schedule(args.seed + k, rate, args.seconds,
                                     run.traffic["rows_mix"])
        before = driver.snapshot(live["control"])
        result = driver.drive(run, live["clients"], live["pools"], due, rows)
        time.sleep(0.5)
        after = driver.snapshot(live["control"])
        counters, hists = driver._diff(after, before)
        lat = sorted(result["latency_s"])
        late = sorted(result["late_s"])
        n = len(lat)
        within = sum(1 for x, ok in zip(result["latency_s"], result["ok"])
                     if ok and x * 1e3 <= args.limit_ms) / n
        occ = hists.get("serve.batch_occupancy", {"sum": 0, "count": 0})
        row = {"rate_per_s": rate, "requests": n,
               "rows_per_s": sum(int(r) for r in rows) / args.seconds,
               "within_limit": within,
               "failed": sum(1 for ok in result["ok"] if not ok),
               "rejected": counters.get("serve.rejected", 0),
               "p50_ms": 1e3 * lat[n // 2], "p99_ms": 1e3 * lat[int(0.99 * n)],
               "p50_ms_by_rows": {
                   str(k): 1e3 * float(np.median(
                       [x for x, r in zip(result["latency_s"], rows)
                        if r == k])) for k in sorted(set(int(r) for r in rows))},
               "late_p99_ms": 1e3 * late[int(0.99 * n)],
               "queue_rows_after": after[0].get("serve.queue_rows", 0),
               "batch_rows_mean": occ["sum"] / max(1, occ["count"])}
        row["holds"] = bool(within >= 0.99 and not row["failed"]
                            and not row["rejected"]
                            and row["queue_rows_after"] <= 16
                            and row["late_p99_ms"] < args.late_ms)
        table.append(row)
        print(json.dumps(row), flush=True)
        time.sleep(1.0)                     # let a backlog drain
        return row["holds"]

    try:
        k, good, bad = 0, None, None
        rate = args.start
        while bad is None and rate < 1e5:
            if holds(rate, k):
                good, rate = rate, rate * 2
            else:
                bad = rate
            k += 1
        for _ in range(args.bisections):
            if good is None or bad is None:
                break
            mid = (good + bad) / 2.0
            if holds(mid, k):
                good = mid
            else:
                bad = mid
            k += 1
        print(json.dumps({"knee_per_s": good, "first_failing": bad,
                          "rate_at_0.8": None if good is None
                          else 0.8 * good}), flush=True)
    finally:
        live["control"].stop()
        for c in live["clients"]:
            c.close()
        try:
            live["replica"].proc.wait(timeout=60)
        finally:
            live["replica"].close()
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "knee-%s.json" % cell["name"]), "w") as f:
        json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
