"""Measure a cell the way the driver does, by hand, on the chip: sets of
runs of the one command, each run a new process with another ``--seed``,
then for every end-to-end metric each set's median and spread (distance
between the quartiles over the median) - what the bounds of
BENCHMARK.json are set from.  This process never touches jax.

    chiprun -- python3 benchmark/tools/measure.py --workload <cell> [--sets 2 --runs 6] [--traced 1]

Every run's last line is appended to chiprun_out/measure-<cell>.jsonl.
"""
import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import stats  # noqa: E402


def one(workload, seed, seconds, trace, log):
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", workload, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True)
    took = time.perf_counter() - t0
    lines = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not lines:
        print(proc.stderr[-3000:], flush=True)
        raise SystemExit("run.py exited %d" % proc.returncode)
    line = json.loads(lines[-1])
    line.update(seed=seed, trace=trace, process_s=took)
    log.write(json.dumps(line) + "\n")
    log.flush()
    for earlier in lines[:-1]:
        if trace or "NOT CORRECT" in earlier:
            print(earlier[:3000], flush=True)
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--traced", type=int, default=0,
                    help="traced runs after the sets")
    ap.add_argument("--seed", type=int, default=100)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = json.load(f)["run_seconds"]
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    table = {}
    with open(os.path.join(out, "measure-%s.jsonl" % args.workload),
              "a") as log:
        for s in range(args.sets):
            values = {}
            for r in range(args.runs):
                line = one(args.workload, args.seed + 100 * s + r, seconds,
                           0, log)
                print(json.dumps({k: line[k] for k in (
                    "seed", "correct", "attempted", "failed", "process_s")}
                    | {k: v["value"] for k, v in line["metrics"].items()}),
                    flush=True)
                for name, m in line["metrics"].items():
                    values.setdefault(name, []).append(m["value"])
            for name, vs in values.items():
                # the first run of a checkout compiles: set-up apart
                kept = vs[1:] if name == "setup_s" and s == 0 else vs
                table.setdefault(name, []).append(
                    {"set": s, "median": stats.median(kept),
                     "spread": stats.spread(kept) if len(kept) > 2 else None,
                     "min": min(kept), "max": max(kept),
                     "first": vs[0]})
        for t in range(args.traced):
            line = one(args.workload, args.seed + 1000 + t, seconds, 1, log)
            print(json.dumps(line)[:6000], flush=True)
    print(json.dumps({"workload": args.workload, "summary": table},
                     indent=1), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
