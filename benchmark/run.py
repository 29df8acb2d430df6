"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process per run: load, warm this cell's shapes, measure for
``--seconds``, print earlier lines freely and as the LAST line of standard
output one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, traced, ``breakdown``.

This file knows no model, no traffic and no metric.  It finds them by the
names in ``BENCHMARK.json``:

    cell.config   -> configs[].file                      (sizes; names its model file)
    cell.traffic  -> benchmark/traffic/<traffic>.json    (parameters; names its driver)
    driver        -> benchmark/drivers/<driver>.py       run(run) -> fills run.facts
    metric        -> benchmark/end_to_end/<name>.py or
                     benchmark/layer_metrics/<name>.py   read(run) -> number or None
                     (an entry named <name>.<variant> is read by <name>.py)

so a later PR adds files and manifest entries and edits nothing here.
``--root`` (default: the checkout this file lies in) is where all of that
is looked up; the tests point it at a temporary copy.
"""
import argparse
import importlib.util
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()          # set-up is counted from here
HERE = os.path.dirname(os.path.abspath(__file__))


class Run:
    """What one run knows: the cell, its files, and the facts its driver
    gathers for the metric readers."""

    def __init__(self, root, manifest, cell, seed, seconds, trace):
        self.root = root
        self.manifest = manifest
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.t_process = T_PROCESS
        entry = next(c for c in manifest["configs"]
                     if c["name"] == cell["config"])
        self.config = self.read_json(entry["file"])
        self.traffic = self.read_json(
            "benchmark/traffic/%s.json" % cell["traffic"])
        self.cache_dir = os.path.join(root, "benchmark", ".cache")
        self.facts = {}          # filled by the driver, read by the readers
        self.correct = True
        self.reasons = []        # why `correct` is false

    def path(self, relative):
        return os.path.join(self.root, relative)

    def read_json(self, relative):
        with open(self.path(relative)) as f:
            return json.load(f)

    def load(self, relative):
        """Import the file at `relative` (under the root) as a module."""
        path = self.path(relative)
        if not os.path.isfile(path):
            raise FileNotFoundError(path)
        name = "_bench_%s" % relative.replace("/", "_").replace(".py", "") \
            .replace("-", "_")
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def model(self):
        return self.load(self.config["model_file"])

    def wrong(self, reason):
        self.correct = False
        self.reasons.append(reason)
        print("benchmark: NOT CORRECT: %s" % reason, flush=True)

    def note(self, **facts):
        """One earlier line of output, for a reader's eye."""
        print("benchmark: " + json.dumps(facts, sort_keys=True), flush=True)


def metrics_of(run, kind):
    """{name: {"value", "unit"}} of the cell's metrics of `kind`
    ("end_to_end" or "per_layer"), each from its own reader."""
    directory = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}
    out = {}
    for m in run.manifest[kind]:
        cells = m.get("workloads")
        if cells is not None and run.cell["name"] not in cells:
            continue
        # "<reader>.<variant>": one reader may serve several entries, one
        # for each end-to-end metric it moves
        reader = run.load("benchmark/%s/%s.py"
                          % (directory[kind], m["name"].split(".")[0]))
        value = reader.read(run)
        if value is None:
            continue             # nothing to read: left out of the line
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--root", default=os.path.dirname(HERE))
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {c["name"]: c for c in manifest["workloads"]}
    if args.workload not in cells:
        raise SystemExit("benchmark: no cell %r in BENCHMARK.json (has %s)"
                         % (args.workload, ", ".join(sorted(cells))))
    seconds = args.seconds if args.seconds is not None \
        else manifest["run_seconds"]
    # the harness package this file belongs to, and the program under test
    for p in (os.path.dirname(HERE), root):
        if p not in sys.path:
            sys.path.insert(0, p)
    run = Run(root, manifest, cells[args.workload], args.seed, seconds,
              bool(args.trace))
    rehearsal = os.environ.get("MX_FORCE_CPU") == "1"
    if bool(run.config.get("rehearsal")) != rehearsal:
        raise SystemExit(
            "benchmark: configuration %r is %s, and MX_FORCE_CPU=1 is %s: "
            "a real configuration runs on the chip only, a rehearsal one "
            "on the host only" % (run.cell["config"],
                                  "a rehearsal" if run.config.get("rehearsal")
                                  else "real", "set" if rehearsal else "not set"))
    if not rehearsal:
        # jax's persistent cache: where the machine says, else at a fixed
        # path inside the checkout (the path is part of the cache's key)
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              os.path.join(run.cache_dir, "xla"))
    driver = run.load("benchmark/drivers/%s.py" % run.traffic["driver"])
    driver.run(run)
    kind = "per_layer" if run.trace else "end_to_end"
    line = {"correct": bool(run.correct),
            "attempted": int(run.facts["attempted"]),
            "failed": int(run.facts["failed"]),
            "metrics": metrics_of(run, kind),
            "device": run.facts["device"]}
    if run.trace and run.facts.get("breakdown"):
        line["breakdown"] = run.facts["breakdown"]
    if not run.correct:
        line["reasons"] = run.reasons
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
