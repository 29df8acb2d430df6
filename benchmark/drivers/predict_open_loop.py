"""Traffic of the kind ``predict_open_loop``: Poisson PREDICT requests at a
fixed rate against one real ``python -m mxnet_tpu.serve`` replica.

This process is the CLIENT and pins itself to the CPU before it imports
jax: a parent that touched the chip would take it from the replica.  The
replica child (``harness/replica_main.py``) holds the chip, says what
device it found and, traced, records the profile.  Set-up is everything
before the first scheduled request: imports, the exported artifact and the
reference answers (kept under ``benchmark/.cache/`` by configuration and
seed), the request pool, the replica's start and bucket warm-up, the
correctness check, one warm request on every connection.
"""
import json
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np

from benchmark.harness import loadgen, trace_reduce

PHASES = ("queue_wait", "pad", "serve_dispatch", "scatter")
CHECK_ROWS = (1, 2, 4, 8, 16, 1, 1, 1)     # 8 answers, every bucket once


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Replica:
    """The replica child; ``close`` stops it whatever happened."""

    def __init__(self, run, prefix, server_env, trace_dir=None,
                 ready_timeout=1000):
        work = os.path.join(run.cache_dir, "replica", run.cell["name"])
        os.makedirs(work, exist_ok=True)
        self.port = _free_port()
        self.facts_file = os.path.join(work, "device.json")
        self.trigger = os.path.join(work, "trace.trigger")
        ready = os.path.join(work, "ready")
        for stale in (self.facts_file, self.trigger, self.trigger + ".done",
                      ready):
            if os.path.exists(stale):
                os.remove(stale)
        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        cmd = [sys.executable,
               os.path.join(here, "harness", "replica_main.py"),
               "--facts", self.facts_file]
        if trace_dir:
            cmd += ["--trace-dir", trace_dir, "--trigger", self.trigger,
                    "--trace-seconds", str(run.traffic["trace_seconds"])]
        cmd += ["--", "--model", prefix, "--example-shape",
                run.model().example_shape(run.config), "--port",
                str(self.port), "--ready-file", ready]
        import mxnet_tpu                   # the program under test: there
        program = os.path.dirname(os.path.dirname(mxnet_tpu.__file__))
        server_env = dict(server_env, PYTHONPATH=program + os.pathsep
                          + server_env.get("PYTHONPATH", ""))
        t0 = time.perf_counter()
        self.proc = subprocess.Popen(cmd, env=server_env, cwd=run.root,
                                     stdout=sys.stderr)
        while not os.path.exists(ready):
            if self.proc.poll() is not None:
                raise SystemExit("benchmark: the replica exited %d before "
                                 "it was ready" % self.proc.returncode)
            if time.perf_counter() - t0 > ready_timeout:
                self.close()
                raise SystemExit("benchmark: the replica was not ready in "
                                 "%d s" % ready_timeout)
            time.sleep(0.05)
        self.ready_s = time.perf_counter() - t0
        self.addr = "127.0.0.1:%d" % self.port

    def device_facts(self):
        with open(self.facts_file) as f:
            return json.load(f)

    def close(self):
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()


def snapshot(client):
    """The replica's registry over the METRICS verb:
    ({counter name: value}, {histogram name or phase: {sum, count}}).
    The serve instruments exist once without labels (the replica's total)
    and once per model: only the totals are read.  Phases are told apart
    by their label; compile seconds are summed over the programs."""
    snap = json.loads(client.metrics(fmt="json"))
    counters, hists = {}, {}
    for entry in snap.values():
        name, labels = entry["name"], entry.get("labels")
        amount = entry["sum"] if entry.get("type") == "histogram" \
            else entry.get("value")
        if name == "program_compile_seconds":
            counters[name] = counters.get(name, 0.0) + amount
        elif name == "step_phase_seconds":
            hists["phase:" + labels["phase"].replace("phase.", "")] = {
                "sum": entry["sum"], "count": entry["count"]}
        elif labels:
            continue
        elif entry.get("type") == "histogram":
            hists[name] = {"sum": entry["sum"], "count": entry["count"]}
        elif isinstance(amount, (int, float)):
            counters[name] = amount
    return counters, hists


def _diff(after, before):
    counters = {k: v - before[0].get(k, 0) for k, v in after[0].items()}
    hists = {k: {"sum": h["sum"] - before[1].get(k, {}).get("sum", 0.0),
                 "count": h["count"] - before[1].get(k, {}).get("count", 0)}
             for k, h in after[1].items()}
    return counters, hists


def artifact(run, model):
    """(prefix, check requests, reference answers): exported and computed
    once per configuration and seed, then read from benchmark/.cache/."""
    import jax
    import mxnet_tpu as mx
    where = os.path.join(run.cache_dir, "artifacts", "%s-seed%d"
                         % (run.cell["config"], run.seed))
    prefix = os.path.join(where, "model")
    answers = os.path.join(where, "reference.npy")
    checks = [model.requests(run.config, rows, 1, run.seed + 7 + i)[0]
              for i, rows in enumerate(CHECK_ROWS)]
    if not os.path.exists(answers):
        os.makedirs(where, exist_ok=True)
        model.export(run.config, run.seed, prefix)
        loaded = mx.nd.load(prefix + "-0000.params")
        params = {k.split(":", 1)[-1]: v.asnumpy() for k, v in loaded.items()}
        want = jax.jit(lambda ps, x: model.reference(
            ps, (x,), run.config))(params, np.concatenate(checks))
        tmp = answers + ".tmp.npy"
        np.save(tmp, np.asarray(want))
        os.replace(tmp, answers)
    return prefix, checks, np.load(answers)


def _check_answers(run, client, checks, want):
    got = np.concatenate([client.predict([x])[1][0] for x in checks])
    tol = run.config["serve_tolerance"]
    if got.shape != want.shape or not np.isfinite(got).all():
        run.wrong("answers of shape %r, finite=%s" % (
            got.shape, bool(np.isfinite(got).all())))
        return
    scale = float(np.abs(want).max())
    err = float(np.abs(got - want).max()) / scale
    top1 = float((got.argmax(1) == want.argmax(1)).mean())
    run.facts["reference_err"] = err
    run.note(reference_err=err, tolerance=tol, top1_agreement=top1,
             logit_scale=scale)
    if not err <= tol or top1 < 1.0:
        run.wrong("answers differ from the plain reference: %g of the "
                  "logit scale > %g, top-1 agreement %g" % (err, tol, top1))


def run(run, rate=None, keep=None):
    """`rate` overrides the traffic file's (tools/find_knee.py); `keep`,
    a dict, receives the live replica and clients instead of stopping
    them (the same tool)."""
    server_env = dict(os.environ)
    if os.environ.get("MX_FORCE_CPU") != "1":
        os.environ["JAX_PLATFORMS"] = "cpu"        # the client's pin
        os.environ["MX_FORCE_CPU"] = "1"
    from mxnet_tpu.serve.client import ServeClient
    facts, traffic = run.facts, run.traffic
    model = run.model()
    t0 = time.perf_counter()
    prefix, checks, want = artifact(run, model)
    facts["build_s"] = time.perf_counter() - t0
    pools = {int(k): model.requests(run.config, int(k),
                                    traffic["pool_per_rows"], run.seed)
             for k in traffic["rows_mix"]}
    trace_dir = os.path.join(run.cache_dir, "trace", run.cell["name"]) \
        if run.trace else None
    replica = Replica(run, prefix, server_env, trace_dir)
    clients = []
    try:
        facts["device"] = replica.device_facts()
        control = ServeClient([replica.addr], timeout=120)
        health = control.health()
        if health.get("param_platform") != facts["device"]["platform"]:
            run.wrong("replica parameters on %r"
                      % health.get("param_platform"))
        _check_answers(run, control, checks, want)
        clients = [ServeClient([replica.addr],
                               timeout=traffic["client_timeout_s"])
                   for _ in range(traffic["connections"])]
        for c in clients:
            c.predict([pools[min(pools)][0]])
        before = snapshot(control)
        facts["cache_hits"] = before[0].get("compile_cache.xla_hits", 0)
        facts["compile_s"] = before[0].get("program_compile_seconds", 0.0)
        facts["replica_ready_s"] = replica.ready_s
        if keep is not None:
            keep.update(replica=replica, clients=clients, control=control,
                        pools=pools)
            return

        rate = rate or traffic["rate_per_s"]
        due, rows = loadgen.schedule(run.seed, rate, run.seconds,
                                     traffic["rows_mix"])
        facts["setup_s"] = time.perf_counter() - run.t_process
        result = drive(run, clients, pools, due, rows, replica)
        after = snapshot(control)
        health_after = control.health()
        control.stop()
        control.close()
        try:
            rc = replica.proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            rc = None
        if rc != 0:
            run.wrong("the replica exited %r after STOP" % rc)
        facts["device"] = replica.device_facts()
        run.note(memory_stats=facts["device"].pop("memory_stats", None))
    finally:
        if keep is None or "replica" not in keep:
            for c in clients:
                c.close()
            replica.close()
    facts["memory_peak_bytes"] = facts["device"].get("memory_peak_bytes", 0)
    facts["compiles_in_window"] = \
        health_after["retraces"] - health["retraces"]
    if facts["compiles_in_window"]:
        run.wrong("%d retraces while serving" % facts["compiles_in_window"])
    fold(run, result, _diff(after, before))
    if run.trace:
        reduced = trace_reduce.read_into(
            facts, trace_dir, window_span="replica.trace_window",
            host_default="replica.host")
        if reduced is not None:
            run.note(trace={k: reduced[k] for k in (
                "window_s", "busy_s", "idle_share")})
        elif facts["device"]["platform"] == "tpu":
            run.wrong("the traced window holds no device operation")


def drive(run, clients, pools, due, rows, replica=None):
    """Send the schedule; traced, pull the replica's trigger
    `trace_seconds` before the window ends."""
    turn = {k: 0 for k in pools}
    picks = []
    for r in rows:                      # which payload each request sends
        picks.append((int(r), turn[int(r)] % len(pools[int(r)])))
        turn[int(r)] += 1
    local = threading.local()
    free = list(clients)
    lock = threading.Lock()

    def send(i):
        if not hasattr(local, "client"):
            with lock:
                local.client = free.pop()
        r, k = picks[i]
        out = local.client.predict([pools[r][k]])[1][0]
        if out.shape[0] != r or not np.isfinite(out).all():
            raise ValueError("answer of shape %r to %d rows" % (out.shape, r))

    timer = None
    if run.trace and replica is not None:
        timer = threading.Timer(
            max(0.0, run.seconds - run.traffic["trace_seconds"]),
            lambda: open(replica.trigger, "w").close())
        timer.start()
    result = loadgen.drive(due, send, len(clients),
                           run.traffic["client_timeout_s"])
    if timer is not None:
        timer.join()
        t_wait = time.perf_counter()
        while not os.path.exists(replica.trigger + ".done") \
                and time.perf_counter() - t_wait < 60:
            time.sleep(0.05)
    result["rows"] = [int(r) for r in rows]
    return result


def fold(run, result, diff):
    """The load's result and the replica's counters into run.facts."""
    facts = run.facts
    counters, hists = diff
    facts["latency_s"] = result["latency_s"]
    facts["late_s"] = result["late_s"]
    facts["attempted"] = len(result["latency_s"])
    facts["failed"] = sum(1 for ok in result["ok"] if not ok)
    facts["window_s"] = result["t_end"] - result["t0"]
    facts["counters"] = counters
    facts["histograms"] = {k: v for k, v in hists.items()
                           if not k.startswith("phase:")}
    facts["phases"] = {k[6:]: v for k, v in hists.items()
                       if k.startswith("phase:")}
    if result["errors"]:
        run.note(first_errors=result["errors"][:3])
    if result["senders_stuck"]:
        run.wrong("%d sender threads never came back"
                  % result["senders_stuck"])
    lat = sorted(result["latency_s"])
    late = sorted(result["late_s"])
    p50 = lat[len(lat) // 2]
    late99 = late[min(len(late) - 1, int(0.99 * len(late)))]
    if late99 > 0.1 * p50:
        run.wrong("the load generator ran late: p99 %.3f ms against a "
                  "median latency of %.3f ms" % (1e3 * late99, 1e3 * p50))
    run.note(requests=facts["attempted"], failed=facts["failed"],
             rows=sum(result["rows"]), window_s=facts["window_s"],
             p50_ms=1e3 * p50, late_p99_ms=1e3 * late99,
             setup_s=facts.get("setup_s"), build_s=facts.get("build_s"),
             replica_ready_s=facts.get("replica_ready_s"),
             batches=hists.get("serve.batch_occupancy", {}).get("count"))
