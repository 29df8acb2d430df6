#!/usr/bin/env python
"""launch.py — start (and supervise) a multi-process / multi-host training job.

Reference: ``tools/launch.py`` + ``3rdparty/ps-lite/tracker``
(dmlc_tracker.local/ssh — spawn workers+servers with DMLC_* envs).

TPU-native contract: there are no parameter servers — every process is a
jax.distributed worker; ``mxnet_tpu.parallel.init_process_group()``
(called by the training script, or implicitly via MX_DIST_AUTO_INIT) reads
the env this launcher sets:

  MX_COORDINATOR    host:port of process 0
  MX_NUM_PROCESSES  world size
  MX_PROCESS_ID     this process's rank

Modes:
  -n N --launcher local  : N processes on this host (separate CPU backends;
                           for pipeline/io testing — real multi-chip needs
                           one process per host)
  -n N --launcher ssh -H hostfile : one process per hostfile line via ssh
  --launcher manual      : print the per-rank environment + command

Supervision (the restart-and-resume layer over ISSUE 1's recovery
primitives): with ``--restart on-failure`` (or ``--restart N``) the
launcher keeps watching every spawned rank and parameter server.  A
process that exits nonzero is restarted with its ORIGINAL environment —
same rank, same MX_COORDINATOR (rank 0 re-binds its own coordinator
port, so a dead rank 0 regenerates the coordinator for the job), same
MX_PS_SNAPSHOT path — so ``fit(checkpoint_dir=..., auto_resume)`` and
the durable PS pick up from the last step instead of from scratch.
Restart delays follow ``mxnet_tpu.fault.RetryPolicy`` exponential
backoff; a rank that exceeds ``--max-restarts`` escalates to whole-job
teardown (every surviving process is killed, the job exits nonzero).
``--hang-timeout S`` additionally arms heartbeat-file liveness: each
rank gets MX_HEARTBEAT_FILE, the fit loop touches it every batch, and a
rank whose file goes stale for S seconds is killed and restarted —
distinguishing *wedged* from merely *slow* (a slow rank keeps beating).
In-process, ``MX_STEP_TIMEOUT`` (mxnet_tpu.health watchdog) converts a
hung step into exit code 86 the supervisor sees like any other crash.

Serving fleet tier (ISSUE 17): ``--serve-port-base B`` tells the
supervisor its command is a serving replica bound at ``B + rank``, so
each process is registered with the embedded fleet collector as a
wire-scraped ``serve`` member (queue depth, decode occupancy, KV
headroom — the router's routing signals).  ``--route PORT``
additionally fronts the replicas with the session router
(``python -m mxnet_tpu.serve.router``) reading an authoritative
replicas file this supervisor rewrites, and ``--autoscale MIN:MAX``
arms the SLO-burn autoscaler: when any fleet SLO burn (from the merged
snapshot; targets via MX_FLEET_SLO_*) holds >= MX_AUTOSCALE_UP_BURN
for MX_AUTOSCALE_HOLD scrape rounds, a warm replica is spawned into
the spike (compile-cache makes that seconds); when every burn holds <=
MX_AUTOSCALE_DOWN_BURN the newest replica is retired DRAIN-not-kill —
dropped from the replicas file first (the router stops admitting),
then the wire DRAIN verb lets its in-flight generations finish against
a bounded deadline; the clean exit 0 is expected, not a failure.
Post-action cooldowns back off exponentially (MX_AUTOSCALE_COOLDOWN)
so the fleet never flaps.  A crashed replica is an involuntary retire:
the router fails its pinned sessions over immediately, the supervisor
restarts it (or, past the restart budget, shrinks the serve tier and
continues, like --elastic does for workers).

Elastic membership (ISSUE 16): ``--elastic`` spawns every worker with
MX_ELASTIC=1, so each rank JOINs the parameter-server membership table
at store init, and changes two supervisor behaviours.  Involuntary: a
worker that exhausts its restart budget is given up — the supervisor
sends LEAVE on its behalf to every server (barriers re-quorum on the
survivors), retires it from the fleet plane, and the job CONTINUES on
the remaining ranks instead of tearing down (teardown only when the
last worker dies).  Voluntary: ``--resize-file PATH`` polls PATH for a
target worker count; when it differs from the live world the supervisor
drains every rank at its next epoch boundary (SIGTERM → the elastic fit
handler checkpoints and exits 0), LEAVEs removed ranks out of the
membership, and respawns ranks ``0..N_new-1`` with the new world size
and a bumped MX_ELASTIC_EPOCH — the epoch salts the fusion-bucket CRC
names, so the resized job replans its exchange layout with zero
coordination and can never misread a pre-resize server accumulator.

Example:
  python tools/launch.py -n 2 --restart on-failure \\
      --fault 'worker.step:crash:after=5' -- python train.py --kv dist
"""
import argparse
import json
import os
import pickle
import shlex
import shutil
import socket
import struct
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# keep in sync with mxnet_tpu.health.WATCHDOG_EXIT_CODE (launch.py stays
# import-light: mxnet_tpu loads lazily, only when a restart is needed)
WATCHDOG_EXIT_CODE = 86


def _free_port() -> int:
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _compat_env(rank: int, coordinator: str, n: int):
    """The launcher contract: MX_* plus the reference-era DMLC_* names,
    for scripts that read either.  launch_manual prints exactly this."""
    return {
        "MX_COORDINATOR": coordinator,
        "MX_NUM_PROCESSES": str(n),
        "MX_PROCESS_ID": str(rank),
        "DMLC_NUM_WORKER": str(n),
        "DMLC_WORKER_ID": str(rank),
        "DMLC_ROLE": "worker",
    }


def _env_for(rank: int, coordinator: str, n: int):
    env = dict(os.environ)
    env.update(_compat_env(rank, coordinator, n))
    return env


# ---------------------------------------------------------------------------
# Supervisor
# ---------------------------------------------------------------------------

class SupervisedProc:
    """One supervised process: argv + frozen env + restart accounting."""

    def __init__(self, name, argv, env, role="worker", addr=None,
                 heartbeat=None):
        self.name = name
        self.argv = list(argv)
        self.env = dict(env)          # frozen: restarts reuse it verbatim
        self.role = role              # "worker"|"server"|"serve"|"router"
        self.addr = addr              # host:port (servers, for STOP)
        self.draining = False         # serve tier: retirement in flight
        self.heartbeat = heartbeat    # liveness file path or None
        self.fleet_key = None         # this proc's fleet-member id
        self.proc = None
        self.restarts = 0
        self.restart_at = None        # backoff deadline for the respawn
        self.spawned_wall = None      # wall clock of the last spawn
        self.rc = None                # final status once permanently done
        self.we_killed = False        # we tore it down: rc not a failure

    @property
    def done(self):
        return self.rc is not None

    def alive(self):
        return self.proc is not None and self.proc.poll() is None


class Supervisor:
    """Restart-and-resume process supervisor (tentpole of ISSUE 2).

    Policy ``never`` reproduces the old launcher: spawn once, wait for
    every worker, fold return codes.  Policy ``on-failure`` restarts a
    crashed process with its original env after a
    ``mxnet_tpu.fault.RetryPolicy`` backoff delay (so restart storms
    decorrelate), up to ``max_restarts`` per process; past the budget
    the whole job is torn down nonzero.  Heartbeat-file staleness
    (``hang_timeout``) counts as a crash: the wedged process is killed
    first, then the restart path runs.

    Backoff is DEADLINE-scheduled, not slept inline: a rank awaiting its
    restart window never blocks reaping, hang detection, or restarts of
    the other processes (a correlated failure restarts every rank after
    ONE backoff, not a serialized sum of them).  All backoff timing goes
    through ``mxnet_tpu.fault``'s module clock — under
    ``fault.use_virtual_time()`` chaos tests drive the full schedule
    with zero real sleeping.
    """

    def __init__(self, restart="never", max_restarts=3, backoff=None,
                 hang_timeout=None, startup_grace=None, poll=0.05,
                 log=None, status_interval=None, elastic=False,
                 resize_file=None, drain_timeout=60.0):
        if restart not in ("never", "on-failure"):
            raise ValueError("restart must be 'never' or 'on-failure'")
        self.restart = restart
        self.max_restarts = int(max_restarts)
        # elastic membership (ISSUE 16): shrink-and-continue past the
        # restart budget, plus resize-file-driven voluntary resize
        self.elastic = bool(elastic)
        self.resize_file = resize_file
        self.drain_timeout = float(drain_timeout)
        self.ps_addrs = []            # server addrs for LEAVE-on-behalf
        self.worker_factory = None    # (rank, n, generation) -> spec
        self.generation = 0           # membership generation: bumped per
                                      # resize, rides MX_ELASTIC_EPOCH
        self._resize_applied = None   # last target honoured (an
                                      # involuntary shrink must not be
                                      # "healed" by a stale resize file)
        self._backoff = backoff       # lazy: RetryPolicy needs mxnet_tpu
        self.hang_timeout = hang_timeout
        # fleet status table (ISSUE 8): every status_interval wall
        # seconds — and on every failure — print one line per process
        # from the heartbeat files' telemetry JSON payload (step,
        # throughput, last-exchange bytes); 0 = failures only, None
        # (default) = no tables at all
        self.status_interval = status_interval
        self._last_status = time.time()
        self._crash_seq = 0
        # before the FIRST beat (no heartbeat file yet) a process gets a
        # generous startup window — jax import + first-batch compile are
        # legitimately slow — but not forever: a (re)spawn that wedges
        # during startup must still be detected or the job hangs for
        # good.  Default: 20x the hang timeout, at least 120s.
        self.startup_grace = startup_grace if startup_grace is not None \
            else (max(120.0, 20.0 * hang_timeout) if hang_timeout
                  else None)
        self.poll = poll
        self.log = log or (lambda msg: print("launch.py: %s" % msg,
                                             file=sys.stderr, flush=True))
        self.procs = []
        self.job_rc = 0
        self._fault = None            # mxnet_tpu.fault, loaded lazily
        self.fleet = None             # embedded FleetCollector (ISSUE 12)
        # serving fleet tier (ISSUE 17): --route/--autoscale wiring
        self.replicas_file = None     # router's authoritative addr list
        self.fleet_port = None        # FLEET wire port (router signals)
        self.autoscale = None         # (min, max) replica bounds or None
        self.serve_factory = None     # index -> (name, argv, env, addr,
                                      #           heartbeat)
        self._as_next_index = 0       # next spawned replica's rank
        self._as_up_hold = 0          # consecutive rounds burn >= up
        self._as_down_hold = 0        # consecutive rounds burn <= down
        self._as_last_round = None    # last scrape round evaluated
        self._as_last_dir = None      # last action direction
        self._as_streak = 0           # consecutive same-direction acts
        self._as_cooldown_until = 0.0
        self._as_policy = None        # RetryPolicy-shaped cooldown

    # -- registration -------------------------------------------------------
    def add(self, name, argv, env, role="worker", addr=None,
            heartbeat=None):
        sp = SupervisedProc(name, argv, env, role=role, addr=addr,
                            heartbeat=heartbeat)
        self.procs.append(sp)
        return sp

    # -- plumbing -----------------------------------------------------------
    def _fault_mod(self):
        """mxnet_tpu.fault, imported on first use only — a job that
        never crashes never pays the framework import in the launcher."""
        if self._fault is None:
            if REPO not in sys.path:
                sys.path.insert(0, REPO)
            from mxnet_tpu import fault
            self._fault = fault
        return self._fault

    def _now(self):
        return self._fault.now() if self._fault is not None \
            else time.monotonic()

    def _sleep_poll(self):
        # once the fault clock is loaded (first failure), poll ticks go
        # through it too, so virtual-time tests advance restart deadlines
        if self._fault is not None:
            self._fault.sleep(self.poll)
        else:
            time.sleep(self.poll)

    def _backoff_delay(self, attempt):
        fault = self._fault_mod()
        if self._backoff is None:
            # deadline is irrelevant (only .delay() is used); jitter
            # decorrelates simultaneous rank restarts after a correlated
            # failure (e.g. the coordinator died under all of them)
            self._backoff = fault.RetryPolicy(
                deadline=float("inf"), base=1.0, max_delay=30.0,
                jitter=0.1)
        return self._backoff.delay(attempt)

    def _spawn(self, sp):
        if sp.heartbeat:
            # drop the previous incarnation's beats: liveness
            # enforcement (re)starts at this process's FIRST beat, so
            # neither a stale leftover file nor a slow startup (jax
            # import, first-batch compile) can get a healthy process
            # killed before its first batch
            try:
                os.remove(sp.heartbeat)
            except OSError:
                pass
        sp.spawned_wall = time.time()
        sp.proc = subprocess.Popen(sp.argv, env=sp.env)

    def _kill(self, sp):
        if not sp.alive():
            return
        sp.we_killed = True
        sp.proc.terminate()
        try:
            sp.proc.wait(timeout=5.0)
        except subprocess.TimeoutExpired:
            sp.proc.kill()
            try:
                # bounded even after SIGKILL: an unkillable (D-state)
                # child must not wedge the whole supervisor loop — the
                # zombie is reaped by a later poll() instead
                sp.proc.wait(timeout=5.0)
            except subprocess.TimeoutExpired:
                self.log("%s ignored SIGKILL (uninterruptible?); "
                         "leaving it to a later poll" % sp.name)

    def _fold(self, rc):
        if rc:
            self.job_rc = self.job_rc or (rc if rc > 0 else 1)

    # -- fleet status (ISSUE 8) --------------------------------------------

    # malformed heartbeat JSON lines seen by _read_beat, tolerated and
    # COUNTED (ISSUE 12 satellite): a half-written payload line must
    # not drop the whole beat — the head line still proves liveness.
    # Class-level because _read_beat is a staticmethod.
    malformed_beats = 0

    @staticmethod
    def _read_beat(sp):
        """(age_seconds_or_None, head_line, telemetry_payload_dict) from
        a rank's heartbeat file.  Line 1 is the classic
        ``<unix-time> <epoch> <batch>`` / ``... done`` beat; line 2, when
        present, is the flight recorder's latest step record as compact
        JSON (mxnet_tpu.telemetry.heartbeat_payload, ``schema``-tagged).

        Age normally compares wall time against the file mtime; when
        this process runs under mxnet_tpu.fault's VIRTUAL clock (chaos
        tests) that compare races — the payload's ``ts`` field was
        stamped by fault.now() in the beating process, so the age is
        computed on that same injectable clock instead."""
        if not sp.heartbeat:
            return None, "", {}
        try:
            age = time.time() - os.stat(sp.heartbeat).st_mtime
            with open(sp.heartbeat) as f:
                lines = f.read().splitlines()
        except OSError:
            return None, "", {}
        # import-light inline copy of mxnet_tpu.telemetry.parse_heartbeat
        # (the launcher must not import the framework on its happy
        # path) — keep the two in sync
        head = lines[0] if lines else ""
        payload = {}
        if len(lines) > 1 and lines[1].strip():
            try:
                payload = json.loads(lines[1])
                if not isinstance(payload, dict):
                    raise ValueError("payload is not a JSON object")
            except ValueError:
                payload = {}
                Supervisor.malformed_beats += 1
        try:
            # schema gate: a beat stamped by a NEWER framework version
            # is ignored, not mis-rendered (1 = the schema this copy
            # understands; mxnet_tpu.telemetry.HEARTBEAT_SCHEMA)
            if payload.get("schema", 1) > 1:
                payload = {}
        except TypeError:
            payload = {}
            Supervisor.malformed_beats += 1
        # only consulted when the framework is already loaded — the
        # launcher stays import-light on the happy path
        _f = sys.modules.get("mxnet_tpu.fault")
        if _f is not None and _f.is_virtual() and \
                isinstance(payload.get("ts"), (int, float)):
            age = max(0.0, _f.now() - float(payload["ts"]))
        return age, head, payload

    @staticmethod
    def _state_of(sp):
        if sp.done:
            return "done(rc=%s)" % sp.rc
        if sp.restart_at is not None:
            return "restarting"
        return "running" if sp.alive() else "spawning"

    # -- embedded fleet collector (ISSUE 12) --------------------------------
    def _start_collector(self):
        """Embed a fleet collector so every supervised job gets the
        fleet plane for free: workers scrape via their heartbeat files,
        parameter servers over the METRICS wire verb.  The collector
        thread runs the scrape/merge/detect loop; the status table and
        crash dumps read its merged snapshot.  Lazy-imports the
        framework (same posture as _fault_mod); any failure degrades to
        the old heartbeat-only table, never to a dead supervisor."""
        if self.fleet is not None:
            return
        candidates = [sp for sp in self.procs
                      if sp.heartbeat or (sp.role in ("server", "serve",
                                                      "router") and
                                          sp.addr)]
        if not candidates:
            return
        try:
            if REPO not in sys.path:
                sys.path.insert(0, REPO)
            from mxnet_tpu import fleet as _fleet
            from mxnet_tpu.base import get_env as _get_env
            interval = _get_env("MX_FLEET_INTERVAL", 2.0, float)
            if not interval or interval <= 0:
                return      # MX_FLEET_INTERVAL=0 opts the embed out
            members = []
            nsrv = 0
            for sp in candidates:
                if sp.role in ("serve", "router") and sp.addr:
                    # serve tier (ISSUE 17): wire-scraped with the
                    # member row carrying its addr, so the merged
                    # snapshot is directly router/autoscaler-consumable
                    # (fleet.replica_signals)
                    rank = sp.env.get("MX_PROCESS_ID",
                                      "0" if sp.role == "router"
                                      else len(members))
                    m = _fleet.FleetMember(sp.role, rank, addr=sp.addr)
                elif sp.heartbeat:
                    rank = sp.env.get("MX_PROCESS_ID", len(members))
                    m = _fleet.FleetMember("worker", rank,
                                           heartbeat=sp.heartbeat)
                else:
                    m = _fleet.FleetMember("server", nsrv, addr=sp.addr)
                    nsrv += 1
                sp.fleet_key = m.key
                members.append(m)
            self.fleet = _fleet.FleetCollector(members).start(
                port=self.fleet_port)
        except Exception as e:
            self.log("fleet collector unavailable (%s); falling back "
                     "to heartbeat-only status" % e)
            self.fleet = None

    def _stop_collector(self):
        if self.fleet is not None:
            try:
                self.fleet.stop()
            except Exception:
                pass

    def status_table(self):
        """Live fleet status as a rendered text table — one row per
        supervised process.  Row data comes from the heartbeat
        telemetry payloads; presence, straggler and SLO flags come from
        the embedded collector's merged fleet snapshot when it runs
        (ISSUE 12 — the table IS the fleet snapshot's view of the job).
        What a human tailing the supervisor log (and chaos_smoke.sh)
        reads to see where the fleet is."""
        snap = self.fleet.snapshot() if self.fleet is not None else None
        fleet_members = (snap or {}).get("members") or {}
        stragglers = {f.get("member"): f
                      for f in (snap or {}).get("stragglers") or []}
        cols = ("proc", "state", "restarts", "step", "epoch",
                "steps/s", "img/s", "wire KB", "beat age", "flags")
        rows = [cols]
        for sp in self.procs:
            age, _head, p = self._read_beat(sp)
            flags = []
            meta = fleet_members.get(sp.fleet_key)
            if meta is not None and not meta.get("present") and \
                    not sp.done:
                flags.append("ABSENT")
            f = stragglers.get(sp.fleet_key)
            if f:
                flags.append("STRAGGLER(%.3gx %s)"
                             % (f.get("ratio", 0),
                                f.get("dominant_phase") or "?"))
            rows.append((
                sp.name, self._state_of(sp), str(sp.restarts),
                str(p.get("step", "-")), str(p.get("epoch", "-")),
                "%.3g" % p["steps_per_sec"] if "steps_per_sec" in p
                else "-",
                "%.4g" % p["throughput"] if "throughput" in p else "-",
                "%.1f" % (p["wire_bytes"] / 1024.0)
                if "wire_bytes" in p else "-",
                "%.1fs" % age if age is not None else "-",
                " ".join(flags) or "-"))
        widths = [max(len(r[i]) for r in rows) for i in range(len(cols))]
        lines = ["  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip()
                 for r in rows]
        sep = "-" * len(lines[0])
        out = ["fleet status:", sep] + lines + [sep]
        slo = (snap or {}).get("slo") or {}
        breached = sorted((slo.get("breached") or {}))
        if breached:
            out.append("SLO BREACH (latched): %s" % ", ".join(breached))
        return "\n".join(out)

    def _maybe_status(self):
        if not self.status_interval:
            return
        now = time.time()
        if now - self._last_status >= self.status_interval:
            self._last_status = now
            self.log("\n" + self.status_table())

    def _crash_dump(self, sp, rc, kind):
        """Supervisor-side crash record into MX_CRASH_DIR: what the
        supervisor observed of a failed process (exit code, restart
        budget, last heartbeat payload).  The worker's own in-process
        dump (flight-recorder ring) lands next to it; together they say
        what the rank was doing and how it died."""
        d = os.environ.get("MX_CRASH_DIR")
        if not d:
            return None
        try:
            os.makedirs(d, exist_ok=True)
            self._crash_seq += 1
            age, head, payload = self._read_beat(sp)
            safe = "".join(c if c.isalnum() else "_" for c in sp.name)
            path = os.path.join(d, "supervisor-%s-%d.json"
                                % (safe, self._crash_seq))
            blob = {"reason": kind, "proc": sp.name, "role": sp.role,
                    "rc": rc, "restarts": sp.restarts,
                    "wall_time": time.time(),
                    "heartbeat_age": age, "heartbeat_head": head,
                    "heartbeat": payload}
            if self.fleet is not None:
                # the last merged fleet snapshot (ISSUE 12): the
                # post-mortem shows what the REST of the job was doing
                # when this rank died, not just the dead rank's story
                try:
                    blob["fleet"] = self.fleet.snapshot()
                except Exception:
                    blob["fleet"] = None
            tmp = "%s.tmp.%d" % (path, os.getpid())
            with open(tmp, "w") as f:
                json.dump(blob, f, indent=1)
            os.replace(tmp, path)
            return path
        except OSError:
            return None

    # -- failure handling ---------------------------------------------------
    def _describe(self, rc):
        if rc == WATCHDOG_EXIT_CODE:
            return ("exit %d (MX_STEP_TIMEOUT watchdog: hung step)"
                    % rc)
        if rc < 0:
            return "signal %d" % -rc
        return "exit %d" % rc

    def _on_failure(self, sp, rc):
        """Crashed (or was hang-killed).  Returns True to keep running,
        False when the budget is exhausted → caller tears the job down."""
        self._crash_dump(sp, rc, self._describe(rc))
        if self.status_interval is not None:
            # a failure is always worth a fleet snapshot, whatever the
            # interval cadence says
            self.log("\n" + self.status_table())
        if self.restart != "on-failure":
            sp.rc = rc
            self._fold(rc)
            return True                       # old posture: wait the rest
        if sp.restarts >= self.max_restarts:
            if self.elastic and sp.role == "worker":
                survivors = [w for w in self.procs
                             if w is not sp and w.role == "worker"
                             and not w.done]
                if survivors:
                    # shrink-and-continue (ISSUE 16): an elastic job
                    # gives the rank up instead of tearing everyone
                    # down.  LEAVE on its behalf evicts it from the PS
                    # membership (barriers re-quorum on the survivors
                    # at the current membership epoch) and the fleet
                    # plane retires it immediately — a departed member
                    # is gone by protocol, not merely silent, so it
                    # must never linger as ABSENT/STRAGGLER.
                    self.log("%s failed (%s) past its restart budget "
                             "(%d) - elastic shrink: continuing with "
                             "%d worker(s)"
                             % (sp.name, self._describe(rc),
                                self.max_restarts, len(survivors)))
                    sp.rc = rc        # done; NOT folded — the job's
                                      # exit code belongs to survivors
                    try:
                        rank = int(sp.env.get("MX_PROCESS_ID", -1))
                    except (TypeError, ValueError):
                        rank = -1
                    if rank >= 0:
                        for addr in self.ps_addrs:
                            try:
                                _send_leave(addr, rank)
                            except OSError as e:
                                self.log("LEAVE r%d -> %s failed (%s); "
                                         "liveness eviction will catch "
                                         "up" % (rank, addr, e))
                    if self.fleet is not None and sp.fleet_key:
                        try:
                            self.fleet.retire(sp.fleet_key)
                        except Exception:
                            pass
                    return True
            if sp.role == "serve":
                survivors = [w for w in self.procs
                             if w is not sp and w.role == "serve"
                             and not w.done]
                if survivors:
                    # involuntary retire (ISSUE 17): the serve tier
                    # shrinks and continues — the router already failed
                    # this replica's pinned sessions over on the first
                    # dead forward; here the supervisor just stops
                    # paying for restarts and retires it from the
                    # signal plane + the replicas file
                    self.log("%s failed (%s) past its restart budget "
                             "(%d) - involuntary retire: serving "
                             "continues on %d replica(s)"
                             % (sp.name, self._describe(rc),
                                self.max_restarts, len(survivors)))
                    sp.rc = rc        # done; NOT folded — the tier's
                                      # exit code belongs to survivors
                    self._write_replicas_file()
                    if self.fleet is not None and sp.fleet_key:
                        try:
                            self.fleet.retire(sp.fleet_key)
                        except Exception:
                            pass
                    return True
            self.log("%s failed (%s) and exhausted its restart budget "
                     "(%d) - tearing the job down"
                     % (sp.name, self._describe(rc), self.max_restarts))
            sp.rc = rc
            self._fold(rc)
            return False
        delay = self._backoff_delay(sp.restarts)
        sp.restarts += 1
        sp.restart_at = self._now() + delay    # deadline, not a sleep:
        extra = ""                             # supervision stays live
        if sp.role == "worker" and sp.env.get("MX_PROCESS_ID") == "0":
            extra = " (rank 0: regenerating the coordinator on %s)" \
                % sp.env.get("MX_COORDINATOR", "?")
        self.log("%s failed (%s) - restart %d/%d in %.3gs with original "
                 "env%s" % (sp.name, self._describe(rc), sp.restarts,
                            self.max_restarts, delay, extra))
        return True

    def _check_hang(self, sp):
        """Heartbeat-file liveness: slow ranks keep the file fresh;
        a file stale past hang_timeout means wedged → kill (the exit
        then routes through the normal failure/restart path)."""
        if not (sp.heartbeat and self.hang_timeout) or not sp.alive():
            return
        try:
            age = time.time() - os.stat(sp.heartbeat).st_mtime
            limit, phase = self.hang_timeout, "--hang-timeout"
            try:
                with open(sp.heartbeat) as f:
                    if f.read().strip().endswith("done"):
                        return     # fit finished its beats: post-fit
                                   # work may be legitimately silent
            except OSError:
                pass
        except OSError:
            # no beat yet: startup.  Slow is allowed (import + compile);
            # wedged-before-the-first-batch is bounded by the grace
            if self.startup_grace is None or sp.spawned_wall is None:
                return
            age = time.time() - sp.spawned_wall
            limit, phase = self.startup_grace, "startup grace"
        if age > limit:
            self.log("%s heartbeat stale for %.3gs (> %s %.3g) - "
                     "killing the wedged process"
                     % (sp.name, age, phase, limit))
            sp.proc.kill()
            try:
                # bounded: a D-state child must not stall hang checks
                # for every OTHER rank; poll() reaps it later
                sp.proc.wait(timeout=5.0)
            except subprocess.TimeoutExpired:
                self.log("%s ignored SIGKILL (uninterruptible?); "
                         "leaving it to a later poll" % sp.name)

    # -- elastic resize (ISSUE 16) ------------------------------------------
    def _check_resize(self):
        """Poll the resize file for a target worker count; a target that
        differs from the last one honoured triggers a live resize."""
        if not (self.elastic and self.resize_file and self.worker_factory):
            return
        try:
            with open(self.resize_file) as f:
                txt = f.read().strip()
        except OSError:
            return
        if not txt:
            return
        try:
            n_new = int(txt)
        except ValueError:
            self.log("resize file %r holds %r (not an integer); ignored"
                     % (self.resize_file, txt))
            return
        if n_new <= 0 or n_new == self._resize_applied:
            return
        self._resize_applied = n_new
        self._do_resize(n_new)

    def _do_resize(self, n_new):
        """Voluntary elastic resize: quiesce every worker at its next
        epoch boundary (SIGTERM → the elastic fit drain handler saves a
        checkpoint and exits 0), LEAVE the removed ranks out of the PS
        membership, then respawn ranks 0..n_new-1 under the new world
        size with a bumped membership generation.  MX_ELASTIC_EPOCH
        carries the generation into every worker, where it salts the
        fusion-bucket CRC names — the resized world's exchange layout
        is replanned deterministically and can never collide with a
        pre-resize server accumulator."""
        old = [sp for sp in self.procs
               if sp.role == "worker" and not sp.done]
        self.generation += 1
        self.log("elastic resize: %d -> %d worker(s) (generation %d); "
                 "draining at the epoch boundary"
                 % (len(old), n_new, self.generation))
        for sp in old:
            if sp.alive():
                sp.proc.terminate()   # drain: checkpoint, then exit 0
        deadline = time.time() + self.drain_timeout
        for sp in old:
            if sp.proc is not None:
                try:
                    sp.proc.wait(timeout=max(0.1,
                                             deadline - time.time()))
                except subprocess.TimeoutExpired:
                    self.log("%s did not drain within %.3gs - killing "
                             "it (auto-resume picks up from its last "
                             "checkpoint)" % (sp.name, self.drain_timeout))
                    sp.we_killed = True
                    sp.proc.kill()
                    try:
                        sp.proc.wait(timeout=5.0)
                    except subprocess.TimeoutExpired:
                        pass
            sp.rc = 0                 # drained by request, not a failure
        # ranks above the new world size leave the membership NOW;
        # continuing/new ranks re-register themselves (JOIN is
        # idempotent) when they come up under the new generation
        for sp in old:
            try:
                rank = int(sp.env.get("MX_PROCESS_ID", -1))
            except (TypeError, ValueError):
                rank = -1
            if rank >= n_new:
                for addr in self.ps_addrs:
                    try:
                        _send_leave(addr, rank)
                    except OSError as e:
                        self.log("LEAVE r%d -> %s failed (%s); liveness "
                                 "eviction will catch up" % (rank, addr, e))
        self.procs = [sp for sp in self.procs if sp.role != "worker"]
        for rank in range(n_new):
            name, argv, env, heartbeat = self.worker_factory(
                rank, n_new, self.generation)
            sp = self.add(name, argv, env, role="worker",
                          heartbeat=heartbeat)
            self._spawn(sp)
        if self.fleet is not None:
            # the collector's member set is frozen at start(): rebuild
            # it over the new world (removed ranks drop out of
            # presence/straggler tracking with it)
            self._stop_collector()
            self.fleet = None
            self._start_collector()

    # -- serving autoscaler (ISSUE 17) --------------------------------------
    def _serve_procs(self, live_only=True):
        return [sp for sp in self.procs
                if sp.role == "serve" and not sp.done
                and not (live_only and sp.draining)]

    def _write_replicas_file(self):
        """Atomically rewrite the router's authoritative replica list:
        live, non-draining replicas only.  Dropping an addr here is the
        FIRST retirement step — the router stops admitting new sessions
        to it before the replica itself is asked to DRAIN."""
        if not self.replicas_file:
            return
        addrs = [sp.addr for sp in self._serve_procs() if sp.addr]
        tmp = "%s.tmp.%d" % (self.replicas_file, os.getpid())
        with open(tmp, "w") as f:
            f.write("".join(a + "\n" for a in addrs))
        os.replace(tmp, self.replicas_file)

    def _as_env(self, name, default):
        from mxnet_tpu.base import get_env as _get_env
        try:
            v = _get_env(name, default, float)
            return float(default if v is None else v)
        except (TypeError, ValueError):
            return float(default)

    def _check_autoscale(self):
        """One autoscale evaluation per fleet scrape round: SLO burn
        (observed/target, from the merged snapshot) must HOLD past the
        hysteresis band for MX_AUTOSCALE_HOLD consecutive rounds before
        an action fires, and every action arms an exponentially
        backed-off cooldown — a spike absorbs with a burst of spawns,
        but up/down flapping gets slower each flip."""
        if not (self.autoscale and self.serve_factory
                and self.fleet is not None):
            return
        snap = None
        try:
            snap = self.fleet.snapshot()
        except Exception:
            return
        if not snap:
            return
        round_id = snap.get("scrape")
        if round_id is None or round_id == self._as_last_round:
            return                      # same round: nothing new to read
        self._as_last_round = round_id
        burn = ((snap.get("slo") or {}).get("burn") or {})
        vals = [float(v) for v in burn.values()
                if isinstance(v, (int, float))]
        worst = max(vals, default=0.0)
        up_t = self._as_env("MX_AUTOSCALE_UP_BURN", 1.0)
        down_t = self._as_env("MX_AUTOSCALE_DOWN_BURN", 0.5)
        hold = max(1, int(self._as_env("MX_AUTOSCALE_HOLD", 3)))
        if worst >= up_t:
            self._as_up_hold += 1
            self._as_down_hold = 0
        elif worst <= down_t:
            self._as_down_hold += 1
            self._as_up_hold = 0
        else:
            # inside the hysteresis band: hold steady both ways
            self._as_up_hold = self._as_down_hold = 0
        if self._now() < self._as_cooldown_until:
            return
        mn, mx = self.autoscale
        n_live = len(self._serve_procs())
        if self._as_up_hold >= hold and n_live < mx:
            self._scale_up(worst, up_t, n_live)
        elif self._as_down_hold >= hold and n_live > mn:
            self._scale_down(worst, down_t, n_live)

    def _as_arm_cooldown(self, direction):
        fault = self._fault_mod()
        if self._as_last_dir == direction:
            self._as_streak += 1
        else:
            self._as_streak = 0
            self._as_last_dir = direction
        base = max(0.1, self._as_env("MX_AUTOSCALE_COOLDOWN", 10.0))
        if self._as_policy is None or self._as_policy.base != base:
            self._as_policy = fault.RetryPolicy(
                deadline=float("inf"), base=base, max_delay=8.0 * base,
                jitter=0.1)
        self._as_cooldown_until = self._now() + \
            self._as_policy.delay(min(self._as_streak, 3))
        self._as_up_hold = self._as_down_hold = 0

    def _scale_up(self, worst, up_t, n_live):
        idx = self._as_next_index
        self._as_next_index += 1
        name, argv, env, addr, heartbeat = self.serve_factory(idx)
        sp = self.add(name, argv, env, role="serve", addr=addr,
                      heartbeat=heartbeat)
        self._spawn(sp)
        self._write_replicas_file()
        self.log("autoscale: burn %.3g >= %.3g held - spawning %s at "
                 "%s (%d -> %d replicas)"
                 % (worst, up_t, name, addr, n_live, n_live + 1))
        if self.fleet is not None:
            try:
                from mxnet_tpu import fleet as _fleet
                m = _fleet.FleetMember("serve", idx, addr=addr)
                sp.fleet_key = m.key
                self.fleet.add_member(m)
            except Exception:
                pass
        self._as_arm_cooldown("up")

    def _scale_down(self, worst, down_t, n_live):
        victims = self._serve_procs()
        if not victims:
            return
        sp = victims[-1]                # newest replica retires first
        sp.draining = True
        self._write_replicas_file()     # router admission closes FIRST
        self.log("autoscale: burn %.3g <= %.3g held - retiring %s "
                 "drain-not-kill (%d -> %d replicas)"
                 % (worst, down_t, sp.name, n_live, n_live - 1))
        try:
            _send_drain(sp.addr)
        except OSError as e:
            # already dead or wedged: the DRAIN courtesy failed, fall
            # back to the supervisor's kill (clients failover-replay)
            self.log("%s: DRAIN failed (%s); killing it" % (sp.name, e))
            self._kill(sp)
        if self.fleet is not None:
            if sp.fleet_key:
                try:
                    self.fleet.retire(sp.fleet_key)
                except Exception:
                    pass
            try:
                # the spike this retirement answers is over: un-latch
                # the breach records so the NEXT breach is a fresh
                # signal, not a stale latch blocking/false-arming scale
                # decisions
                self.fleet.slo.reset()
            except Exception:
                pass
        self._as_arm_cooldown("down")

    def _teardown(self):
        for sp in self.procs:
            self._kill(sp)
            if sp.rc is None:
                sp.rc = 0 if sp.proc is None else (sp.proc.poll() or 0)

    # -- run ----------------------------------------------------------------
    def run(self):
        """Spawn everything, supervise until every worker is done, then
        stop the servers gracefully.  Returns the job return code."""
        for sp in self.procs:
            self._spawn(sp)
        if self.status_interval is not None or self.hang_timeout \
                or self.replicas_file or self.autoscale:
            # the fleet plane rides the same provisioning as the status
            # table / hang detection (heartbeat files, server addrs);
            # the serve router/autoscaler REQUIRE it (load signals)
            self._start_collector()
        try:
            while True:
                # elastic: the resize file can swap the whole worker set
                # out from under this loop, so the membership is read
                # fresh each tick rather than captured once up front
                self._check_resize()
                self._check_autoscale()
                for sp in list(self.procs):
                    if sp.done or sp.proc is None:
                        continue
                    if sp.restart_at is not None:
                        if self._now() >= sp.restart_at:
                            sp.restart_at = None
                            self._spawn(sp)
                        continue           # awaiting its backoff window
                    self._check_hang(sp)
                    rc = sp.proc.poll()
                    if rc is None:
                        continue
                    if rc == 0:
                        # a server exiting 0 early means a worker sent
                        # STOP (its own shutdown path) — that's done too
                        sp.rc = 0
                        continue
                    if not self._on_failure(sp, rc):
                        self._teardown()
                        return self.job_rc
                # serve replicas and the router count as workers for
                # job lifetime: the job ends when every non-server
                # process is done (serve: a STOP through the client or
                # router stops the whole tier)
                workers = [sp for sp in self.procs
                           if sp.role != "server"]
                if all(w.done for w in workers):
                    break
                self._maybe_status()
                self._sleep_poll()
        except BaseException:
            # ^C or any supervisor bug (e.g. a respawn Popen failing):
            # never exit leaving ranks/servers running unsupervised
            self._teardown()
            raise
        finally:
            self._stop_collector()
        self.stop_servers()
        return self.job_rc

    # -- graceful server shutdown ------------------------------------------
    def stop_servers(self, timeout=10.0):
        """Workers are done: drain each surviving parameter server with
        the wire-protocol STOP (ISSUE 1's graceful drain — in-flight
        requests finish, the snapshot lands) instead of SIGTERM, and
        fold server exit codes into the job's return code.  SIGTERM is
        the fallback for a server that won't take the hint; a kill WE
        sent is not treated as a server failure."""
        for sp in self.procs:
            if sp.role != "server" or sp.done:
                continue
            if sp.restart_at is not None and not sp.alive():
                # its crash was already forgiven by the restart policy
                # and the workers finished before the backoff window —
                # nothing left to restart, and folding the stale rc
                # would make the job's exit code a race
                sp.rc = 0
                continue
            stop_sent = False
            if sp.alive() and sp.addr:
                try:
                    _send_stop(sp.addr)
                    stop_sent = True
                except OSError as e:
                    self.log("%s: graceful STOP failed (%s); falling "
                             "back to terminate" % (sp.name, e))
            if not stop_sent:
                # no drain was requested — waiting for one is pointless
                self._kill(sp)
            try:
                sp.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                self._kill(sp)
            rc = sp.proc.poll()
            sp.rc = rc if rc is not None else 0
            if not sp.we_killed:
                self._fold(sp.rc)


def _send_stop(addr, timeout=5.0):
    """Send the kvstore wire-protocol STOP (length-prefixed pickle; see
    mxnet_tpu/kvstore/server.py) and await the ack.  Inlined rather than
    imported so the launcher never has to load the framework."""
    host, _, port = addr.rpartition(":")
    with socket.create_connection((host or "127.0.0.1", int(port)),
                                  timeout=timeout) as s:
        s.settimeout(timeout)
        payload = pickle.dumps(("STOP", None), protocol=4)
        s.sendall(struct.pack("<Q", len(payload)) + payload)
        head = b""
        while len(head) < 8:                  # ack: (True, "stopping")
            chunk = s.recv(8 - len(head))
            if not chunk:
                return
            head += chunk
        (n,) = struct.unpack("<Q", head)
        body = b""
        while len(body) < n:
            chunk = s.recv(min(1 << 16, n - len(body)))
            if not chunk:
                return
            body += chunk


def _send_leave(addr, rank, timeout=5.0):
    """Send the kvstore wire-protocol LEAVE for rank ``rank`` (elastic
    membership, ISSUE 16) — the supervisor departs a dead or removed
    worker on its behalf so barriers re-quorum on the survivors
    immediately instead of waiting out liveness eviction.  Same inlined
    length-prefixed-pickle framing as _send_stop: the launcher never
    loads the framework for it.  LEAVE is idempotent server-side, so
    racing the worker's own voluntary leave() is harmless."""
    host, _, port = addr.rpartition(":")
    with socket.create_connection((host or "127.0.0.1", int(port)),
                                  timeout=timeout) as s:
        s.settimeout(timeout)
        payload = pickle.dumps(("LEAVE", "r%d" % int(rank)), protocol=4)
        s.sendall(struct.pack("<Q", len(payload)) + payload)
        head = b""
        while len(head) < 8:                  # ack: (True, (epoch, ...))
            chunk = s.recv(8 - len(head))
            if not chunk:
                return
            head += chunk
        (n,) = struct.unpack("<Q", head)
        body = b""
        while len(body) < n:
            chunk = s.recv(min(1 << 16, n - len(body)))
            if not chunk:
                return
            body += chunk


def _send_drain(addr, drain_timeout=None, timeout=5.0):
    """Send the serve wire-protocol DRAIN (drain-not-kill retirement,
    ISSUE 17) and await the status ack.  Same inlined length-prefixed-
    pickle framing as _send_stop — the launcher never loads the
    framework for it.  ``drain_timeout=None`` lets the replica's own
    MX_SERVE_DRAIN_TIMEOUT bound the retirement; DRAIN is idempotent
    (a retry keeps the replica's FIRST deadline)."""
    host, _, port = addr.rpartition(":")
    with socket.create_connection((host or "127.0.0.1", int(port)),
                                  timeout=timeout) as s:
        s.settimeout(timeout)
        msg = ("DRAIN",) if drain_timeout is None \
            else ("DRAIN", float(drain_timeout))
        payload = pickle.dumps(msg, protocol=4)
        s.sendall(struct.pack("<Q", len(payload)) + payload)
        head = b""
        while len(head) < 8:              # ack: (True, {status dict})
            chunk = s.recv(8 - len(head))
            if not chunk:
                return
            head += chunk
        (n,) = struct.unpack("<Q", head)
        body = b""
        while len(body) < n:
            chunk = s.recv(min(1 << 16, n - len(body)))
            if not chunk:
                return
            body += chunk


def _make_supervisor(args):
    restart = getattr(args, "restart", "never")
    max_restarts = getattr(args, "max_restarts", 3)
    if restart not in ("never", "on-failure"):
        try:
            max_restarts = int(restart)
        except ValueError:
            raise SystemExit("--restart must be never, on-failure, or an "
                             "integer budget (got %r)" % restart)
        if max_restarts < 0:
            raise SystemExit("--restart N needs N >= 0")
        restart = "on-failure"
    return Supervisor(restart=restart, max_restarts=max_restarts,
                      hang_timeout=getattr(args, "hang_timeout", None),
                      status_interval=getattr(args, "status_interval",
                                              None),
                      elastic=getattr(args, "elastic", False),
                      resize_file=getattr(args, "resize_file", None),
                      drain_timeout=getattr(args, "drain_timeout", None)
                      or 60.0)


# ---------------------------------------------------------------------------
# Launch modes
# ---------------------------------------------------------------------------

def launch_local(args, command):
    coordinator = "127.0.0.1:%d" % _free_port()
    sup = _make_supervisor(args)
    hb_dir = None
    if sup.hang_timeout or sup.status_interval:
        # status tables read the same per-rank heartbeat files hang
        # detection uses — either feature provisions them
        hb_dir = tempfile.mkdtemp(prefix="mx-heartbeat-")
    # warm respawn: one resolved directory for jax's persistent
    # compilation cache frozen into EVERY rank's env — workers and PS
    # servers alike, and every RESTART of them (the supervisor respawns
    # with the original env) — so a chaos-killed process finds its XLA
    # compiles again instead of re-paying the cold-start compile bill
    compile_cache_dir = getattr(args, "compile_cache", None)
    if compile_cache_dir:
        compile_cache_dir = os.path.abspath(compile_cache_dir)
        os.makedirs(compile_cache_dir, exist_ok=True)
    ps_roots = []
    if getattr(args, "num_servers", 0) > 0:
        # dist_async parameter server(s) (reference: tracker starting
        # DMLC_ROLE=server processes); with -s N keys shard across the N
        # servers by hash (kvstore_dist.h key->server assignment role)
        snap_dir = getattr(args, "ps_snapshot_dir", None)
        if snap_dir:
            os.makedirs(snap_dir, exist_ok=True)
        for s in range(args.num_servers):
            port = _free_port()
            addr = "127.0.0.1:%d" % port
            ps_roots.append(addr)
            env = dict(os.environ)
            env.update({"DMLC_ROLE": "server",
                        "DMLC_NUM_WORKER": str(args.num_workers),
                        "MX_PS_PORT": str(port),
                        "MX_FORCE_CPU": "1", "JAX_PLATFORMS": "cpu",
                        "PYTHONPATH": REPO + os.pathsep +
                        env.get("PYTHONPATH", "")})
            if compile_cache_dir:
                env["JAX_COMPILATION_CACHE_DIR"] = compile_cache_dir
            if snap_dir:
                # durable PS: a restarted server (same snapshot path,
                # same port via the frozen env) resumes with no data
                # loss — the client side's reconnect-and-replay then
                # rides straight through
                env["MX_PS_SNAPSHOT"] = os.path.join(
                    snap_dir, "server_%d.pkl" % s)
            if getattr(args, "fault", None):
                env["MX_FAULT_INJECT"] = args.fault
            sup.add("server %d" % s,
                    [sys.executable, "-m", "mxnet_tpu.kvstore.server"],
                    env, role="server", addr=addr)
    elastic = bool(getattr(args, "elastic", False))

    def make_worker(rank, n, generation):
        """(name, argv, env, heartbeat) for one worker — used for the
        initial spawn AND stored as the supervisor's worker_factory so
        an elastic resize can respawn the world at any size."""
        env = _env_for(rank, coordinator, n)
        if compile_cache_dir:
            env["JAX_COMPILATION_CACHE_DIR"] = compile_cache_dir
        if getattr(args, "fault", None):
            # arm the chaos spec in every worker (mxnet_tpu.fault reads
            # MX_FAULT_INJECT at import) — a restarted rank re-arms the
            # same spec, keeping chaos runs deterministic per process
            env["MX_FAULT_INJECT"] = args.fault
        heartbeat = None
        if hb_dir:
            heartbeat = os.path.join(hb_dir, "rank_%d" % rank)
            env["MX_HEARTBEAT_FILE"] = heartbeat
        if ps_roots:
            env["MX_PS_ROOT"] = ps_roots[0]
            env["MX_PS_ROOTS"] = ",".join(ps_roots)
            env["DMLC_PS_ROOT_URI"] = ps_roots[0].split(":")[0]
            env["DMLC_PS_ROOT_PORT"] = ps_roots[0].split(":")[1]
            env["DMLC_NUM_SERVER"] = str(len(ps_roots))
        if elastic:
            # MX_ELASTIC: the dist store JOINs the membership at init
            # and fit arms the SIGTERM epoch-boundary drain.
            # MX_ELASTIC_EPOCH: supervisor-assigned membership
            # generation — salts the fusion-bucket names so each
            # incarnation's exchange layout is distinct and agreed
            # (every worker of a generation gets the SAME value; a
            # racily-observed server epoch could disagree mid-join)
            env["MX_ELASTIC"] = "1"
            env["MX_ELASTIC_EPOCH"] = str(int(generation))
        return "rank %d" % rank, list(command), env, heartbeat

    # serving fleet tier (ISSUE 17): replicas get wire addrs on the
    # fleet plane; --route adds the session router; --autoscale arms
    # the SLO-burn resize loop
    serve_base = getattr(args, "serve_port_base", None)
    route_port = getattr(args, "route", None)
    autoscale = getattr(args, "autoscale", None)
    if (route_port is not None or autoscale) and serve_base is None:
        raise SystemExit("launch.py: --route/--autoscale need "
                         "--serve-port-base B (the replicas' "
                         "--port-base, so the supervisor knows their "
                         "addrs)")

    def make_replica(index):
        """serve_factory face of make_worker: (name, argv, env, addr,
        heartbeat) for replica ``index`` at serve-port-base + index —
        used for the initial spawn AND every autoscaler scale-up."""
        name, argv, env, heartbeat = make_worker(index,
                                                 args.num_workers, 0)
        return (name, argv, env,
                "127.0.0.1:%d" % (serve_base + index), heartbeat)

    rt_dir = None
    for rank in range(args.num_workers):
        if serve_base is not None:
            name, argv, env, addr, heartbeat = make_replica(rank)
            sup.add(name, argv, env, role="serve", addr=addr,
                    heartbeat=heartbeat)
        else:
            name, argv, env, heartbeat = make_worker(
                rank, args.num_workers, 0)
            sup.add(name, argv, env, role="worker", heartbeat=heartbeat)
    if route_port is not None:
        rt_dir = tempfile.mkdtemp(prefix="mx-router-")
        sup.replicas_file = os.path.join(rt_dir, "replicas.txt")
        sup.fleet_port = _free_port()
        sup._write_replicas_file()
        env = dict(os.environ)
        env.update({"MX_FORCE_CPU": "1", "JAX_PLATFORMS": "cpu",
                    "PYTHONPATH": REPO + os.pathsep +
                    env.get("PYTHONPATH", "")})
        if getattr(args, "fault", None):
            # the router has its own chaos sites (router.request /
            # router.forward) — arm the same spec everywhere
            env["MX_FAULT_INJECT"] = args.fault
        heartbeat = None
        if hb_dir:
            heartbeat = os.path.join(hb_dir, "router")
            env["MX_HEARTBEAT_FILE"] = heartbeat
        sup.add("router",
                [sys.executable, "-m", "mxnet_tpu.serve.router",
                 "--port", str(route_port),
                 "--replicas-file", sup.replicas_file,
                 "--fleet", "127.0.0.1:%d" % sup.fleet_port],
                env, role="router",
                addr="127.0.0.1:%d" % route_port, heartbeat=heartbeat)
    if autoscale:
        try:
            mn, mx = (int(x) for x in str(autoscale).split(":", 1))
        except ValueError:
            raise SystemExit("launch.py: --autoscale wants MIN:MAX "
                             "(got %r)" % autoscale)
        if not (1 <= mn <= mx):
            raise SystemExit("launch.py: --autoscale needs "
                             "1 <= MIN <= MAX")
        sup.autoscale = (mn, mx)
        sup.serve_factory = make_replica
        sup._as_next_index = args.num_workers
    sup.ps_addrs = list(ps_roots)
    if elastic:
        sup.worker_factory = make_worker
        sup._resize_applied = args.num_workers
    try:
        return sup.run()
    finally:
        if hb_dir:
            shutil.rmtree(hb_dir, ignore_errors=True)
        if rt_dir:
            shutil.rmtree(rt_dir, ignore_errors=True)


def launch_ssh(args, command):
    if getattr(args, "hang_timeout", None):
        raise SystemExit(
            "launch.py: --hang-timeout reads a LOCAL heartbeat file and "
            "cannot observe remote ranks; it is only supported with "
            "--launcher local (use MX_STEP_TIMEOUT for in-process "
            "hang detection on remote ranks)")
    if getattr(args, "restart", "never") != "never":
        # an ssh CLIENT exiting nonzero does not mean the REMOTE rank
        # died (a transport blip orphans it alive); respawning would
        # start a duplicate rank k against the same PS/checkpoints, and
        # teardown could only kill the local clients.  Restart
        # supervision therefore stays a local-launcher feature.
        raise SystemExit(
            "launch.py: --restart is only supported with --launcher "
            "local (an ssh client's exit cannot be distinguished from "
            "the remote rank's death; restarting on it risks duplicate "
            "ranks)")
    if getattr(args, "elastic", False) or getattr(args, "resize_file",
                                                  None):
        # same reasoning as --restart: elastic respawn/drain needs
        # authoritative process lifecycle, which ssh clients cannot give
        raise SystemExit(
            "launch.py: --elastic/--resize-file are only supported "
            "with --launcher local")
    if getattr(args, "num_servers", 0) > 0:
        raise SystemExit(
            "launch.py: -s/--num-servers is only implemented for the "
            "local launcher; start `python -m mxnet_tpu.kvstore.server` "
            "on a host manually and export MX_PS_ROOT=host:port")
    if getattr(args, "route", None) is not None or \
            getattr(args, "serve_port_base", None) is not None or \
            getattr(args, "autoscale", None):
        # the serve tier needs authoritative local process lifecycle
        # (replicas file, DRAIN-then-reap, fleet wire scrapes) — same
        # reasoning as --restart/--elastic
        raise SystemExit(
            "launch.py: --route/--serve-port-base/--autoscale are only "
            "supported with --launcher local")
    hosts = []
    with open(args.hostfile) as f:
        for line in f:
            line = line.strip()
            if line and not line.startswith("#"):
                hosts.append(line.split()[0])
    if len(hosts) < args.num_workers:
        raise SystemExit("hostfile has %d hosts < -n %d"
                         % (len(hosts), args.num_workers))
    coordinator = "%s:%d" % (hosts[0], 43117)
    sup = _make_supervisor(args)   # restart=never (guarded above): the
                                   # supervisor just waits + folds rcs
    for rank in range(args.num_workers):
        env = _env_for(rank, coordinator, args.num_workers)
        exports = " ".join("%s=%s" % (k, shlex.quote(v))
                           for k, v in env.items()
                           if k.startswith(("MX_", "DMLC_", "JAX_")))
        remote = "cd %s && env %s %s" % (
            shlex.quote(os.getcwd()), exports,
            " ".join(shlex.quote(c) for c in command))
        sup.add("rank %d (%s)" % (rank, hosts[rank]),
                ["ssh", "-o", "StrictHostKeyChecking=no", hosts[rank],
                 remote],
                dict(os.environ), role="worker")
    return sup.run()


def launch_manual(args, command):
    coordinator = "<host0>:43117"
    for rank in range(args.num_workers):
        # exactly the contract _env_for gives spawned workers — MX_*
        # plus the DMLC_* compat names, so a manually-started process
        # behaves identically to a launched one
        env = _compat_env(rank, coordinator, args.num_workers)
        exports = " ".join("%s=%s" % kv for kv in env.items())
        print("rank %d:  env %s %s" % (rank, exports, " ".join(command)))
    return 0


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("-n", "--num-workers", type=int, required=True)
    p.add_argument("-s", "--num-servers", type=int, default=0)
    p.add_argument("--launcher", default="local",
                   choices=["local", "ssh", "manual"])
    p.add_argument("-H", "--hostfile", default=None)
    p.add_argument("--restart", default="never", metavar="POLICY",
                   help="never (default) | on-failure | N (shorthand for "
                        "on-failure with --max-restarts N).  on-failure "
                        "restarts a crashed rank/server with its original "
                        "env (RetryPolicy backoff) so checkpoint "
                        "auto-resume and MX_PS_SNAPSHOT pick up from the "
                        "last step; past the budget the whole job is "
                        "torn down nonzero.  Local launcher only")
    p.add_argument("--max-restarts", type=int, default=3, metavar="N",
                   help="per-process restart budget under --restart "
                        "on-failure (default 3)")
    p.add_argument("--hang-timeout", type=float, default=None,
                   metavar="SECS",
                   help="supervisor-side wedge detection: each rank gets "
                        "MX_HEARTBEAT_FILE (touched every batch by the "
                        "fit loop); a rank whose file goes stale this "
                        "many seconds is killed and handled like a "
                        "crash.  Set it well above your slowest "
                        "batch+eval gap — slow is fine, wedged is not.  "
                        "Before a rank's first beat a startup grace of "
                        "max(120s, 20x this) applies (import + compile)")
    p.add_argument("--status-interval", type=float, default=None,
                   metavar="SECS",
                   help="print a live fleet status table every SECS "
                        "seconds (and on every failure): per-rank step, "
                        "throughput and last-exchange bytes read from "
                        "the heartbeat files' telemetry JSON payload "
                        "(implies per-rank heartbeat files, like "
                        "--hang-timeout).  Unset = no tables")
    p.add_argument("--elastic", action="store_true",
                   help="elastic membership (preemption tolerance): "
                        "workers JOIN the parameter-server membership "
                        "at startup (MX_ELASTIC=1); a rank that "
                        "exhausts its restart budget is LEAVEd out and "
                        "the job continues on the survivors "
                        "(shrink-and-continue) instead of tearing "
                        "down.  Local launcher only")
    p.add_argument("--resize-file", default=None, metavar="PATH",
                   help="poll PATH for a target worker count (an "
                        "integer); when it changes the supervisor "
                        "drains every rank at its next epoch boundary "
                        "(SIGTERM -> checkpoint -> exit 0), LEAVEs "
                        "removed ranks from the PS membership, and "
                        "respawns the new world with a bumped "
                        "MX_ELASTIC_EPOCH (bucket-layout salt).  "
                        "Requires --elastic")
    p.add_argument("--drain-timeout", type=float, default=None,
                   metavar="SECS",
                   help="how long a resize waits for workers to reach "
                        "their epoch-boundary drain before killing "
                        "them (default 60; auto-resume then picks up "
                        "from the last checkpoint)")
    p.add_argument("--serve-port-base", type=int, default=None,
                   metavar="PORT",
                   help="the command is a serving replica bound at "
                        "PORT + rank (its own --port-base): each "
                        "replica is registered on the fleet plane as a "
                        "wire-scraped 'serve' member whose merged "
                        "signals (queue depth, decode occupancy, KV "
                        "headroom) feed the router and autoscaler.  "
                        "Local launcher only")
    p.add_argument("--route", type=int, default=None, metavar="PORT",
                   help="front the replicas with the session router "
                        "(python -m mxnet_tpu.serve.router) on PORT: "
                        "clients speak to ONE addr, sessions pin to "
                        "replicas, retirement is drain-not-kill.  The "
                        "supervisor owns the router's replicas file "
                        "and an embedded fleet collector wire port "
                        "for its load signals.  Needs "
                        "--serve-port-base")
    p.add_argument("--autoscale", default=None, metavar="MIN:MAX",
                   help="SLO-burn autoscaler over the serve tier: "
                        "spawn a warm replica when any fleet SLO burn "
                        "(MX_FLEET_SLO_* targets) holds >= "
                        "MX_AUTOSCALE_UP_BURN, retire-and-DRAIN the "
                        "newest when every burn holds <= "
                        "MX_AUTOSCALE_DOWN_BURN; hysteresis hold + "
                        "exponentially backed-off cooldowns stop "
                        "flapping.  Needs --serve-port-base (and "
                        "usually --route)")
    p.add_argument("--fault", default=None, metavar="SPEC",
                   help="arm fault injection in every spawned process "
                        "(MX_FAULT_INJECT spec, e.g. "
                        "'worker.step:crash:after=5' or "
                        "'kvstore.send:close:after=3'); chaos testing "
                        "only")
    p.add_argument("--compile-cache", default=None, metavar="DIR",
                   help="directory of jax's persistent compilation "
                        "cache (sets JAX_COMPILATION_CACHE_DIR in every "
                        "rank): a respawned/restarted rank finds its "
                        "XLA compiles here instead of paying them again")
    p.add_argument("--ps-snapshot-dir", default=None, metavar="DIR",
                   help="persist each parameter server's store under "
                        "DIR (atomic pickles) so a restarted server "
                        "loses no data")
    p.add_argument("command", nargs=argparse.REMAINDER)
    args = p.parse_args()
    command = args.command
    if command and command[0] == "--":   # strip only the leading separator
        command = command[1:]
    if not command:
        raise SystemExit("no command given")
    if args.resize_file and not args.elastic:
        raise SystemExit("--resize-file requires --elastic")
    if args.launcher == "local":
        sys.exit(launch_local(args, command))
    elif args.launcher == "ssh":
        if not args.hostfile:
            raise SystemExit("--launcher ssh needs -H hostfile")
        sys.exit(launch_ssh(args, command))
    else:
        sys.exit(launch_manual(args, command))


if __name__ == "__main__":
    main()
