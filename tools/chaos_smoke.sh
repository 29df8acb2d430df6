#!/usr/bin/env bash
# chaos_smoke.sh — exercise the supervised-elastic-launch resilience path
# end-to-end through the CLI, outside the unit suite (CI smoke).
#
# Runs tools/chaos_fit.py under `launch.py -n 2 --restart on-failure` with
# an armed `worker.step:crash:after=5` spec: each rank is killed
# mid-epoch-1, restarted by the supervisor with its original env, and
# auto-resumed from its epoch-0 checkpoint.  Asserts exit 0, both ranks
# finishing, and the resumed ranks' final params matching an
# uninterrupted single-rank reference run.
set -euo pipefail

REPO="$(cd "$(dirname "$0")/.." && pwd)"
WORK="$(mktemp -d /tmp/mx-chaos-smoke.XXXXXX)"
trap 'rm -rf "$WORK"' EXIT

export JAX_PLATFORMS=cpu MX_FORCE_CPU=1
unset XLA_FLAGS || true
PY="${PYTHON:-python3}"

echo "== chaos_smoke: uninterrupted reference run (-n 1)"
"$PY" "$REPO/tools/launch.py" -n 1 --launcher local -- \
    "$PY" "$REPO/tools/chaos_fit.py" \
    --ckpt-dir "$WORK/ref" --out "$WORK/ref" > "$WORK/ref.log" 2>&1

echo "== chaos_smoke: -n 2 --restart on-failure --fault worker.step:crash:after=5"
rc=0
MX_CRASH_DIR="$WORK/crash" \
"$PY" "$REPO/tools/launch.py" -n 2 --launcher local \
    --restart on-failure --max-restarts 2 --status-interval 2 \
    --fault 'worker.step:crash:after=5' -- \
    "$PY" "$REPO/tools/chaos_fit.py" \
    --ckpt-dir "$WORK/chaos" --out "$WORK/chaos" 2>&1 \
    | tee "$WORK/chaos.log" || rc=$?
if [ "$rc" -ne 0 ]; then
    echo "chaos_smoke: FAIL - launch.py exited $rc" >&2
    exit 1
fi
grep -q 'restart 1/' "$WORK/chaos.log" || {
    echo "chaos_smoke: FAIL - no restart happened (fault spec not armed?)" >&2
    exit 1
}
DONE=$(grep -c 'CHAOS_FIT_DONE' "$WORK/chaos.log" || true)
if [ "$DONE" -ne 2 ]; then
    echo "chaos_smoke: FAIL - expected 2 completed ranks, saw $DONE" >&2
    exit 1
fi

echo "== chaos_smoke: flight-recorder crash dumps + supervisor status table (ISSUE 8)"
# the kill-mid-fit above must leave BOTH sides of the observability
# story in MX_CRASH_DIR: each crashed rank's in-process flight-recorder
# dump (>= 1 structured step record) and the supervisor's own record of
# what it saw; the supervisor log must render the fleet status table
grep -q 'fleet status:' "$WORK/chaos.log" || {
    echo "chaos_smoke: FAIL - supervisor never printed a fleet status table" >&2
    exit 1
}
"$PY" - "$WORK/crash" <<'EOF'
import glob, json, sys
d = sys.argv[1]
worker = sorted(glob.glob("%s/crash-rank*.json" % d))
sup = sorted(glob.glob("%s/supervisor-*.json" % d))
assert worker, "no worker flight-recorder crash dumps in %s" % d
assert sup, "no supervisor crash records in %s" % d
blob = json.load(open(worker[0]))
assert len(blob.get("records") or []) >= 1, \
    "crash dump %s has no step records: %s" % (worker[0], blob.keys())
rec = blob["records"][-1]
for field in ("step", "phases", "dispatches", "wire_bytes"):
    assert field in rec, (field, rec)
# ISSUE 10: crash dumps carry the device-buffer census and the program
# registry — a dead rank's memory story and compiled-program set are
# part of the flight recording
census = blob.get("buffer_census")
assert census and census.get("total_bytes", 0) > 0, \
    "crash dump %s has no buffer census: %r" % (worker[0], census)
assert census.get("params", {}).get("count", 0) >= 1, \
    "census attributed no parameter buffers: %r" % (census,)
progs = blob.get("programs")
assert progs and len(progs) >= 1, \
    "crash dump %s has no registered programs" % worker[0]
assert any(t.get("compile_seconds", {}).get("total", 0) > 0
           for t in progs.values()), \
    "no program carries compile time: %r" % (list(progs),)
sblob = json.load(open(sup[0]))
assert sblob["rc"] != 0 and "heartbeat" in sblob, sblob
print("chaos_smoke: %d worker crash dump(s) with step records + %d "
      "supervisor record(s)" % (len(worker), len(sup)))
EOF

echo "== chaos_smoke: comparing resumed params to the uninterrupted run"
"$PY" - "$WORK" <<'EOF'
import sys
import numpy as np
work = sys.argv[1]
ref = np.load("%s/ref.rank0.npz" % work)
for rank in (0, 1):
    got = np.load("%s/chaos.rank%d.npz" % (work, rank))
    assert set(got.files) == set(ref.files), (got.files, ref.files)
    for k in ref.files:
        np.testing.assert_allclose(got[k], ref[k], rtol=1e-5, atol=1e-6,
                                   err_msg="rank %d param %s" % (rank, k))
print("chaos_smoke: resumed params match the uninterrupted run")
EOF

echo "== chaos_smoke: 3-step int8-compressed overlap-scheduled fit"
XLA_FLAGS="--xla_force_host_platform_device_count=2" \
MX_GRAD_COMPRESS=int8 MX_EXCHANGE_OVERLAP=1 \
"$PY" - "$REPO" <<'EOF'
import sys
sys.path.insert(0, sys.argv[1])
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.engine import engine

# 2-device DP fit through the int8-quantized, overlap-scheduled exchange:
# grad hooks fire during backward, bucket collectives launch early, drain
# commits before the fused update — 3 steps must train (loss drops) and
# the wire must carry compressed bytes.
mx.random.seed(0)
ctxs = [mx.cpu(0), mx.cpu(1)]
net = gluon.nn.Dense(4, in_units=8)
net.initialize(mx.init.Xavier(), ctx=ctxs)
trainer = gluon.Trainer(net.collect_params(), "sgd",
                        {"learning_rate": 0.1}, kvstore="device")
loss_fn = gluon.loss.L2Loss()
rng = np.random.RandomState(0)
X = rng.randn(16, 8).astype(np.float32)
W = rng.randn(8, 4).astype(np.float32)
Y = X.dot(W)
losses = []
w0 = engine.wire_bytes
for step in range(3):
    half = len(X) // 2
    tot = 0.0
    with autograd.record():
        for ctx, sl in zip(ctxs, (slice(0, half), slice(half, None))):
            loss = loss_fn(net(nd.array(X[sl], ctx=ctx)),
                           nd.array(Y[sl], ctx=ctx))
            loss.backward()
            tot += float(loss.mean().asnumpy())
    trainer.step(batch_size=len(X))
    losses.append(tot)
wire = engine.wire_bytes - w0
assert all(np.isfinite(l) for l in losses), losses
assert losses[-1] < losses[0], losses
assert trainer._kvstore is not None and trainer._kvstore._gc.type == "int8"
assert 0 < wire, wire
print("compressed_fit_smoke: PASS losses=%s wire_bytes=%d"
      % (["%.4f" % l for l in losses], wire))
EOF

echo "== chaos_smoke: compiled-mode fit (MX_STEP_COMPILE=1) + crash->restart->resume"
# reference run under the whole-step-compiled lane; its params must ALSO
# match the eager reference (compiled == eager parity through the CLI)
MX_STEP_COMPILE=1 "$PY" "$REPO/tools/launch.py" -n 1 --launcher local -- \
    "$PY" "$REPO/tools/chaos_fit.py" \
    --ckpt-dir "$WORK/cref" --out "$WORK/cref" > "$WORK/cref.log" 2>&1
rc=0
MX_STEP_COMPILE=1 "$PY" "$REPO/tools/launch.py" -n 2 --launcher local \
    --restart on-failure --max-restarts 2 \
    --fault 'worker.step:crash:after=5' -- \
    "$PY" "$REPO/tools/chaos_fit.py" \
    --ckpt-dir "$WORK/cchaos" --out "$WORK/cchaos" 2>&1 \
    | tee "$WORK/cchaos.log" || rc=$?
if [ "$rc" -ne 0 ]; then
    echo "chaos_smoke: FAIL - compiled-mode launch.py exited $rc" >&2
    exit 1
fi
grep -q 'restart 1/' "$WORK/cchaos.log" || {
    echo "chaos_smoke: FAIL - no compiled-mode restart happened" >&2
    exit 1
}
"$PY" - "$WORK" <<'EOF'
import sys
import numpy as np
work = sys.argv[1]
eager = np.load("%s/ref.rank0.npz" % work)
cref = np.load("%s/cref.rank0.npz" % work)
# compiled fit == eager fit (same trajectory, one dispatch per batch)
for k in eager.files:
    np.testing.assert_allclose(cref[k], eager[k], rtol=1e-5, atol=1e-6,
                               err_msg="compiled-vs-eager %s" % k)
# crash->restart->resume round-trips the DONATED optimizer state: the
# resumed compiled ranks land on the uninterrupted compiled run's params
for rank in (0, 1):
    got = np.load("%s/cchaos.rank%d.npz" % (work, rank))
    for k in cref.files:
        np.testing.assert_allclose(got[k], cref[k], rtol=1e-5, atol=1e-6,
                                   err_msg="rank %d param %s" % (rank, k))
print("chaos_smoke: compiled-mode fit matches eager; resume round-trips "
      "donated optimizer state")
EOF

echo "== chaos_smoke: 3-step compiled int8 fit (CompiledStep, EF residuals donated)"
XLA_FLAGS="--xla_force_host_platform_device_count=2" \
"$PY" - "$REPO" <<'EOF'
import sys
sys.path.insert(0, sys.argv[1])
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import gluon, nd
from mxnet_tpu.engine import engine

# single-program steps through the int8-compressed ICI exchange body on
# a 2-device store: loss drops, the EF residual store fills, EVERY step
# is one dispatch and the 4-step scan window costs 2 dispatches total
mx.random.seed(0)
ctxs = [mx.cpu(0), mx.cpu(1)]
net = gluon.nn.Dense(4, in_units=8)
net.initialize(mx.init.Xavier(), ctx=ctxs)
trainer = gluon.Trainer(net.collect_params(), "sgd",
                        {"learning_rate": 0.1}, kvstore="ici",
                        compression_params={"type": "int8"})
step = trainer.make_compiled_step(net, gluon.loss.L2Loss())
rng = np.random.RandomState(0)
X = rng.randn(16, 8).astype(np.float32)
Y = X.dot(rng.randn(8, 4)).astype(np.float32)
x_nd = nd.array(X, ctx=ctxs[0])
y_nd = nd.array(Y, ctx=ctxs[0])
losses = []
for _ in range(3):
    losses.append(float(step.step(x_nd, y_nd).mean().asnumpy()))
c0 = engine.dispatch_count
step.step(x_nd, y_nd)
per_step = engine.dispatch_count - c0
assert step.compiled, step.fallback_reason
assert losses[-1] < losses[0], losses
assert per_step <= 2, per_step
assert trainer._kvstore._gc._residuals, "EF residual store never filled"
Xw, Yw = np.stack([X] * 4), np.stack([Y] * 4)
step.run_window(Xw, Yw)           # warm: the trace itself runs eager ops
snap0 = engine.snapshot()         # ONE consistent counter-group read
step.run_window(Xw, Yw)
snap1 = engine.snapshot()
assert snap1["dispatches"] - snap0["dispatches"] <= 2, snap1
assert snap1["compiled_steps"] - snap0["compiled_steps"] == 4, snap1
print("compiled_step_smoke: PASS losses=%s dispatches/step=%d"
      % (["%.4f" % l for l in losses], per_step))
EOF

echo "== chaos_smoke: two-replica serving - kill one mid-load (ISSUE 9)"
# two supervised serving replicas (health-gated via --hang-timeout +
# heartbeat beats from the batcher loop); the serve.request fault kills
# replica 0 mid-request ~45, the sticky client fails over to replica 1,
# the supervisor restarts replica 0, and the driver asserts: every one
# of its 100 requests got a CORRECT answer (zero lost in-flight), >=1
# failover happened, and both replicas serve again at the end.
SERVE_BASE=$("$PY" - <<'EOF'
import socket
while True:
    s1 = socket.socket(); s1.bind(("", 0)); p = s1.getsockname()[1]
    s2 = socket.socket()
    try:
        s2.bind(("", p + 1))
    except OSError:
        s1.close(); s2.close(); continue
    s1.close(); s2.close(); print(p); break
EOF
)
rc=0
# 100 requests with a crash every ~45 handled → at most 2 crashes
# fleet-wide, comfortably inside a 3-per-replica restart budget (the
# failed-over survivor can crash too — rolling chaos is the point)
PYTHONPATH="$REPO${PYTHONPATH:+:$PYTHONPATH}" \
"$PY" "$REPO/tools/launch.py" -n 2 --launcher local \
    --restart on-failure --max-restarts 3 --hang-timeout 30 \
    --fault 'serve.request:crash:after=45' -- \
    "$PY" -m mxnet_tpu.serve --demo --port-base "$SERVE_BASE" \
    > "$WORK/serve.log" 2>&1 &
LAUNCH_PID=$!
"$PY" "$REPO/tools/serve_load.py" \
    --addrs "127.0.0.1:$SERVE_BASE,127.0.0.1:$((SERVE_BASE+1))" \
    --requests 100 --chaos --stop 2>&1 \
    | tee "$WORK/serve_load.log" || rc=$?
if [ "$rc" -ne 0 ]; then
    echo "chaos_smoke: FAIL - serve load driver exited $rc" >&2
    kill "$LAUNCH_PID" 2>/dev/null || true
    cat "$WORK/serve.log" >&2 || true
    exit 1
fi
wait "$LAUNCH_PID" || rc=$?
if [ "$rc" -ne 0 ]; then
    echo "chaos_smoke: FAIL - serve launch.py exited $rc" >&2
    cat "$WORK/serve.log" >&2 || true
    exit 1
fi
grep -q 'restart 1/' "$WORK/serve.log" || {
    echo "chaos_smoke: FAIL - no serving replica was restarted" >&2
    exit 1
}
grep -q 'SERVE_LOAD_OK' "$WORK/serve_load.log" || {
    echo "chaos_smoke: FAIL - serve load driver never reported OK" >&2
    exit 1
}
echo "chaos_smoke: serving chaos PASS (failover + restart, zero lost)"

echo "== chaos_smoke: decode serving - kill a replica mid-generation (ISSUE 15/18)"
# two supervised PAGED decode replicas (GENERATE verb, continuous
# batching, shared page heap + hash-shared prefixes + chunked
# prefill); the serve.request fault kills a replica mid-load under the
# shared-prefix workload, in-flight generations fail over and
# RE-PREFILL on the survivor — as chunk trains, against the survivor's
# OWN hash table — and completed sequences replay from the
# exactly-once cache.  The driver verifies every sequence against a
# local reference decode of the same seeded demo LM — deterministic
# greedy decode means a re-prefilled generation must reproduce its
# tokens EXACTLY, so correctness (not just arrival) survives the crash
# whether the survivor answered from a CoW fork or a cold chunk train.
DECODE_BASE=$("$PY" - <<'EOF'
import socket
while True:
    s1 = socket.socket(); s1.bind(("", 0)); p = s1.getsockname()[1]
    s2 = socket.socket()
    try:
        s2.bind(("", p + 1))
    except OSError:
        s1.close(); s2.close(); continue
    s1.close(); s2.close(); print(p); break
EOF
)
rc=0
# 80 generations with a crash every ~50 handled requests: the first
# crash lands mid-load, and end-of-load per-replica counters stay well
# below the NEXT trip point so the driver's closing health probes and
# STOPs cannot themselves crash a replica into the assertion window
MX_SERVE_KV_PAGES=64 MX_SERVE_KV_PAGE_LEN=16 \
MX_SERVE_PREFIX_SHARE=1 MX_SERVE_PREFILL_CHUNK=16 \
PYTHONPATH="$REPO${PYTHONPATH:+:$PYTHONPATH}" \
"$PY" "$REPO/tools/launch.py" -n 2 --launcher local \
    --restart on-failure --max-restarts 3 --hang-timeout 60 \
    --fault 'serve.request:crash:after=50' -- \
    "$PY" -m mxnet_tpu.serve --decode --port-base "$DECODE_BASE" \
    > "$WORK/decode.log" 2>&1 &
DECODE_LAUNCH_PID=$!
"$PY" "$REPO/tools/serve_load.py" \
    --addrs "127.0.0.1:$DECODE_BASE,127.0.0.1:$((DECODE_BASE+1))" \
    --decode --requests 80 --shared-prefix 3 --chaos --stop 2>&1 \
    | tee "$WORK/decode_load.log" || rc=$?
if [ "$rc" -ne 0 ]; then
    echo "chaos_smoke: FAIL - decode load driver exited $rc" >&2
    kill "$DECODE_LAUNCH_PID" 2>/dev/null || true
    cat "$WORK/decode.log" >&2 || true
    exit 1
fi
wait "$DECODE_LAUNCH_PID" || rc=$?
if [ "$rc" -ne 0 ]; then
    echo "chaos_smoke: FAIL - decode launch.py exited $rc" >&2
    cat "$WORK/decode.log" >&2 || true
    exit 1
fi
grep -q 'restart 1/' "$WORK/decode.log" || {
    echo "chaos_smoke: FAIL - no decode replica was restarted" >&2
    exit 1
}
grep -q 'SERVE_LOAD_OK' "$WORK/decode_load.log" || {
    echo "chaos_smoke: FAIL - decode load driver never reported OK" >&2
    exit 1
}
grep -q 'paged: 64 pages' "$WORK/decode.log" || {
    echo "chaos_smoke: FAIL - decode replicas did not come up PAGED" >&2
    exit 1
}
echo "chaos_smoke: decode chaos PASS (paged failover + chunked" \
     "re-prefill under shared prefixes, sequences exact)"

echo "== chaos_smoke: speculative decode - kill a replica mid-window (ISSUE 20)"
# two supervised SPECULATIVE replicas (MX_SERVE_DRAFT spawns the
# draft/verify pair co-hosted on the paged heap); the serve.request
# fault kills one mid-load under the shared-prefix workload, so
# in-flight generations die between a draft tick and its verify and
# must fail over — the survivor re-prefills BOTH models (chunk train +
# draft-prefill sentinel) and resumes windowed decode.  The driver's
# oracle is the spec pair's TARGET (serve_load honors MX_SERVE_DRAFT),
# and speculative output is bit-identical to target greedy decode, so
# every recovered sequence must still match token for token.
SPEC_BASE=$("$PY" - <<'EOF'
import socket
while True:
    s1 = socket.socket(); s1.bind(("", 0)); p = s1.getsockname()[1]
    s2 = socket.socket()
    try:
        s2.bind(("", p + 1))
    except OSError:
        s1.close(); s2.close(); continue
    s1.close(); s2.close(); print(p); break
EOF
)
rc=0
MX_SERVE_DRAFT=1 MX_SERVE_SPEC_K=4 \
MX_SERVE_KV_PAGES=64 MX_SERVE_KV_PAGE_LEN=16 \
MX_SERVE_PREFIX_SHARE=1 MX_SERVE_PREFILL_CHUNK=16 \
PYTHONPATH="$REPO${PYTHONPATH:+:$PYTHONPATH}" \
"$PY" "$REPO/tools/launch.py" -n 2 --launcher local \
    --restart on-failure --max-restarts 3 --hang-timeout 60 \
    --fault 'serve.request:crash:after=50' -- \
    "$PY" -m mxnet_tpu.serve --decode --port-base "$SPEC_BASE" \
    > "$WORK/spec_decode.log" 2>&1 &
SPEC_LAUNCH_PID=$!
MX_SERVE_DRAFT=1 MX_SERVE_SPEC_K=4 \
"$PY" "$REPO/tools/serve_load.py" \
    --addrs "127.0.0.1:$SPEC_BASE,127.0.0.1:$((SPEC_BASE+1))" \
    --decode --requests 80 --shared-prefix 3 --chaos --stop 2>&1 \
    | tee "$WORK/spec_load.log" || rc=$?
if [ "$rc" -ne 0 ]; then
    echo "chaos_smoke: FAIL - speculative load driver exited $rc" >&2
    kill "$SPEC_LAUNCH_PID" 2>/dev/null || true
    cat "$WORK/spec_decode.log" >&2 || true
    exit 1
fi
wait "$SPEC_LAUNCH_PID" || rc=$?
if [ "$rc" -ne 0 ]; then
    echo "chaos_smoke: FAIL - speculative launch.py exited $rc" >&2
    cat "$WORK/spec_decode.log" >&2 || true
    exit 1
fi
grep -q 'restart 1/' "$WORK/spec_decode.log" || {
    echo "chaos_smoke: FAIL - no speculative replica was restarted" >&2
    exit 1
}
grep -q 'SERVE_LOAD_OK' "$WORK/spec_load.log" || {
    echo "chaos_smoke: FAIL - speculative load driver never reported OK" >&2
    exit 1
}
grep -q 'speculative: k=4 draft=demo-lm-draft' "$WORK/spec_decode.log" || {
    echo "chaos_smoke: FAIL - replicas did not come up SPECULATIVE" >&2
    exit 1
}
echo "chaos_smoke: speculative chaos PASS (draft+target failover" \
     "re-prefill, windowed sequences bit-exact)"

echo "== chaos_smoke: session router - kill a replica UNDER the router (ISSUE 17)"
# the fleet front-tier: one router address fronting two supervised
# decode replicas.  The serve.request fault kills a replica mid-load;
# the ROUTER absorbs the failover (re-pins the dead replica's sessions,
# re-prefills stragglers on the survivor) while the client keeps
# talking to the one address it knows.  Every GENERATE answer is
# verified against the local reference decode THROUGH the router —
# exactly-once end to end: a retry through the router must replay from
# the replica's cache, never burn a second prefill with different
# tokens.
ROUTER_BASE=$("$PY" - <<'EOF'
import socket
while True:
    s1 = socket.socket(); s1.bind(("", 0)); p = s1.getsockname()[1]
    s2 = socket.socket()
    try:
        s2.bind(("", p + 1))
    except OSError:
        s1.close(); s2.close(); continue
    s1.close(); s2.close(); print(p); break
EOF
)
ROUTER_PORT=$("$PY" - <<'EOF'
import socket
s = socket.socket(); s.bind(("", 0)); print(s.getsockname()[1]); s.close()
EOF
)
rc=0
PYTHONPATH="$REPO${PYTHONPATH:+:$PYTHONPATH}" \
"$PY" "$REPO/tools/launch.py" -n 2 --launcher local \
    --restart on-failure --max-restarts 3 --hang-timeout 60 \
    --serve-port-base "$ROUTER_BASE" --route "$ROUTER_PORT" \
    --fault 'serve.request:crash:after=50' -- \
    "$PY" -m mxnet_tpu.serve --decode --port-base "$ROUTER_BASE" \
    > "$WORK/router.log" 2>&1 &
ROUTER_LAUNCH_PID=$!
# the router binds instantly but decode replicas bind only once warm —
# wait for the REPLICA ports too, or the first routed request spends
# its whole retry deadline probing a fleet that isn't up yet
"$PY" - "$ROUTER_BASE" <<'EOF'
import socket, sys, time
base = int(sys.argv[1])
for port in (base, base + 1):
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port),
                                     timeout=0.5).close()
            break
        except OSError:
            time.sleep(0.2)
    else:
        raise SystemExit("replica on %d never came up" % port)
EOF
"$PY" "$REPO/tools/serve_load.py" \
    --addrs "127.0.0.1:$ROUTER_PORT" --routed \
    --decode --requests 100 --chaos --stop 2>&1 \
    | tee "$WORK/router_load.log" || rc=$?
if [ "$rc" -ne 0 ]; then
    echo "chaos_smoke: FAIL - routed load driver exited $rc" >&2
    kill "$ROUTER_LAUNCH_PID" 2>/dev/null || true
    cat "$WORK/router.log" >&2 || true
    exit 1
fi
wait "$ROUTER_LAUNCH_PID" || rc=$?
if [ "$rc" -ne 0 ]; then
    echo "chaos_smoke: FAIL - routed launch.py exited $rc" >&2
    cat "$WORK/router.log" >&2 || true
    exit 1
fi
grep -q 'restart 1/' "$WORK/router.log" || {
    echo "chaos_smoke: FAIL - no replica was restarted under the router" >&2
    exit 1
}
grep -q 'SERVE_LOAD_OK' "$WORK/router_load.log" || {
    echo "chaos_smoke: FAIL - routed load driver never reported OK" >&2
    exit 1
}
echo "chaos_smoke: router chaos PASS (replica killed, router absorbed it, 100/100 exact)"

echo "== chaos_smoke: session router - kill the ROUTER itself mid-load (ISSUE 17)"
# router-targeted fault burst: the router.request crash site kills the
# front tier mid-request.  The supervisor restarts it; the client fails
# over (reconnect + SEQ replay through the fresh router), the replicas'
# replay caches dedupe anything already dispatched — 100/100 verified
# answers with zero double-dispatches.
RB2=$("$PY" - <<'EOF'
import socket
while True:
    s1 = socket.socket(); s1.bind(("", 0)); p = s1.getsockname()[1]
    s2 = socket.socket()
    try:
        s2.bind(("", p + 1))
    except OSError:
        s1.close(); s2.close(); continue
    s1.close(); s2.close(); print(p); break
EOF
)
RP2=$("$PY" - <<'EOF'
import socket
s = socket.socket(); s.bind(("", 0)); print(s.getsockname()[1]); s.close()
EOF
)
rc=0
PYTHONPATH="$REPO${PYTHONPATH:+:$PYTHONPATH}" \
"$PY" "$REPO/tools/launch.py" -n 2 --launcher local \
    --restart on-failure --max-restarts 3 --hang-timeout 60 \
    --serve-port-base "$RB2" --route "$RP2" \
    --fault 'router.request:crash:after=60' -- \
    "$PY" -m mxnet_tpu.serve --demo --port-base "$RB2" \
    > "$WORK/router2.log" 2>&1 &
ROUTER2_LAUNCH_PID=$!
"$PY" - "$RB2" <<'EOF'
import socket, sys, time
base = int(sys.argv[1])
for port in (base, base + 1):
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port),
                                     timeout=0.5).close()
            break
        except OSError:
            time.sleep(0.2)
    else:
        raise SystemExit("replica on %d never came up" % port)
EOF
"$PY" "$REPO/tools/serve_load.py" \
    --addrs "127.0.0.1:$RP2" --routed \
    --requests 100 --chaos --stop 2>&1 \
    | tee "$WORK/router2_load.log" || rc=$?
if [ "$rc" -ne 0 ]; then
    echo "chaos_smoke: FAIL - router-kill load driver exited $rc" >&2
    kill "$ROUTER2_LAUNCH_PID" 2>/dev/null || true
    cat "$WORK/router2.log" >&2 || true
    exit 1
fi
wait "$ROUTER2_LAUNCH_PID" || rc=$?
if [ "$rc" -ne 0 ]; then
    echo "chaos_smoke: FAIL - router-kill launch.py exited $rc" >&2
    cat "$WORK/router2.log" >&2 || true
    exit 1
fi
grep -q 'restart 1/' "$WORK/router2.log" || {
    echo "chaos_smoke: FAIL - the router was never restarted" >&2
    exit 1
}
grep -q 'SERVE_LOAD_OK' "$WORK/router2_load.log" || {
    echo "chaos_smoke: FAIL - router-kill load never reported OK" >&2
    exit 1
}
echo "chaos_smoke: router-kill chaos PASS (front tier restarted, 100/100 exact)"

echo "== chaos_smoke: autoscaler - 4x Poisson spike absorbed, drains back (ISSUE 17)"
# SLO-burn autoscaler: 1-3 replicas behind the router, a 1ms p99 target
# any sustained traffic breaches.  The Poisson spike must burn the SLO
# -> spawn(s) observed while EVERY answer stays verified-correct; once
# the spike ends the rolling window ages out, burn drops under the
# scale-down band, and the newest replica retires DRAIN-not-kill.
AS_BASE=$("$PY" - <<'EOF'
import socket
while True:
    s1 = socket.socket(); s1.bind(("", 0)); p = s1.getsockname()[1]
    ss = []
    try:
        for off in (1, 2):
            s = socket.socket(); s.bind(("", p + off)); ss.append(s)
    except OSError:
        s1.close(); [s.close() for s in ss]; continue
    s1.close(); [s.close() for s in ss]; print(p); break
EOF
)
AS_PORT=$("$PY" - <<'EOF'
import socket
s = socket.socket(); s.bind(("", 0)); print(s.getsockname()[1]); s.close()
EOF
)
rc=0
PYTHONPATH="$REPO${PYTHONPATH:+:$PYTHONPATH}" \
MX_FLEET_INTERVAL=0.5 MX_FLEET_SLO_P99_MS=1 \
MX_AUTOSCALE_HOLD=2 MX_AUTOSCALE_COOLDOWN=1 \
"$PY" "$REPO/tools/launch.py" -n 1 --launcher local \
    --restart on-failure --hang-timeout 60 \
    --serve-port-base "$AS_BASE" --route "$AS_PORT" --autoscale 1:3 -- \
    "$PY" -m mxnet_tpu.serve --demo --port-base "$AS_BASE" \
    > "$WORK/autoscale.log" 2>&1 &
AS_LAUNCH_PID=$!
"$PY" - "$AS_BASE" <<'EOF'
import socket, sys, time
port = int(sys.argv[1])
deadline = time.monotonic() + 120
while time.monotonic() < deadline:
    try:
        socket.create_connection(("127.0.0.1", port), timeout=0.5).close()
        break
    except OSError:
        time.sleep(0.2)
else:
    raise SystemExit("replica on %d never came up" % port)
EOF
# the 4x spike: open-loop Poisson arrivals at 40/s vs the 10/s baseline
# trickle, all through the router, every answer verified
"$PY" "$REPO/tools/serve_load.py" \
    --addrs "127.0.0.1:$AS_PORT" --routed \
    --requests 30 --poisson 10 > "$WORK/as_baseline.log" 2>&1 || rc=$?
if [ "$rc" -ne 0 ]; then
    echo "chaos_smoke: FAIL - autoscaler baseline load exited $rc" >&2
    kill "$AS_LAUNCH_PID" 2>/dev/null || true
    cat "$WORK/autoscale.log" >&2 || true
    exit 1
fi
"$PY" "$REPO/tools/serve_load.py" \
    --addrs "127.0.0.1:$AS_PORT" --routed \
    --requests 240 --poisson 40 2>&1 \
    | tee "$WORK/as_spike.log" || rc=$?
if [ "$rc" -ne 0 ]; then
    echo "chaos_smoke: FAIL - autoscaler spike load exited $rc" >&2
    kill "$AS_LAUNCH_PID" 2>/dev/null || true
    cat "$WORK/autoscale.log" >&2 || true
    exit 1
fi
# spike over: wait for the scale-down (window ages out -> burn ~0 ->
# hold -> drain-not-kill retire), then stop the fleet
for _i in $(seq 1 120); do
    grep -q 'drain-not-kill' "$WORK/autoscale.log" && break
    sleep 0.5
done
"$PY" "$REPO/tools/serve_load.py" \
    --addrs "127.0.0.1:$AS_PORT" --routed \
    --requests 0 --stop > "$WORK/as_stop.log" 2>&1 || true
wait "$AS_LAUNCH_PID" || rc=$?
if [ "$rc" -ne 0 ]; then
    echo "chaos_smoke: FAIL - autoscaler launch.py exited $rc" >&2
    cat "$WORK/autoscale.log" >&2 || true
    exit 1
fi
grep -q 'autoscale: .* spawning' "$WORK/autoscale.log" || {
    echo "chaos_smoke: FAIL - the spike never spawned a replica" >&2
    cat "$WORK/autoscale.log" >&2 || true
    exit 1
}
grep -q 'drain-not-kill' "$WORK/autoscale.log" || {
    echo "chaos_smoke: FAIL - the fleet never drained back down" >&2
    cat "$WORK/autoscale.log" >&2 || true
    exit 1
}
grep -q 'SERVE_LOAD_OK' "$WORK/as_spike.log" || {
    echo "chaos_smoke: FAIL - spike load never reported OK" >&2
    exit 1
}
echo "chaos_smoke: autoscaler PASS (spike spawned, drained back, all answers exact)"

echo "== chaos_smoke: serve dispatch budgets (1/batch, 1/decode step, +0 routed, spec window k+1)"
"$PY" "$REPO/tools/dispatch_count.py" --serve --decode --routed \
    --speculative > "$WORK/serve_budget.json"
"$PY" - "$WORK/serve_budget.json" <<'EOF'
import json, sys
r = json.load(open(sys.argv[1]))
assert r["serve"]["ok"], r["serve"]
assert r["decode"]["ok"], r["decode"]
assert r["routed"]["ok"], r["routed"]
assert r["speculative"]["ok"], r["speculative"]
print("serve budget: %(dispatches)d dispatches / %(batches)d batches, "
      "%(retraces)d retraces" % r["serve"])
print("decode budget: %(dispatches)d dispatches = %(prefill_dispatches)d "
      "prefills + %(decode_steps)d steps, %(retraces)d retraces"
      % r["decode"])
print("routed budget: %(routed_dispatches)d dispatches routed == "
      "%(direct_dispatches)d direct (+%(extra_dispatches)d), "
      "%(routed_retraces)d retraces" % r["routed"])
print("speculative budget: %(sequential_dispatches)d dispatches == "
      "%(expected_sequential)d planned (k=%(spec_k)d windows exact), "
      "%(retraces)d retraces" % r["speculative"])
EOF

echo "== chaos_smoke: fleet telemetry plane - kill a replica + a worker mid-load (ISSUE 12)"
"$PY" - "$REPO" "$WORK" <<'EOF'
import json, os, socket, subprocess, sys, threading, time
sys.path.insert(0, sys.argv[1])
WORK = sys.argv[2]
import numpy as np
from mxnet_tpu import fleet, telemetry
from mxnet_tpu.serve import ServeClient, ServeServer, Servable, serve_forever
from mxnet_tpu.serve.demo import DEMO_IN, demo_block, demo_example

def free_port():
    s = socket.socket(); s.bind(("", 0)); p = s.getsockname()[1]; s.close()
    return p

# two in-process serve replicas (separate ports; the abort_event on
# replica 0 is the in-process stand-in for a kill)
replicas = []
for i in range(2):
    port = free_port()
    state = ServeServer()
    state.host.deploy(Servable(demo_block(), name="demo-mlp", version=1),
                      example=demo_example())
    stop_ev, abort_ev = threading.Event(), threading.Event()
    threading.Thread(target=serve_forever,
                     kwargs=dict(port=port, state=state, stop_event=stop_ev,
                                 abort_event=abort_ev), daemon=True).start()
    replicas.append(("127.0.0.1:%d" % port, stop_ev, abort_ev))
for addr, _s, _a in replicas:
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        try:
            socket.create_connection(tuple([addr.split(":")[0],
                                            int(addr.split(":")[1])]),
                                     timeout=0.2).close()
            break
        except OSError:
            time.sleep(0.05)

# two fake training workers beating heartbeat files; rank 1 is 3x slow
hb_dir = os.path.join(WORK, "fleet-hb"); os.makedirs(hb_dir, exist_ok=True)
def beat(rank, step, sps, data_wait):
    path = os.path.join(hb_dir, "rank_%d" % rank)
    payload = {"schema": 1, "step": step, "steps_per_sec": sps,
               "phases": {"forward": 0.05, "data_wait": data_wait}}
    with open(path, "w") as f:
        f.write("%f 0 %d\n%s\n" % (time.time(), step, json.dumps(payload)))
beat(0, 10, 10.0, 0.01); beat(1, 4, 3.3, 0.22)

members = [fleet.FleetMember("serve", i, addr=a)
           for i, (a, _s, _ab) in enumerate(replicas)]
members += [fleet.FleetMember("worker", r,
                              heartbeat=os.path.join(hb_dir, "rank_%d" % r))
            for r in (0, 1)]
coll = fleet.FleetCollector(members, interval=0.2, stale_after=0.5,
                            scrape_timeout=1.0,
                            slo_targets={"rejection_rate": 0.01})

# drive some load so the serve histograms have mass
cli = ServeClient([replicas[0][0]], timeout=10)
x = np.zeros((1, DEMO_IN), np.float32)
for _ in range(10): cli.predict([x])
cli.close()
m = coll.scrape_once()
assert all(mm["present"] for mm in m["members"].values()), m["members"]

# straggler: the 3x-slow rank is named within 2 windows
for _ in range(2):
    m = coll.scrape_once()
names = [f["member"] for f in m["stragglers"]]
assert names == ["worker:1"], m["stragglers"]
assert m["stragglers"][0]["dominant_phase"] == "data_wait"

# fleet_top --once renders off the FLEET wire verb (straggler visible)
srv = fleet.serve_fleet(coll, 0)
addr = "127.0.0.1:%d" % srv.server_address[1]
out = subprocess.run(
    [sys.executable, os.path.join(sys.argv[1], "tools", "fleet_top.py"),
     "--fleet", addr, "--once"],
    capture_output=True, text=True, timeout=60)
assert out.returncode == 0, out.stderr
assert "serve:1" in out.stdout and "STRAGGLER" in out.stdout, out.stdout
srv.shutdown(); srv.server_close()

# forced rejection spike trips the SLO burn + latch
telemetry.registry.counter("serve.rejected").inc(50)
m = coll.scrape_once()
assert m["slo"]["burn"]["rejection_rate"] > 1.0, m["slo"]
assert "rejection_rate" in m["slo"]["breached"], m["slo"]

# kill replica 0 and silence worker 1: both absent within one interval
before = m["counters"]["serve.requests"]["total"]
replicas[0][2].set()          # sever the replica's listener + conns
time.sleep(0.6)               # > stale_after: worker 1's beat goes stale
beat(0, 20, 10.0, 0.01)       # survivor keeps beating
m = coll.scrape_once()
assert not m["members"]["serve:0"]["present"], m["members"]["serve:0"]
assert not m["members"]["worker:1"]["present"], m["members"]["worker:1"]
assert m["members"]["serve:1"]["present"] and m["members"]["worker:0"]["present"]

# survivors' crash-free rollups keep advancing
cli = ServeClient([replicas[1][0]], timeout=10)
for _ in range(5): cli.predict([x])
cli.close()
m = coll.scrape_once()
assert m["counters"]["serve.requests"]["total"] > before, \
    (m["counters"]["serve.requests"], before)
# the latched breach survives the healthy rounds
assert "rejection_rate" in m["slo"]["breached"], m["slo"]
for _a, stop_ev, ab in replicas:
    stop_ev.set()
print("fleet_smoke: PASS (absent within one scrape, straggler named, "
      "SLO latched, survivors advancing, fleet_top renders)")
EOF

echo "== chaos_smoke: warm respawn — jax's persistent compilation cache"
# kill-and-respawn with launch.py --compile-cache (JAX_COMPILATION_CACHE_DIR
# in every rank): the respawned worker must find its step programs' XLA
# compiles in the cache — the DONE receipt line carries jax's hits — and
# a respawned serve replica must warm its bucket table from hits while
# serving correct answers.
CACHE="$WORK/ccache"
rc=0
MX_STEP_COMPILE=1 "$PY" "$REPO/tools/launch.py" -n 1 --launcher local \
    --restart on-failure --max-restarts 2 --compile-cache "$CACHE" \
    --fault 'worker.step:crash:after=5' -- \
    "$PY" "$REPO/tools/chaos_fit.py" \
    --ckpt-dir "$WORK/warm-ckpt" --out "$WORK/warm" 2>&1 \
    | tee "$WORK/warm.log" || rc=$?
if [ "$rc" -ne 0 ]; then
    echo "chaos_smoke: FAIL - warm-respawn launch.py exited $rc" >&2
    exit 1
fi
grep -q 'restart 1/' "$WORK/warm.log" || {
    echo "chaos_smoke: FAIL - warm-respawn: no restart happened" >&2
    exit 1
}
"$PY" - "$WORK/warm.log" <<'EOF'
import re, sys
log = open(sys.argv[1]).read()
done = re.findall(r"CHAOS_FIT_DONE rank \S+ cache_hits=(\d+) "
                  r"cache_misses=(\d+) compile_seconds=([\d.]+)", log)
assert done, "no warm-respawn DONE receipt in log"
hits, _misses, comp = done[-1]
# the crashed first incarnation populated the cache; the incarnation
# that FINISHED (the respawn) must have found its compiles there (it
# still traces: compile_seconds is trace + lower + load)
assert int(hits) >= 1, "respawned worker reported no cache hits: %s" % (done,)
print("warm respawn worker: PASS (hits=%s, trace+load %ss)" % (hits, comp))
EOF

# serve replica warm respawn: same cache flag, crash mid-load; the
# respawn banner itself carries the receipts, and every answer the
# driver got must still be CORRECT
WARM_BASE=$("$PY" - <<'EOF'
import socket
while True:
    s1 = socket.socket(); s1.bind(("", 0)); p = s1.getsockname()[1]
    s2 = socket.socket()
    try:
        s2.bind(("", p + 1))
    except OSError:
        s1.close(); s2.close(); continue
    s1.close(); s2.close(); print(p); break
EOF
)
rc=0
PYTHONPATH="$REPO${PYTHONPATH:+:$PYTHONPATH}" \
"$PY" "$REPO/tools/launch.py" -n 2 --launcher local \
    --restart on-failure --max-restarts 3 --hang-timeout 30 \
    --compile-cache "$CACHE" \
    --fault 'serve.request:crash:after=45' -- \
    "$PY" -m mxnet_tpu.serve --demo --port-base "$WARM_BASE" \
    > "$WORK/warm_serve.log" 2>&1 &
WARM_LAUNCH_PID=$!
"$PY" "$REPO/tools/serve_load.py" \
    --addrs "127.0.0.1:$WARM_BASE,127.0.0.1:$((WARM_BASE+1))" \
    --requests 100 --chaos --stop 2>&1 \
    | tee "$WORK/warm_serve_load.log" || rc=$?
if [ "$rc" -ne 0 ]; then
    echo "chaos_smoke: FAIL - warm serve load driver exited $rc" >&2
    kill "$WARM_LAUNCH_PID" 2>/dev/null || true
    cat "$WORK/warm_serve.log" >&2 || true
    exit 1
fi
wait "$WARM_LAUNCH_PID" || rc=$?
if [ "$rc" -ne 0 ]; then
    echo "chaos_smoke: FAIL - warm serve launch.py exited $rc" >&2
    cat "$WORK/warm_serve.log" >&2 || true
    exit 1
fi
grep -q 'SERVE_LOAD_OK' "$WORK/warm_serve_load.log" || {
    echo "chaos_smoke: FAIL - warm serve load never reported OK" >&2
    exit 1
}
"$PY" - "$WORK/warm_serve.log" <<'EOF'
import re, sys
log = open(sys.argv[1]).read()
banners = re.findall(r"warm on .*?, (\d+) bucket\(s\).* in ([\d.]+)s "
                     r"\(compile-cache hits=(\d+) misses=(\d+)\)", log)
assert len(banners) >= 3, \
    "expected 2 cold + >=1 respawn banner, got %r" % (banners,)
buckets = int(banners[0][0])
# the two cold replicas compile (and may hit each other's entries); a
# respawn finds every compile in the cache
warm = [b for b in banners if int(b[2]) >= buckets and int(b[3]) == 0]
assert warm, "no respawned replica warmed from cache hits: %r" % (banners,)
print("warm respawn serve: PASS (%d respawn banner(s) with hits>=%d and "
      "no miss, fastest warm deploy %.2fs)"
      % (len(warm), buckets, min(float(b[1]) for b in warm)))
EOF
echo "chaos_smoke: warm respawn PASS (worker + serve replica came back warm)"

echo "== chaos_smoke: sharded dryrun — 3-step dp×fsdp SpecLayout fit (ISSUE 14)"
# The FSDP lane end-to-end on a fake 8-device mesh: a SpecLayout-sharded
# CompiledStep must (a) run 3 steps as one-donated-jit dispatches within
# the <=2/step budget, (b) match the replicated trajectory, and (c) cut
# per-chip params+optimizer bytes ~linearly with the fsdp axis.
PYTHONPATH="$REPO${PYTHONPATH:+:$PYTHONPATH}" \
XLA_FLAGS="--xla_force_host_platform_device_count=8" "$PY" - <<'EOF'
import gc
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, programs
from mxnet_tpu.engine import engine
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import SpecLayout, make_mesh

rng = np.random.RandomState(0)
X = rng.randn(16, 8).astype(np.float32)
Y = rng.randn(16, 4).astype(np.float32)
LOSS = gluon.loss.L2Loss()

def run(layout, ctxs=None):
    gc.collect()
    before = programs.buffer_census()
    mx.random.seed(0)
    net = nn.Sequential()
    net.add(nn.Dense(32, in_units=8, activation="relu"),
            nn.Dense(4, in_units=32))
    net.initialize(mx.init.Xavier(), ctx=ctxs)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05, "momentum": 0.9},
                       kvstore="ici",
                       compression_params={"type": "int8"})
    step = tr.make_compiled_step(net, LOSS, layout=layout)
    losses = []
    dispatches = []
    for _ in range(3):
        c0 = engine.snapshot()["dispatches"]
        loss = step.step(nd.array(X), nd.array(Y), batch_size=16)
        dispatches.append(engine.snapshot()["dispatches"] - c0)
        losses.append(float(np.mean(loss.asnumpy())))   # host-side mean
    assert step.compiled, step.fallback_reason
    gc.collect()
    after = programs.buffer_census()
    chip = sum(max(0, after[o]["bytes_per_chip"]
                   - before[o]["bytes_per_chip"])
               for o in ("params", "optimizer_state"))
    return losses, dispatches, chip

# replicated twin: the classic 2-device-copy trainer with the SAME
# quantized ici exchange — the sharded reduce-scatter lane must match
# its trajectory exactly
ref, _d, repl_bytes = run(None, ctxs=[mx.cpu(0), mx.cpu(1)])
mesh = make_mesh(axes=("data", "fsdp"), shape=(-1, 2))
got, disp, chip_bytes = run(SpecLayout.infer(mesh))
assert all(np.isfinite(ref)) and got[-1] < got[0], (ref, got)
np.testing.assert_allclose(ref, got, rtol=2e-4)
assert max(disp[1:]) <= 2, "sharded step over dispatch budget: %s" % disp
# the replicated twin keeps TWO full device copies of params+state; the
# fsdp=2 lane keeps one half-sheet per chip -> ideal 2*2=4x per chip
ratio = repl_bytes / max(1, chip_bytes)
assert ratio >= 0.85 * 4, \
    "per-chip state drop %.2fx outside 15%% of ideal 4x" % ratio
print("sharded_dryrun: PASS (int8 dp*fsdp loss %.4f -> %.4f == "
      "replicated 2-copy trajectory, %d dispatches/step, per-chip "
      "state %.2fx smaller)" % (got[0], got[-1], max(disp[1:]), ratio))
EOF

echo "== chaos_smoke: elastic membership - resize mid-fit + budget shrink (ISSUE 16)"
# The canonical elastic-resize chaos tests live in tests/test_elastic.py:
# grow 2->4 and shrink 4->3 mid-fit through `launch.py --elastic
# --resize-file` with final-param parity against an uninterrupted run,
# plus a rank SIGKILLed past --max-restarts retiring (shrink-and-continue,
# exit 0) instead of failing the job.  Run the WHOLE file here — the
# slow-marked CLI acceptance tests included (tier-1 only runs the fast
# in-process ones).
"$PY" -m pytest "$REPO/tests/test_elastic.py" -q \
    -p no:cacheprovider -p no:randomly
echo "chaos_smoke: elastic PASS (grow 2->4, shrink 4->3, SIGKILL shrink-and-continue)"

echo "== chaos_smoke: wire-protocol verifier has teeth (ISSUE 19)"
# The --protocol lane must (a) pass on the shipped tree — lint.sh below
# runs the real CLI with the pinned schedule count — and (b) actually
# trip when a protocol fault is injected.  Reinject the classic one
# in-memory (drop GENERATE from the serve replay cache: a retried
# generation would re-decode instead of replaying) and assert the lane
# catches it; the full quad lives in tests/test_protocol.py.
"$PY" - <<'EOF'
import os
from tools.mxlint import protocol

repo = os.getcwd()
sources = {}
for fp in protocol.iter_py_files([os.path.join(repo, "mxnet_tpu")]):
    rel = os.path.relpath(fp, repo).replace(os.sep, "/")
    sources[rel] = open(fp, encoding="utf-8").read()
diags, stats = protocol.check_sources(sources)
assert not diags, "shipped tree must be clean: %r" % [
    (d.rule, d.path, d.line) for d in diags]

mut = sources["mxnet_tpu/serve/server.py"].replace(
    '_CACHED = ("PREDICT", "SWAP", "GENERATE")',
    '_CACHED = ("PREDICT", "SWAP")')
assert mut != sources["mxnet_tpu/serve/server.py"], "anchor drifted"
sources["mxnet_tpu/serve/server.py"] = mut
diags, _ = protocol.check_sources(sources)
rules = sorted({d.rule for d in diags})
assert "protocol-replay-class" in rules, rules
print("chaos_smoke: protocol verifier PASS (clean tree certifies; "
      "injected replay-set hole trips %s)" % rules)
EOF

echo "== chaos_smoke: static-analysis lane (tools/lint.sh)"
bash "$REPO/tools/lint.sh"

echo "chaos_smoke: PASS"
