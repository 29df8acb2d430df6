#!/usr/bin/env bash
# lint.sh — the static-analysis lane as one CLI smoke (chaos_smoke.sh's
# sibling; the builder loop runs the same checks inside tier-1 via
# tests/test_mxlint.py).
#
#   1. mxlint over mxnet_tpu/ (incl. telemetry.py — span helpers are
#      hot-path roots) + tools/launch.py + tools/telemetry_dump.py —
#      the per-file TPU-invariant rules (host syncs in the hot path, jit
#      purity, wall clocks in fault paths, the MX_* env registry,
#      donation-after-use)
#      PLUS the whole-program concurrency rules (unguarded-shared-write,
#      inconsistent-guard, lock-order-cycle, blocking-wait-unbounded,
#      thread-leak) with the checked-in baseline; also asserts the
#      runtime's static lock-acquisition graph stays acyclic.
#   2. gen_env_docs --check — docs/ENV_VARS.md must match base.ENV_CATALOG
#      and every MX_* read in mxnet_tpu/ + tools/ must be cataloged.
#
# Exit nonzero on any new violation.  To suppress a justified hit, append
# `# mxlint: disable=<rule-id>` to the line (for a two-site concurrency
# finding: on the WRITE site, where it anchors); to re-baseline after
# review, run `python -m tools.mxlint --write-baseline` (every
# concurrency entry needs a `why` justification — docs/TESTING.md §5).
set -euo pipefail

REPO="$(cd "$(dirname "$0")/.." && pwd)"
cd "$REPO"
PY="${PYTHON:-python3}"

echo "== lint: mxlint (tools/mxlint, baseline $(
    "$PY" -c 'import json;print(len(json.load(open("tools/mxlint/baseline.json"))["entries"]))' 2>/dev/null || echo 0) entries)"
# ONE json run carries both the violation exit contract and the lock
# graph; the checker re-prints violations textually and fails on a
# cyclic graph
rc=0
out="$("$PY" -m tools.mxlint --format json --jobs 4)" || rc=$?
if [ "$rc" -ge 2 ] || [ -z "$out" ]; then
    echo "lint: mxlint internal/usage error (exit $rc)" >&2
    exit 2
fi
MXLINT_JSON="$out" "$PY" - "$rc" <<'PYEOF'
import json, os, sys
rc = int(sys.argv[1])
payload = json.loads(os.environ["MXLINT_JSON"])
for v in payload["violations"]:
    print("%(path)s:%(line)d: %(rule)s: %(message)s" % v)
g = payload["lock_graph"]
print("lock-acquisition graph (%s):" %
      ("acyclic" if g["acyclic"] else "CYCLIC"))
for e in g["edges"]:
    print("   " + e)
sys.exit(rc or (0 if g["acyclic"] else 1))
PYEOF

echo "== lint: env-var doc consistency (tools/gen_env_docs.py --check)"
"$PY" tools/gen_env_docs.py --check

echo "== lint: wire-protocol verifier (python -m tools.mxlint --protocol)"
# altitude 4 (ISSUE 19): per-verb effect summaries + exhaustive bounded
# fault-schedule model checking of the exactly-once layer.  Never
# baselined — a finding here is fix-now or suppress-at-line-with-why.
# The schedule count is pinned: the checker is deterministic (virtual
# clock, no sockets, sorted enumeration), so a drift in the count means
# a machine/verb/SEQ-shape change that must be reviewed (and the doc
# regenerated).  Wall budget <60s like the contracts lane (measured ~4s).
proto_out="$(timeout -k 10 60 "$PY" -m tools.mxlint --protocol)"
echo "$proto_out"
echo "$proto_out" | grep -q "737 fault schedule(s) checked" || {
    echo "lint: protocol fault-schedule count drifted from the pinned 737" \
         "— review the machine change, then repin here and in" \
         "tests/test_protocol.py" >&2
    exit 1
}

echo "== lint: wire-protocol doc consistency (tools/gen_wire_docs.py --check)"
"$PY" tools/gen_wire_docs.py --check

echo "== lint: contract manifest shape (python -m tools.mxlint --check-manifest)"
"$PY" -m tools.mxlint --check-manifest

echo "== lint: program contracts (python -m tools.mxlint --contracts)"
# device-free donation/HBM/trace-closure proofs (ISSUE 11): lowers every
# contracted jit program under JAX_PLATFORMS=cpu and prints the
# per-program budget table.  Wall-time budget: the lane must stay a
# CI-speed check (<60s CPU; measured ~4s), so a hung lowering fails
# loudly instead of stalling the pipeline.
timeout -k 10 60 env JAX_PLATFORMS=cpu "$PY" -m tools.mxlint --contracts

echo "lint: PASS"
