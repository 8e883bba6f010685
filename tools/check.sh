#!/usr/bin/env bash
# Pre-PR gate (ISSUE 9): the lint plane (invariant rules + generic pass),
# the mission-control self-test and the two seconds-scale fencing drills.
# Every stage reports a verdict, none a rate: speed is measured on the
# chip by benchmark/run.py (PERF.md).  Exit nonzero on the first failing
# stage.  TESTING.md "Static-analysis gate" documents the workflow.
#
#   tools/check.sh                 # full gate
#   APEXLINT_ONLY=1 tools/check.sh # lint + self-test only (skips the drills)
set -u -o pipefail
cd "$(dirname "$0")/.."

tmp="$(mktemp -d)"
trap 'rm -rf "$tmp"' EXIT

echo "== stage 1: apexlint (invariant rules + generic pass) =="
python tools/apexlint.py pytorch_distributed_tpu tools --json \
    > "$tmp/apexlint.json"
lint_rc=$?
if [ "$lint_rc" -ne 0 ]; then
    # exit 2 = usage/config error (malformed baseline, unknown rule):
    # the real message is already on stderr and no JSON was written
    if [ "$lint_rc" -eq 2 ]; then
        echo "apexlint: CONFIG ERROR (see the message above — likely"
        echo "tools/apexlint_baseline.json or the invocation)"
        exit "$lint_rc"
    fi
    python - "$tmp/apexlint.json" <<'EOF' || true
import json, sys
try:
    d = json.load(open(sys.argv[1]))
except Exception:
    sys.exit(1)
for f in d["findings"]:
    print(f"  {f['path']}:{f['line']} · {f['rule']} · {f['message']}")
for e in d["stale_baseline"]:
    print(f"  stale baseline: {e['rule']} at {e['path']}")
EOF
    echo "apexlint: FAIL (fix the findings or baseline them with a"
    echo "justification in tools/apexlint_baseline.json)"
    exit "$lint_rc"
fi
echo "apexlint: PASS ($(python -c "import json,sys;d=json.load(open('$tmp/apexlint.json'));print(f\"{d['files']} files, {d['baselined']} baselined\")"))"

echo "== stage 1b: fleet_top --selftest (mission-control alert plane) =="
# the ISSUE-10 smoke: a synthetic gateway + mission control probed over
# the real wire — T_METRICS push, absence alert fires, --json blocks
# round-trip.  Seconds-scale, no jax.
if ! JAX_PLATFORMS=cpu python tools/fleet_top.py --selftest; then
    echo "fleet_top --selftest: FAIL"
    exit 1
fi

if [ "${APEXLINT_ONLY:-0}" = "1" ]; then
    echo "APEXLINT_ONLY=1: skipping the fencing drills (stages 1c, 1d)"
    exit 0
fi

echo "== stage 1c: gateway failover drill (ISSUE 16) =="
# the fast HA drill: kill the primary under a live synthetic fleet —
# the warm standby must promote within one lease window, clients must
# fail over, the ledger must stay EXACT (failover_lost counted), and
# the gateway_failover alert must fire and resolve.  Seconds-scale,
# no jax; a standby that never promotes is a readable nonzero verdict
if ! JAX_PLATFORMS=cpu python tools/chaos_soak.py \
        --seconds 6 --kill-gateway 1.5 --gateway-lease 0.6; then
    echo "gateway failover drill: FAIL"
    exit 1
fi

echo "== stage 1d: shard-loss degradation drill (ISSUE 20) =="
# the fast replay-shard drill: kill one shard of a live 3-shard
# priority plane — the lease must fence within one window, sampling
# must continue on the survivors, the row ledger must stay EXACT
# (minted == ingested + shard_lost + route_dropped), the dead
# generation's write-backs must be rejected, and the rejoined shard
# must pass the join barrier.  Seconds-scale, no jax.
if ! JAX_PLATFORMS=cpu python tools/chaos_soak.py \
        --seconds 6 --kill-shard 1.5 --rejoin-shard --shard-lease 0.5; then
    echo "shard-loss drill: FAIL"
    exit 1
fi

echo "pre-PR gate: ALL STAGES PASS"
