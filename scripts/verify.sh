#!/usr/bin/env bash
# Canonical tier-1 verification gate (the exact ROADMAP.md command):
# CPU-only pytest over tests/, excluding slow tests, with a dot-count
# summary.  CI and the builder invoke this one script so the gate can't
# drift between them.
#
# Usage: scripts/verify.sh [extra pytest args...]
set -o pipefail

cd "$(dirname "$0")/.."
LOG="${T1_LOG:-/tmp/_t1.log}"
TIMEOUT="${T1_TIMEOUT:-870}"
rm -f "$LOG"

# Static analysis first: rtlint (RT001-RT012) takes about a minute on the
# live package (53 s alone on an idle 8-core machine, 39 s of it in RT010;
# measured for PR 51, not the "~2s" this comment used to claim), still far
# less than the tests, and a drift finding fails faster and more precisely
# than the test breakage it foreshadows.  scripts/lint.sh exits non-zero on unallowlisted findings.
if ! scripts/lint.sh; then
    echo "rtlint failed — fix the findings above (or justify them in"
    echo ".rtlint-allowlist) before running tests"
    exit 1
fi

timeout -k 10 "$TIMEOUT" env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly "$@" 2>&1 | tee "$LOG"
rc=${PIPESTATUS[0]}

echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$LOG" \
    | tr -cd . | wc -c)"

if [ "$rc" -ne 0 ]; then
    # Failure forensics: tail every cluster process log (worker/daemon
    # side) so CI failures come with post-mortems.  Routes through the
    # head's log index when a cluster is still up; otherwise falls back to
    # scanning /tmp/ray_tpu_logs on this machine.
    echo "=== cluster process log tails (tier-1 run failed, rc=$rc) ==="
    python -m ray_tpu logs --post-mortem --tail 4000 || true
    # Health-plane snapshot: if a cluster is still reachable, the open
    # incident ring usually names the failure class (partition, drop
    # pressure, SLO burn) faster than the raw log tails do.
    echo "=== open incidents (health plane) ==="
    python -m ray_tpu incidents 2>/dev/null || true
    # Gang skew snapshot: a hung/failed train test usually shows up here
    # as a straggling rank or a round that never joined.
    echo "=== gang round skew (train plane) ==="
    python -m ray_tpu gang 2>/dev/null || true
fi
exit "$rc"
