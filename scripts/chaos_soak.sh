#!/usr/bin/env bash
# Chaos soak: run the fault-injection test subset N times with rotating
# seeds and fail on ANY flake.  The chaos subset is everything marked
# `chaos` (see pyproject.toml markers) plus the kill-cadence tests in
# tests/test_chaos.py — the tests that exercise preemption drains,
# in-memory checkpoint recovery, and elastic gang resize.
#
# Usage:
#   scripts/chaos_soak.sh [N]               # default N=5
#   scripts/chaos_soak.sh --race-sentinel [N]
#   scripts/chaos_soak.sh --head-kill [N]   # head SIGKILL+restart subset only
#   scripts/chaos_soak.sh --netfault [N]    # network fault-injection subset
#   scripts/chaos_soak.sh --straggler [N]   # gang-straggler drill only
#   CHAOS_PYTEST_ARGS="-k drain" scripts/chaos_soak.sh 10
#
# Rotating seeds: each iteration exports RT_CHAOS_SEED=<iter>, which the
# chaos tests feed to their PreemptionInjector / victim RNGs, so every
# pass kills a different node/worker mix.
#
# --netfault soaks the network chaos subset (tests/test_netfault.py):
# seeded partitions, gray stalls, and dropped/duplicated frames via the
# util/netfault FaultSchedule.  Each iteration rotates RT_NETFAULT_SEED;
# on a failure the armed schedule lines ("netfault: armed seed=... spec=...")
# are replayed from the log so the exact fault sequence reproduces with
# RT_NETFAULT_SEED=<seed> alone.
#
# --race-sentinel (or RT_DEBUG_LOCKS=2 in the environment) soaks with the
# devtools.locks runtime race sentinel armed in EVERY process: lock
# ordering is checked transitively and each guarded dataplane field
# rebind asserts its _RT_GUARDED_BY lock is held — so the SIGTERM chaos
# interleavings double as a data-race hunt, not just a recovery test.
#
# --head-kill soaks only the head-crash drill (tests/test_head_crash.py):
# an external head is SIGKILLed mid-workload and restarted with the same
# port/session/state; the pass criteria are zero failed direct calls,
# full field-state resync, and the headless suicide deadline.
#
# --straggler soaks the gang-straggler drill (tests/test_gang_obs.py
# -m chaos): a seeded util/chaos StragglerSchedule slows ONE rank's data
# phase, and the pass criteria are exactly one gang_straggler incident
# naming the seeded rank + phase (with worst-round evidence and linked
# traces), then resolution after the run ends.  Rotating RT_CHAOS_SEED
# rotates the victim rank, so a soak sweeps detection across ranks.
set -u -o pipefail

LOCKS_LEVEL="${RT_DEBUG_LOCKS:-0}"
MODE="default"
while [ $# -gt 0 ]; do
    case "$1" in
        --race-sentinel) LOCKS_LEVEL=2; shift ;;
        --head-kill) MODE="head-kill"; shift ;;
        --netfault) MODE="netfault"; shift ;;
        --straggler) MODE="straggler"; shift ;;
        *) break ;;
    esac
done
N="${1:-5}"
cd "$(dirname "$0")/.."

if [ "$MODE" = "head-kill" ]; then
    TARGETS="tests/test_head_crash.py"
    MARK="chaos"
elif [ "$MODE" = "netfault" ]; then
    # test_health.py's chaos test is the incident-plane assertion for this
    # mode: a seeded partition under live traffic must open >=1
    # partition-suspicion incident (with evidence) and resolve after heal.
    TARGETS="tests/test_netfault.py tests/test_health.py"
    MARK="chaos"
elif [ "$MODE" = "straggler" ]; then
    # The seeded-straggler drill: each seed picks a different victim
    # rank (random.Random(seed).randrange(world)), so the soak sweeps
    # the skew-join + detector + doctor path across every rank.
    TARGETS="tests/test_gang_obs.py"
    MARK="chaos"
else
    TARGETS="tests/test_fault_tolerance.py tests/test_chaos.py tests/test_head_crash.py"
    MARK="chaos"
fi

fails=0
for i in $(seq 1 "$N"); do
    echo "=== chaos soak iteration $i/$N (mode=$MODE seed=$i) ==="
    LOG="$(mktemp /tmp/chaos_soak.XXXXXX.log)"
    # RT_DEBUG_JIT=1: every engine/learner warmup arms the recompile
    # sentinel, so a chaos path that perturbs a jitted program's shapes
    # fails the iteration with the arg delta instead of silently
    # paying a compile per step (devtools.jitguard / rtlint RT010).
    if ! env JAX_PLATFORMS=cpu RT_CHAOS_SEED="$i" \
        RT_NETFAULT_SEED="$i" \
        RT_DEBUG_LOCKS="$LOCKS_LEVEL" \
        RT_DEBUG_JIT=1 \
        timeout -k 10 600 python -m pytest -q \
        -m "$MARK" $TARGETS \
        -p no:cacheprovider -p no:randomly \
        ${CHAOS_PYTEST_ARGS:-} 2>&1 | tee "$LOG"; then
        echo "!!! chaos soak FAILED on iteration $i (seed $i)"
        if [ "$MODE" = "netfault" ]; then
            echo "!!! failing fault schedules (replay with RT_NETFAULT_SEED=$i):"
            grep -h "netfault: armed" "$LOG" | sort -u || true
        fi
        fails=$((fails + 1))
    fi
    rm -f "$LOG"
done

if [ "$fails" -gt 0 ]; then
    echo "chaos soak: $fails/$N iterations flaked"
    exit 1
fi

if [ "$MODE" = "netfault" ]; then
    # False-positive gate: with the chaos plane disarmed, a clean serve
    # smoke plus a clean cluster under live traffic must open ZERO
    # incidents — the detectors page on faults, not on ordinary load.
    echo "=== netfault false-positive gate (clean run, no injection) ==="
    if ! env JAX_PLATFORMS=cpu timeout -k 10 300 python -m pytest -q \
        tests/test_serve_engine.py -k "span_tree or one_compiled" \
        -p no:cacheprovider -p no:randomly >/dev/null 2>&1; then
        echo "!!! false-positive gate: clean serve engine smoke failed"
        exit 1
    fi
    if ! env JAX_PLATFORMS=cpu timeout -k 10 300 python -m pytest -q \
        tests/test_health.py::test_clean_cluster_opens_no_incidents \
        -p no:cacheprovider -p no:randomly; then
        echo "!!! false-positive gate: clean cluster opened incidents"
        exit 1
    fi
    echo "netfault false-positive gate: clean (zero incidents)"
fi

if [ "$MODE" = "straggler" ]; then
    # False-positive gate: an uninjected gang must open ZERO gang_*
    # incidents — the dominance test exists so ordinary round jitter
    # never pages.
    echo "=== straggler false-positive gate (clean gang, no injection) ==="
    if ! env JAX_PLATFORMS=cpu timeout -k 10 300 python -m pytest -q \
        tests/test_gang_obs.py::test_clean_gang_joins_profiles_and_opens_no_incidents \
        -p no:cacheprovider -p no:randomly; then
        echo "!!! false-positive gate: clean gang opened incidents"
        exit 1
    fi
    echo "straggler false-positive gate: clean (zero gang incidents)"
fi
echo "chaos soak: $N/$N iterations green"
