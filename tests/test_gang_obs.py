"""Gang training observability: recorder-ring units (bound + drop
accounting + black box), skew-join units, gang detector units (seeded
fires AND clean stays silent), the gang CLI, and the chaos e2e — a seeded
slow rank inside a live 4-rank gang must open exactly ONE gang-straggler
incident naming the injected rank and phase, `doctor` must replay its
evidence chain (worst rounds + a linked trace critical-pathed through a
collective-op span), and the incident must resolve after the slowdown
lifts.

The clean-gang test doubles as the false-positive gate: an evenly paced
gang must open ZERO gang incidents while still joining skew profiles.
"""

import json
import os
import random
import time
from collections import deque
from types import SimpleNamespace

import pytest

import ray_tpu
from ray_tpu.util import chaos, gangrec
from ray_tpu.util.health import (
    SEV_CRIT,
    SEV_WARN,
    detect_gang_collective_desync,
    detect_gang_data_starvation,
    detect_gang_mfu_regression,
    detect_gang_straggler,
)

SEED = int(os.environ.get("RT_CHAOS_SEED", "3"))
WORLD = 4


# ------------------------------------------------------------ recorder ring


@pytest.fixture
def fresh_rec(monkeypatch):
    """Isolated gangrec module state with a small, test-sized config."""
    monkeypatch.setattr(gangrec, "_ring", deque())
    monkeypatch.setattr(gangrec, "_recent", deque())
    monkeypatch.setattr(gangrec, "_dropped_total", 0)
    monkeypatch.setattr(gangrec, "_warned_drop", False)
    monkeypatch.setattr(gangrec, "_last_dump_t", 0.0)
    monkeypatch.setattr(gangrec, "_cfg", lambda: SimpleNamespace(
        gang_ring_size=32, gang_dump_records=8, gang_dump_interval_s=0.0))
    return gangrec


def test_ring_bounds_and_drop_accounting(fresh_rec):
    """Overflow past gang_ring_size drops (counted, never blocking) while
    the black-box mirror keeps only the last gang_dump_records."""
    for i in range(40):
        fresh_rec.record_round({"round": i, "rank": 0})
    kept = fresh_rec.drain_buffered()
    # ring floor is max(16, cfg) = 32; the ring keeps the OLDEST records
    # (drops happen at the tail so flushed batches stay contiguous).
    assert [r["round"] for r in kept] == list(range(32))
    assert fresh_rec.dropped_total() == 8
    # the last-N mirror tracks the newest records regardless of drops.
    assert [r["round"] for r in fresh_rec._recent] == list(range(32, 40))
    # drain emptied the ring; new records buffer again.
    fresh_rec.record_round({"round": 99, "rank": 0})
    assert [r["round"] for r in fresh_rec.drain_buffered()] == [99]


def test_flush_batches_and_counts_failures(fresh_rec):
    calls = []

    class _RPC:
        closed = False

    class _Client:
        rpc = _RPC()

        def call_batched(self, method, body):
            calls.append((method, body))

    for i in range(5):
        fresh_rec.record_round({"round": i, "rank": 1})
    assert fresh_rec.flush_rounds(_Client()) == 5
    assert calls == [("gang_round_batch",
                      {"rounds": [{"round": i, "rank": 1}
                                  for i in range(5)]})]
    # nothing buffered -> no RPC.
    assert fresh_rec.flush_rounds(_Client()) == 0
    assert len(calls) == 1

    class _Failing(_Client):
        def call_batched(self, method, body):
            raise OSError("wire down")

    fresh_rec.record_round({"round": 9, "rank": 1})
    assert fresh_rec.flush_rounds(_Failing()) == 0
    assert fresh_rec.dropped_total() == 1
    # headless (no client, no ctx): records HOLD in the ring.
    fresh_rec.record_round({"round": 10, "rank": 1})
    assert fresh_rec.flush_rounds(None) == 0
    assert [r["round"] for r in fresh_rec.drain_buffered()] == [10]


def test_black_box_sidecar_atomic_rewrite(fresh_rec, tmp_path, monkeypatch):
    monkeypatch.setenv("RT_LOG_PATH", str(tmp_path / "rank0.log"))
    assert fresh_rec.black_box_path() == str(tmp_path / "rank0.rounds.log")
    for i in range(12):
        fresh_rec.record_round({"round": i, "rank": 0, "wall_s": 0.01})
    assert fresh_rec.dump_black_box(force=True)
    lines = (tmp_path / "rank0.rounds.log").read_text().splitlines()
    assert lines[0].startswith("#")
    recs = [json.loads(ln) for ln in lines[1:]]
    # only the last gang_dump_records (8) survive, newest last.
    assert [r["round"] for r in recs] == list(range(4, 12))


# ------------------------------------------------------------- skew join


def _rec(rank, wall, data=0.0, coll=0.0, ckpt=0.0, comp=0.0, **kw):
    rec = {"gang": "g1", "rank": rank, "round": 7, "t": 100.0 + rank,
           "wall_s": wall, "data_s": data, "coll_s": coll, "ckpt_s": ckpt,
           "compile_s": comp, "ack_s": 0.0}
    rec.update(kw)
    return rec


def test_skew_profile_names_data_straggler():
    prof = gangrec.skew_profile({
        0: _rec(0, 0.10, data=0.01),
        1: _rec(1, 0.40, data=0.31),
        2: _rec(2, 0.11, data=0.02),
        3: _rec(3, 0.10, data=0.01),
    })
    assert prof["straggler"] == 1 and prof["phase"] == "data"
    assert prof["world"] == 4 and prof["round"] == 7
    assert 0.25 < prof["skew_s"] < 0.35
    assert prof["skew_frac"] > 1.0


def test_skew_profile_collective_wait_not_charged_to_waiter():
    """Ranks parked inside allreduce waiting on a slow peer must NOT read
    as stragglers: collective wait subtracts from own time, so the rank
    that made everyone wait carries the skew."""
    prof = gangrec.skew_profile({
        0: _rec(0, 0.50, coll=0.40),   # waited 0.4s inside the collective
        1: _rec(1, 0.50, coll=0.02),   # arrived last: real work the while
    })
    assert prof["straggler"] == 1 and prof["phase"] == "compute"
    assert prof["skew_s"] == pytest.approx(0.38, abs=0.01)
    assert prof["coll_frac"] > 0.3


def test_skew_profile_checkpoint_phase_and_world1():
    prof = gangrec.skew_profile({
        0: _rec(0, 0.10, ckpt=0.30),
        1: _rec(1, 0.10, ckpt=0.01),
        2: _rec(2, 0.10, ckpt=0.01),
    })
    assert prof["straggler"] == 0 and prof["phase"] == "checkpoint"
    # single-rank gang: profile exists, zero skew (nothing to lag).
    solo = gangrec.skew_profile({0: _rec(0, 0.2)})
    assert solo["world"] == 1 and solo["skew_s"] == 0.0
    assert gangrec.skew_profile({}) is None


# --------------------------------------------------------- detector units


def _prof(rnd, straggler=1, phase="data", skew_s=0.05, wall_s=0.1,
          now=1000.0, gang="g1", **kw):
    p = {"gang": gang, "round": rnd, "world": 4, "t": now - 0.2 * rnd,
         "wall_s": wall_s, "skew_s": skew_s, "skew_frac": skew_s / wall_s,
         "straggler": straggler, "phase": phase, "phase_lag_s": skew_s,
         "data_frac": 0.1, "coll_frac": 0.1, "mfu": None}
    p.update(kw)
    return p


def test_straggler_detector_fires_with_rank_phase_and_worst_rounds():
    profs = [_prof(i, straggler=2, phase="data", skew_s=0.04 + 0.01 * i)
             for i in range(8)]
    hits = detect_gang_straggler(profs, 1000.0, 30.0)
    assert [f["kind"] for f in hits] == ["gang_straggler"]
    f = hits[0]
    assert f["key"] == "gang_straggler:g1" and f["severity"] == SEV_WARN
    assert f["data"]["rank"] == 2 and f["data"]["phase"] == "data"
    worst = f["data"]["worst_rounds"]
    assert len(worst) == 3
    assert [w["round"] for w in worst] == [7, 6, 5]  # ranked by skew


def test_straggler_detector_crit_escalation():
    profs = [_prof(i, straggler=0, phase="checkpoint", skew_s=0.15)
             for i in range(6)]
    hits = detect_gang_straggler(profs, 1000.0, 30.0)
    assert hits and hits[0]["severity"] == SEV_CRIT  # skew >= median wall


def test_straggler_detector_clean_silent():
    # Round-robin slow ranks (ordinary jitter): dominance test holds.
    rotate = [_prof(i, straggler=i % 4, skew_s=0.06) for i in range(12)]
    assert detect_gang_straggler(rotate, 1000.0, 30.0) == []
    # One dominant rank but negligible skew: fraction test holds.
    tiny = [_prof(i, straggler=1, skew_s=0.005) for i in range(12)]
    assert detect_gang_straggler(tiny, 1000.0, 30.0) == []
    # Too few rounds in window.
    few = [_prof(i, straggler=1, skew_s=0.08) for i in range(4)]
    assert detect_gang_straggler(few, 1000.0, 30.0) == []
    # Stale profiles outside the window never count.
    stale = [_prof(i, straggler=1, skew_s=0.08, now=0.0) for i in range(8)]
    assert detect_gang_straggler(stale, 1000.0, 30.0) == []


def test_data_starvation_detector_fires_and_clean_silent():
    starved = [_prof(i, data_frac=0.65) for i in range(6)]
    hits = detect_gang_data_starvation(starved, 1000.0, 30.0)
    assert [f["key"] for f in hits] == ["gang_data_starvation:g1"]
    assert hits[0]["data"]["data_frac"] >= 0.5
    fed = [_prof(i, data_frac=0.2) for i in range(12)]
    assert detect_gang_data_starvation(fed, 1000.0, 30.0) == []


def test_collective_desync_detector_fires_and_clean_silent():
    parked = [_prof(i, coll_frac=0.75) for i in range(6)]
    hits = detect_gang_collective_desync(parked, 1000.0, 30.0)
    assert [f["key"] for f in hits] == ["gang_collective_desync:g1"]
    synced = [_prof(i, coll_frac=0.2) for i in range(12)]
    assert detect_gang_collective_desync(synced, 1000.0, 30.0) == []


def test_mfu_regression_detector_fires_and_clean_silent():
    sagging = [_prof(i, mfu=0.5 if i < 6 else 0.3) for i in range(12)]
    hits = detect_gang_mfu_regression(sagging, 1000.0, 30.0)
    assert [f["kind"] for f in hits] == ["gang_mfu_regression"]
    assert hits[0]["data"]["drop_frac"] >= 0.2
    flat = [_prof(i, mfu=0.5) for i in range(12)]
    assert detect_gang_mfu_regression(flat, 1000.0, 30.0) == []
    # MFU-less gangs (no flops_per_step reported) never fire.
    blind = [_prof(i) for i in range(12)]
    assert detect_gang_mfu_regression(blind, 1000.0, 30.0) == []


# ----------------------------------------------------------- cluster e2e


def _incidents(kind=None):
    from ray_tpu.core.context import ctx

    reply = ctx.client.call("list_state", {"kind": "incidents"})
    if kind is not None:
        reply = dict(reply, items=[i for i in reply["items"]
                                   if i["kind"] == kind])
    return reply


def _gang_state():
    from ray_tpu.core.context import ctx

    return ctx.client.call("list_state", {"kind": "gang_rounds"})["items"]


def _gang_loop(config=None):
    import time as _t

    import numpy as np

    from ray_tpu import collective, train
    from ray_tpu.train.session import get_session

    sess = get_session()
    shard = train.get_dataset_shard("train")
    it = shard.iter_batches(batch_size=8)
    # Fixed round count per rank (streaming_split hands blocks out
    # dynamically, so batch counts per rank are NOT equal — but the skew
    # join needs every rank to report every round).
    for _ in range(int((config or {}).get("rounds", 10))):
        batch = next(it, None)
        n = int(len(batch["id"])) if batch is not None else 0
        _t.sleep((config or {}).get("body_s", 0.01))
        # One host collective per round: the round record's coll_s and the
        # propagation-only collective:allreduce span both come from here.
        collective.allreduce(np.array([float(n)], np.float32),
                             group_name=sess.collective_group)
        train.report({"tokens": n})


def _fit_gang(tmp_path, rounds_per_rank, env_vars=None, body_s=0.01):
    import ray_tpu.data as rtd
    from ray_tpu.train import DataParallelTrainer, RunConfig, ScalingConfig

    # 2x row headroom over the gang's total demand: streaming_split hands
    # blocks to whichever rank asks, so no rank may run dry mid-run.
    rows = WORLD * rounds_per_rank * 8 * 2
    ds = rtd.range(rows, override_num_blocks=WORLD * 4)
    sc = dict(num_workers=WORLD)
    if env_vars:
        sc["runtime_env"] = {"env_vars": env_vars}
    trainer = DataParallelTrainer(
        _gang_loop,
        train_loop_config={"body_s": body_s, "rounds": rounds_per_rank},
        scaling_config=ScalingConfig(**sc),
        run_config=RunConfig(storage_path=str(tmp_path)),
        datasets={"train": ds},
    )
    return trainer.fit()


@pytest.fixture
def rt_gang_tight():
    """Short health windows so the straggle -> incident -> resolve arc
    fits a test's patience."""
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8, system_config={
        "health_window_s": 6.0,
        "health_resolve_after_s": 2.0,
    })
    yield ray_tpu
    chaos.disarm_straggler()
    ray_tpu.shutdown()


def test_clean_gang_joins_profiles_and_opens_no_incidents(
        rt_gang_tight, tmp_path, capsys):
    """False-positive gate: an evenly paced 4-rank gang joins skew
    profiles head-side (world, rounds, per-rank records, skew metrics)
    and opens ZERO gang incidents; the gang CLI renders both views."""
    result = _fit_gang(tmp_path, rounds_per_rank=10, body_s=0.05)
    assert result.error is None

    deadline = time.monotonic() + 20.0
    gangs = []
    while time.monotonic() < deadline:
        gangs = _gang_state()
        if gangs and len(gangs[0].get("profiles") or []) >= 6:
            break
        time.sleep(0.3)
    else:
        pytest.fail(f"gang rounds never joined: {gangs}")
    g = gangs[0]
    assert g["world"] == WORLD
    assert len(g["ranks"]) == WORLD
    prof = g["latest"]
    assert prof["world"] == WORLD and prof["wall_s"] > 0
    # an evenly paced gang keeps skew well under the detector threshold.
    for pr in g["profiles"][2:]:
        assert pr["skew_frac"] < 3.0  # sanity bound, not the detector gate

    # let at least one full health window of ticks pass: detectors see
    # >= straggler_min_rounds profiles and must stay quiet.
    time.sleep(3.0)
    reply = _incidents()
    gang_incs = [i for i in reply["items"] if i["kind"].startswith("gang_")]
    assert gang_incs == [], f"clean gang opened: {gang_incs}"

    # satellite metrics land in the cluster aggregate: per-op collective
    # timing/bytes from the ranks, skew + data-wait from head and ranks.
    from ray_tpu.core.context import ctx

    rows = ctx.client.call("list_state", {"kind": "metrics"})["items"]
    names = {r["name"] for r in rows}
    assert "ray_tpu_gang_round_skew_seconds" in names
    # Rank-side counters survive teardown because TrainWorker.run ships
    # the final metrics window synchronously before the done sentinel.
    assert "ray_tpu_gang_rounds_flushed_total" in names
    ops = {r["tags"].get("op") for r in rows
           if r["name"] == "ray_tpu_collective_op_seconds"}
    assert "allreduce" in ops
    assert any(r["name"] == "ray_tpu_collective_bytes_total"
               and r["value"] > 0 for r in rows)

    from ray_tpu import scripts

    assert scripts.main(["gang"]) == 0
    out = capsys.readouterr().out
    assert g["gang"] in out and "STRAGGLER" in out
    assert scripts.main(["gang", g["gang"], "--rounds", "5"]) == 0
    out = capsys.readouterr().out
    assert f"world {WORLD}" in out and "PHASE" in out
    assert scripts.main(["gang", "no-such-gang"]) == 1


@pytest.mark.chaos
def test_seeded_straggler_opens_one_incident_then_resolves(
        rt_gang_tight, tmp_path, capsys):
    """Chaos e2e: RT_CHAOS_STRAGGLER slows ONE seeded rank's data phase
    inside a live 4-rank gang.  Exactly one gang_straggler incident must
    open naming that rank and the data phase, `doctor` replays the
    evidence (worst rounds + linked trace critical-pathed through a
    collective-op span), and the incident resolves once the slowdown
    lifts with the run's end."""
    from ray_tpu.util import tracing

    expected_rank = random.Random(SEED).randrange(WORLD)
    with tracing.trace("gang-train", force=True):
        result = _fit_gang(
            tmp_path, rounds_per_rank=12, body_s=0.01,
            env_vars={
                "RT_CHAOS_STRAGGLER": f"phase=data,ms=250,ranks={WORLD}",
                "RT_CHAOS_SEED": str(SEED),
            })
    assert result.error is None

    inc = None
    deadline = time.monotonic() + 25.0
    while time.monotonic() < deadline and inc is None:
        items = _incidents("gang_straggler")["items"]
        if items and items[0].get("evidence", {}).get("worst_rounds"):
            inc = items[0]
        time.sleep(0.3)
    assert inc is not None, \
        f"straggler incident never opened; gangs={_gang_state()}"

    items = _incidents("gang_straggler")["items"]
    assert len(items) == 1, f"dedup failed: {items}"
    assert inc["data"]["rank"] == expected_rank, inc["summary"]
    assert inc["data"]["phase"] == "data", inc["summary"]
    ev = inc["evidence"]
    assert ev["rank"] == expected_rank and ev["phase"] == "data"
    assert 1 <= len(ev["worst_rounds"]) <= 3
    assert len(ev["trace_ids"]) >= 1, ev

    from ray_tpu import scripts

    assert scripts.main(["doctor", inc["id"]]) == 0
    out = capsys.readouterr().out
    assert f"straggler rank {expected_rank}" in out
    assert "late in data" in out and "worst round:" in out
    # the slowest linked trace's rendering walks through the gang's
    # collective-op spans (propagation-only tracing in collective.py).
    assert "collective:allreduce" in out
    assert scripts.main(["gang"]) == 0
    assert "r" + str(expected_rank) in capsys.readouterr().out

    # Heal: the run ended with the slowdown, profiles age out of the 6s
    # window, 2s of detector quiet resolves the incident.
    deadline = time.monotonic() + 45.0
    while time.monotonic() < deadline:
        items = _incidents("gang_straggler")["items"]
        if items and items[0]["state"] == "resolved":
            break
        time.sleep(0.5)
    else:
        pytest.fail("straggler incident never resolved after heal")
