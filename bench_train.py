"""Benchmark: train-plane round flight recorder overhead + completeness.

Writes BENCH_TRAIN.json: the per-round cost of the gang round flight
recorder (util/gangrec.py) as a fraction of step wall, measured on a
standalone in-process TrainSession driving the REAL report() path —
telemetry derivation, phase accounting, and the record append — with no
cluster (headless: records hold in the bounded ring, exactly the
contract a head outage exercises).

Three rows:

1. ``recorder_overhead`` — identical spin-calibrated train loops with
   the record append live vs patched out.  The recorder's contract is
   <= 2% of step wall (one dict append per round; no locks beyond the
   ring's, no device work); ``overhead_frac`` is the tracked number.
   The hard gate is deliberately loose (25%) —
   a noisy 2-vCPU CI box cannot hold a 2% assertion without flaking,
   but a blowup means the record path grew a sync or lock contention
   and must fail loudly.
2. ``record_completeness`` — after N reported rounds, drain_buffered()
   must hold exactly N records, sequentially numbered, every one
   carrying the full field set, with ZERO drops.  A recorder regression
   (ring stops filling, a field dropped, silent drops) fails here
   instead of surviving until a post-mortem needs the black box.
3. ``skew_join_check`` — a synthetic 4-rank round through the pure
   head-side join (gangrec.skew_profile) must name the seeded straggler
   rank and guilty phase.

Usage:
    python bench_train.py            # full counts -> BENCH_TRAIN.json
    python bench_train.py --smoke    # small counts, no artifact rewrite
                                     # unless --out is given
"""

from __future__ import annotations

import argparse
import json
import os
import tempfile
import time
from typing import Dict, List

from ray_tpu.train import session as train_session
from ray_tpu.util import gangrec

#: Per-round record fields the completeness row requires (the skew join
#: and the detectors read these; a dropped field breaks them silently).
REQUIRED_FIELDS = {
    "gang", "rank", "world", "round", "t", "wall_s", "data_s", "coll_s",
    "coll_bytes", "ack_s", "ckpt_s", "compile_s", "tokens", "tps", "mfu",
}


def _build_session(trial_dir: str) -> "train_session.TrainSession":
    sess = train_session.TrainSession(
        world_rank=0, world_size=1, trial_dir=trial_dir,
        restored_checkpoint=None)
    sess.gang_id = "bench"
    return sess


def _spin(seconds: float) -> None:
    """Busy-wait step body: identical wall in both arms, so the loop
    delta isolates the recorder (a sleep would let the OS hide it)."""
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def _run_loop(n_rounds: int, step_s: float, record: bool,
              trial_dir: str) -> float:
    """One train loop through the real report() path; returns total
    wall.  The lockstep ack is pre-released each round — a standalone
    session has no driver, and the semaphore acquire must not block."""
    gangrec.drain_buffered()
    sess = _build_session(trial_dir)
    orig = gangrec.record_round
    if not record:
        gangrec.record_round = lambda rec: None
    try:
        t0 = time.perf_counter()
        for _ in range(n_rounds):
            _spin(step_s)
            sess.consumed.release()
            sess.report({"tokens": 256})
        wall = time.perf_counter() - t0
    finally:
        gangrec.record_round = orig
        gangrec.drain_buffered()
    return wall


def run_recorder_overhead(n_rounds: int, step_s: float,
                          trial_dir: str) -> Dict:
    """Recorder-on vs recorder-off wall on identical spin-calibrated
    loops, best-of-2 trials per arm (interference only slows a trial
    down)."""
    walls: Dict[str, float] = {}
    for on in (True, False):
        trials = [_run_loop(n_rounds, step_s, on, trial_dir)
                  for _ in range(2)]
        walls["on" if on else "off"] = min(trials)
    overhead = walls["on"] / max(walls["off"], 1e-9) - 1.0
    if overhead > 0.25:
        raise SystemExit(
            f"recorder-overhead row FAILED: round flight recorder cost "
            f"{overhead:.1%} of step wall (contract: ~2%)")
    return {
        "rounds": n_rounds,
        "step_wall_s": step_s,
        "wall_on_s": round(walls["on"], 6),
        "wall_off_s": round(walls["off"], 6),
        "per_round_cost_us": round(
            max(0.0, walls["on"] - walls["off"]) / n_rounds * 1e6, 2),
        "overhead_frac": round(max(0.0, overhead), 4),
    }


def run_record_completeness(n_rounds: int, trial_dir: str) -> Dict:
    """Every reported round must land in the ring, fully populated, with
    zero drops — and the headless flush must be a hold, not a loss."""
    gangrec.drain_buffered()
    dropped0 = gangrec.dropped_total()
    sess = _build_session(trial_dir)
    for _ in range(n_rounds):
        sess.consumed.release()
        sess.report({"tokens": 64})
    # Headless contract: no client -> flush is a no-op for the RPC half
    # and the records stay buffered in the BOUNDED ring.
    if gangrec.flush_rounds(None) != 0:
        raise SystemExit(
            "record-completeness row FAILED: headless flush claimed to "
            "ship records with no client")
    recs: List[Dict] = gangrec.drain_buffered()
    if len(recs) != n_rounds:
        raise SystemExit(
            f"record-completeness row FAILED: {n_rounds} rounds reported "
            f"but {len(recs)} records buffered")
    if [r.get("round") for r in recs] != list(range(1, n_rounds + 1)):
        raise SystemExit(
            "record-completeness row FAILED: rounds not sequential")
    for r in recs:
        missing = REQUIRED_FIELDS - set(r)
        if missing:
            raise SystemExit(
                "record-completeness row FAILED: record missing fields "
                f"{sorted(missing)}")
    if gangrec.dropped_total() != dropped0:
        raise SystemExit(
            "record-completeness row FAILED: records dropped during an "
            "in-bounds run")
    return {"rounds": n_rounds, "records": len(recs), "dropped": 0}


def run_skew_join_check() -> Dict:
    """The pure head-side join must name a seeded data straggler."""
    def rec(rank: int, wall: float, data: float) -> Dict:
        return {"gang": "bench", "rank": rank, "world": 4, "round": 7,
                "t": time.time(), "wall_s": wall, "data_s": data,
                "coll_s": 0.0, "ckpt_s": 0.0, "compile_s": 0.0,
                "ack_s": 0.0, "tokens": 64, "mfu": 0.3}

    prof = gangrec.skew_profile({
        0: rec(0, 0.10, 0.01), 1: rec(1, 0.10, 0.01),
        2: rec(2, 0.42, 0.33), 3: rec(3, 0.11, 0.02)})
    if prof is None or prof["straggler"] != 2 or prof["phase"] != "data":
        raise SystemExit(
            f"skew-join row FAILED: expected straggler rank 2 in data, "
            f"got {prof}")
    return {"straggler": prof["straggler"], "phase": prof["phase"],
            "skew_s": prof["skew_s"], "skew_frac": prof["skew_frac"]}


def main(argv=None) -> Dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small counts; no artifact rewrite unless --out")
    ap.add_argument("--out", default=None,
                    help="artifact path (default BENCH_TRAIN.json unless "
                         "--smoke)")
    args = ap.parse_args(argv)

    n_rounds = 60 if args.smoke else 300
    step_s = 0.002

    report: Dict = {"metric": "train_round_recorder_bench"}
    with tempfile.TemporaryDirectory() as trial_dir:
        report["skew_join_check"] = run_skew_join_check()
        report["record_completeness"] = run_record_completeness(
            n_rounds, trial_dir)
        report["recorder_overhead"] = run_recorder_overhead(
            n_rounds, step_s, trial_dir)

    out = args.out or (None if args.smoke else "BENCH_TRAIN.json")
    if out:
        with open(out, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        print(f"wrote {os.path.abspath(out)}")
    print(json.dumps(report, indent=2))
    ov = report["recorder_overhead"]
    print(f"round recorder: {ov['per_round_cost_us']}us/round "
          f"({ov['overhead_frac']:.2%} of a {step_s * 1e3:.0f}ms step)")
    return report


if __name__ == "__main__":
    main()
