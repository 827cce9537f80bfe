"""The per-layer readers of the engine loop's own time account
(``benchmarks/layer_metrics/_phases.py``): on hand-made step records, on the
real records of a tiny engine on the CPU, and in the traced rehearsal of the
saturated cell.  No reader times anything: they read what the engine put on
its records."""

import importlib
import threading

import pytest
from bench_testlib import run_bench

SAT = "internlm2-1.8b.serve-saturated"


def reader(metric):
    return importlib.import_module(
        "benchmarks.layer_metrics." + metric.replace(".", "_")).read


def step(i, admitted=0, **kw):
    """A hand-made record: a pure decode step unless told otherwise, with
    a between that grows with ``i`` so that medians are not a constant."""
    rec = {"t": 100.0 + 0.04 * i, "engine": "1.0", "step": i, "t0": 0.04 * i,
           "wall_s": 0.035, "stall_s": 0.0, "occupancy": 16, "slots": 16,
           "admitted": admitted, "between_s": 0.001 * (i % 5), "idle_s": 0.0,
           "upload_s": 0.0, "dispatch_s": 0.002, "readback_s": 0.030 + 0.001
           * (i % 3), "emit_s": 0.001, "first_tokens": []}
    rec.update(kw)
    return rec


def entries(n):
    return [{"queue_s": 0.001 * k, "prefill_s": 0.020 + 0.001 * k,
             "prefill_wait_s": 0.015, "ttft_s": 0.03 + 0.002 * k,
             "prompt": 100, "bucket": 128, "cached": 0} for k in range(n)]


def hand_made(n_requests):
    """Fifty steps of a second each two: pure decode steps, and every fifth
    a step that admitted (and stalled for) its share of the requests."""
    steps, todo = [], entries(n_requests)
    for i in range(50):
        if i % 5 == 4:
            mine, todo = todo[:n_requests // 10], todo[n_requests // 10:]
            steps.append(step(i, admitted=len(mine), stall_s=0.025,
                              wall_s=0.060, upload_s=0.0005,
                              first_tokens=mine))
        else:
            steps.append(step(i))
    return steps


def ctx_of(steps, kind="serve_closed", seconds=2.0):
    return {"kind": kind, "steps": steps, "seconds": seconds}


STRIPPED = ("between_s", "idle_s", "upload_s", "dispatch_s", "readback_s",
            "emit_s", "first_tokens")

# metric, what the hand-made records with 60 requests give, and the number
# of requests under which the reader has nothing to say (its floor).
CASES = [
    # pure steps: between 0,1,2,3 ms over i%5 in 0..3 (median 1.5) + 2 + 1
    ("decode_host_ms.sat", 4.5, None),
    # 30, 31, 32 ms over i%3 of the 40 pure steps: median 31
    ("decode_device_wait_ms.sat", 31.0, None),
    # 40 x 35 ms + 10 x 60 ms of wall, between 10 x (0+1+2+3+4) ms, of 2 s
    ("loop_accounted_share.sat", 105.0, None),
    # queue_s 0..59 ms: the 90th percentile interpolates to 53.1
    ("admit_queue_wait_p90_ms.mixed", 53.1, 50),
    # prefill_s 20..79 ms: median 49.5
    ("prefill_ms.mixed", 49.5, 20),
]


@pytest.mark.parametrize("metric,want,floor", CASES,
                         ids=[c[0] for c in CASES])
def test_reader_on_hand_made_records(metric, want, floor):
    read = reader(metric)
    steps = hand_made(60)
    assert read(ctx_of(steps)) == pytest.approx(want, rel=1e-9)
    # The parent's records carry no account: nothing to read, no raise.
    for key in STRIPPED:
        old = [{k: v for k, v in r.items() if k != key} for r in steps]
        assert read(ctx_of(old)) is None, key
    assert read(ctx_of([])) is None
    assert read({"kind": "train", "steps": 4, "seconds": 2.0}) is None
    if floor is not None:
        assert read(ctx_of(hand_made(floor))) is not None
        assert read(ctx_of(hand_made(floor - 10))) is None


def test_mixed_readers_on_a_tiny_engines_real_records():
    """Sixty requests through a tiny engine on the CPU: the two readers of
    ``first_tokens`` give what the entries themselves say."""
    import jax
    import jax.numpy as jnp

    from benchmarks.arith import median, percentile
    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine
    from ray_tpu.util import steprec

    cfg = LlamaConfig.tiny(remat=False, dtype=jnp.float32)
    eng = InferenceEngine(
        cfg, llama_init(cfg, jax.random.PRNGKey(0)),
        EngineConfig(batch_slots=4, page_size=8, max_prompt_len=16,
                     max_new_tokens_cap=32, max_queue=64), seed=0)
    steprec.drain_buffered()
    try:
        threads = [threading.Thread(target=lambda i=i: list(eng.submit(
            [1 + i % 7, 2, 3 + i % 5], max_new_tokens=3))) for i in range(60)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    finally:
        eng.shutdown()
    steps = [r for r in steprec.drain_buffered()
             if r["engine"] == eng.engine_id]
    got = [e for r in steps for e in r["first_tokens"]]
    assert len(got) == 60
    ctx = ctx_of(steps, kind="serve_open")
    assert reader("admit_queue_wait_p90_ms.mixed")(ctx) == pytest.approx(
        1e3 * percentile([e["queue_s"] for e in got], 90.0))
    assert reader("prefill_ms.mixed")(ctx) == pytest.approx(
        1e3 * median([e["prefill_s"] for e in got]))
    # Four slots for sixty requests at once: the tail waited for a slot,
    # which is many steps, and the wait is part of its time to first token.
    assert reader("admit_queue_wait_p90_ms.mixed")(ctx) \
        > reader("prefill_ms.mixed")(ctx)
    assert all(e["ttft_s"] >= e["queue_s"] for e in got)


def test_saturated_rehearsal_prints_the_loops_account_beside_the_old():
    rc, lines, err = run_bench(
        "--workload", SAT, "--rehearse", "--trace", "1", "--seconds", "3")
    assert rc == 0, err[-2000:]
    out = lines[-1]
    assert out["correct"] is True and out["device"]["platform"] == "cpu"
    assert set(out["metrics"]) == {
        "prefill_stall_share.sat", "batch_occupancy.sat",
        "decode_step_ms.sat", "worker_start_s", "warmup_compile_s",
        "decode_host_ms.sat", "decode_device_wait_ms.sat",
        "loop_accounted_share.sat"}
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert all(v > 0 for v in m.values())
    assert out["metrics"]["loop_accounted_share.sat"]["unit"] == "%"
