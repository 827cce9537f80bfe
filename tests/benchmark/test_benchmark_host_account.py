"""The benchmark's share of PR 54: the six readers of who had the loop
thread and of a token's way out of the replica
(``benchmarks/layer_metrics/_quiet.py``) on hand-made step records and on
the real records of the rehearsal deployment on the CPU with a profiler
session open for part of the run, and their entries in ``BENCHMARK.json``,
looked up by name and by membership.  No reader times anything: they read
what the engine put on the records the profiler did not touch."""

import importlib
import threading
import time

import pytest
from bench_testlib import ROOT

from benchmarks import spec

CELLS = {"internlm2-1.8b.serve-prefix-sessions",
         "olmoe-1b-7b-0125.serve-saturated",
         "smallthinker-21b-a3b-L8.serve-long-mixed",
         "glm-4.7-flash-L6.serve-agent-shared-context",
         "trinity-mini-L5.serve-reasoning-long-decode",
         "kimi-linear-48b-a3b-L13.serve-long-decode-doc-tail",
         "jamba2-3b.serve-reasoning-wide-batch"}
ENTRIES = {
    "decode_period_ms.quiet": (
        "ms", "lower", "program_span", "engine loop (host)"),
    "loop_cpu_ms.quiet": (
        "ms", "lower", "program_counter", "engine loop (host)"),
    "loop_wait_ms.quiet": (
        "ms", "lower", "program_counter", "engine loop (host)"),
    "replica_cpu_share.quiet": (
        "%", "lower", "program_counter", "engine loop (host)"),
    "token_exit_ms.quiet": (
        "ms", "lower", "program_span", "handle and router"),
    "stream_pull_waiting_share.quiet": (
        "%", "higher", "program_counter", "handle and router"),
}
NEW_KEYS = ("cpu_s", "wait_s", "proc_cpu_s", "tokens_out", "wake_s",
            "store_s", "pull_s", "pull_waiting")


def reader(metric):
    return importlib.import_module(
        "benchmarks.layer_metrics." + metric.replace(".", "_")).read


def step(i, **kw):
    """A hand-made record of a pure decode step dispatched ahead at 128
    slots: a 15 ms period (16 every third) of which the loop thread ran 6,
    waited 7 (8) for the interpreter and 2 for the chip, with the process at
    14 ms of CPU; 128 tokens left the replica after 4 ms each, 96 of them
    into a pull that waited."""
    between = 0.005 + 0.001 * (i % 3 == 0)
    rec = {"t": 100.0 + 0.016 * i, "engine": "1.0", "step": i,
           "t0": 0.016 * i, "ahead": 1, "wall_s": 0.010, "stall_s": 0.0,
           "occupancy": 128, "slots": 128, "admitted": 0,
           "between_s": between, "idle_s": 0.0, "upload_s": 0.0,
           "dispatch_s": 0.003, "readback_s": 0.002, "emit_s": 0.003,
           "starved_s": 0.0, "starved": {}, "first_tokens": [],
           "cpu_s": 0.006, "wait_s": 0.002 + between,
           "proc_cpu_s": 0.014, "tokens_out": 128, "wake_s": 0.256,
           "store_s": 0.064, "pull_s": 0.192, "pull_waiting": 96}
    rec.update(kw)
    return rec


def hand_made(n=120, traced=range(40, 100), **kw):
    """``n`` records; those in ``traced`` were closed while a profiler ran,
    and read as the profiler's loop does: a 27 ms period, 20 ms of CPU."""
    slow = dict(traced=1, wall_s=0.020, cpu_s=0.020, proc_cpu_s=0.040,
                wake_s=1.0, pull_waiting=0)
    after = dict(wall_s=0.015, cpu_s=0.012, wake_s=0.6)
    first = min(traced, default=n)
    return [step(i, **dict(slow if i in traced else after if i > first
                           else {}, **kw)) for i in range(n)]


def ctx_of(steps, kind="serve_closed"):
    return {"kind": kind, "steps": steps, "seconds": 2.0, "trace": {}}


WANT = {
    # 40 quiet records: 15 ms, every third 16: the median is 15
    "decode_period_ms.quiet": 15.0,
    # means, not medians (a CPU clock that ticks): 26 waits of 7 ms, 14 of 8
    "loop_cpu_ms.quiet": 6.0,
    "loop_wait_ms.quiet": 7.35,
    # 14 ms of CPU over 26 periods of 15 ms and 14 of 16
    "replica_cpu_share.quiet": 100.0 * 40 * 0.014 / (26 * 0.015 + 14 * 0.016),
    # (256 + 64 + 192) ms over 128 tokens
    "token_exit_ms.quiet": 4.0,
    "stream_pull_waiting_share.quiet": 75.0,
}


@pytest.mark.parametrize("metric", sorted(ENTRIES))
def test_reader_on_hand_made_records(metric):
    read = reader(metric)
    # The records of the traced seconds and those behind them are slower in
    # every key: none of them is read.
    assert read(ctx_of(hand_made())) == pytest.approx(WANT[metric], rel=1e-9)
    # An untraced window is quiet throughout: the same run's first forty
    # records alone read the same, and all of them where none was traced.
    assert read(ctx_of(hand_made()[:40])) == pytest.approx(WANT[metric])
    assert read(ctx_of(hand_made(traced=()))) is not None
    # The floor is twenty quiet records.
    assert read(ctx_of(hand_made(traced=range(20, 100)))) is not None
    assert read(ctx_of(hand_made(traced=range(19, 100)))) is None
    assert read(ctx_of(hand_made(traced=range(0, 100)))) is None
    # The parent's records carry none of the keys: nothing to read, no
    # raise; nor where one key is missing from one record.
    old = [{k: v for k, v in r.items() if k not in NEW_KEYS}
           for r in hand_made()]
    assert read(ctx_of(old)) is None
    for key in NEW_KEYS:
        steps = hand_made()
        del steps[7][key]
        assert read(ctx_of(steps)) is None, key
    # Nor records from before the starvation account, no records, training.
    bare = [{k: v for k, v in r.items() if k != "starved_s"}
            for r in hand_made()]
    assert read(ctx_of(bare)) is None
    assert read(ctx_of([])) is None
    assert read({"kind": "train", "steps": 4, "seconds": 2.0}) is None


def test_the_loop_readers_filter_as_the_period_reader_does():
    steps = hand_made()
    # Not pure decode steps dispatched ahead: an admission, a turn that
    # waited for its dispatch, an empty engine.  The loop's four readers
    # pass over them; the token's two count every quiet record.
    steps[0].update(admitted=1, stall_s=0.03, wall_s=0.5, cpu_s=0.4,
                    wait_s=0.05, proc_cpu_s=0.5)
    steps[1].update(ahead=0, wall_s=0.5, cpu_s=0.4, wait_s=0.05)
    steps[2].update(occupancy=0, wall_s=0.7, cpu_s=0.6, wait_s=0.05)
    for metric in ("decode_period_ms.quiet", "loop_cpu_ms.quiet"):
        assert reader(metric)(ctx_of(steps)) == pytest.approx(WANT[metric])
    # The mean of the 37 left: 24 waits of 7 ms, 13 of 8.
    assert reader("loop_wait_ms.quiet")(ctx_of(steps)) \
        == pytest.approx((24 * 7 + 13 * 8) / 37)
    steps[0].update(tokens_out=0, wake_s=0.0, store_s=0.0, pull_s=0.0,
                    pull_waiting=0)
    assert reader("token_exit_ms.quiet")(ctx_of(steps)) == pytest.approx(4.0)
    steps[1].update(pull_waiting=128)
    assert reader("stream_pull_waiting_share.quiet")(ctx_of(steps)) \
        == pytest.approx(100.0 * (38 * 96 + 128) / (39 * 128))
    # Quiet records with no pure step among them, and an engine no worker
    # pulls from (tokens_out 0 throughout): nothing, no division.
    only_admissions = [dict(r, admitted=1) for r in hand_made()]
    assert reader("loop_cpu_ms.quiet")(ctx_of(only_admissions)) is None
    assert reader("replica_cpu_share.quiet")(ctx_of(only_admissions)) is None
    unpulled = hand_made(tokens_out=0)
    assert reader("token_exit_ms.quiet")(ctx_of(unpulled)) is None
    assert reader("stream_pull_waiting_share.quiet")(ctx_of(unpulled)) is None


def test_quiet_is_by_the_wall_clock_at_which_a_record_closed():
    from benchmarks.layer_metrics._quiet import FLOOR, quiet

    assert FLOOR == 20
    steps = hand_made()
    assert [r["step"] for r in quiet(ctx_of(steps))] == list(range(40))
    # A record with no `traced` key that closed after the session opened
    # (another engine's, the session's last): not quiet.
    steps[30]["t"] = steps[45]["t"]
    assert [r["step"] for r in quiet(ctx_of(steps))] \
        == [i for i in range(40) if i != 30]


@pytest.mark.parametrize("metric", sorted(ENTRIES))
def test_the_metric_is_in_the_benchmark_by_name_and_membership(metric):
    doc = spec.load_benchmark(ROOT)
    spec.validate(doc)
    # By name and by membership, not by place: the next entry goes behind.
    entry, = [m for m in doc["per_layer"] if m["name"] == metric]
    unit, better, source, layer = ENTRIES[metric]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": metric, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": "serve_tok_s"}
    # The seven cells and no other: those of the period's older reader.
    assert set(entry["workloads"]) == CELLS
    assert len(entry["workloads"]) == len(CELLS)
    period, = [m for m in doc["per_layer"]
               if m["name"] == "decode_period_ms.sat"]
    assert set(period["workloads"]) == CELLS
    assert layer in {m["layer"] for m in doc["per_layer"]
                     if m["name"] not in ENTRIES}
    serve_tok_s, = [m for m in doc["end_to_end"]
                    if m["name"] == "serve_tok_s"]
    assert CELLS <= set(serve_tok_s["workloads"])


def test_the_harness_finds_each_reader_by_its_name_in_its_cells():
    from benchmarks.run import read_metrics

    doc = spec.load_benchmark(ROOT)
    for w in doc["workloads"]:
        cell = spec.load_cell(w["name"], ROOT)
        mine = [m for m in cell["per_layer"] if m["name"] in ENTRIES]
        if w["name"] not in CELLS:
            assert not mine, w["name"]
            continue
        assert {m["name"] for m in mine} == set(ENTRIES), w["name"]
        got = read_metrics(mine, "layer_metrics", ctx_of(hand_made()))
        assert set(got) == set(ENTRIES)
        assert got["token_exit_ms.quiet"] == {"value": 4.0, "unit": "ms"}
        # The parent's records: the line is printed without them.
        old = [{k: v for k, v in r.items() if k not in NEW_KEYS}
               for r in hand_made()]
        assert read_metrics(mine, "layer_metrics", ctx_of(old)) == {}


def test_the_six_on_the_rehearsal_deployments_real_records(tmp_path):
    """The benchmark's own deployment at the tiny configuration on the CPU,
    its streams pulled through a real handle, a profiler session open for
    the middle third of the run (the replica's own ``trace_start``): the six
    readers return numbers from the real records, leave the traced ones and
    those behind them out, and read nothing once the new keys are gone."""
    import ray_tpu
    from benchmarks.serve_cell import deploy
    from ray_tpu import serve
    from ray_tpu.core.context import ctx as rt_ctx

    cell = spec.rehearsal_cell(
        spec.load_cell("internlm2-1.8b.serve-prefix-sessions", ROOT), ROOT)
    cell["traffic"] = dict(cell["traffic"], engine=dict(
        cell["traffic"]["engine"], batch_slots=4, max_new_tokens_cap=64))
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4,
                 system_config=cell["traffic"].get("system_config"))
    try:
        served = deploy(cell, seed=5400000001, platform="cpu", num_tpus=0,
                        fail_phase="", log=lambda rec: None,
                        phase=lambda name: None)

        def third():
            def client(i):
                for _ in served.stream.remote([3 + i, 5, 7, 11], 60, 0.0):
                    pass
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(4)]
            for th in threads:
                th.start()
            for th in threads:
                th.join()

        third()
        served.call("trace_start", str(tmp_path))
        third()
        served.call("trace_stop")
        third()
        # What the last streams' consumers pulled after the engine's last
        # record closed is booked by the next record: one more token's.
        time.sleep(0.5)
        assert len(list(served.stream.remote([1], 1, 0.0))) == 1
        assert served.call("flush_step_records")["dropped"] == 0
        time.sleep(0.5)
        rows = rt_ctx.client.call(
            "list_state", {"kind": "engine_steps"})["items"]
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    steps = [r for row in rows for r in row["records"]]
    flags = ["traced" in r for r in steps]
    first, last = flags.index(True), len(flags) - flags[::-1].index(True)
    assert all(flags[first:last]) and first >= 40 and len(flags) - last >= 40
    ctx = ctx_of(steps)
    got = {m: reader(m)(ctx) for m in ENTRIES}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["stream_pull_waiting_share.quiet"] <= 100
    assert got["loop_cpu_ms.quiet"] <= got["decode_period_ms.quiet"]
    # What they read is the first third's and no other record's.
    assert got == {m: reader(m)(ctx_of(steps[:first])) for m in ENTRIES}
    assert got["decode_period_ms.quiet"] \
        == reader("decode_period_ms.sat")(ctx_of(steps[:first]))
    # Quiet tokens were counted out, each once: the first third's 240 less
    # those whose pull ended after its last record closed (a consumer on a
    # busy machine is handed its last tokens in one late bundle).
    quiet_out = sum(r["tokens_out"] for r in steps[:first])
    assert 120 <= quiet_out <= 240
    # Over the whole run every token: 720, and the last request's one if
    # its pull ended before its record closed.
    assert sum(r["tokens_out"] for r in steps) in (720, 721)
    # The parent's program writes none of the keys.
    old = [{k: v for k, v in r.items() if k not in NEW_KEYS} for r in steps]
    assert {m: reader(m)(ctx_of(old)) for m in ENTRIES} \
        == dict.fromkeys(ENTRIES)
    assert reader("decode_period_ms.sat")(ctx_of(old)) is not None
