"""Who had the loop thread, and a token's way out of the replica (PR 54):
``cpu_s`` / ``wait_s`` / ``proc_cpu_s`` and ``tokens_out`` /
``wake_s`` / ``store_s`` / ``pull_s`` / ``pull_waiting`` on the step record,
on a real ``InferenceEngine`` on the CPU and through a real ``serve.run``
handle; the worker's direct-stream counters on a plain streaming actor; the
columns of ``ray_tpu status`` / ``top``; and the histogram's many-values
call, which is what lets the emit pass call each instrument once a step.

The invariants (PERF.md §3): on the record's own numbers ``cpu_s + wait_s +
readback_s`` (plus its admissions' ``prefill_wait_s``) is ``wall_s +
between_s`` to the rounding; another thread that spins in Python takes the
interpreter from the loop, which shows as waiting and not as CPU; every
token a client got was counted out once; a consumer that lags shows in
``pull_s`` and ``pull_waiting``, not in ``wake_s``.
"""

import threading
import time

import pytest

from test_engine_starved import _records, _tiny_engine

import ray_tpu
from ray_tpu import serve
from ray_tpu.util import steprec

#: test_engine_starved's tiny engine, with room for a hundred tokens.
GEOMETRY = dict(batch_slots=4, page_size=8, max_prompt_len=16,
                max_new_tokens_cap=128)
HOPS = ("tokens_out", "wake_s", "store_s", "pull_s", "pull_waiting")
TOL = 1e-5  # a dozen seconds on a record, each rounded to the microsecond


def _pure(recs):
    return [r for r in recs if not r["admitted"] and r["occupancy"]]


def test_the_threads_seconds_tile_the_records_period():
    steprec.drain_buffered()
    eng = _tiny_engine(**GEOMETRY)
    try:
        threads = [threading.Thread(target=lambda i=i: list(eng.submit(
            [1 + i, 2, 3], max_new_tokens=20))) for i in range(6)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        recs = _records(eng, 6)
    finally:
        eng.shutdown()
    assert len(_pure(recs)) >= 20
    # Every record, on its own numbers: what is not the thread's CPU nor
    # the chip's is its waiting.  (The first record's between_s reaches
    # back to the engine's making, before the loop thread started.)
    for r in recs[1:]:
        assert r["cpu_s"] >= 0 and r["proc_cpu_s"] >= 0
        chip = r["readback_s"] + sum(e["prefill_wait_s"]
                                     for e in r["first_tokens"])
        assert r["cpu_s"] + r["wait_s"] + chip == pytest.approx(
            r["wall_s"] + r["between_s"], abs=TOL), r
        # The thread's CPU inside a wait for the chip is in cpu_s too.
        assert r["wait_s"] > -1e-3
    # Without admission nothing but the readback is the chip's.
    for r in _pure(recs[1:]):
        assert r["cpu_s"] + r["wait_s"] + r["readback_s"] == pytest.approx(
            r["wall_s"] + r["between_s"], abs=TOL)
    # The loop ran: most of a tiny step's period on the CPU is its Python.
    assert sum(r["cpu_s"] for r in recs) > 0
    # No worker pulls these streams: the hops a worker times are empty,
    # the one the engine times is not.
    assert sum(r["wake_s"] for r in recs) > 0
    assert all(r[k] == 0 for r in recs for k in HOPS if k != "wake_s")


def test_with_the_step_record_off_no_hop_is_kept():
    """``EngineConfig.step_record=False`` closes no record, so nothing would
    take a hop's seconds off the engine: none are stamped, and what a
    stream's end hands over does not pile up for the life of the server."""
    steprec.drain_buffered()
    eng = _tiny_engine(step_record=False, **GEOMETRY)
    try:
        streams = [eng.submit([1 + i, 2, 3], max_new_tokens=12)
                   for i in range(6)]
        reqs = [st._req for st in streams]
        assert all(len(list(st)) == 12 for st in streams)
    finally:
        eng.shutdown()
    assert not eng._wake_late and eng._wake_s == 0
    assert all(r.wake_s == 0 and r.wake_seen == 0 for r in reqs)
    assert not [r for r in steprec.drain_buffered()
                if r.get("engine") == eng.engine_id]


def test_a_spinning_thread_shows_as_waiting_not_as_cpu():
    """A second thread in pure Python holds the interpreter for a switch
    interval (5 ms) whenever the loop lets go of it: the loop thread's
    periods grow by what it WAITED, and its CPU seconds a step stay."""
    steprec.drain_buffered()
    eng = _tiny_engine(**GEOMETRY)
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            pass

    try:
        list(eng.submit([1, 2, 3], max_new_tokens=4))  # compiled before
        _records(eng, 1)
        list(eng.submit([1, 2, 3], max_new_tokens=60))
        alone = _pure(_records(eng, 1))
        spinner = threading.Thread(target=spin, daemon=True)
        spinner.start()
        list(eng.submit([1, 2, 3], max_new_tokens=60))
        stop.set()
        spinner.join()
        beside = _pure(_records(eng, 1))
    finally:
        stop.set()
        eng.shutdown()
    assert len(alone) >= 40 and len(beside) >= 40

    def mean(recs, key):
        return sum(r[key] for r in recs) / len(recs)

    def period(recs):
        return mean(recs, "wall_s") + mean(recs, "between_s")

    # The period grows by milliseconds a step, and what grows is not the
    # thread's CPU: it is waiting, booked as ``wait_s`` or, where the loop
    # has to take the interpreter back on its way out of the blocking
    # read, inside ``readback_s`` (PERF.md §7).
    grown = period(beside) - period(alone)
    assert grown > 1e-3
    assert mean(beside, "cpu_s") - mean(alone, "cpu_s") < 0.25 * grown
    assert (mean(beside, "wait_s") + mean(beside, "readback_s")
            - mean(alone, "wait_s") - mean(alone, "readback_s")) > 0.75 * grown
    assert mean(beside, "cpu_s") < 3 * mean(alone, "cpu_s")
    # Two threads that both want the interpreter keep the process on a core
    # (where the machine has one to give).
    busy = sum(r["proc_cpu_s"] for r in beside) / sum(
        r["wall_s"] + r["between_s"] for r in beside)
    assert busy > 0.4


@pytest.fixture
def rt():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    yield ray_tpu
    serve.shutdown()
    ray_tpu.shutdown()


def _hop_sums(handle):
    """The hop keys summed over every record the head has of the replica's
    engine, after a one-token request whose record (a prefill that ends at
    once) books what the requests before it left unbooked."""
    from ray_tpu.core.context import ctx

    time.sleep(0.3)  # the last pulls of what came before
    assert len(list(handle.options(stream=True).remote([1], 1))) == 1
    seen, deadline = -1, time.time() + 30
    while time.time() < deadline:
        time.sleep(0.5)
        rows = ctx.client.call("list_state",
                               {"kind": "engine_steps"})["items"]
        recs = [r for row in rows for r in row["records"]]
        if recs and len(recs) == seen:  # the flush cadence has caught up
            break
        seen = len(recs)
    return {k: sum(r[k] for r in recs) for k in HOPS}


def test_every_token_a_client_got_is_counted_out_and_a_late_consumer_shows(
        rt):
    handle = serve.run(serve.llm_app(
        engine=dict(GEOMETRY, max_queue=8), name="llm"))

    def consume(n, nap):
        got = 0
        for _ in handle.options(stream=True).remote([5, 7, 11], n):
            got += 1
            if nap:
                time.sleep(nap)
        return got

    assert consume(5, 0) == 5
    base = _hop_sums(handle)
    # Each _hop_sums sends one token of its own, whose pull may or may not
    # have ended when its record closed.
    assert 5 <= base["tokens_out"] <= 6
    assert consume(100, 0) == 100
    fast = _hop_sums(handle)
    assert 106 <= fast["tokens_out"] <= 107
    assert consume(100, 0.01) == 100
    slow = _hop_sums(handle)
    assert 207 <= slow["tokens_out"] <= 208

    def over(a, b, key):
        return b[key] - a[key]

    # A consumer that keeps up finds its pull waiting for some tokens (the
    # tiny engine emits faster than a round trip, so on a busy machine for
    # as few as a stream's first ones); one that naps between pulls for no
    # more than those, and its tokens wait at the replica.  (The strict
    # fall is held on a paced source, in the next test.)
    assert over(fast, slow, "pull_waiting") <= over(base, fast, "pull_waiting")
    assert over(fast, slow, "pull_waiting") < 50
    assert over(fast, slow, "pull_s") > 10 * over(base, fast, "pull_s")
    assert over(fast, slow, "pull_s") > 0.5  # 100 tokens, 10 ms naps
    # Not the engine's hop, nor the store: the stream's thread took each
    # token as soon as before.
    assert over(fast, slow, "wake_s") < 5 * over(base, fast, "wake_s") + 0.05
    assert over(fast, slow, "store_s") < 5 * over(base, fast, "store_s") + 0.05
    assert over(base, fast, "wake_s") > 0 and over(base, fast, "store_s") > 0


def test_the_workers_direct_stream_counters(rt):
    """``core.worker_main`` knows nothing of engines: a plain actor whose
    method yields an item every 10 ms.  A consumer that keeps pace finds
    its pull WAITING for every item; one that naps finds the items it missed
    waiting for its pull."""

    @ray_tpu.remote
    class Source:
        def items(self, n):
            for i in range(n):
                time.sleep(0.01)
                yield i

        def counts(self):
            from ray_tpu.core.context import direct_stream_counts

            return direct_stream_counts()

    src = Source.remote()

    def consume(nap):
        got = []
        for ref in src.items.options(num_returns="streaming").remote(30):
            got.append(ray_tpu.get(ref))
            if nap:
                time.sleep(nap)
        assert got == list(range(30))
        time.sleep(0.2)
        return ray_tpu.get(src.counts.remote())

    zero = ray_tpu.get(src.counts.remote())
    assert zero == {"items": 0, "store_s": 0.0, "pull_s": 0.0, "waiting": 0}
    fast = consume(0)
    assert fast["items"] == 30 and fast["waiting"] >= 25
    assert 0 < fast["store_s"] < 0.1
    assert fast["pull_s"] < 0.1
    slow = consume(0.05)
    assert slow["items"] == 60
    assert slow["waiting"] - fast["waiting"] <= 5
    assert slow["pull_s"] - fast["pull_s"] > 0.2


def test_status_rows_show_who_had_the_loop_thread():
    """`ray_tpu status` / `top`: `cpu%`, `wait%` and `proc%` are `cpu_s`,
    `wait_s` and `proc_cpu_s` over `wall_s + between_s` of the records that
    carry them, `exit_ms` a token's mean way out; '-' for an engine whose
    records do not."""
    from ray_tpu.scripts import _engine_rows

    def rec(**kw):
        return dict({"wall_s": 0.03, "stall_s": 0.0, "occupancy": 2,
                     "slots": 4, "between_s": 0.01, "ahead": 1}, **kw)

    held = dict(cpu_s=0.02, wait_s=0.012, proc_cpu_s=0.036, tokens_out=4,
                wake_s=0.004, store_s=0.001, pull_s=0.003, pull_waiting=3)
    new = [rec(**held), rec(**held), rec(**dict(held, tokens_out=0))]
    old = [rec(), rec()]
    silent = [rec(**dict(held, tokens_out=0))]
    rows = _engine_rows(
        [{"engine": "1.0", "records": new, "latest": new[-1]},
         {"engine": "2.0", "records": old, "latest": old[-1]},
         {"engine": "3.0", "records": silent, "latest": silent[-1]}], [])
    assert [(r["cpu%"], r["wait%"], r["proc%"], r["exit_ms"])
            for r in rows] == [
        ("50.0", "30.0", "90.0", "3.00"),  # 3 x 8 ms over 8 tokens
        ("-", "-", "-", "-"),
        ("50.0", "30.0", "90.0", "-")]  # an engine no worker pulls from


CASES = {
    "a step's gaps": [0.0004, 0.0012, 0.0031, 0.0031, 0.02, 0.3, 2.0],
    "one value": [0.0025],
    "on the boundaries": [0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                          0.25, 1],
    "128 slots": [0.0149 + 1e-5 * (i % 7) for i in range(128)],
    "nothing": [],
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_many_values_leave_the_histogram_where_single_calls_do(case):
    from ray_tpu.util.metrics import Histogram

    bounds = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 1)
    one = Histogram("one_by_one", boundaries=bounds, register=False)
    many = Histogram("at_once", boundaries=bounds, register=False)
    for _ in range(2):  # onto what is there, not only from nothing
        for v in CASES[case]:
            one.observe(v)
        many.observe_many(CASES[case])
    a, b = one._snapshot(), many._snapshot()
    assert len(a) == len(b) == (1 if CASES[case] else 0)
    for x, y in zip(a, b):
        assert (x["buckets"], x["sum"], x["count"]) \
            == (y["buckets"], y["sum"], y["count"])  # exactly: same order
        assert x["count"] == 2 * len(CASES[case]) == sum(x["buckets"])
    tagged = Histogram("tagged", boundaries=bounds, tag_keys=("k",),
                       register=False)
    tagged.observe_many(CASES[case], tags={"k": "v"})
    assert [r["tags"] for r in tagged._snapshot()] \
        == ([{"k": "v"}] if CASES[case] else [])
