"""The engine's starvation account (PR 38): ``starved_s`` / ``starved`` on
the step record and ``starved_s`` on a ``first_tokens`` entry, on a real
``InferenceEngine`` on the CPU; ``traced`` under a profiler session;
``starved%`` of ``ray_tpu status`` / ``top``; and the mean token gap of the
``engine:decode`` span, which no longer comes from a list a request kept.

The invariants (PERF.md §3): the starved seconds of a record lie inside its
``wall_s + between_s`` and sum over the phases they passed in; a record
with no admission whose step went out ahead, and whose successor's did too,
has none (the chip always had a step); nothing to run is ``idle_s`` and not
starvation; a record's entries sum to no more than the record's total.
"""

import threading
import time

import pytest

from ray_tpu.util import steprec

GEOMETRY = dict(batch_slots=4, page_size=8, max_prompt_len=16,
                max_new_tokens_cap=32)
PHASES = {"admit", "prefill", "upload", "dispatch", "emit", "record",
          "between"}
TOL = 3e-6  # every second on a record is rounded to the microsecond


def _tiny_engine(**overrides):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    cfg = LlamaConfig.tiny(remat=False, dtype=jnp.float32)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    kw = dict(GEOMETRY, max_queue=16)
    kw.update(overrides)
    return InferenceEngine(cfg, params, EngineConfig(**kw), seed=0)


def _records(eng, evicted, timeout_s=10.0):
    """This engine's step records, drained until ``evicted`` requests have
    left their slots."""
    recs, deadline = [], time.time() + timeout_s
    while time.time() < deadline:
        recs += [r for r in steprec.drain_buffered()
                 if r.get("engine") == eng.engine_id]
        if sum(r["evicted"] for r in recs) >= evicted:
            break
        time.sleep(0.05)
    return recs


def _hold_invariants(recs):
    for r in recs:
        assert isinstance(r["starved_s"], float) and r["starved_s"] >= 0
        assert set(r["starved"]) <= PHASES, r["starved"]
        assert all(s > 0 for s in r["starved"].values()), r["starved"]
        assert sum(r["starved"].values()) == pytest.approx(
            r["starved_s"], abs=TOL)
        assert r["starved_s"] <= r["wall_s"] + r["between_s"] + TOL, r
        assert sum(e["starved_s"] for e in r["first_tokens"]) \
            <= r["starved_s"] + TOL, r
        assert all(e["starved_s"] >= 0 for e in r["first_tokens"])
        assert "traced" not in r  # no profiler session in these tests
    for r, succ in zip(recs, recs[1:]):
        if r["admitted"] == 0 and r["ahead"] and succ["ahead"]:
            assert r["starved_s"] == 0 and r["starved"] == {}, (r, succ)


def test_steps_dispatched_ahead_between_two_admissions_starve_nothing():
    """One sequence decodes alone for a while, then a second is admitted
    beside it: the records of a run of steps dispatched ahead between two
    admissions, and of the admissions themselves."""
    steprec.drain_buffered()
    eng = _tiny_engine()
    try:
        a = eng.submit([2, 4, 6, 8], max_new_tokens=24)
        head = [next(a) for _ in range(10)]
        b = threading.Thread(
            target=lambda: list(eng.submit([9, 1], max_new_tokens=5)))
        b.start()
        b.join()
        assert len(head + list(a)) == 24
        recs = _records(eng, 2)
    finally:
        eng.shutdown()
    _hold_invariants(recs)
    assert [r["admitted"] for r in recs if r["admitted"]] == [1, 1]
    first, second = [i for i, r in enumerate(recs) if r["admitted"]]
    run = recs[first + 1:second - 1]  # second - 1 read its step alone
    assert len(run) >= 6
    assert all(r["ahead"] == 1 and r["starved_s"] == 0 for r in run), run
    # The chip stood still around each admission, and the loop says in
    # which phases: the prefill's host part, the mirrors' upload, the
    # step's dispatch.
    for r in (recs[first], recs[second]):
        assert r["ahead"] == 0 and r["starved_s"] > 0
        assert {"prefill", "upload", "dispatch"} <= set(r["starved"])
        entry, = r["first_tokens"]
        assert 0 < entry["starved_s"] <= r["starved_s"] + TOL
        # All of it but what passed after the record's own step was read.
        assert entry["starved_s"] >= r["starved_s"] - sum(
            r["starved"].get(k, 0.0) for k in ("emit", "record")) - TOL
    # The step read just before the second admission went out ahead but
    # had none ahead of it: from its readback on the chip had nothing.
    assert recs[second - 1]["ahead"] == 1
    assert set(recs[second - 1]["starved"]) <= {"emit", "record", "between"}
    assert recs[second - 1]["starved_s"] > 0


def test_a_record_with_two_admissions_charges_each_its_own_stretch():
    """Two requests admitted in one round: two prefills, the second behind
    the first's token, then the upload and the dispatch, which are the
    second's: the entries split the record's starved seconds between them.
    A control op holds the loop thread while both are queued; its seconds
    lie in no phase, `between`, with the chip empty all the while."""
    steprec.drain_buffered()
    eng = _tiny_engine()
    hold = threading.Event()
    try:
        parked = threading.Thread(
            target=lambda: eng._run_on_loop(lambda: hold.wait(5.0)))
        parked.start()
        time.sleep(0.06)
        streams = [eng.submit([3, 5, 7], max_new_tokens=4),
                   eng.submit([2, 4, 6, 8, 1], max_new_tokens=4)]
        hold.set()
        parked.join()
        assert [len(list(s)) for s in streams] == [4, 4]
        recs = _records(eng, 2)
    finally:
        hold.set()
        eng.shutdown()
    _hold_invariants(recs)
    two, = [r for r in recs if r["admitted"] == 2]
    first, second = two["first_tokens"]
    assert first["starved_s"] > 0 and second["starved_s"] > 0
    assert first["starved_s"] + second["starved_s"] <= two["starved_s"] + TOL
    assert 0.04 <= two["starved"]["between"] <= two["between_s"] + TOL
    # The parked seconds are the first admission's: nothing had the chip
    # from the loop's waking until its prefill's first call returned.
    assert first["starved_s"] >= two["starved"]["between"] - TOL


def test_an_engine_without_traffic_idles_and_starves_nothing():
    from ray_tpu.serve.engine import PH_RECORD, _Starved

    class Watched(_Starved):
        """Notes the seconds of the `record` phase that reported last."""

        __slots__ = ("record_s",)

        def spend(self, name, t0, t1):
            if name == PH_RECORD:
                self.record_s = t1 - t0
            super().spend(name, t0, t1)

    warm = _tiny_engine()  # run alone, the admission would starve a compile
    try:
        assert len(list(warm.submit([3, 5, 7], max_new_tokens=3))) == 3
    finally:
        warm.shutdown()
    steprec.drain_buffered()
    eng = _tiny_engine()
    watched = Watched()
    try:
        time.sleep(0.4)  # several turns that find nothing to run
        assert eng._starved.by_phase == {} and eng._starved.stretch == 0
        assert eng._gap_acct.get("rt:engine/idle", 0.0) >= 0.25
        eng._run_on_loop(lambda: setattr(eng, "_starved", watched))
        assert len(list(eng.submit([3, 5, 7], max_new_tokens=3))) == 3
        recs = _records(eng, 1)
        time.sleep(0.3)
        # All that waits for the next record is the drain's last `record`:
        # what of that phase lay behind the record's own end, so no more
        # than the phase took, however slow the box; a turn of the 0.3 s
        # without traffic that charged anything would be over it.
        assert set(watched.by_phase) <= {PH_RECORD}
        assert sum(watched.by_phase.values()) <= watched.record_s < 0.15
    finally:
        eng.shutdown()
    _hold_invariants(recs)
    assert recs[0]["idle_s"] >= 0.25
    # The wait is idle_s, not starvation: what starved is the admission.
    assert recs[0]["starved_s"] < 0.25
    assert recs[0]["starved_s"] <= recs[0]["wall_s"] + recs[0]["between_s"]
    assert sum(r["starved_s"] for r in recs) > 0


def test_a_chunked_prompts_later_calls_add_nothing():
    """A prompt past the largest bucket goes through in chunks: the chip
    has work from the first call's return until the last one's token is
    read, so the later calls find the stamp clear and add nothing."""
    from ray_tpu.serve.engine import PH_PREFILL, _Starved

    class Watched(_Starved):
        """Notes, each time the prefill phase reports, whether the chip
        stood still."""

        __slots__ = ("seen",)

        def spend(self, name, t0, t1):
            if name == PH_PREFILL:
                self.seen.append(self.since is not None)
            super().spend(name, t0, t1)

    steprec.drain_buffered()
    eng = _tiny_engine(max_prompt_len=32, prefill_chunk=8, prefix_cache=False)
    watched = Watched()
    watched.seen = []
    try:
        prompt = list(range(1, 31))  # four calls of at most 8 rows
        assert len(list(eng.submit(prompt, max_new_tokens=2))) == 2  # compile
        _records(eng, 1)
        eng._run_on_loop(lambda: setattr(eng, "_starved", watched))
        assert len(list(eng.submit(prompt, max_new_tokens=2))) == 2
        recs = _records(eng, 1)
    finally:
        eng.shutdown()
    _hold_invariants(recs)
    entry, = [e for r in recs for e in r["first_tokens"]]
    assert entry["chunks"] == 4
    # The first call's return (the chip stood still until then), the four
    # calls' phases ending (three with the chip at work, the last after the
    # first token was read).
    assert watched.seen == [True, False, False, False, True]
    rec, = [r for r in recs if r["admitted"]]
    assert 0 < entry["starved_s"] <= rec["starved_s"] + TOL
    # prefill_s holds the four calls' host seconds and the wait; what of
    # them the chip starved is the first call's and the tail's.
    assert rec["starved"]["prefill"] < entry["prefill_s"]


def test_traced_is_on_records_closed_under_a_profiler_session(tmp_path):
    from ray_tpu.util import profiling

    steprec.drain_buffered()
    eng = _tiny_engine()
    try:
        assert len(list(eng.submit([3, 5, 7], max_new_tokens=3))) == 3
        before = _records(eng, 1)
        with profiling.device_trace(str(tmp_path), host_tracer_level=2):
            assert len(list(eng.submit([2, 4, 6, 8], max_new_tokens=6))) == 6
            during = _records(eng, 1)
        assert len(list(eng.submit([1, 2], max_new_tokens=3))) == 3
        after = _records(eng, 1)
    finally:
        eng.shutdown()
    assert before and during and after
    assert all("traced" not in r for r in before + after)
    assert all(r["traced"] == 1 for r in during)


def test_status_rows_show_the_share_of_the_loop_the_chip_starved():
    """`ray_tpu status` / `top`: `starved%` is `starved_s` over `wall_s +
    between_s` of the retained records, beside `stall%`, `host%` and
    `ahead%`; '-' for records of an engine that keeps no such account."""
    from ray_tpu.scripts import _engine_rows

    def rec(starved_s=None, **kw):
        r = dict({"wall_s": 0.03, "stall_s": 0.0, "occupancy": 2,
                  "slots": 4, "between_s": 0.01, "ahead": 1}, **kw)
        if starved_s is not None:
            r["starved_s"] = starved_s
        return r

    new = [rec(0.0), rec(0.012, ahead=0, stall_s=0.015), rec(0.0), rec(0.0)]
    old = [rec(), rec()]
    rows = _engine_rows(
        [{"engine": "1.0", "records": new, "latest": new[-1]},
         {"engine": "2.0", "records": old, "latest": old[-1]}], [])
    assert [row["starved%"] for row in rows] == ["7.5", "-"]  # 12 of 160 ms
    assert rows[0]["stall%"] == "12.5" and rows[0]["ahead%"] == "75.0"


def test_the_decode_spans_mean_gap_is_what_a_list_of_gaps_gave():
    """``mean_itl_s`` of the ``engine:decode`` span comes from the first
    and the last token's stamps and the count: the value the per-request
    list of gaps gave (each gap is still observed into the histogram, which
    is where this test takes the list from)."""
    from ray_tpu.util import tracing

    steprec.drain_buffered()
    eng = _tiny_engine()
    gaps = []
    try:
        list(eng.submit([3, 5, 7], max_new_tokens=2))  # compiled before
        observe = eng._m_itl.observe_many  # a step's gaps in one call
        eng._m_itl.observe_many = lambda vs: (gaps.extend(vs),
                                              observe(vs))[1]
        tracing.drain_buffered()
        with tracing.trace("req_root", force=True) as root:
            assert len(list(eng.submit([5, 7, 11, 13],
                                       max_new_tokens=9))) == 9
            one = list(eng.submit([5, 7], max_new_tokens=1))
        assert len(one) == 1
        _records(eng, 3)
        spans = [s for s in tracing.drain_buffered()
                 if s.get("trace_id") == root["trace_id"]
                 and s["name"] == "engine:decode"]
    finally:
        eng.shutdown()
    assert len(gaps) == 8
    many, = [s for s in spans if s["attrs"]["tokens"] == 9]
    assert many["attrs"]["mean_itl_s"] == pytest.approx(
        round(sum(gaps) / len(gaps), 6), abs=1.5e-6)
    assert many["attrs"]["mean_itl_s"] > 0
    # One token has no gap, as an empty list had no mean.
    single, = [s for s in spans if s["attrs"]["tokens"] == 1]
    assert single["attrs"]["mean_itl_s"] is None
    from ray_tpu.serve.engine import _Request

    assert "itls" not in _Request.__slots__
