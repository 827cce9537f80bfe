"""Cluster health & root-cause plane: pure detector units (seeded fires
AND clean stays silent), incident hysteresis/dedup lifecycle, the head
facade, put-path contention accounting, the incidents/doctor CLI, and the
chaos e2e — a seeded peer partition under live traffic must open exactly
one partition-suspicion incident whose evidence chain links traces and
the quarantine counter delta, then resolve after the wire heals.

The clean-cluster test doubles as the false-positive gate: a healthy
cluster doing ordinary work must open ZERO incidents.
"""

import json
import os
import time

import pytest

import ray_tpu
from ray_tpu.util import netfault
from ray_tpu.util.health import (
    DEFAULTS,
    HealthEngine,
    IncidentManager,
    RatioWindow,
    SEV_CRIT,
    SEV_WARN,
    SeriesWindow,
    detect_devmem_leak,
    detect_drop_pressure,
    detect_head_pressure,
    detect_partition,
    detect_slo_burn,
    detect_stall_pressure,
    firing,
)

SEED = int(os.environ.get("RT_NETFAULT_SEED", "1"))


# ------------------------------------------------------------ window units


def test_series_window_delta_reset_tolerant():
    w = SeriesWindow()
    for ts, v in [(0, 5.0), (1, 8.0), (2, 2.0), (3, 4.0)]:
        w.add(ts, v)
    # 5->8 (+3), 8->2 counter reset (counts the post-reset value, +2),
    # 2->4 (+2).
    assert w.delta(3.0, 10.0) == 7.0
    assert w.latest() == 4.0
    assert w.max_over(3.0, 10.0) == 8.0
    # Narrower window: only in-window increments count (base = last
    # sample before the window start).
    assert w.delta(3.0, 0.75) == 2.0
    # A window containing the reset counts the post-reset value too.
    assert w.delta(3.0, 1.5) == 4.0
    # Non-monotonic timestamps are ignored, not crashed on.
    w.add(1.0, 99.0)
    assert w.latest() == 4.0


def test_ratio_window_bad_fraction():
    w = RatioWindow()
    assert w.bad_fraction(0.0, 60.0) == (None, 0)
    w.add(0.0, 0.0, 0.0)
    w.add(1.0, 8.0, 10.0)
    w.add(2.0, 16.0, 20.0)
    bad, events = w.bad_fraction(2.0, 60.0)
    assert abs(bad - 0.2) < 1e-9 and events == 20
    # No delta in the window -> no signal, not a 0% claim.
    w.add(3.0, 16.0, 20.0)
    assert w.bad_fraction(3.0, 0.5) == (None, 0)


# --------------------------------------------------------- detector units


def _burn_window(bad_frac, n=31, step=10.0, per=2.0):
    w = RatioWindow()
    for i in range(n):
        total = i * per
        w.add(i * step, total * (1.0 - bad_frac), total)
    return w, (n - 1) * step


def test_slo_burn_fires_on_sustained_breach():
    w, now = _burn_window(0.8)  # 80% over target, goal 95% -> burn 16x
    hits = detect_slo_burn({"ttft": w}, now)
    assert len(hits) == 1
    f = hits[0]
    assert f["kind"] == "slo_burn" and f["key"] == "slo_burn:ttft"
    assert f["severity"] == SEV_CRIT
    assert f["data"]["fast_burn"] >= DEFAULTS["burn_fast_x"]


def test_slo_burn_warn_tier_and_clean_silent():
    # 40% bad -> burn 8x: above the slow threshold (6x), below fast (14.4).
    w, now = _burn_window(0.4)
    hits = detect_slo_burn({"itl": w}, now)
    assert [f["severity"] for f in hits] == [SEV_WARN]
    # Clean traffic and thin traffic both stay silent.
    clean, now = _burn_window(0.0)
    assert detect_slo_burn({"ttft": clean}, now) == []
    thin, now = _burn_window(0.9, per=0.1)  # < burn_min_events
    assert detect_slo_burn({"ttft": thin}, now) == []


def test_stall_pressure_fires_and_clean_silent():
    now = 100.0
    stalled = [{"t": now - i, "engine": "e0", "wall_s": 0.1, "stall_s": 0.3}
               for i in range(10)]
    hits = detect_stall_pressure(stalled, now, 30.0)
    assert [f["kind"] for f in hits] == ["stall_pressure"]
    assert hits[0]["key"] == "stall:e0"
    assert hits[0]["data"]["stall_frac"] >= 0.5
    healthy = [{"t": now - i, "engine": "e0", "wall_s": 0.1, "stall_s": 0.0}
               for i in range(10)]
    assert detect_stall_pressure(healthy, now, 30.0) == []
    # Records outside the window don't count toward min_steps.
    assert detect_stall_pressure(stalled, now + 500.0, 30.0) == []


def test_stall_share_is_of_the_step_wall_which_contains_the_stall():
    """``wall_s`` already holds ``stall_s`` (the engine reads both off one
    clock): half of every step stalled is 50%, as `ray_tpu status` and the
    benchmark's ``prefill_stall_share`` have it, not a third."""
    now = 100.0
    half = [{"t": now - i, "engine": "e0", "wall_s": 0.2, "stall_s": 0.1}
            for i in range(10)]
    hit, = detect_stall_pressure(half, now, 30.0)
    assert hit["data"]["stall_frac"] == 0.5
    assert DEFAULTS["stall_frac_warn"] == 0.5  # at the threshold, not under


def test_step_jitter_fires_and_clean_silent():
    now = 100.0
    walls = [0.001] * 28 + [0.1, 0.1]
    jittery = [{"t": now - i * 0.1, "engine": "e1", "wall_s": w,
                "stall_s": 0.0} for i, w in enumerate(walls)]
    hits = detect_stall_pressure(jittery, now, 30.0)
    assert [f["kind"] for f in hits] == ["step_jitter"]
    assert hits[0]["data"]["ratio"] >= DEFAULTS["jitter_ratio_warn"]
    steady = [{"t": now - i * 0.1, "engine": "e1", "wall_s": 0.001,
               "stall_s": 0.0} for i in range(30)]
    assert detect_stall_pressure(steady, now, 30.0) == []


def _counter_windows(**deltas):
    wins = {}
    for key in ("quarantines", "deadline_exceeded", "retries", "netfaults"):
        w = SeriesWindow()
        w.add(0.0, 0.0)
        w.add(10.0, float(deltas.get(key, 0.0)))
        wins[key] = w
    return wins


def test_partition_fires_on_quarantine_and_deadline_burst():
    hits = detect_partition(
        _counter_windows(quarantines=1, netfaults=4), 10.0, 30.0)
    assert len(hits) == 1
    f = hits[0]
    assert f["kind"] == "partition_suspicion" and f["key"] == "partition"
    assert f["severity"] == SEV_CRIT
    assert f["data"]["deltas"]["quarantines"] == 1
    # Deadline burst alone (gray failure, no quarantine yet) also fires.
    assert detect_partition(
        _counter_windows(deadline_exceeded=5), 10.0, 30.0)


def test_partition_clean_silent():
    assert detect_partition(_counter_windows(), 10.0, 30.0) == []
    # Sub-threshold deadline noise does not page.
    assert detect_partition(
        _counter_windows(deadline_exceeded=2, retries=1), 10.0, 30.0) == []
    # Old counters falling out of the window stop firing.
    assert detect_partition(
        _counter_windows(quarantines=3), 100.0, 30.0) == []


def test_drop_pressure_fires_and_clean_silent():
    wins = {"spans": SeriesWindow(), "logs": SeriesWindow()}
    for w in wins.values():
        w.add(0.0, 0.0)
        w.add(5.0, 0.0)
    assert detect_drop_pressure(wins, 5.0, 30.0) == []
    wins["spans"].add(10.0, 7.0)
    hits = detect_drop_pressure(wins, 10.0, 30.0)
    assert len(hits) == 1 and hits[0]["kind"] == "drop_pressure"
    assert hits[0]["data"]["deltas"] == {"spans": 7.0}


def test_devmem_leak_fires_on_monotone_growth_only():
    mib = 1024 * 1024
    leaky, churny = SeriesWindow(), SeriesWindow()
    for i in range(8):
        leaky.add(float(i * 10), float(i * 16 * mib))
        # Same net growth but it shrinks once mid-window: churn, not leak.
        churny.add(float(i * 10), float((i if i != 4 else 1) * 16 * mib))
    now, win = 70.0, 120.0
    hits = detect_devmem_leak({"123:hbm": leaky}, now, win)
    assert len(hits) == 1
    assert hits[0]["key"] == "devmem_leak:123:hbm"
    assert hits[0]["data"]["growth_bytes"] == 7 * 16 * mib
    assert detect_devmem_leak({"123:hbm": churny}, now, win) == []
    # Growth below the floor is pool warmup, not a leak.
    small = SeriesWindow()
    for i in range(8):
        small.add(float(i * 10), float(i * mib))
    assert detect_devmem_leak({"123:hbm": small}, now, win) == []


def test_head_pressure_tiers_and_clean_silent():
    def lag_win(worst):
        w = SeriesWindow()
        w.add(0.0, 0.01)
        w.add(1.0, worst)
        return w

    assert detect_head_pressure(lag_win(0.05), 1.0, 30.0) == []
    warn = detect_head_pressure(lag_win(0.8), 1.0, 30.0)
    assert [f["severity"] for f in warn] == [SEV_WARN]
    crit = detect_head_pressure(lag_win(2.5), 1.0, 30.0)
    assert [f["severity"] for f in crit] == [SEV_CRIT]
    assert crit[0]["key"] == "head_loop_lag"


# ------------------------------------------------------ incident lifecycle


def test_incident_manager_dedup_hysteresis_and_grade():
    opened_log, resolved_log = [], []
    m = IncidentManager(resolve_after_s=5.0, max_incidents=8,
                        on_open=opened_log.append,
                        on_resolve=resolved_log.append)
    f = firing("partition_suspicion", "partition", SEV_WARN, "s1", x=1)
    opened = m.observe([f], now=0.0,
                       evidence=lambda fi, now: {"trace_ids": ["t1"]})
    assert len(opened) == 1
    inc = opened[0]
    assert inc["state"] == "open" and inc["fired_count"] == 1
    assert inc["evidence"] == {"trace_ids": ["t1"]}
    assert m.grade() == "WARN" and m.open_count() == 1

    # Re-fire: dedup into the SAME incident, severity only escalates.
    f2 = firing("partition_suspicion", "partition", SEV_CRIT, "s2", x=2)
    assert m.observe([f2], now=1.0) == []
    assert inc["state"] == "active" and inc["fired_count"] == 2
    assert inc["severity"] == SEV_CRIT and inc["summary"] == "s2"
    assert m.grade() == "CRIT"
    # Evidence is captured once, at open — not churned per firing.
    assert inc["evidence"] == {"trace_ids": ["t1"]}

    # Quiet for resolve_after_s -> resolved, grade back to OK.
    assert m.observe([], now=6.5) == []
    assert inc["state"] == "resolved" and inc["resolved"] == 6.5
    assert m.grade() == "OK" and m.open_count() == 0
    assert [i["id"] for i in resolved_log] == [inc["id"]]

    # Same key after resolution opens a NEW incident (new id).
    reopened = m.observe([f], now=7.0)
    assert len(reopened) == 1 and reopened[0]["id"] != inc["id"]
    assert [i["id"] for i in opened_log] == [inc["id"], reopened[0]["id"]]
    # Prefix lookup and newest-first snapshot.
    assert m.get(inc["id"])[0]["id"] == inc["id"]
    assert m.snapshot()[0]["id"] == reopened[0]["id"]


def test_incident_ring_bounded_evicts_resolved_first():
    m = IncidentManager(resolve_after_s=1.0, max_incidents=8)
    # 6 incidents that resolve, then 8 that stay open.
    m.observe([firing("k", f"old:{i}", SEV_WARN, "old") for i in range(6)],
              now=0.0)
    m.observe([firing("k", f"new:{i}", SEV_WARN, "new") for i in range(8)],
              now=10.0)  # also resolves the old 6 (quiet > 1s)
    assert len(m.incidents) == 8
    keys = {inc["key"] for inc in m.incidents.values()}
    assert keys == {f"new:{i}" for i in range(8)}  # resolved evicted first
    assert m.open_count() == 8


def test_health_engine_tick_end_to_end_and_clean():
    eng = HealthEngine(window_s=30.0, resolve_after_s=5.0)

    def rows(quar):
        return [{"name": "ray_tpu_peer_quarantines_total", "kind": "counter",
                 "tags": {"peer": "10.0.0.2:7001"}, "value": float(quar)}]

    captured = []

    def evidence(f, now):
        captured.append(f["kind"])
        return {"trace_ids": ["abc123"]}

    assert eng.tick(0.0, rows(0), [], {}, 0.0, evidence=evidence) == []
    opened = eng.tick(2.0, rows(2), [], {}, 0.0, evidence=evidence)
    assert [i["kind"] for i in opened] == ["partition_suspicion"]
    assert captured == ["partition_suspicion"]
    assert opened[0]["evidence"]["trace_ids"] == ["abc123"]
    assert eng.manager.grade() == "CRIT"
    # Counter flat + window passed + quiet -> resolves.
    for t in (40.0, 41.0, 46.5):
        assert eng.tick(t, rows(2), [], {}, 0.0) == []
    assert eng.manager.grade() == "OK"
    assert opened[0]["state"] == "resolved"

    # A clean engine never opens anything across many ticks.
    clean = HealthEngine(window_s=30.0)
    for t in range(60):
        assert clean.tick(float(t), rows(0), [], {}, 0.0) == []
    assert clean.manager.snapshot() == []


def test_slo_targets_via_engine_silent_without_targets():
    """No configured/declared SLO target -> the burn detector never runs,
    however bad the latencies look (false-positive safety)."""
    eng = HealthEngine(window_s=30.0)
    row = {"name": "ray_tpu_serve_engine_ttft_seconds", "kind": "histogram",
           "tags": {}, "boundaries": (0.1, 1.0), "buckets": (0, 100),
           "count": 100, "sum": 90.0}
    for t in range(12):
        eng.tick(float(t * 10), [dict(row, count=100 + t * 10,
                                      buckets=(0, 100 + t * 10))], [], {},
                 0.0)
    assert eng.manager.snapshot() == []
    # Same traffic WITH a target: every observation lands over 0.1s.
    eng2 = HealthEngine(window_s=30.0)
    opened = []
    for t in range(40):
        opened += eng2.tick(
            float(t * 10),
            [dict(row, count=100 + t * 10, buckets=(0, 100 + t * 10))],
            [], {}, 0.0, slo_targets={"ttft": 0.1})
    assert [i["kind"] for i in opened] == ["slo_burn"]


# ------------------------------------------------------------ cluster plane


def _incidents(cl=None):
    from ray_tpu.core.context import ctx

    return (cl or ctx.client).call("list_state", {"kind": "incidents"})


def test_clean_cluster_opens_no_incidents(rt_shared, capsys):
    """False-positive gate: a healthy cluster doing ordinary work must
    grade OK with zero incidents, and `status`/`top` surface that line."""
    rt = rt_shared

    @ray_tpu.remote
    def f(x):
        return x * 2

    deadline = time.monotonic() + 6.0
    while time.monotonic() < deadline:
        assert rt.get([f.remote(i) for i in range(8)]) == \
            [i * 2 for i in range(8)]
        time.sleep(0.2)
    reply = _incidents()
    assert reply["open"] == 0, f"clean cluster opened: {reply['items']}"
    assert reply["grade"] == "OK"

    from ray_tpu import scripts

    assert scripts.main(["status"]) == 0
    out = capsys.readouterr().out
    assert "health: OK" in out and "open incidents: 0" in out
    assert scripts.main(["incidents"]) == 0
    out = capsys.readouterr().out
    assert "health: OK" in out and "(no incidents)" in out
    assert scripts.main(["incidents", "--json"]) == 0
    blob = json.loads(capsys.readouterr().out)
    assert blob["grade"] == "OK" and blob["incidents"] == []
    # Doctor with nothing recorded: calm narrative, rc 0.
    assert scripts.main(["doctor"]) == 0
    assert "nothing to diagnose" in capsys.readouterr().out


def test_put_stage_accounting_and_object_plane_cli(rt_shared, capsys):
    """A large put splits its wall across named stages locally, the stage
    histograms flush to the head, and `doctor --object-plane` renders the
    cluster-wide attribution table."""
    from ray_tpu.core import object_store

    rt = rt_shared
    object_store.reset_put_stages()
    ref = rt.put(b"\x5a" * (8 << 20))
    assert bytes(rt.get(ref))[:1] == b"\x5a"
    stages = object_store.put_stage_snapshot()
    assert "serialize" in stages and stages["serialize"]["count"] >= 1
    assert any(k in stages for k in ("copy", "alloc")), stages
    attributed = sum(s["seconds"] for s in stages.values())
    assert attributed > 0.0

    # The flusher ships the histograms on its own cadence; await them.
    from ray_tpu import scripts
    from ray_tpu.core.context import ctx

    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline:
        rows = ctx.client.call("list_state", {"kind": "metrics"})["items"]
        # THIS put's: another stage's row (an earlier test's small puts
        # allocate too) says nothing of the flush that carries serialize.
        if any(r["name"] == "ray_tpu_put_copy_seconds" and "sum" in r
               and r.get("tags", {}).get("stage") == "serialize"
               for r in rows):
            break
        time.sleep(0.5)
    else:
        pytest.fail("put stage histograms never reached the head")
    assert scripts.main(["doctor", "--object-plane"]) == 0
    out = capsys.readouterr().out
    assert "object-plane put attribution" in out
    assert "serialize" in out


# ------------------------------------------------------------- chaos e2e


@ray_tpu.remote
class _Counter:
    def __init__(self):
        self.n = 0

    def ping(self):
        return self.n

    def add(self):
        self.n += 1
        return self.n


def _establish_direct(rt, actor, timeout=15.0):
    from ray_tpu.core.context import ctx

    raw = actor._actor_id.binary()
    dp = ctx.client._dataplane
    assert dp is not None
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        rt.get(actor.ping.remote())
        with dp._lock:
            route = dp._routes.get(raw)
            slot = route.slot if route is not None else None
            if slot is not None and not slot.dead:
                return route
        time.sleep(0.3)
    raise AssertionError("actor route never switched to the direct plane")


@pytest.fixture
def rt_health_tight():
    """Tight peer deadlines + short health windows so the partition ->
    incident -> resolve arc fits a test's patience."""
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, system_config={
        "peer_call_deadline_s": 1.0,
        "peer_quarantine_probe_s": 0.5,
        "health_window_s": 5.0,
        "health_resolve_after_s": 2.0,
    })
    yield ray_tpu
    netfault.disarm()
    ray_tpu.shutdown()


@pytest.mark.chaos
@pytest.mark.skipif(os.environ.get("RT_DIRECT_CALLS") == "0",
                    reason="dataplane force-disabled via env")
def test_partition_opens_one_incident_with_evidence_then_resolves(
        rt_health_tight, capsys):
    """Seeded peer partition under live traffic: the quarantine counter
    delta trips the partition detector, exactly ONE partition-suspicion
    incident opens (dedup holds while the counter stays in window), its
    evidence chain links >=1 trace id and the quarantine delta, `doctor`
    replays it, and the incident resolves once the wire heals."""
    from ray_tpu.core.context import ctx
    from ray_tpu.util import tracing

    rt = rt_health_tight
    c = _Counter.remote()
    _establish_direct(rt, c)
    # Warm the trace plane: spans ride a batched flush, and evidence links
    # whatever the timeline ring holds when the incident opens — make sure
    # the in-window TRACED traffic's spans have actually landed.
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline:
        with tracing.trace("chaos-traffic", force=True):
            rt.get(c.ping.remote(), timeout=30)
        if ctx.client.call("list_state", {"kind": "traces"})["items"]:
            break
        time.sleep(0.3)
    else:
        pytest.fail("no spans reached the head; tracing disabled?")
    netfault.arm("partition:link=peer-direct,dur=2,mode=in", SEED)
    try:
        done = 0
        inc = None
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and inc is None:
            with tracing.trace("chaos-traffic", force=True):
                rt.get(c.add.remote(), timeout=60)
            done += 1
            parts = [i for i in _incidents()["items"]
                     if i["kind"] == "partition_suspicion"]
            if parts and parts[0]["evidence"].get("counter_deltas"):
                inc = parts[0]
            time.sleep(0.25)
        assert inc is not None, "partition incident never opened"
    finally:
        netfault.disarm()

    parts = [i for i in _incidents()["items"]
             if i["kind"] == "partition_suspicion"]
    assert len(parts) == 1, f"dedup failed: {parts}"
    assert inc["severity"] == "crit"
    ev = inc["evidence"]
    assert len(ev["trace_ids"]) >= 1, ev
    assert ev["counter_deltas"].get("quarantines", 0) >= 1, ev
    assert _incidents()["grade"] == "CRIT"

    from ray_tpu import scripts

    assert scripts.main(["doctor", inc["id"]]) == 0
    out = capsys.readouterr().out
    assert inc["id"] in out and "counter deltas" in out
    assert "quarantines" in out
    assert scripts.main(["incidents"]) == 0
    assert "partition_suspicion" in capsys.readouterr().out

    # Heal: counter delta falls out of the 5s window, then 2s of quiet
    # resolves the incident and the grade returns to OK.
    deadline = time.monotonic() + 40.0
    while time.monotonic() < deadline:
        rt.get(c.add.remote(), timeout=60)
        done += 1
        reply = _incidents()
        parts = [i for i in reply["items"]
                 if i["kind"] == "partition_suspicion"]
        if parts and parts[0]["state"] == "resolved":
            assert reply["grade"] == "OK"
            break
        time.sleep(0.5)
    else:
        pytest.fail("partition incident never resolved after heal")
    # Exactly-once held throughout the chaos window.
    assert rt.get(c.ping.remote(), timeout=30) == done
