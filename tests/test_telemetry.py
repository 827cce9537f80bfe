"""Telemetry v2: time-series metrics history, built-in ray_tpu_* metrics,
Prometheus histogram exposition, trace flow events, and train goodput (MFU).

Reference analogs: src/ray/stats/metric_defs.cc built-in metrics,
_private/prometheus_exporter.py exposition tests, TorchTitan-style MFU
accounting (arXiv:2410.06511).
"""

import json
import math
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu.util import tracing
from ray_tpu.util.metrics import prometheus_text


# ---------------------------------------------------------------- unit tests


def test_prometheus_histogram_exposition_golden():
    """Histograms must emit cumulative le-buckets (incl. +Inf), _sum and
    _count per the Prometheus spec — not a single value line."""
    rows = [{
        "name": "req_latency", "kind": "histogram",
        "description": "request latency",
        "tags": {"app": "demo"},
        "boundaries": [0.1, 1.0],
        "buckets": [2.0, 3.0, 1.0],  # per-bucket counts: <=0.1, <=1, +Inf
        "sum": 2.5, "count": 6, "value": 6,
    }]
    text = prometheus_text(rows)
    assert text == (
        "# HELP req_latency request latency\n"
        "# TYPE req_latency histogram\n"
        'req_latency_bucket{app="demo",le="0.1"} 2\n'
        'req_latency_bucket{app="demo",le="1"} 5\n'
        'req_latency_bucket{app="demo",le="+Inf"} 6\n'
        'req_latency_sum{app="demo"} 2.5\n'
        'req_latency_count{app="demo"} 6\n'
    )


def test_prometheus_label_escaping():
    rows = [{"name": "m", "kind": "gauge",
             "tags": {"path": 'a"b\\c\nd'}, "value": 1.0}]
    text = prometheus_text(rows)
    assert 'path="a\\"b\\\\c\\nd"' in text


def test_prometheus_counter_gauge_unchanged():
    rows = [
        {"name": "c", "kind": "counter", "description": "d",
         "tags": {"k": "v"}, "value": 4},
        {"name": "g", "kind": "gauge", "tags": {}, "value": 1.5},
    ]
    text = prometheus_text(rows)
    assert '# TYPE c counter\nc{k="v"} 4' in text
    assert "# TYPE g gauge\ng 1.5" in text


def test_metrics_history_ring():
    from ray_tpu.core.telemetry import MetricsHistory

    h = MetricsHistory(max_samples=4, min_interval_s=0.0, max_series=2)
    for i in range(6):
        h.record([{"name": "m", "tags": {"a": "1"}, "kind": "gauge",
                   "value": float(i)}], ts=100.0 + i)
    series = h.snapshot()
    assert len(series) == 1
    pts = series[0]["points"]
    assert len(pts) == 4  # ring bounded
    assert pts[-1] == [105.0, 5.0, 5.0, 5.0]
    assert pts[0] == [102.0, 2.0, 2.0, 2.0]
    # Series cap with stale eviction: at the cap, a new series evicts the
    # longest-idle DEAD series ("m", idle > 60 s) but a new arrival is
    # dropped while every retained series is still live.
    h.record([{"name": "m2", "tags": {}, "kind": "gauge", "value": 1.0}],
             ts=200.0)
    h.record([{"name": "m3", "tags": {}, "kind": "gauge", "value": 1.0}],
             ts=201.0)  # evicts "m" (last sample 105.0, stale)
    names = {s["name"] for s in h.snapshot()}
    assert names == {"m2", "m3"}
    h.record([{"name": "m4", "tags": {}, "kind": "gauge", "value": 1.0}],
             ts=202.0)  # m2/m3 are fresh: m4 is dropped, rings intact
    names = {s["name"] for s in h.snapshot()}
    assert names == {"m2", "m3"}


def test_metrics_history_downsamples():
    from ray_tpu.core.telemetry import MetricsHistory

    h = MetricsHistory(max_samples=100, min_interval_s=1.0)
    for i in range(10):
        h.record([{"name": "m", "tags": {}, "kind": "gauge", "value": float(i)}],
                 ts=100.0 + i * 0.1)  # 10 Hz feed, 1 s min interval
    pts = h.snapshot()[0]["points"]
    assert len(pts) == 1
    # Within-interval samples fold into the open bucket instead of being
    # dropped: the point keeps [ts, mean, min, max] of everything seen.
    ts, mean, lo, hi = pts[0]
    assert ts == 100.0
    assert (lo, hi) == (0.0, 9.0)
    assert abs(mean - 4.5) < 1e-9


def test_tracing_public_api_and_aliases():
    assert len(tracing.new_id()) == 16
    assert tracing._new_id is tracing.new_id  # legacy alias kept
    assert tracing._emit is tracing.emit_span


def test_chrome_trace_flow_events():
    events = [
        {"kind": "span", "trace_id": "t", "span_id": "sub1",
         "parent_id": "root", "name": "submit:work", "start": 1.0,
         "end": 1.0, "pid": 1, "attrs": {"flow_id": "exec1"}},
        {"kind": "span", "trace_id": "t", "span_id": "exec1",
         "parent_id": "root", "name": "task:work", "start": 1.5,
         "end": 2.0, "pid": 2},
    ]
    out = tracing.chrome_trace(events)
    flows = [e for e in out if e["ph"] in ("s", "f")]
    assert len(flows) == 2
    start = next(e for e in flows if e["ph"] == "s")
    finish = next(e for e in flows if e["ph"] == "f")
    assert start["id"] == finish["id"] == "exec1"
    assert start["ts"] == pytest.approx(1.0e6)
    assert finish["ts"] == pytest.approx(1.5e6)
    assert finish["bp"] == "e"
    # Plain spans still export exactly one X event each, no spurious flows.
    plain = tracing.chrome_trace([events[1]])
    assert [e["ph"] for e in plain] == ["X"]


def test_flusher_config_knobs():
    from ray_tpu.core.config import Config

    cfg = Config()
    assert cfg.metrics_flush_interval_s == 2.0
    assert cfg.metrics_history_max_samples >= 2
    assert cfg.metrics_history_min_interval_s > 0


def test_train_telemetry_cpu_reports_no_mfu():
    """On the CPU backend there is no peak on record: step time and
    tokens/sec are derived, MFU is not reported against an invented one."""
    import jax.numpy as jnp

    from ray_tpu.train import telemetry

    flops = telemetry.flops_per_step(
        lambda x: (x @ x).sum(), jnp.ones((32, 32)))
    assert flops is None or flops > 0
    if flops is None:  # backend without a cost model: static fallback
        flops = telemetry.transformer_flops(1e4, 32)
    tel = telemetry.TrainTelemetry(flops_per_step=flops)
    out = tel.record_step(0.01, tokens=512)
    assert out["step_time_s"] == pytest.approx(0.01)
    assert out["tokens_per_sec"] == pytest.approx(51200.0)
    assert "mfu" not in out
    assert telemetry.device_peak_flops() is None
    assert tel.peak_flops_total() is None


def test_train_telemetry_mfu_against_the_peak_table():
    """MFU = flops / step seconds / (chips x the table's peak for the
    device_kind); an accelerator the table does not know raises."""
    import types

    from ray_tpu.train import telemetry

    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert telemetry.device_peak_flops(v5e) == 197e12
    tel = telemetry.TrainTelemetry(
        flops_per_step=197e12, num_devices=4,
        peak_flops=telemetry.device_peak_flops(v5e))
    assert tel.record_step(0.5)["mfu"] == pytest.approx(0.5)
    unknown = types.SimpleNamespace(platform="tpu", device_kind="TPU v9")
    with pytest.raises(ValueError, match="no peak FLOP/s on record"):
        telemetry.device_peak_flops(unknown)


def test_train_telemetry_step_context():
    from ray_tpu.train.telemetry import TrainTelemetry

    tel = TrainTelemetry(tokens_per_step=100)
    with tel.step():
        time.sleep(0.01)
    assert tel.last["step_time_s"] >= 0.01
    assert tel.last["tokens_per_sec"] > 0


def test_train_telemetry_counts_the_compiled_steps_collectives():
    """A loop hands its compiled step over once: the collectives it holds,
    by kind, returned and on the gauge, a series a rank and kind."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.train.telemetry import TrainTelemetry

    mesh = Mesh(jax.devices()[:2], ("tp",))
    rows = NamedSharding(mesh, P(None, "tp"))
    compiled = jax.jit(lambda a, b: a @ b, in_shardings=(
        rows, NamedSharding(mesh, P("tp", None))),
        out_shardings=NamedSharding(mesh, P())).lower(
            jnp.ones((8, 16)), jnp.ones((16, 8))).compile()
    tel = TrainTelemetry(rank=3)
    counts = tel.record_compiled(compiled)
    assert counts["all-reduce"] == 1 and counts["all-to-all"] == 0
    series = {tuple(sorted(r["tags"].items())): r["value"]
              for r in tel._g_collectives._snapshot()}
    assert series[(("kind", "all-reduce"), ("rank", "3"))] == 1
    assert series[(("kind", "all-to-all"), ("rank", "3"))] == 0


def test_session_report_augments_goodput():
    """report() derives step_time_s / tokens_per_sec / mfu for each round
    after the first, without clobbering user keys."""
    import threading

    from ray_tpu.train import session as smod

    s = smod.TrainSession(world_rank=0, world_size=1,
                          trial_dir="/tmp/rt_tel_trial",
                          restored_checkpoint=None)

    def driver():
        for _ in range(3):
            r = s.next_result(timeout=10)
            results.append(r)
            s.ack()

    results = []
    t = threading.Thread(target=driver, daemon=True)
    t.start()
    s.report({"loss": 1.0})
    time.sleep(0.02)
    s.report({"loss": 0.5, "tokens": 1000,
              "flops_per_step": 1e6, "step_time_s": 123.0})
    time.sleep(0.02)
    s.report({"loss": 0.25, "tokens": 1000})
    t.join(timeout=10)
    assert len(results) == 3
    assert "step_time_s" not in results[0]["metrics"]  # no previous round
    m1 = results[1]["metrics"]
    assert m1["step_time_s"] == 123.0  # user key wins
    assert m1["tokens_per_sec"] > 0
    assert "mfu" not in m1  # CPU backend: no peak on record
    m2 = results[2]["metrics"]
    assert 0 < m2["step_time_s"] < 60


# ------------------------------------------------------------- cluster smoke


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.status, resp.read()


@pytest.fixture(scope="module")
def tel_cluster():
    from ray_tpu.core.context import ctx

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, include_dashboard=True)
    yield ray_tpu, ctx
    ray_tpu.shutdown()


def test_cluster_telemetry_smoke(tel_cluster):
    """The acceptance scenario: a few tasks + one jitted train step; then
    the history endpoint has >=2 timestamped samples for a built-in
    scheduler metric, /metrics exposes a spec-compliant histogram, and
    ray_tpu_train_mfu (against an explicit peak: the CPU backend has none
    on record) is finite."""
    import jax
    import jax.numpy as jnp

    rt, ctx = tel_cluster
    dash = ctx.dashboard

    @ray_tpu.remote
    def work(x):
        return x + 1

    assert sorted(rt.get([work.remote(i) for i in range(4)])) == [1, 2, 3, 4]

    # One jitted train step with goodput accounting in the driver process.
    from ray_tpu.train import telemetry

    step = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    flops = telemetry.flops_per_step(step, x) \
        or telemetry.transformer_flops(64 * 64, 64)
    tel = telemetry.TrainTelemetry(flops_per_step=flops, peak_flops=1e12)
    with tel.step(tokens=64 * 64):
        step(x).block_until_ready()
    assert math.isfinite(tel.last["mfu"])

    # Ship the driver's gauges to the head now (don't wait out the flusher).
    from ray_tpu.util.metrics import _flush_once

    _flush_once()
    ctx.client.drain_bg()

    # (1) >=2 retained, timestamped samples for a built-in scheduler series.
    deadline = time.time() + 20
    points = []
    while time.time() < deadline:
        _, body = _get(dash.url + "/api/metrics/history")
        items = json.loads(body)["items"]
        sched = [s for s in items
                 if s["name"] == "ray_tpu_scheduler_queue_depth"]
        if sched and len(sched[0]["points"]) >= 2:
            points = sched[0]["points"]
            break
        time.sleep(0.3)
    assert len(points) >= 2, "no retained history for built-in metric"
    assert points[0][0] < points[-1][0]  # timestamped, monotonic

    # (2) /metrics histogram follows the exposition spec.
    _, body = _get(dash.url + "/metrics")
    text = body.decode()
    assert "# TYPE ray_tpu_scheduler_submit_to_start_seconds histogram" in text
    assert 'ray_tpu_scheduler_submit_to_start_seconds_bucket{le="+Inf"}' in text
    assert "ray_tpu_scheduler_submit_to_start_seconds_sum" in text
    assert "ray_tpu_scheduler_submit_to_start_seconds_count" in text

    # (3) the MFU gauge reached the cluster metrics plane, finite.
    deadline = time.time() + 10
    mfu_rows = []
    while time.time() < deadline:
        rows = ctx.client.call("list_state", {"kind": "metrics"})["items"]
        mfu_rows = [r for r in rows if r["name"] == "ray_tpu_train_mfu"]
        if mfu_rows:
            break
        _flush_once()
        ctx.client.drain_bg()
        time.sleep(0.3)
    assert mfu_rows and math.isfinite(mfu_rows[0]["value"])
    assert mfu_rows[0]["value"] > 0


def test_cluster_task_duration_histogram(tel_cluster):
    """Traced task execution spans feed ray_tpu_task_duration_seconds —
    the trace<->metrics link."""
    rt, ctx = tel_cluster

    @ray_tpu.remote
    def slowish():
        time.sleep(0.01)
        return 1

    with tracing.trace("drive"):
        assert rt.get(slowish.remote()) == 1
    deadline = time.time() + 10
    while time.time() < deadline:
        rows = ctx.client.call("list_state", {"kind": "metrics"})["items"]
        dur = [r for r in rows if r["name"] == "ray_tpu_task_duration_seconds"]
        if dur and dur[0].get("count", 0) >= 1:
            return
        time.sleep(0.2)
    pytest.fail("task span never reached the duration histogram")


def test_cluster_submit_flow_spans(tel_cluster):
    """Traced submissions leave submit spans whose flow ids match the
    execution spans, and the Chrome export links them."""
    rt, ctx = tel_cluster

    @ray_tpu.remote
    def job():
        return 1

    with tracing.trace("flow-root"):
        assert rt.get(job.remote()) == 1
    deadline = time.time() + 10
    while time.time() < deadline:
        events = ctx.client.call("list_state", {"kind": "timeline"})["items"]
        spans = [e for e in events if e.get("kind") == "span"]
        submits = [s for s in spans
                   if str(s.get("name", "")).startswith("submit:")]
        flows = [e for e in tracing.chrome_trace(events)
                 if e["ph"] in ("s", "f")]
        if submits and len(flows) >= 2:
            return
        time.sleep(0.2)
    pytest.fail("no flow-linked submit/execute span pair in the timeline")
