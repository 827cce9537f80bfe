"""Observability plane: engine step flight recorder, device-memory
accounting, on-demand profiler capture, `ray_tpu top`.

Reference analog: TorchTitan's flight-recorder posture on the serving
side (PAPERS.md) + the reference's dashboard memory panels / `ray
status -v` — the decode loop leaves a bounded record trail that reaches
the head live, survives SIGKILL as an on-disk black box, and renders as
a cluster table.
"""

import glob
import json
import os
import signal
import subprocess
import sys
import time
import types

import pytest

import ray_tpu
from ray_tpu.util import steprec

# Same decode geometry as test_serve_engine: the per-process jit cache
# is shared across test files, so these engines reuse already-compiled
# programs instead of paying a fresh compile.
GEOMETRY = dict(batch_slots=4, page_size=8, max_prompt_len=16,
                max_new_tokens_cap=32)

# Every field the `top`/`status` renderers and the benchmark's readers
# (benchmarks/layer_metrics/) rely on.
STEP_FIELDS = {
    "t", "engine", "step", "wall_s", "stall_s", "occupancy", "slots",
    "admitted", "evicted", "shed", "queued", "pages_used", "pages_free",
    "pages_shared", "prefix_hits", "adapter_pins", "tenants",
}


def _tiny_engine(layers=2, **overrides):
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    cfg = dataclasses.replace(
        LlamaConfig.tiny(remat=False, dtype=jnp.float32), n_layers=layers)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    kw = dict(GEOMETRY, max_queue=16)
    kw.update(overrides)
    return InferenceEngine(cfg, params, EngineConfig(**kw), seed=0)


@pytest.fixture
def rt():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    yield ray_tpu
    ray_tpu.shutdown()


def _cli(*argv, timeout=120):
    return subprocess.run(
        [sys.executable, "-m", "ray_tpu", "--address",
         os.environ["RT_ADDRESS"], *argv],
        capture_output=True, text=True, env=dict(os.environ),
        timeout=timeout,
    )


# ---------------------------------------------------------------------------
# Ring semantics: bounded, drop-counted, black-box mirrored.
# ---------------------------------------------------------------------------


@pytest.fixture
def small_ring(monkeypatch):
    """Shrink the recorder's config without touching the global Config
    (steprec resolves every limit through its _cfg hook)."""
    cfg = types.SimpleNamespace(
        step_ring_size=16, step_dump_records=8, step_dump_interval_s=0.0)
    steprec.drain_buffered()
    monkeypatch.setattr(steprec, "_cfg", lambda: cfg)
    yield cfg
    steprec.drain_buffered()


def test_step_ring_bounded_and_drops_counted(small_ring):
    """Overflow must DROP (counted), never grow or block: the ring is on
    the decode loop's hot path."""
    dropped0 = steprec.dropped_total()
    for i in range(40):
        steprec.record_step({"engine": "ringtest.0", "step": i})
    buffered = steprec.drain_buffered()
    assert len(buffered) == 16  # ring capacity, not 40
    assert [r["step"] for r in buffered] == list(range(16))  # oldest kept
    assert steprec.dropped_total() - dropped0 == 24  # every loss counted


def test_black_box_last_n_atomic_and_throttled(small_ring, tmp_path,
                                               monkeypatch):
    """The sidecar holds the LAST N records (JSON lines), rewrites are
    throttled by step_dump_interval_s, and the path derives from
    RT_LOG_PATH so the post-mortem glob finds it next to the log."""
    monkeypatch.setenv("RT_LOG_PATH", str(tmp_path / "worker-abc.log"))
    assert steprec.black_box_path() == str(tmp_path / "worker-abc.steps.log")

    for i in range(20):
        steprec.record_step({"engine": "boxtest.0", "step": i})
    box = tmp_path / "box.steps.log"
    assert steprec.dump_black_box(str(box), force=True)
    lines = [ln for ln in box.read_text().splitlines()
             if not ln.startswith("#")]
    assert len(lines) == 8  # step_dump_records mirror, not the full ring
    assert [json.loads(ln)["step"] for ln in lines] == list(range(12, 20))

    # Throttle: a non-forced dump inside the interval is a no-op.
    small_ring.step_dump_interval_s = 3600.0
    box.write_text("sentinel-unchanged")
    assert not steprec.dump_black_box(str(box))
    assert box.read_text() == "sentinel-unchanged"
    # force bypasses the throttle (the exit/crash path).
    assert steprec.dump_black_box(str(box), force=True)
    assert "boxtest.0" in box.read_text()


# ---------------------------------------------------------------------------
# Device-memory accounting.
# ---------------------------------------------------------------------------


def test_devmem_pools_sum_to_live_bytes():
    """The attribution invariant: pools (including "other") sum EXACTLY
    to live array bytes; a raising pool fn reports 0; over-attribution
    (stale fn racing a teardown) scales down instead of driving "other"
    negative."""
    import jax.numpy as jnp

    from ray_tpu.util import devmem

    anchor = jnp.arange(4096.0)  # keeps live_bytes > 0
    anchor.block_until_ready()
    try:
        devmem.register_pool("t_anchor", lambda: anchor.nbytes)
        devmem.register_pool("t_raises", lambda: 1 // 0)
        snap = devmem.snapshot()
        assert snap["live_bytes"] >= anchor.nbytes
        assert sum(snap["pools"].values()) == snap["live_bytes"]
        assert snap["pools"]["t_anchor"] == anchor.nbytes
        assert snap["pools"]["t_raises"] == 0
        assert snap["pools"]["other"] >= 0

        # Over-attribution: a pool claiming 10x live must be scaled, the
        # sum invariant and other>=0 must still hold.
        devmem.register_pool("t_liar", lambda: snap["live_bytes"] * 10)
        snap2 = devmem.snapshot()
        assert sum(snap2["pools"].values()) == snap2["live_bytes"]
        assert snap2["pools"]["other"] >= 0
        assert snap2["pools"]["t_liar"] <= snap2["live_bytes"]
    finally:
        for name in ("t_anchor", "t_raises", "t_liar"):
            devmem.unregister_pool(name)

    devmem.record_compile("t_prog", 0.25)
    devmem.record_compile("t_prog", 0.5)
    stats = devmem.compile_stats()
    assert stats["t_prog"]["count"] == 2
    assert stats["t_prog"]["wall_s"] == pytest.approx(0.75)


def test_compile_spans_are_totalled_as_a_union():
    """Tracing a jitted function traces the ones it calls, each with a span
    of its own that ends first: the total counts every second once."""
    from ray_tpu.util import devmem

    counted, total = [], 0.0
    for start, end in [(1, 2), (3, 4), (0, 5),   # two nested in a third
                       (6, 7), (6.5, 8),         # overlap from a thread
                       (10, 11)]:
        total += devmem._add_span(counted, start, end)
    assert total == 8.0  # [0, 5] + [6, 8] + [10, 11]
    assert counted == [(0, 5), (6, 7), (7, 8), (10, 11)]
    # On the real listener: a jit that calls a jit, traced and built once.
    import jax
    import jax.numpy as jnp

    before = devmem.compile_totals()
    inner = jax.jit(lambda x: x * 2 + 1)
    jax.jit(lambda x: inner(x) - 3)(jnp.arange(5.0)).block_until_ready()
    after = devmem.compile_totals()
    assert after["compiles"] - before["compiles"] >= 1
    assert after["trace_s"] > before["trace_s"]
    assert devmem.compile_count() == after["compiles"]


def test_maybe_snapshot_never_forces_jax_import():
    """A worker that hasn't touched jax must report nothing (importing
    XLA into every worker is exactly what maybe_snapshot avoids) — probed
    in a fresh interpreter where jax is genuinely unimported."""
    code = (
        "import sys; from ray_tpu.util import devmem; "
        "assert 'jax' not in sys.modules; "
        "assert devmem.maybe_snapshot() is None; "
        "assert 'jax' not in sys.modules; print('clean')"
    )
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "clean" in out.stdout


# ---------------------------------------------------------------------------
# Profiler capture: exclusivity contract (the live-worker path is below).
# ---------------------------------------------------------------------------


def test_device_trace_busy_is_typed(tmp_path):
    from ray_tpu.util import profiling

    with profiling.device_trace(str(tmp_path / "a")):
        assert profiling.active_trace_dir() == str(tmp_path / "a")
        with pytest.raises(profiling.ProfilerBusyError):
            with profiling.device_trace(str(tmp_path / "b")):
                pass
    assert profiling.active_trace_dir() is None


def test_device_trace_passes_the_host_tracer_level(tmp_path, monkeypatch):
    """jax 0.9's ProfileOptions takes no constructor arguments: the level
    is set on the object and reaches start_trace (it used to be dropped
    behind an except)."""
    import jax

    from ray_tpu.util import profiling

    seen = {}
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda log_dir, **kw: seen.update(kw, log_dir=log_dir))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    with profiling.device_trace(str(tmp_path), host_tracer_level=3):
        pass
    assert seen["profiler_options"].host_tracer_level == 3


# ---------------------------------------------------------------------------
# Engine integration: records carry the full schema, slo_signals gains
# stall/jitter, controller reacts to stall pressure.
# ---------------------------------------------------------------------------


def test_engine_records_full_schema_and_slo_stall_signals():
    steprec.drain_buffered()
    eng = _tiny_engine()
    try:
        toks = list(eng.submit([3, 5, 7], max_new_tokens=4))
        assert len(toks) == 4
        pid, seq = eng.engine_id.split(".")
        assert int(pid) == os.getpid() and seq.isdigit()

        deadline = time.time() + 5
        recs = []
        while time.time() < deadline:
            recs += [r for r in steprec.drain_buffered()
                     if r.get("engine") == eng.engine_id]
            if any(r["occupancy"] > 0 for r in recs):
                break
            time.sleep(0.05)
        assert recs, "decode loop produced no step records"
        for r in recs:
            assert STEP_FIELDS <= set(r), STEP_FIELDS - set(r)
        assert sum(r["admitted"] for r in recs) >= 1
        assert all(r["wall_s"] >= 0 and r["stall_s"] >= 0 for r in recs)

        sig = eng.slo_signals()
        for key in ("stall_frac", "stall_s_window", "stall_window_s",
                    "step_p50_s", "step_p99_s", "step_jitter_p99_s"):
            assert key in sig, key
        assert 0.0 <= sig["stall_frac"] <= 1.0
    finally:
        eng.shutdown()


def test_step_record_off_switch():
    """step_record=False keeps the decode loop silent (the <=2% overhead
    contract's escape hatch must actually disconnect the recorder)."""
    steprec.drain_buffered()
    eng = _tiny_engine(step_record=False)
    try:
        assert list(eng.submit([3, 5], max_new_tokens=3))
        time.sleep(0.2)
        assert not [r for r in steprec.drain_buffered()
                    if r.get("engine") == eng.engine_id]
    finally:
        eng.shutdown()


def test_scale_decision_stall_pressure():
    """Stall pressure scales up BEFORE the TTFT breach, and blocks
    scale-down until comfortably below target (unit, no actors)."""
    from ray_tpu.serve.controller import _scale_decision

    # Queue and TTFT healthy, stall breached -> scale up.
    assert _scale_decision(2, 1, 4, per_queue=0.1, target_q=2.0,
                           stall_frac=0.6, target_stall_frac=0.25) == 3
    # Everything comfortably idle (stall < target/2) -> scale down.
    assert _scale_decision(2, 1, 4, per_queue=0.1, target_q=2.0,
                           stall_frac=0.05, target_stall_frac=0.25) == 1
    # Stall in the gray zone [target/2, target): hold, don't shrink.
    assert _scale_decision(2, 1, 4, per_queue=0.1, target_q=2.0,
                           stall_frac=0.2, target_stall_frac=0.25) == 2
    # No stall signal at all: legacy behavior unchanged.
    assert _scale_decision(2, 1, 4, per_queue=0.1, target_q=2.0) == 1


# ---------------------------------------------------------------------------
# Live plane: transport to the head, list_state kinds, top/profile CLI.
# ---------------------------------------------------------------------------


def test_engine_steps_and_devmem_reach_head_and_top(rt):
    """End to end: records flushed from this driver land in the head's
    per-engine ring; a worker that touched jax reports devmem on the
    metrics cadence; `list`, `status` and `top --once` all render both."""
    from ray_tpu.core.context import ctx

    eid = f"{os.getpid()}.77"
    steprec.drain_buffered()
    for i in range(5):
        steprec.record_step({
            "t": float(i), "engine": eid, "step": i, "wall_s": 0.01,
            "stall_s": 0.0, "occupancy": 2, "slots": 4, "admitted": 1,
            "evicted": 0, "shed": 0, "queued": 0, "pages_used": 3,
            "pages_free": 13, "pages_shared": 0, "prefix_hits": 0,
            "adapter_pins": 0, "tenants": {"default": 2},
        })
    assert steprec.flush_steps(ctx.client) == 5

    @ray_tpu.remote
    def touch_jax():
        import jax.numpy as jnp

        return int(jnp.arange(8.0).sum())

    assert ray_tpu.get(touch_jax.remote(), timeout=120) == 28

    rows = []
    deadline = time.time() + 20
    while time.time() < deadline:
        rows = ctx.client.call(
            "list_state", {"kind": "engine_steps", "engine": eid})["items"]
        if rows:
            break
        time.sleep(0.2)
    assert rows and rows[0]["engine"] == eid
    assert rows[0]["latest"]["step"] == 4
    assert len(rows[0]["records"]) == 5
    # limit trims the window tail-first.
    rows = ctx.client.call(
        "list_state", {"kind": "engine_steps", "engine": eid,
                       "limit": 2})["items"]
    assert [r["step"] for r in rows[0]["records"]] == [3, 4]

    # The jax-touching worker's devmem report arrives on the metrics
    # cadence (its reporter thread snapshots only once jax is imported).
    dm = []
    deadline = time.time() + 30
    while time.time() < deadline:
        dm = ctx.client.call("list_state", {"kind": "devmem"})["items"]
        if dm:
            break
        time.sleep(0.3)
    assert dm, "no worker ever reported a devmem snapshot"
    snap = dm[0]["devmem"]
    assert sum(snap["pools"].values()) == snap["live_bytes"]
    assert dm[0]["worker_id"] and dm[0]["node_id"]

    out = _cli("list", "engine_steps")
    assert out.returncode == 0, out.stderr
    assert eid in out.stdout
    out = _cli("list", "devmem")
    assert out.returncode == 0, out.stderr
    assert str(dm[0]["pid"]) in out.stdout

    out = _cli("status")
    assert out.returncode == 0, out.stderr
    assert f"engine {eid}" in out.stdout
    assert "stall" in out.stdout and "starved -%" in out.stdout

    out = _cli("top", "--once")
    assert out.returncode == 0, out.stderr
    assert "ray_tpu top" in out.stdout
    assert eid in out.stdout  # the engine table rendered
    assert "STARVED%" in out.stdout
    assert "2/4" in out.stdout  # slots occupancy/total from the record


def test_profile_cli_captures_worker_trace(rt, tmp_path):
    """`ray_tpu profile <worker>` round-trips head -> worker: the worker
    wraps itself in device_trace for N seconds (on a side thread — the
    actor keeps serving) and the reply names a TensorBoard-readable
    trace dir."""
    from ray_tpu.core.context import ctx

    @ray_tpu.remote
    class Burner:
        def warm(self):
            import jax.numpy as jnp

            return int(jnp.arange(4.0).sum())

        def spin(self, seconds):
            import jax.numpy as jnp

            deadline = time.time() + seconds
            x = jnp.arange(1.0, 1025.0)
            while time.time() < deadline:
                x = (x * 1.0001).block_until_ready()
            return float(x[0])

    b = Burner.remote()
    assert ray_tpu.get(b.warm.remote(), timeout=120) == 6  # jax imported

    workers = ctx.client.call("list_state", {"kind": "workers"})["items"]
    actor_workers = [w for w in workers if w["state"] == "actor"]
    assert actor_workers
    wid = actor_workers[0]["worker_id"]

    spin_ref = b.spin.remote(4.0)  # device work DURING the capture
    logdir = str(tmp_path / "tb")
    out = _cli("profile", wid, "--seconds", "1.5", "--logdir", logdir)
    assert out.returncode == 0, out.stdout + out.stderr
    assert f"trace dir: {logdir}" in out.stdout
    assert "tensorboard --logdir" in out.stdout
    traces = glob.glob(f"{logdir}/**/plugins/profile/**/*", recursive=True)
    assert traces, f"no profile output under {logdir}"
    assert ray_tpu.get(spin_ref, timeout=60) > 0  # capture didn't disturb it

    # Unknown worker: a clean error, not a hang.
    out = _cli("profile", "ffffffff", "--seconds", "0.5")
    assert out.returncode == 1
    assert out.stderr.strip()


# ---------------------------------------------------------------------------
# Crash forensics: the black box outlives SIGKILL.
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_black_box_survives_sigkill_postmortem(rt):
    """A SIGKILLed worker runs no exit hook — the sidecar written AHEAD
    of death is the only record of its final steps, and `ray_tpu logs
    --post-mortem` (a separate driver) must surface it."""

    @ray_tpu.remote
    class Doomed:
        def record(self):
            from ray_tpu.util import steprec as sr

            for i in range(6):
                sr.record_step({
                    "engine": f"{os.getpid()}.0", "step": i,
                    "t": float(i), "wall_s": 0.01, "stall_s": 0.0,
                    "sentinel": "BLACKBOX-SENTINEL-93251",
                })
            assert sr.dump_black_box(force=True)
            return sr.black_box_path(), os.getpid()

    d = Doomed.remote()
    box_path, pid = ray_tpu.get(d.record.remote(), timeout=120)
    assert box_path and box_path.endswith(".steps.log")
    assert os.path.exists(box_path)

    os.kill(pid, signal.SIGKILL)
    deadline = time.time() + 20
    while time.time() < deadline:
        try:
            os.kill(pid, 0)
            time.sleep(0.1)
        except OSError:
            break

    assert os.path.exists(box_path)  # the box outlived the process
    text = open(box_path).read()
    assert "BLACKBOX-SENTINEL-93251" in text

    out = _cli("logs", "--post-mortem")
    assert out.returncode == 0, out.stderr
    assert "BLACKBOX-SENTINEL-93251" in out.stdout
    assert ".steps.log" in out.stdout  # surfaced as a named sidecar


# ---------------------------------------------------------------------------
# Headless hold -> replay through a head restart.
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_headless_step_records_hold_and_replay(tmp_path, monkeypatch):
    """Records emitted while the head is DOWN stay in the bounded ring
    (flush is a no-op, nothing is lost) and replay into the restarted
    head's engine ring on the first post-reconnect flush — the span
    plane's exact survival contract, for step records."""
    from ray_tpu.cluster_utils import ExternalHead

    monkeypatch.setenv("RT_HEAD_RECONNECT_DEADLINE_S", "20")
    monkeypatch.delenv("RT_ADDRESS", raising=False)
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    head = ExternalHead(state_path=str(tmp_path / "head.state"), num_cpus=2)
    try:
        ray_tpu.init(address=head.addr)
        from ray_tpu.core.context import ctx as rt_ctx

        eid = f"{os.getpid()}.88"
        steprec.drain_buffered()
        steprec.record_step({"engine": eid, "step": 0, "t": 0.0})
        assert steprec.flush_steps(rt_ctx.client) == 1

        head.kill()
        obs_deadline = time.monotonic() + 10
        while not rt_ctx.client.rpc.closed \
                and time.monotonic() < obs_deadline:
            time.sleep(0.05)
        assert rt_ctx.client.rpc.closed

        # Emitted INSIDE the outage window.
        steprec.record_step({"engine": eid, "step": 1, "t": 1.0})
        assert steprec.flush_steps(rt_ctx.client) == 0  # headless: held
        head.restart()

        # The background flusher replays the held record by itself.
        steps = set()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            try:
                rows = rt_ctx.client.call(
                    "list_state",
                    {"kind": "engine_steps", "engine": eid})["items"]
            except Exception:
                rows = []
            steps = {r["step"] for row in rows
                     for r in row.get("records", [])}
            if 1 in steps:
                break
            time.sleep(0.5)
        assert 1 in steps, (
            "step record emitted while headless was lost across restart")
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        head.shutdown()


# ---------------------------------------------------------------------------
# The loop's own time account: phases and request stages on the step
# record, the same phases on the profiler's clock, the set-up table.
# ---------------------------------------------------------------------------

PHASE_FIELDS = {"t0": float, "between_s": float, "idle_s": float,
                "upload_s": float, "dispatch_s": float, "readback_s": float,
                "emit_s": float, "first_tokens": list, "ahead": int,
                "starved_s": float, "starved": dict}
ENTRY_FIELDS = {"queue_s": float, "prefill_s": float,
                "prefill_wait_s": float, "ttft_s": float, "prompt": int,
                "bucket": int, "cached": int, "chunks": int,
                "starved_s": float}
RT_PHASES = {"admit", "prefill", "prefill_wait", "upload", "dispatch",
             "readback", "emit", "record"}
REPEATED = [9, 8, 7, 6, 5, 4, 3, 2, 1, 2, 3]  # one full page of 8, then 3


def _engine_records(eng, until, timeout_s=10.0):
    """This engine's step records, drained until ``until(records)``."""
    recs, deadline = [], time.time() + timeout_s
    while time.time() < deadline:
        recs += [r for r in steprec.drain_buffered()
                 if r.get("engine") == eng.engine_id]
        if until(recs):
            break
        time.sleep(0.05)
    return recs


@pytest.fixture(scope="module")
def served():
    """A tiny engine that served eight concurrent requests on four slots,
    then one prompt twice (a prefix hit) and one request under a rooted
    trace: (records, spans of the trace, number of requests)."""
    import threading

    from ray_tpu.util import tracing

    steprec.drain_buffered()
    # Eight layers: a step of 2-4 ms, of which the two gauge writes and the
    # record's own close are a twentieth.  At two layers a step is 0.5 ms
    # and on a loaded machine they were 11-14% of it
    # (``test_phases_leave_only_the_gauges_of_the_step_wall`` holds 10%).
    eng = _tiny_engine(layers=8)
    try:
        threads = [threading.Thread(target=lambda i=i: list(eng.submit(
            [1 + i, 2, 3, 4 + i], max_new_tokens=12))) for i in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        for _ in range(2):
            assert len(list(eng.submit(REPEATED, max_new_tokens=3))) == 3
        tracing.drain_buffered()
        with tracing.trace("req_root", force=True) as root:
            assert len(list(eng.submit([5, 7, 11, 13, 17],
                                       max_new_tokens=3))) == 3
        n = 11
        recs = _engine_records(
            eng, lambda rs: sum(r["evicted"] for r in rs) >= n)
        spans = [s for s in tracing.drain_buffered()
                 if s.get("trace_id") == root["trace_id"]]
    finally:
        eng.shutdown()
    assert sum(r["evicted"] for r in recs) == n
    return recs, spans, n


def test_step_records_carry_the_loops_account(served):
    recs, _, _ = served
    for r in recs:
        assert STEP_FIELDS <= set(r)
        for key, kind in PHASE_FIELDS.items():
            assert isinstance(r[key], kind), (key, r[key])
            assert kind in (list, dict) or r[key] >= 0, (key, r[key])
        for e in r["first_tokens"]:
            assert {k: type(v) for k, v in e.items()} == ENTRY_FIELDS


def test_step_records_tile_the_loops_time(served):
    """t0[k] - t0[k-1] = wall_s[k-1] + between_s[k] + idle_s[k]: no moment
    of the loop thread belongs to no record, also where the loop dispatches
    a step before it reads the one in flight (most of this window)."""
    recs, _, _ = served
    assert len(recs) > 20
    assert sum(r["ahead"] for r in recs) > len(recs) // 3
    assert [r["step"] for r in recs] == sorted(r["step"] for r in recs)
    for prev, cur in zip(recs, recs[1:]):
        assert cur["t0"] - prev["t0"] == pytest.approx(
            prev["wall_s"] + cur["between_s"] + cur["idle_s"], abs=2e-6)
    # The waits between the three rounds of requests are idle, not work.
    assert sum(r["idle_s"] for r in recs) > 0


def test_ahead_is_one_on_steady_steps_and_zero_where_membership_changed():
    """``ahead`` says the step went out before the one ahead of it was read:
    so on every step but the first after an admission or after a token
    budget's end, both of which the loop meets with nothing in flight.  The
    record carries the number of the step it read, whatever was dispatched
    since."""
    import threading

    steprec.drain_buffered()
    eng = _tiny_engine()
    try:
        assert len(list(eng.submit([3, 5, 7], max_new_tokens=8))) == 8
        alone = _engine_records(
            eng, lambda rs: sum(r["evicted"] for r in rs) >= 1)
        a = eng.submit([2, 4, 6, 8], max_new_tokens=24)
        head = [next(a) for _ in range(6)]
        b = threading.Thread(
            target=lambda: list(eng.submit([9, 1], max_new_tokens=5)))
        b.start()
        b.join()
        assert len(head + list(a)) == 24
        mixed = _engine_records(
            eng, lambda rs: sum(r["evicted"] for r in rs) >= 2)
    finally:
        eng.shutdown()
    assert [r["ahead"] for r in alone] == [0] + [1] * 6
    assert [r["step"] for r in alone] == list(range(alone[0]["step"],
                                                    alone[0]["step"] + 7))
    assert sum(r["admitted"] for r in mixed) == 2
    for prev, cur in zip([{"evicted": 1}] + mixed, mixed):
        changed = cur["admitted"] > 0 or prev["evicted"] > 0
        assert cur["ahead"] == (0 if changed else 1), (prev, cur)
    assert sum(r["ahead"] for r in mixed) >= len(mixed) - 4


def test_phases_leave_only_the_gauges_of_the_step_wall(served):
    import statistics

    recs, _, _ = served
    phases = [r["stall_s"] + r["upload_s"] + r["dispatch_s"]
              + r["readback_s"] + r["emit_s"] for r in recs]
    for r, inside in zip(recs, phases):
        assert inside <= r["wall_s"] + 5e-6
    assert statistics.median(phases) >= 0.9 * statistics.median(
        r["wall_s"] for r in recs)


def test_first_tokens_has_one_entry_for_each_admitted_request(served):
    recs, _, n = served
    for r in recs:
        assert len(r["first_tokens"]) == r["admitted"]
        assert r["prefix_hits"] == sum(
            1 for e in r["first_tokens"] if e["cached"])
    entries = [e for r in recs for e in r["first_tokens"]]
    assert len(entries) == n
    for e in entries:
        assert 0 <= e["prefill_wait_s"] <= e["prefill_s"]
        assert e["queue_s"] >= 0 and e["ttft_s"] >= e["queue_s"]
        assert e["bucket"] >= e["prompt"] - e["cached"] > 0
    # The repeated prompt: a cold prefill, then a hit on its one full page.
    twice = [e for e in entries if e["prompt"] == len(REPEATED)]
    assert [e["cached"] for e in twice] == [0, GEOMETRY["page_size"]]
    assert [e["cached"] for e in entries if e not in twice] == [0] * (n - 2)


def test_request_spans_and_entry_come_from_the_same_stamps(served):
    recs, spans, _ = served
    by_name = {s["name"]: s for s in spans}
    entry, = [e for r in recs for e in r["first_tokens"]
              if e["prompt"] == 5]
    queue, prefill = by_name["engine:queue"], by_name["engine:prefill"]
    assert queue["end"] - queue["start"] == pytest.approx(
        entry["queue_s"], abs=2e-6)
    assert prefill["end"] - prefill["start"] == pytest.approx(
        entry["prefill_s"], abs=2e-6)
    assert prefill["attrs"] == {"bucket": entry["bucket"], "prompt_len": 5,
                                "cached_prefix": entry["cached"]}
    assert queue["end"] <= prefill["start"] + 2e-6
    assert by_name["engine:decode"]["attrs"]["ttft_s"] == entry["ttft_s"]


def test_a_device_trace_holds_every_phase_once_a_step(tmp_path):
    """The same phases as ``rt:engine/*`` host spans in a profiler capture,
    nested in an annotation bound onto the instance from outside (as the
    benchmark binds ``bench:engine_step``): a turn of the loop holds at
    most one dispatch and one readback, emit and record, the dispatch
    first (the step ahead goes out before the one in flight is read), and
    each prefill its wait."""
    import jax
    from jax.profiler import ProfileData

    from ray_tpu.util import profiling

    eng = _tiny_engine()
    try:
        list(eng.submit([3, 5, 7], max_new_tokens=3))  # compiled before
        inner = eng._run_step

        def outer(admitted):
            with jax.profiler.TraceAnnotation("test:step"):
                return inner(admitted)

        eng._run_step = outer
        with profiling.device_trace(str(tmp_path), host_tracer_level=2):
            assert len(list(eng.submit([2, 4, 6, 8], max_new_tokens=6))) == 6
    finally:
        eng.shutdown()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith(("rt:engine/", "test:step"))]
    assert {n[len("rt:engine/"):] for n, _, _ in events
            if n.startswith("rt:")} >= RT_PHASES
    steps = sorted((s, e) for n, s, e in events if n == "test:step")

    def inside(name, span):
        return [(s, e) for n, s, e in events
                if n == "rt:engine/" + name and span[0] <= s and e <= span[1]]

    # Five decode steps after the prefill's token, over six turns: the
    # first dispatches and reads nothing, four dispatch the step ahead and
    # then read the one in flight, the last (the budget ends at its step)
    # dispatches nothing.
    shapes = []
    for step in steps:
        held = [(name, inside(name, step))
                for name in ("dispatch", "readback", "emit", "record")]
        assert all(len(spans) <= 1 for _, spans in held), (held, step)
        order = [spans[0] for _, spans in held if spans]
        assert all(a[1] <= b[0] for a, b in zip(order, order[1:]))
        assert len(inside("upload", step)) <= 1
        shapes.append("".join(name[0] for name, spans in held if spans))
    assert shapes == ["d"] + ["drer"] * 4 + ["rer"], shapes
    prefill, = [(s, e) for n, s, e in events if n == "rt:engine/prefill"]
    assert len(inside("prefill_wait", prefill)) == 1
    assert len(inside("prefill", steps[0])) == 1
    assert len(inside("upload", steps[0])) == 1
    # Admission's locked section lies between the turns, once before each.
    for before, step in zip(steps, steps[1:]):
        assert len(inside("admit", (before[1], step[0]))) == 1


def test_setup_table_has_a_row_for_each_program_warmed():
    """``LLMServer.stats()["setup"]``: weights, pools and one row a program
    of ``InferenceEngine.warmup`` from JAX's own compile events; the step
    that paid a compile says so (``compiles``), a warmed step does not."""
    from ray_tpu.serve.engine import LLMServer

    # A geometry no other test compiles, so the programs are really built.
    geometry = dict(batch_slots=2, page_size=8, max_prompt_len=16,
                    max_new_tokens_cap=16)
    steprec.drain_buffered()
    srv = LLMServer("tiny", engine=geometry, warmup=True)
    try:
        cold = _engine_records(srv.engine, lambda rs: len(rs) >= 4, 2.0)
        assert len(list(srv.engine.submit([3, 5, 7], max_new_tokens=4))) == 4
        warm = _engine_records(
            srv.engine, lambda rs: sum(r["evicted"] for r in rs) >= 1)
        st = srv.stats()
    finally:
        srv.engine.shutdown()
    setup = st["setup"]
    assert setup["weights_s"] >= 0 and setup["pools_s"] >= 0
    rows = setup["programs"]
    assert [(r["program"], r["bucket"]) for r in rows] == [
        ("prefill", 8), ("decode", None), ("prefill", 16),
        ("prefix_hit", 16), ("prefill_prefix", 8), ("prefill_prefix", 16),
        ("copy_page", None), ("adapter_load", None)]
    for r in rows:
        assert set(r) == {"program", "bucket", "wall_s", "trace_s",
                          "lower_s", "compile_s", "cache_hit",
                          "cache_read_s"}
        # Each a union of intervals inside the row's wall (they may overlap
        # one another: an eager operation compiles while a program traces).
        for key in ("trace_s", "lower_s", "compile_s", "cache_read_s"):
            assert 0 <= r[key] <= r["wall_s"] + 0.002, (key, r)
    assert sum(r["wall_s"] for r in rows) <= st["warmup_s"] + 0.0005 * len(rows)
    built = {r["program"] for r in rows if r["compile_s"] > 0}
    assert {"prefill", "decode", "prefill_prefix"} <= built
    # Which step recompiled: the warm-up's first steps did, a served one
    # after it did not.
    assert cold[0]["compiles"] >= 1
    assert warm and all("compiles" not in r for r in warm)


def test_status_rows_show_host_share_and_queue_wait():
    """`ray_tpu status` / `top`: the window's host share of a step and the
    median queue wait, from the same records; '-' for records without."""
    from ray_tpu.scripts import _engine_rows

    def rec(**kw):
        return dict({"wall_s": 0.03, "stall_s": 0.0, "occupancy": 2,
                     "slots": 4}, **kw)

    accounted = [rec(t0=10.0 + 0.54 * i, between_s=0.01, upload_s=0.001,
                     dispatch_s=0.002, readback_s=0.02, emit_s=0.007,
                     idle_s=0.5, first_tokens=[{"queue_s": q}])
                 for i, q in enumerate((0.004, 0.030, 0.012))]
    accounted[1]["compiles"] = 2
    engines = [{"engine": "1.0", "records": accounted,
                "latest": accounted[-1]},
               {"engine": "2.0", "records": [rec()], "latest": rec()}]
    row, old = _engine_rows(engines, [])
    assert row["host%"] == "50.0"  # (10+1+2+7) of (30+10) ms
    assert row["qwait_ms"] == "12.0"
    assert (row["loop%"], row["compiles"]) == ("100.0", 2)
    assert (old["host%"], old["qwait_ms"], old["loop%"]) == ("-", "-", "-")
    # A record lost from the middle of the window shows as untiled time.
    engines[0]["records"] = [accounted[0], accounted[2]]
    assert _engine_rows(engines, [])[0]["loop%"] == "66.7"


def test_status_rows_show_the_share_of_steps_dispatched_ahead():
    """`ray_tpu status` / `top`: beside the host's share, the share of the
    window's decode steps that went out before the step ahead of them was
    read; '-' for records without the key."""
    from ray_tpu.scripts import _engine_rows

    def rec(ahead, occupancy=2):
        return {"wall_s": 0.01, "stall_s": 0.0, "occupancy": occupancy,
                "slots": 4, "ahead": ahead}

    steps = [rec(0), rec(1), rec(1), rec(1), rec(0, occupancy=0)]
    old = [{k: v for k, v in rec(0).items() if k != "ahead"}]
    rows = _engine_rows(
        [{"engine": "1.0", "records": steps, "latest": steps[-1]},
         {"engine": "2.0", "records": old, "latest": old[-1]}], [])
    assert [row["ahead%"] for row in rows] == ["75.0", "-"]
