"""Observability + persistence + job submission tests.

Reference analogs: util/metrics tests, _private/log_monitor streaming,
util/state CLI (`ray list`/`ray status`), GCS Redis persistence tests,
dashboard/modules/job tests.
"""

import os
import subprocess
import sys
import time

import pytest

import ray_tpu


@pytest.fixture
def rt():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    yield ray_tpu
    ray_tpu.shutdown()


def test_metrics_aggregate_across_processes(rt):
    @ray_tpu.remote
    def work(i):
        from ray_tpu.util.metrics import Counter, Gauge, _flush_once

        c = Counter("tasks_finished", description="done tasks",
                    tag_keys=("kind",))
        c.inc(1, tags={"kind": "work"})
        g = Gauge("last_i")
        g.set(i)
        _flush_once()
        from ray_tpu.core.context import ctx

        ctx.client.drain_bg()
        return i

    assert sorted(ray_tpu.get([work.remote(i) for i in range(4)])) == [0, 1, 2, 3]
    from ray_tpu.core.context import ctx

    deadline = time.time() + 10
    while time.time() < deadline:
        rows = ctx.client.call("list_state", {"kind": "metrics"})["items"]
        counters = [r for r in rows if r["name"] == "tasks_finished"]
        if counters and counters[0]["value"] >= 4:
            break
        time.sleep(0.2)
    assert counters and counters[0]["value"] == 4  # summed across workers
    assert counters[0]["tags"] == {"kind": "work"}

    from ray_tpu.util.metrics import prometheus_text

    text = prometheus_text(rows)
    assert 'tasks_finished{kind="work"} 4' in text


def test_worker_logs_stream_to_driver(rt, capfd):
    @ray_tpu.remote
    def shout():
        print("HELLO-FROM-WORKER")
        return 1

    assert ray_tpu.get(shout.remote()) == 1
    deadline = time.time() + 10
    seen = ""
    while time.time() < deadline:
        seen += capfd.readouterr().out
        if "HELLO-FROM-WORKER" in seen:
            break
        time.sleep(0.2)
    assert "HELLO-FROM-WORKER" in seen
    assert "(pid=" in seen  # prefixed with the worker pid


def _cli(*argv, timeout=60):
    return subprocess.run(
        [sys.executable, "-m", "ray_tpu", "--address",
         os.environ["RT_ADDRESS"], *argv],
        capture_output=True, text=True, env=dict(os.environ),
        timeout=timeout,
    )


def test_state_cli(rt):
    @ray_tpu.remote
    class Keeper:
        def ping(self):
            return "ok"

    k = Keeper.options(name="cli-keeper").remote()
    assert ray_tpu.get(k.ping.remote()) == "ok"
    out = _cli("list", "actors")
    assert out.returncode == 0, out.stderr
    assert "Keeper" in out.stdout and "cli-keeper" in out.stdout

    out = _cli("status")
    assert out.returncode == 0, out.stderr
    assert "nodes: 1 alive" in out.stdout

    out = _cli("summary")
    assert out.returncode == 0, out.stderr
    assert "COUNT" in out.stdout and "ping" in out.stdout

    out = _cli("metrics")
    assert out.returncode == 0, out.stderr
    assert "NAME" in out.stdout or "no items" in out.stdout

    out = _cli("timeline")
    assert out.returncode == 0, out.stderr
    assert "task_submitted" in out.stdout

    # Empty kinds print a clean no-items line instead of a bare table.
    out = _cli("list", "pgs")
    assert out.returncode == 0, out.stderr
    assert "no placement_groups" in out.stdout

    # events: table view, --errors filter (empty here), and --task detail.
    out = _cli("events")
    assert out.returncode == 0, out.stderr
    assert "Keeper.ping" in out.stdout and "FINISHED" in out.stdout
    out = _cli("events", "--errors")
    assert out.returncode == 0, out.stderr
    assert "no task events" in out.stdout
    out = _cli("events", "--task", "ffffffff")
    assert out.returncode == 0, out.stderr
    assert "no task events" in out.stdout

    # logs: index listing shows the keeper's (live) worker.
    out = _cli("logs")
    assert out.returncode == 0, out.stderr
    assert "PROC_ID" in out.stdout and "worker" in out.stdout

    # stack: dump the actor's worker; its rpc thread must be visible.
    from ray_tpu.core.context import ctx

    workers = ctx.client.call("list_state", {"kind": "workers"})["items"]
    actor_worker = [w for w in workers if w["state"] == "actor"]
    assert actor_worker
    out = _cli("stack", actor_worker[0]["worker_id"])
    assert out.returncode == 0, out.stderr
    assert "Thread" in out.stdout and "threads=" in out.stdout


def test_dead_worker_log_postmortem(rt):
    """Acceptance: the full stdout/stderr of an already-dead worker stays
    retrievable via get_log — in-process, by actor id, and from a SEPARATE
    driver process (the CLI) — because the head's log index retains entries
    past death and the file outlives the process."""

    @ray_tpu.remote
    class Doomed:
        def scribble(self):
            print("POSTMORTEM-STDOUT-LINE")
            print("POSTMORTEM-STDERR-LINE", file=sys.stderr)
            sys.stdout.flush()
            sys.stderr.flush()
            os._exit(1)

    d = Doomed.remote()
    with pytest.raises(Exception):
        ray_tpu.get(d.scribble.remote(), timeout=60)

    actor_hex = d._actor_id.hex()
    from ray_tpu.core.context import ctx

    entry = None
    deadline = time.time() + 20
    while time.time() < deadline:
        entries = ctx.client.call("list_state", {"kind": "logs"})["items"]
        dead = [e for e in entries
                if e.get("actor_id") == actor_hex and not e["alive"]]
        if dead:
            entry = dead[0]
            break
        time.sleep(0.1)
    assert entry is not None, "dead worker never appeared in the log index"

    text = ray_tpu.get_log(entry["proc_id"])
    assert "POSTMORTEM-STDOUT-LINE" in text
    assert "POSTMORTEM-STDERR-LINE" in text
    # Actor-id resolution hits the same (dead) worker's file.
    assert "POSTMORTEM-STDOUT-LINE" in ray_tpu.get_log(actor_hex)
    # Separate driver process: the CLI routes through its own head client.
    out = _cli("logs", entry["proc_id"])
    assert out.returncode == 0, out.stderr
    assert "POSTMORTEM-STDOUT-LINE" in out.stdout
    assert "POSTMORTEM-STDERR-LINE" in out.stdout


def test_stack_dump_mid_task(rt):
    """Acceptance: a live worker's all-thread stacks are captured while a
    task runs (the executing frame is visible in the dump) without failing
    or interrupting the task."""

    @ray_tpu.remote
    def snoozer():
        import time as _time

        def distinctive_inner_frame():
            _time.sleep(2.5)

        distinctive_inner_frame()
        return "done"

    ref = snoozer.remote()
    from ray_tpu.core.context import ctx

    worker_id = None
    deadline = time.time() + 15
    while time.time() < deadline:
        workers = ctx.client.call("list_state", {"kind": "workers"})["items"]
        leased = [w for w in workers if w["state"] == "leased"]
        if leased:
            worker_id = leased[0]["worker_id"]
            break
        time.sleep(0.02)
    assert worker_id, "task never dispatched"
    # Head-side LEASED can precede the worker dequeuing the spec by a few
    # ms; retry inside the task's sleep window until the frame is visible.
    dump = ""
    deadline = time.time() + 10
    while time.time() < deadline:
        dump = ray_tpu.stack_dump(worker_id)
        if "distinctive_inner_frame" in dump:
            break
        time.sleep(0.05)
    assert "distinctive_inner_frame" in dump  # the mid-task frame
    assert "Thread" in dump
    assert "running task" in dump  # the executing thread is annotated
    assert ray_tpu.get(ref, timeout=60) == "done"  # task undisturbed


def test_task_event_history_survives_worker_exit(rt):
    """Acceptance: a failed task's full traceback and state-transition
    timestamps stay in list_state(kind="task_events") after the worker
    that ran it has exited (the history lives at the head)."""

    @ray_tpu.remote
    class Faulty:
        def explode(self):
            raise ValueError("kaboom-sentinel-1234")

    f = Faulty.remote()
    with pytest.raises(Exception):
        ray_tpu.get(f.explode.remote(), timeout=60)
    from ray_tpu.core.context import ctx

    workers = ctx.client.call("list_state", {"kind": "workers"})["items"]
    actor_workers = {w["worker_id"] for w in workers if w["state"] == "actor"}
    ray_tpu.kill(f)  # the hosting worker process exits
    deadline = time.time() + 20
    while time.time() < deadline:
        workers = ctx.client.call("list_state", {"kind": "workers"})["items"]
        if not any(w["worker_id"] in actor_workers for w in workers):
            break
        time.sleep(0.1)
    else:
        pytest.fail("actor worker never exited")

    records = ray_tpu.task_events(errors=True)
    match = [r for r in records
             if "kaboom-sentinel-1234" in (r.get("traceback") or "")]
    assert match, f"no failed record with the traceback in {records}"
    rec = match[0]
    assert rec["state"] == "FAILED"
    assert "ValueError" in rec["traceback"]
    assert rec["worker_id"] and rec["node_id"]  # placement retained
    states = [e["state"] for e in rec["events"]]
    assert states[0] == "SUBMITTED" and states[-1] == "FAILED"
    assert "RUNNING" in states
    stamps = [e["ts"] for e in rec["events"]]
    assert stamps == sorted(stamps) and stamps[-1] > stamps[0] >= 0


def test_remote_node_log_routing():
    """get_log routes head -> owning node daemon -> file for workers on
    non-head nodes (the read_log RPC), so `ray_tpu logs` works from any
    machine."""
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(head_num_cpus=1)
    try:
        node = cluster.add_node(num_cpus=2)

        @ray_tpu.remote
        def say():
            print("REMOTE-NODE-LOG-LINE")
            sys.stdout.flush()
            return os.getpid()

        strat = ray_tpu.NodeAffinitySchedulingStrategy(node.hex)
        said_pid = ray_tpu.get(
            say.options(scheduling_strategy=strat).remote(), timeout=60)
        from ray_tpu.core.context import ctx

        # The node runs a worker for each of its CPUs and their logs join
        # the index in either order: wait for the entry of the worker that
        # printed, by its pid, and for the line to reach its file (a
        # deadline for a loaded host; a quiet one is done in under 1 s).
        text = ""
        deadline = time.time() + 90
        while time.time() < deadline:
            entries = ctx.client.call(
                "list_state", {"kind": "logs"})["items"]
            remote = [e for e in entries if e["kind"] == "worker"
                      and e["node_id"] == node.hex
                      and e["pid"] == said_pid]
            if remote:
                text = ray_tpu.get_log(remote[0]["proc_id"])
                if "REMOTE-NODE-LOG-LINE" in text:
                    break
            time.sleep(0.2)
        assert "REMOTE-NODE-LOG-LINE" in text
        # The node daemon registered its own log too.
        assert any(e["kind"] == "node" and e["log_path"] for e in entries)
    finally:
        cluster.shutdown()


def test_log_tee_drop_metric_and_residual_flush():
    """_LogTee satellite: lines past the in-flight window count into
    ray_tpu_logs_dropped_total instead of vanishing silently, and a
    trailing partial line (no newline) flushes at shutdown."""
    import io

    from ray_tpu.core.worker_main import _LogTee

    class FakeFut:
        def done(self):
            return False  # window never drains: forces drops

        def result(self, timeout=None):
            return {}

    class FakeRpc:
        def __init__(self):
            self.published = []

        def call_async(self, method, body):
            self.published.append(body)
            return FakeFut()

    class FakeClient:
        def __init__(self):
            self.rpc = FakeRpc()

    client = FakeClient()
    tee = _LogTee(io.StringIO(), client, "stdout")
    for i in range(250):
        tee.write(f"line-{i}\n")
    assert tee.dropped == 50  # window is 200
    assert len(client.rpc.published) == 200
    from ray_tpu.util.metrics import get_counter

    counter = get_counter("ray_tpu_logs_dropped_total")
    rows = counter._snapshot()
    assert sum(r["value"] for r in rows) >= 50

    tee.write("trailing-partial-no-newline")  # stays buffered: no newline
    assert len(client.rpc.published) == 200
    tee.flush_residual()
    assert client.rpc.published[-1]["data"]["line"] == \
        "trailing-partial-no-newline"


def test_head_state_persistence(tmp_path):
    state = str(tmp_path / "head.state")
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, system_config={"head_state_path": state})
    from ray_tpu.core.context import ctx

    ctx.client.kv_put("persisted-key", b"persisted-value")

    @ray_tpu.remote
    class Durable:
        def __init__(self, tag):
            self.tag = tag

        def get_tag(self):
            return self.tag

    d = Durable.options(name="durable-actor", lifetime="detached").remote("v1")
    assert ray_tpu.get(d.get_tag.remote()) == "v1"
    ray_tpu.shutdown()

    # "Restarted" head restores KV and re-creates the named actor.
    ray_tpu.init(num_cpus=2, system_config={"head_state_path": state})
    from ray_tpu.core.context import ctx as ctx2

    assert ctx2.client.kv_get("persisted-key") == b"persisted-value"
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            d2 = ray_tpu.get_actor("durable-actor")
            assert ray_tpu.get(d2.get_tag.remote(), timeout=30) == "v1"
            break
        except Exception:
            time.sleep(0.2)
    else:
        pytest.fail("named actor not restored from head state")
    ray_tpu.shutdown()


def test_job_submission(rt):
    from ray_tpu.job_submission import JobSubmissionClient

    client = JobSubmissionClient()
    job_id = client.submit_job(
        entrypoint=f"{sys.executable} -c \"print('job ran ok'); print(6*7)\"",
    )
    status = client.wait_until_finished(job_id, timeout=120)
    assert status == "SUCCEEDED"
    logs = client.get_job_logs(job_id)
    assert "job ran ok" in logs and "42" in logs
    assert any(j["job_id"] == job_id for j in client.list_jobs())


def test_job_failure_status(rt):
    from ray_tpu.job_submission import JobSubmissionClient

    client = JobSubmissionClient()
    job_id = client.submit_job(entrypoint=f"{sys.executable} -c 'exit(3)'")
    assert client.wait_until_finished(job_id, timeout=120) == "FAILED"


def test_device_trace_produces_profile(tmp_path):
    """jax.profiler wrapper: a traced block writes a TensorBoard profile
    (the TPU-side profiling story — reference ships nsight plugins for
    CUDA; XLA's profiler is the TPU equivalent)."""
    import jax.numpy as jnp

    from ray_tpu.util import profiling

    logdir = str(tmp_path / "tb")
    with profiling.device_trace(logdir):
        with profiling.step_annotation(0):
            x = jnp.arange(1024.0)
            with profiling.annotation("square"):
                (x * x).block_until_ready()

    import glob as g

    traces = g.glob(f"{logdir}/**/plugins/profile/**/*", recursive=True)
    assert traces, f"no profile output under {logdir}"


def test_cluster_down_cli(rt):
    """`ray_tpu down` routes shutdown_cluster over the control plane: the
    head must actually tear itself down (the CLI wiring for the formerly
    orphaned h_shutdown_cluster handler — rtlint RT003)."""
    import socket
    import time

    out = _cli("down")
    assert out.returncode == 0, out.stdout + out.stderr
    assert "shutdown requested" in out.stdout
    host, port = os.environ["RT_ADDRESS"].rsplit(":", 1)
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        try:
            socket.create_connection((host, int(port)), timeout=1).close()
            time.sleep(0.2)  # head still accepting: not down yet
        except OSError:
            break  # control-plane port closed: the head is gone
    else:
        raise AssertionError("head still accepting connections after down")
