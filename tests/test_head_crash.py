"""Head-crash survival: headless degraded mode, field-state resync, and the
head-kill chaos drill.

The control-plane crash drill this suite models: SIGKILL a standalone head
(``core/head_main.py`` via ``cluster_utils.ExternalHead``) while a workload
is in flight, restart it with the same port/session/node-id/state-path, and
assert the field survived — zero failed direct actor calls, nodes/workers
resync instead of dying, pre-crash objects stay readable, and the driver
completes without manual intervention.  Plus the safety half: when the head
NEVER returns, every node daemon and worker self-terminates within
``head_reconnect_deadline_s`` (no orphaned processes).

(reference: the Ray GCS FT release tests kill the GCS process under load
and assert raylets/workers reconnect and replay — gcs_server FT suite.)
"""

import os
import threading
import time

import pytest

import ray_tpu
from ray_tpu import exceptions

# Generous for the drill fixtures: reconnect backoff gaps (cap 2 s) plus
# head boot must fit comfortably inside it.  The deadline-suicide test
# overrides with its own tiny value.
DEADLINE_S = "20"


def _fresh_env(monkeypatch, deadline=DEADLINE_S):
    monkeypatch.setenv("RT_HEAD_RECONNECT_DEADLINE_S", deadline)
    monkeypatch.delenv("RT_ADDRESS", raising=False)


def _proc_gone(pid: int) -> bool:
    """True when the pid is not a LIVE process (dead or zombie): a reaped-
    by-init orphan disappears entirely; an unreaped child lingers as a
    zombie, which counts as exited for orphan-leak purposes."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            state = f.read().split(")")[-1].split()[0]
        return state == "Z"
    except OSError:
        return True


def _wait_procs_gone(pids, timeout_s):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if all(_proc_gone(p) for p in pids):
            return True
        time.sleep(0.25)
    return all(_proc_gone(p) for p in pids)


@pytest.fixture
def external_head(tmp_path, monkeypatch):
    """A standalone head + attached driver; tears down hard."""
    from ray_tpu.cluster_utils import ExternalHead

    _fresh_env(monkeypatch)
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    head = ExternalHead(state_path=str(tmp_path / "head.state"), num_cpus=2)
    ray_tpu.init(address=head.addr)
    yield head
    try:
        ray_tpu.shutdown()
    except Exception:
        pass
    head.shutdown()


# ---------------------------------------------------------------------------
# Acceptance: serve traffic + direct actor calls through a head SIGKILL.
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_head_kill_restart_zero_direct_call_failures(external_head):
    """The tentpole acceptance drill: continuous direct actor calls AND
    serve traffic run through a head SIGKILL -> outage -> restart.  Direct
    calls must see ZERO failures (the peer plane never touches the head);
    head-routed ops resume after a bounded pause; every worker resyncs
    (nobody os._exits on disconnect); the driver finishes by itself."""
    import warnings

    from ray_tpu import serve
    from ray_tpu.util.chaos import HeadKillInjector

    head = external_head

    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def bump(self):
            self.n += 1
            return self.n

        def pid(self):
            return os.getpid()

    c = Counter.remote()
    assert ray_tpu.get(c.bump.remote(), timeout=60) == 1
    actor_pid_before = ray_tpu.get(c.pid.remote(), timeout=60)

    @serve.deployment(num_replicas=1)
    def echo(x):
        return {"echo": x}

    handle = serve.run(echo.bind(), name="headkill-app")

    from ray_tpu.core.context import ctx

    direct_failures = []
    serve_failures = []
    direct_results = []
    serve_results = []
    stop = threading.Event()

    def direct_traffic():
        while not stop.is_set():
            try:
                direct_results.append(ray_tpu.get(c.bump.remote(), timeout=60))
            except Exception as e:  # noqa: BLE001 — collected for assertion
                direct_failures.append(repr(e))
                time.sleep(0.2)
            time.sleep(0.01)

    def serve_traffic():
        i = 0
        while not stop.is_set():
            try:
                r = handle.remote(i).result(timeout=60)
                serve_results.append(r["echo"])
            except Exception as e:  # noqa: BLE001
                serve_failures.append(repr(e))
                time.sleep(0.2)
            i += 1
            time.sleep(0.02)

    threads = [
        threading.Thread(target=direct_traffic, daemon=True),
        threading.Thread(target=serve_traffic, daemon=True),
    ]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        for t in threads:
            t.start()
        time.sleep(1.0)
        before_kill = len(direct_results)

        injector = HeadKillInjector(head, outage_s=1.5, max_kills=1)
        assert injector.kill_once()
        # Headless window check rode inside kill_once (outage_s); after the
        # restart the field resyncs while traffic keeps flowing.
        time.sleep(6.0)
        during = len(direct_results)
        stop.set()
        for t in threads:
            t.join(timeout=30)

    assert injector.kills == 1
    assert direct_failures == [], (
        f"direct calls failed across the head restart: {direct_failures[:3]}")
    assert during > before_kill, "direct traffic stalled across the restart"
    assert serve_results, "serve traffic never completed"

    # The direct-call actor's worker SURVIVED the restart (same process,
    # in-memory state intact: the counter never reset) and resynced into
    # the new head's worker table.
    assert ray_tpu.get(c.pid.remote(), timeout=60) == actor_pid_before, \
        "actor worker was replaced across the restart (state lost)"
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        workers_after = {
            w["pid"]
            for w in ctx.client.call(
                "list_state", {"kind": "workers"})["items"]
            if w.get("pid")
        }
        if actor_pid_before in workers_after:
            break
        time.sleep(0.5)
    assert actor_pid_before in workers_after, (
        "surviving actor worker never resynced into the head's table")

    # Head-routed ops work again post-resync (bounded pause, not an outage).
    @ray_tpu.remote
    def plain(x):
        return x * 3

    assert ray_tpu.get(plain.remote(5), timeout=60) == 15
    # The restart is visible in telemetry.
    rows = ctx.client.call("list_state", {"kind": "metrics"})["items"]
    by_name = {}
    for r in rows:
        by_name.setdefault(r["name"], 0)
        by_name[r["name"]] += r.get("value", 0)
    assert by_name.get("ray_tpu_head_restarts_total", 0) >= 1
    assert by_name.get("ray_tpu_resync_reports_total", 0) >= 1
    serve.delete("headkill-app")


@pytest.mark.chaos
def test_head_kill_node_manifest_and_named_actor_adoption(tmp_path, monkeypatch):
    """Field-state resync, node half: a non-head node's store manifest
    re-enters the restarted head's directory (pre-crash shm objects stay
    readable), and a LIVE named detached actor is ADOPTED from its worker's
    field report — not re-created fresh from the snapshot."""
    import numpy as np

    from ray_tpu.cluster_utils import Cluster, ExternalHead

    _fresh_env(monkeypatch, deadline="20")
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    head = ExternalHead(state_path=str(tmp_path / "head.state"), num_cpus=2)
    cluster = None
    try:
        ray_tpu.init(address=head.addr)
        cluster = Cluster.attach(head.addr)
        node = cluster.add_node(num_cpus=2)

        @ray_tpu.remote(scheduling_strategy=ray_tpu.
                        NodeAffinitySchedulingStrategy(node.hex, soft=False))
        def make_big():
            return np.arange(1024 * 1024, dtype=np.uint8)

        ref = make_big.remote()
        assert int(ray_tpu.get(ref, timeout=60)[:3].sum()) == 3

        @ray_tpu.remote
        class Keeper:
            def __init__(self):
                self.state = []

            def add(self, x):
                self.state.append(x)
                return len(self.state)

        k = Keeper.options(name="headkill-keeper",
                           lifetime="detached").remote()
        assert ray_tpu.get(k.add.remote("pre"), timeout=60) == 1

        head.kill()
        time.sleep(0.5)
        # Span emitted INSIDE the outage window: the batched span plane
        # must hold it in the bounded ring (headless flush is a no-op)
        # and replay it to the restarted head on the first post-reconnect
        # flush — spans survive a head crash like task_done reports.
        from ray_tpu.core.context import ctx as rt_ctx
        from ray_tpu.util import tracing

        # Wait for the driver to OBSERVE the dead connection (EOF on the
        # reader) so the emit below is deterministically headless.
        obs_deadline = time.monotonic() + 10
        while not rt_ctx.client.rpc.closed \
                and time.monotonic() < obs_deadline:
            time.sleep(0.05)
        assert rt_ctx.client.rpc.closed
        with tracing.trace("during_outage", force=True) as outage_root:
            pass
        assert tracing.flush_spans(rt_ctx.client) == 0  # headless: held
        time.sleep(1.0)
        head.restart()

        # The adopted actor kept its IN-MEMORY state: a fresh re-creation
        # from the snapshot would have restarted from [].
        assert ray_tpu.get(k.add.remote("post"), timeout=60) == 2
        # The outage-window span replayed into the restarted head's
        # timeline ring.
        deadline = time.monotonic() + 20
        names = set()
        while time.monotonic() < deadline:
            try:
                spans = rt_ctx.client.call(
                    "list_state",
                    {"kind": "traces",
                     "trace_id": outage_root["trace_id"]})["items"]
            except Exception:
                spans = []
            names = {s.get("name") for s in spans}
            if "during_outage" in names:
                break
            time.sleep(0.5)
        assert "during_outage" in names, (
            "span emitted while headless was lost across the restart")
        # The node's manifest replayed: the pre-crash object still reads.
        arr = ray_tpu.get(ref, timeout=60)
        assert int(arr[:3].sum()) == 3
        # And get_actor resolves the SAME adopted instance, once its worker
        # has resynced its name into the new head's table (the direct call
        # above never touched the head); the deadline is the failure only.
        deadline = time.monotonic() + 20
        while True:
            try:
                k2 = ray_tpu.get_actor("headkill-keeper")
                break
            except ValueError:
                assert time.monotonic() < deadline, \
                    "the adopted actor's name never reached the new head"
                time.sleep(0.25)
        assert ray_tpu.get(k2.add.remote("again"), timeout=60) == 3
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        if cluster is not None:
            cluster.shutdown()
        head.shutdown()


# ---------------------------------------------------------------------------
# Headless deadline: head never returns -> everything self-terminates.
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_headless_deadline_suicide_no_orphans(tmp_path, monkeypatch):
    """With the head never restarted, node daemons AND workers self-
    terminate within head_reconnect_deadline_s — no orphaned forkserver or
    worker processes survive the cluster."""
    from ray_tpu.cluster_utils import Cluster, ExternalHead

    _fresh_env(monkeypatch, deadline="3")
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    head = ExternalHead(state_path=str(tmp_path / "head.state"), num_cpus=2)
    cluster = None
    try:
        ray_tpu.init(address=head.addr)
        cluster = Cluster.attach(head.addr)
        node = cluster.add_node(num_cpus=2)

        @ray_tpu.remote(scheduling_strategy=ray_tpu.
                        NodeAffinitySchedulingStrategy(node.hex, soft=False))
        def where():
            return os.getpid()

        worker_pid = ray_tpu.get(where.remote(), timeout=60)

        @ray_tpu.remote
        class A:
            def pid(self):
                return os.getpid()

        actor_pid = ray_tpu.get(A.remote().pid.remote(), timeout=60)

        head.kill()  # and never restart
        # Deadline 3s + teardown slack: everything must be gone well within
        # the configured bound (assert generously at 4x).
        assert _wait_procs_gone(
            [node.proc.pid, worker_pid, actor_pid], timeout_s=20), (
            "processes survived the headless deadline: "
            f"node={_proc_gone(node.proc.pid)} "
            f"worker={_proc_gone(worker_pid)} actor={_proc_gone(actor_pid)}")
    finally:
        from ray_tpu.core.context import ctx

        # The driver's own client is stranded (head dead): close it
        # directly instead of shutdown()'s graceful path.
        try:
            if ctx.client is not None:
                ctx.client.rpc.close()
        except Exception:
            pass
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        if cluster is not None:
            cluster.shutdown()
        head.shutdown()


# ---------------------------------------------------------------------------
# Reconnect edges (satellite coverage).
# ---------------------------------------------------------------------------


def test_stale_worker_incarnation_refused(monkeypatch):
    """A worker claiming an actor the head has bound to another LIVE worker
    is refused adoption (stale incarnation), not silently adopted."""
    monkeypatch.delenv("RT_ADDRESS", raising=False)
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2)
    try:
        from ray_tpu.core import schema as wire_schema
        from ray_tpu.core.rpc import RpcClient

        @ray_tpu.remote
        class A:
            def ping(self):
                return "pong"

        a = A.remote()
        assert ray_tpu.get(a.ping.remote(), timeout=60) == "pong"

        host, port = os.environ["RT_ADDRESS"].rsplit(":", 1)
        impostor = RpcClient(host, int(port), name="impostor")
        try:
            reply = impostor.call("register", {
                "kind": "worker",
                "protocol": wire_schema.PROTOCOL_VERSION,
                "worker_id": os.urandom(16),
                "node_id": bytes.fromhex(ray_tpu.nodes()[0]["node_id"]),
                "pid": 999999,
                "reconnect": True,
                "resync": {"actor_id": a._actor_id.binary()},
            })
            assert reply.get("refused") == "stale_incarnation", reply
        finally:
            impostor.close()
        # The real actor is untouched by the refused impostor.
        assert ray_tpu.get(a.ping.remote(), timeout=60) == "pong"
    finally:
        ray_tpu.shutdown()


def test_worker_reconnect_unknown_actor_without_spec_refused(monkeypatch):
    """A reconnecting worker claiming an unknown actor WITHOUT a usable
    creation spec cannot be adopted: refused with a typed reason."""
    monkeypatch.delenv("RT_ADDRESS", raising=False)
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=1)
    try:
        from ray_tpu.core import schema as wire_schema
        from ray_tpu.core.rpc import RpcClient

        host, port = os.environ["RT_ADDRESS"].rsplit(":", 1)
        impostor = RpcClient(host, int(port), name="impostor2")
        try:
            reply = impostor.call("register", {
                "kind": "worker",
                "protocol": wire_schema.PROTOCOL_VERSION,
                "worker_id": os.urandom(16),
                "node_id": bytes.fromhex(ray_tpu.nodes()[0]["node_id"]),
                "pid": 999998,
                "reconnect": True,
                "resync": {"actor_id": os.urandom(16)},
            })
            assert reply.get("refused") == \
                "unknown_actor_without_creation_spec", reply
        finally:
            impostor.close()
    finally:
        ray_tpu.shutdown()


@pytest.mark.chaos
def test_driver_reconnect_races_inflight_lease_renewal(external_head):
    """Driver reconnect concurrent with lease renew/return traffic: stale
    lease ids land on the new head (which must ignore them without error),
    held slots are dropped and re-granted, and leased task submission keeps
    working after the restart."""
    import warnings

    head = external_head

    @ray_tpu.remote
    def leaf(x):
        return x + 1

    # Prime lease pools.
    assert sorted(ray_tpu.get([leaf.remote(i) for i in range(16)],
                              timeout=60)) == list(range(1, 17))

    from ray_tpu.core.context import ctx

    dp = ctx.client._dataplane
    stop = threading.Event()
    renew_errors = []

    def renew_storm():
        # Hammer maintain() (lease renewals/returns) right through the
        # restart window: stale ids must be ignored, never crash.
        while not stop.is_set():
            try:
                dp.maintain()
            except Exception as e:  # noqa: BLE001
                renew_errors.append(repr(e))
            time.sleep(0.01)

    t = threading.Thread(target=renew_storm, daemon=True)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        t.start()
        head.kill()
        time.sleep(1.0)
        head.restart()
        # First post-restart call heals the connection (or a maintain()
        # beat us to it) and re-primes leases.
        deadline = time.monotonic() + 30
        got = None
        while time.monotonic() < deadline:
            try:
                got = ray_tpu.get(leaf.remote(100), timeout=20)
                break
            except exceptions.HeadRestartedError:
                continue  # typed: resubmit is the documented contract
        stop.set()
        t.join(timeout=10)
    assert got == 101
    assert renew_errors == [], renew_errors
    # Leased submission still flows (new grants from the new head).
    assert sorted(ray_tpu.get([leaf.remote(i) for i in range(8)],
                              timeout=60)) == list(range(1, 9))


def test_head_restarted_error_is_typed_and_carries_method():
    err = exceptions.HeadRestartedError("submit_task", "resubmit the spec")
    from ray_tpu.core.rpc import ConnectionLost

    assert isinstance(err, ConnectionLost)
    assert err.method == "submit_task"
    import pickle

    err2 = pickle.loads(pickle.dumps(err))
    assert err2.method == "submit_task"
    assert err2.detail == "resubmit the spec"


def test_persist_state_dump_failure_rearms_dirty_bit(monkeypatch):
    """Satellite: a failed snapshot write (ENOSPC-class) must re-arm the
    dirty bit so the next tick retries — not leave the snapshot silently
    stale forever."""
    monkeypatch.delenv("RT_ADDRESS", raising=False)
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=1)
    try:
        from ray_tpu.core.context import ctx

        head, _ = ctx.head_process
        # Point the snapshot at an unwritable path and force a dump.
        head.config.head_state_path = "/proc/no/such/dir/head.state"
        head._state_dirty = True
        # No running loop on this thread -> persist_state runs dump inline.
        head.persist_state()
        assert head._state_dirty, (
            "failed dump left the dirty bit cleared: snapshot silently stale")
    finally:
        try:
            from ray_tpu.core.context import ctx

            ctx.head_process[0].config.head_state_path = ""
        except Exception:
            pass
        ray_tpu.shutdown()


def test_headless_client_buffers_batches_until_reconnect():
    """Satellite/unit: with a closed head connection, put/submit batches
    queue client-side (headless buffering) instead of vanishing into the
    dead socket."""
    import threading as _threading
    from collections import deque

    from ray_tpu.core import client as client_mod

    class DeadRpc:
        closed = True

        def call_async(self, *a, **k):  # pragma: no cover — must not fire
            raise AssertionError("headless client fired into a dead socket")

    c = client_mod.Client.__new__(client_mod.Client)
    c.rpc = DeadRpc()
    c._bg_exc = None
    c._bg_futs = deque()
    c._bg_lock = _threading.Lock()
    c._put_batch = [{"object_id": b"x" * 16, "inline": b"v"}]
    c._put_batch_lock = _threading.Lock()
    c._submit_batch = [{"method": "task_done", "body": {"task_id": b"t"}}]
    c._submit_batch_lock = _threading.Lock()

    c._flush_put_batch()
    c._flush_submit_batch()
    assert len(c._put_batch) == 1, "put batch dropped while headless"
    assert len(c._submit_batch) == 1, "submit batch dropped while headless"
