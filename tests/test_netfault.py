"""Network fault-injection plane: the seeded FaultSchedule (util/netfault),
the unified deadline/backoff policy (core/deadline), and the gray-failure
handling they enable — partitions heal without duplicate execution, stalled
peers get quarantined, stalled serve replicas get ejected.

Reference analogs: release/nightly_tests/chaos_test network chaos + the
gcs_health_check_manager gray-failure tests.  Chaos-marked tests rotate
seeds under scripts/chaos_soak.sh --netfault via RT_NETFAULT_SEED.
"""

import asyncio
import os
import time
from concurrent.futures import TimeoutError as CfTimeoutError

import pytest

import ray_tpu
from ray_tpu import exceptions
from ray_tpu.util import netfault

SEED = int(os.environ.get("RT_NETFAULT_SEED", "1"))


# ------------------------------------------------------------- schedule unit


def test_parse_rejects_unknown_kinds_and_keys():
    with pytest.raises(ValueError, match="unknown fault kind"):
        netfault.FaultSchedule("explode:p=1")
    with pytest.raises(ValueError, match="unknown rule key"):
        netfault.FaultSchedule("delay:frobnicate=1")


def test_schedule_is_deterministic_per_seed():
    """Same (seed, traffic order) -> identical decision sequence; a soak
    failure replays exactly from its printed seed."""
    spec = "drop_request:link=x,p=0.4;dup_reply:link=x,p=0.3"

    def drive(seed):
        s = netfault.FaultSchedule(spec, seed)
        sends = [s.on_send("x-client", "m") is not None for _ in range(200)]
        recvs = [s.on_recv("x-client", "m") is not None for _ in range(200)]
        return sends, recvs

    assert drive(7) == drive(7)
    assert drive(7) != drive(8)
    # Both branches actually exercised at these probabilities.
    sends, recvs = drive(7)
    assert 20 < sum(sends) < 180 and 10 < sum(recvs) < 180


def test_schedule_window_and_link_matching():
    s = netfault.FaultSchedule("partition:link=node-rpc,at=3600,dur=1")
    # Window not open yet: nothing injected.
    assert s.on_send("node-rpc", "heartbeat") is None
    s2 = netfault.FaultSchedule("partition:link=node-rpc")
    assert s2.on_send("node-rpc", "x") == {"kind": "drop"}
    assert s2.on_send("worker-rpc", "x") is None  # link mismatch
    assert s2.on_recv("node-rpc", "x") == {"kind": "drop"}  # sym: both ways
    s3 = netfault.FaultSchedule("partition:link=node-rpc,mode=out")
    assert s3.on_send("node-rpc", "x") == {"kind": "drop"}
    assert s3.on_recv("node-rpc", "x") is None  # one-way: replies pass


# ------------------------------------------------------- deadline/backoff unit


def test_backoff_policy_curve_and_jitter():
    from ray_tpu.core.deadline import BackoffPolicy

    p = BackoffPolicy(base_s=0.1, multiplier=2.0, cap_s=0.4, jitter=0.0)
    assert [p.delay(i) for i in range(1, 5)] == [0.1, 0.2, 0.4, 0.4]
    j = BackoffPolicy(base_s=0.1, multiplier=2.0, cap_s=10.0, jitter=0.5)
    for _ in range(50):
        assert 0.05 <= j.delay(1) <= 0.15


def test_deadline_budget_and_clipping():
    from ray_tpu.core.deadline import BackoffPolicy, Deadline

    d = Deadline.after(0.2)
    assert 0.0 < d.remaining() <= 0.2 and not d.expired
    assert d.timeout(cap=10.0) <= 0.2
    # sleep() clips to the deadline: a 1s backoff inside a 0.2s budget
    # must return quickly, not overshoot.
    t0 = time.monotonic()
    BackoffPolicy(base_s=1.0, jitter=0.0).sleep(1, deadline=d)
    assert time.monotonic() - t0 < 0.5
    time.sleep(0.25)
    assert d.expired and d.timeout() == 0.0


# -------------------------------------------------------- rpc loopback + arm


@pytest.fixture
def loopback():
    """A loopback RpcServer/RpcClient pair; any in-process schedule is
    disarmed on the way out."""
    from ray_tpu.core import rpc

    server = rpc.RpcServer(name="unit-server")
    server.register("ping", lambda conn, body: {"echo": body})

    async def slow(conn, body):
        await asyncio.sleep(body["s"])
        return "slept"

    server.register("slow", slow)
    st = rpc.ServerThread(server)
    port = st.start()
    client = rpc.RpcClient("127.0.0.1", port, name="unit-client")
    try:
        yield server, client
    finally:
        netfault.disarm()
        client.close()
        st.stop()


def test_rpc_timeout_cleans_pending_and_late_reply_is_noop(loopback):
    """Regression: a timed-out call used to leak its _pending entry; the
    late reply then resolved a future nobody owned (and a dup delivery
    could double-resolve).  The abandon path must pop its own seq."""
    server, client = loopback
    with pytest.raises(CfTimeoutError):
        client.call("slow", {"s": 1.0}, timeout=0.2)
    assert client._pending == {}, "timed-out call leaked its pending entry"
    # The late reply (handler finishes ~0.8s from now) must be a silent
    # no-op; the connection stays healthy for the next caller.
    time.sleep(1.0)
    assert client.call("ping", {"x": 1}, timeout=5) == {"echo": {"x": 1}}
    assert client._pending == {}


def test_drop_reply_injection_counts_and_recovers(loopback):
    server, client = loopback
    sched = netfault.arm("drop_reply:link=unit-client,method=ping", SEED)
    with pytest.raises(CfTimeoutError):
        client.call("ping", {}, timeout=0.3)
    with sched._lock:
        assert sched.counts.get("drop_reply", 0) >= 1
    netfault.disarm()
    assert client.call("ping", {"y": 2}, timeout=5) == {"echo": {"y": 2}}


def test_dup_reply_delivered_once_to_caller(loopback):
    server, client = loopback
    sched = netfault.arm("dup_reply:link=unit-client", SEED)
    assert client.call("ping", {"z": 3}, timeout=5) == {"echo": {"z": 3}}
    with sched._lock:
        assert sched.counts.get("dup_reply", 0) >= 1
    # The duplicate resolved nothing twice; the next seq is undisturbed.
    assert client.call("ping", {"z": 4}, timeout=5) == {"echo": {"z": 4}}


def test_delay_injection_adds_latency(loopback):
    server, client = loopback
    netfault.arm("delay:link=unit-client,ms=150", SEED)
    t0 = time.monotonic()
    assert client.call("ping", {}, timeout=5) == {"echo": {}}
    assert time.monotonic() - t0 >= 0.1


def test_server_stall_models_gray_failure(loopback):
    """stall: the TCP accept succeeds (peer looks alive) but nothing is
    read until the window closes — the canonical gray failure."""
    from ray_tpu.core import rpc

    server, _ = loopback
    armed = time.monotonic()
    sched = netfault.arm("stall:link=unit-server,dur=1", SEED)
    stalled = rpc.RpcClient("127.0.0.1", server.port, name="unit-client-2")
    try:
        with pytest.raises(CfTimeoutError):
            stalled.call("ping", {}, timeout=0.3)  # alive but mute
        # After the stall window the same connection serves normally: the
        # answer cannot come before the window, armed for 1 s, has closed.
        assert stalled.call("ping", {"w": 5}, timeout=5) == {"echo": {"w": 5}}
        assert time.monotonic() - armed >= 0.95
        # The stall this test armed and saw end was injected, not a slow
        # box.  Not an exact count: the server's loop may reach the accept
        # of the fixture's own client only after the arming on a loaded
        # box, and that connection then stalls and counts beside this one.
        with sched._lock:
            assert sched.counts.get("stall", 0) >= 1
    finally:
        stalled.close()


def test_netfault_off_means_off(loopback):
    """With nothing armed the transport must not consult any schedule."""
    from ray_tpu.core import rpc

    server, client = loopback
    assert rpc._netfault is None
    assert client.call("ping", {}, timeout=5) == {"echo": {}}


# --------------------------------------------------------------- cluster chaos


def _metric(name):
    from ray_tpu.core.context import ctx

    rows = ctx.client.call("list_state", {"kind": "metrics"})["items"]
    return sum(float(r["value"]) for r in rows if r["name"] == name)


def _await_metric(name, floor=0.0, timeout=10.0):
    """Counters ride the background metrics flusher; poll for them."""
    deadline = time.monotonic() + timeout
    v = _metric(name)
    while time.monotonic() < deadline and v <= floor:
        time.sleep(0.25)
        v = _metric(name)
    return v


def _dp():
    from ray_tpu.core.context import ctx

    assert ctx.client._dataplane is not None
    return ctx.client._dataplane


def _establish_direct(rt, actor, timeout=15.0):
    raw = actor._actor_id.binary()
    dp = _dp()
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


@ray_tpu.remote
class Counter:
    def __init__(self):
        self.n = 0

    def ping(self):
        return self.n

    def add(self):
        self.n += 1
        return self.n


@pytest.mark.chaos
@pytest.mark.skipif(os.environ.get("RT_DIRECT_CALLS") == "0",
                    reason="dataplane force-disabled via env")
def test_head_partition_heals_with_zero_duplicate_executions(monkeypatch):
    """A seeded 5s head<->node partition (node daemon + worker head links
    dark, inside the reconnect deadline) under live serve + direct-actor
    traffic: every call completes, every increment executes exactly once,
    and the node is still a live member afterwards."""
    from ray_tpu import serve
    from ray_tpu.cluster_utils import Cluster

    monkeypatch.setenv(
        "RT_NETFAULT",
        "partition:link=node-rpc,at=4,dur=5;"
        "partition:link=worker-rpc,at=4,dur=5",
    )
    monkeypatch.setenv("RT_NETFAULT_SEED", str(SEED))
    cluster = Cluster(head_num_cpus=2)
    try:
        n1 = cluster.add_node(num_cpus=2)
        c = Counter.options(
            scheduling_strategy=ray_tpu.NodeAffinitySchedulingStrategy(
                n1.hex)
        ).remote()
        _establish_direct(ray_tpu, c)

        @serve.deployment(num_replicas=2)
        class Doubler:
            def __call__(self, x):
                return x * 2

        handle = serve.run(Doubler.bind())
        try:
            # Drive increments + serve requests continuously across the
            # partition windows: each node process armed at its spawn, so
            # its dark window spans roughly [spawn+4, spawn+9] — the 12s
            # drive from here straddles every window.
            t_end = time.monotonic() + 12.0
            done = 0
            while done < 40 or time.monotonic() < t_end:
                assert ray_tpu.get(c.add.remote(), timeout=60) == done + 1
                assert handle.remote(done).result(timeout=60) == done * 2
                done += 1
                time.sleep(0.15)
            # Exactly-once: the actor's counter equals the number of
            # calls — a duplicate delivery or replayed retry overshoots.
            assert ray_tpu.get(c.ping.remote(), timeout=60) == done
            # The partition healed inside the deadline: node still alive.
            alive = {n["node_id"] for n in ray_tpu.nodes() if n["alive"]}
            assert n1.hex in alive
            # The chaos actually fired: the node's processes flushed
            # their injection counters to the head.
            assert _await_metric("ray_tpu_netfaults_injected_total") > 0, \
                "partition never dropped a frame; the test proved nothing"
        finally:
            serve.shutdown()
    finally:
        cluster.shutdown()


@pytest.fixture(scope="module")
def rt_tight():
    """A cluster whose peer deadline budget is tight enough to watch the
    quarantine machinery act within a test's patience."""
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, system_config={
        "peer_call_deadline_s": 1.0,
        "peer_quarantine_probe_s": 0.5,
    })
    yield ray_tpu
    netfault.disarm()
    ray_tpu.shutdown()


@pytest.mark.chaos
@pytest.mark.skipif(os.environ.get("RT_DIRECT_CALLS") == "0",
                    reason="dataplane force-disabled via env")
def test_peer_partition_quarantines_then_reprobes(rt_tight):
    """One-way peer partition (the worker RECEIVES and executes, its
    replies vanish): within one deadline budget the watchdog quarantines
    the route and the in-flight call completes via the head — where the
    worker's dedup cache answers the re-dispatch from the recorded result
    instead of executing twice.  After the window the next dial re-probes
    and traffic goes direct again."""
    rt = rt_tight
    c = Counter.remote()
    route = _establish_direct(rt, c)
    addr = route.slot.addr
    q0 = _metric("ray_tpu_peer_quarantines_total")
    sched = netfault.arm("partition:link=peer-direct,dur=2,mode=in", SEED)
    try:
        t0 = time.monotonic()
        # The direct reply is dropped on the wire; the peer watchdog must
        # reroute via the head well before the 60s get timeout.  The
        # increment must land exactly once (== 1, not 2) even though the
        # task was delivered twice.
        assert rt.get(c.add.remote(), timeout=60) == 1
        assert time.monotonic() - t0 < 10.0
        with sched._lock:
            assert sched.counts.get("partition", 0) >= 1
        dp = _dp()
        with dp._lock:
            assert addr in dp._quarantine, "slow route was not quarantined"
        assert _await_metric("ray_tpu_peer_quarantines_total", floor=q0) \
            > q0
        # Calls keep flowing (head path) while the route is dark.
        assert rt.get([c.add.remote() for _ in range(5)],
                      timeout=60) == list(range(2, 7))
    finally:
        netfault.disarm()
    # Partition over: the quarantine lift re-probes and the route heals to
    # the direct plane (exactly-once held throughout: count is exact).
    route = _establish_direct(rt, c)
    assert not route.slot.dead
    assert rt.get(c.ping.remote(), timeout=30) == 6


@pytest.mark.chaos
@pytest.mark.skipif(os.environ.get("RT_DIRECT_CALLS") == "0",
                    reason="dataplane force-disabled via env")
def test_stream_survives_peer_partition_or_fails_typed(rt_tight):
    """Peer partition mid-stream: the indexed item pull retries after the
    window (items resume, each exactly once) or fails with the typed
    WorkerCrashedError — never a hang, never a duplicated item."""
    rt = rt_tight

    @ray_tpu.remote
    class Streamer:
        def ping(self):
            return 1

        def stream(self, k):
            for i in range(k):
                time.sleep(0.1)
                yield i * 10

    s = Streamer.remote()
    _establish_direct(rt, s)
    gen = s.stream.options(num_returns="streaming").remote(8)
    it = iter(gen)
    got = [rt.get(next(it), timeout=30) for _ in range(2)]
    netfault.arm("partition:link=peer-direct,dur=1.2", SEED)
    try:
        for r in it:
            got.append(rt.get(r, timeout=30))
        assert got == [i * 10 for i in range(8)]
    except exceptions.WorkerCrashedError:
        pass  # typed mid-stream failure is the accepted degraded outcome
    finally:
        netfault.disarm()


@pytest.mark.chaos
def test_serve_stalled_replica_ejected_and_retried(rt_tight):
    """A replica that accepts a request and goes mute: the handle ejects
    it after stall_timeout_s, retries on the healthy replica within
    REPLICA_RETRY_BUDGET, and the retry lands in the existing replica
    retry metric under path=stall."""
    from ray_tpu import serve

    rt = rt_tight

    @ray_tpu.remote
    class Roles:
        def __init__(self):
            self.n = 0

        def next(self):
            self.n += 1
            return self.n

    roles = Roles.remote()

    @serve.deployment(num_replicas=2)
    class Svc:
        def __init__(self, roles):
            # First replica up becomes the (one-shot) staller.
            self.stall = ray_tpu.get(roles.next.remote()) == 1

        def __call__(self, x):
            if self.stall:
                self.stall = False
                time.sleep(3.0)
            return x * 2

    handle = serve.run(Svc.bind(roles))
    r0 = _metric("ray_tpu_serve_replica_retries_total")
    try:
        h = handle.options(stall_timeout_s=0.6)
        results = [h.remote(i).result(timeout=30) for i in range(8)]
        assert results == [i * 2 for i in range(8)]
        assert _await_metric("ray_tpu_serve_replica_retries_total",
                             floor=r0) > r0, \
            "stall retry never landed in the replica retry metric"
    finally:
        serve.shutdown()
