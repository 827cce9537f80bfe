"""Dataplane tests: peer-to-peer actor calls, node-local task leases, and —
most importantly — every degraded path's fallback to the head-mediated
plane (the correctness baseline).

Models the reference's direct-call/lease coverage
(python/ray/tests/test_actor_*.py direct-call paths,
test_multinode_failures.py lease reclamation).
"""

import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu import exceptions

# The fallback-correctness CI run (RT_DIRECT_CALLS=0 RT_TASK_LEASES=0 over
# the whole suite) proves the head-mediated path alone; these tests assert
# dataplane behavior and are vacuous there.
pytestmark = pytest.mark.skipif(
    os.environ.get("RT_DIRECT_CALLS") == "0"
    or os.environ.get("RT_TASK_LEASES") == "0",
    reason="dataplane force-disabled via env",
)


@pytest.fixture(scope="module")
def rt():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    yield ray_tpu
    ray_tpu.shutdown()


def _dp():
    from ray_tpu.core.context import ctx

    assert ctx.client._dataplane is not None
    return ctx.client._dataplane


def _head_dispatched():
    from ray_tpu.core.context import ctx

    rows = ctx.client.call("list_state", {"kind": "metrics"})["items"]
    for r in rows:
        if r["name"] == "ray_tpu_scheduler_tasks_dispatched_total":
            return float(r["value"])
    return 0.0


def _metric(name):
    from ray_tpu.core.context import ctx

    rows = ctx.client.call("list_state", {"kind": "metrics"})["items"]
    return sum(float(r["value"]) for r in rows if r["name"] == name)


def _await_metric(name, timeout=8.0):
    """Counters ride the 2s background metrics flusher; poll for them."""
    deadline = time.monotonic() + timeout
    v = _metric(name)
    while time.monotonic() < deadline and v == 0.0:
        time.sleep(0.25)
        v = _metric(name)
    return v


@ray_tpu.remote
class Echo:
    def __init__(self):
        self.n = 0

    def ping(self, x=None):
        self.n += 1
        return x if x is not None else self.n

    def crash(self):
        os._exit(1)

    def stream(self, k):
        for i in range(k):
            yield i * 10


def _establish_direct(rt, actor, timeout=15.0):
    """Drive the route to the direct plane: calls + idle gaps until the
    client's cache holds a live peer slot."""
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


# --------------------------------------------------------------- direct plane


def test_direct_calls_bypass_head_dispatch(rt):
    """Steady-state actor calls must leave the head's dispatch counter
    flat: the head sees liveness and batched telemetry, never per-call
    traffic (the PR's acceptance probe)."""
    a = Echo.remote()
    _establish_direct(rt, a)
    d0 = _head_dispatched()
    vals = rt.get([a.ping.remote(7) for _ in range(200)])
    assert vals == [7] * 200
    assert _head_dispatched() - d0 == 0.0
    assert _await_metric("ray_tpu_direct_calls_total") > 0


def test_direct_fifo_order_preserved(rt):
    a = Echo.remote()
    _establish_direct(rt, a)
    base = rt.get(a.ping.remote())
    vals = rt.get([a.ping.remote() for _ in range(60)])
    assert vals == list(range(base + 1, base + 61))


def test_peer_dial_failure_falls_back_and_reresolves(rt):
    """Dead peer connection: calls degrade to the head path (correct
    results, no hang) and a later call re-resolves a fresh route."""
    a = Echo.remote()
    route = _establish_direct(rt, a)
    old_slot = route.slot
    old_slot.conn.close()  # simulates the worker endpoint going away
    # Every call keeps working through the fallback...
    assert rt.get([a.ping.remote(1) for _ in range(10)]) == [1] * 10
    # ...and the cache heals to a live route again.
    route = _establish_direct(rt, a)
    assert route.slot is not old_slot and not route.slot.conn.closed


def test_stale_incarnation_refused_not_misexecuted(rt):
    """A call carrying a stale worker identity must be REFUSED by the peer
    server (never executed on the wrong worker) and complete correctly via
    the head fallback."""
    a = Echo.remote()
    b = Echo.remote()
    route_a = _establish_direct(rt, a)
    _establish_direct(rt, b)
    na = rt.get(a.ping.remote())
    nb = rt.get(b.ping.remote())
    # Corrupt a's cached identity: the next direct submit hits a live
    # server that answers for a DIFFERENT worker id.
    with _dp()._lock:
        route_a.slot.worker_id = os.urandom(16)
    assert rt.get(a.ping.remote()) == na + 1  # refused -> head -> actor a
    assert rt.get(b.ping.remote()) == nb + 1  # b untouched


def test_actor_restart_invalidates_route(rt):
    """Worker death + actor restart: the cached address dies with the
    incarnation; calls flow via the head during the restart and the route
    re-resolves to the NEW worker."""
    a = Echo.options(max_restarts=1).remote()
    route = _establish_direct(rt, a)
    old_worker = route.slot.worker_id
    try:
        rt.get(a.crash.remote(), timeout=30)
    except (exceptions.WorkerCrashedError, exceptions.ActorDiedError,
            exceptions.TaskError):
        pass
    # Restarted actor answers (head path first, then direct again).
    assert rt.get(a.ping.remote(5), timeout=60) == 5
    route = _establish_direct(rt, a)
    assert route.slot.worker_id != old_worker


def test_direct_result_shared_with_other_process(rt):
    """A direct-call result ref passed onward must be readable by another
    process: the submitter registers it head-side before sharing."""
    a = Echo.remote()
    _establish_direct(rt, a)
    ref = a.ping.remote({"payload": 123})

    @rt.remote
    def consume(v):
        return v["payload"] + 1

    # SPREAD forces the consumer through the head path on a non-leased
    # worker — it can only resolve the arg if the head knows the object.
    assert rt.get(
        consume.options(scheduling_strategy="SPREAD").remote(ref),
        timeout=60,
    ) == 124


def test_direct_streaming(rt):
    """Direct-result streaming: items flow straight from the executing
    worker (peer_next_stream_item), not via head stream_item traffic."""
    a = Echo.remote()
    _establish_direct(rt, a)
    d0 = _head_dispatched()
    gen = a.stream.options(num_returns="streaming").remote(5)
    assert [rt.get(r) for r in gen] == [0, 10, 20, 30, 40]
    assert _head_dispatched() - d0 == 0.0


def test_a_late_consumer_catches_up_in_one_pull(rt):
    """A consumer behind its producer is handed, with the item it asked for,
    the inline items already behind it (``_STREAM_AHEAD`` at most), and
    adopts them without a round trip each: every item once, in order,
    whatever mix of pulls and adoptions brought it; one that keeps pace
    still pulls item by item."""
    a = Echo.remote()
    _establish_direct(rt, a)
    gen = a.stream.options(num_returns="streaming").remote(150)
    time.sleep(1.0)  # the producer runs ahead of the first pull
    first = rt.get(next(gen))
    assert first == 0 and len(gen._ahead) == 64
    # Adopted refs resolve like pulled ones, and the end is still the
    # producer's to say (the list ends on its StopIteration).
    assert [rt.get(r) for r in gen] == [10 * i for i in range(1, 150)]
    assert gen._index == 150 and not gen._ahead


def test_a_streams_values_come_without_references(rt):
    """``ObjectRefGenerator.values()``: each inline item unpacked where it
    arrives, late or on time, in order; nothing is sealed for it."""
    a = Echo.remote()
    _establish_direct(rt, a)
    dp = _dp()
    gen = a.stream.options(num_returns="streaming").remote(100)
    time.sleep(0.5)
    with dp._lock:
        sealed = len(dp._results)
    assert list(gen.values()) == [10 * i for i in range(100)]
    with dp._lock:
        assert len(dp._results) <= sealed
    assert gen._index == 100


def test_direct_error_and_cancel(rt):
    @rt.remote
    class Bad:
        def fail(self):
            raise RuntimeError("direct boom")

        def ping(self):
            return 1

    b = Bad.remote()
    rt.get(b.ping.remote())
    time.sleep(0.6)
    rt.get(b.ping.remote())
    with pytest.raises(exceptions.TaskError, match="direct boom"):
        rt.get(b.fail.remote(), timeout=30)
    # The actor survives the method error on the direct plane too.
    assert rt.get(b.ping.remote()) == 1


def test_route_prewarmed_at_creation(rt):
    """Satellite: the ALIVE broadcast carries the peer address and the
    creating client dials during creation dispatch — the first call finds
    a warm route instead of paying the resolve+handshake cliff."""
    a = Echo.remote()  # no calls yet
    raw = a._actor_id.binary()
    dp = _dp()
    deadline = time.monotonic() + 20
    warmed = False
    while time.monotonic() < deadline and not warmed:
        with dp._lock:
            route = dp._routes.get(raw)
            warmed = (route is not None and route.slot is not None
                      and not route.slot.dead)
        time.sleep(0.1)
    assert warmed, "creation broadcast never pre-dialed the peer route"
    # First call rides the warm route: head dispatch counter stays flat.
    d0 = _head_dispatched()
    assert rt.get(a.ping.remote(9)) == 9
    assert _head_dispatched() - d0 == 0.0


# ---------------------------------------------------------------- task leases


def test_leased_tasks_bypass_head_dispatch(rt):
    @rt.remote
    def nop():
        return b"ok"

    rt.get([nop.remote() for _ in range(10)])
    time.sleep(1.0)
    rt.get([nop.remote() for _ in range(10)])  # leases engaged by now
    dp = _dp()
    with dp._lock:
        have_slots = any(
            s for p in dp._pools.values() for s in p.slots if not s.dead)
    assert have_slots, "no lease slots were ever granted"
    d0 = _head_dispatched()
    assert rt.get([nop.remote() for _ in range(100)]) == [b"ok"] * 100
    assert _head_dispatched() - d0 == 0.0
    assert _await_metric("ray_tpu_leased_tasks_total") > 0


def test_lease_idle_return_frees_slots(rt):
    """Idle-held slots (and their reserved resources) must flow back: the
    workers leave the 'direct' state and cluster capacity recovers."""
    from ray_tpu.core.config import get_config
    from ray_tpu.core.context import ctx

    @rt.remote
    def nop():
        return 1

    rt.get([nop.remote() for _ in range(8)])
    deadline = time.monotonic() + get_config().lease_idle_return_s + 10
    while time.monotonic() < deadline:
        ws = ctx.client.call("list_state", {"kind": "workers"})["items"]
        if not any(w["state"] == "direct" for w in ws):
            break
        time.sleep(0.3)
    ws = ctx.client.call("list_state", {"kind": "workers"})["items"]
    assert not any(w["state"] == "direct" for w in ws), \
        "leases never returned after going idle"
    total = rt.cluster_resources()["CPU"]
    avail = rt.available_resources()["CPU"]
    assert avail == total, f"leaked lease resources: {avail}/{total}"


def test_lease_preempted_for_starved_head_shape(rt):
    """Scheduler invariant: leases must not starve shapes only the head
    can place — a queued task waiting on leased-out capacity revokes a
    lease and runs."""

    @rt.remote
    def nop():
        return 1

    rt.get([nop.remote() for _ in range(8)])  # grab slots (4 CPU leased)

    @rt.remote(num_cpus=4)
    def big():
        return "ran"

    # Needs every CPU on the node: can only place once leases give back.
    assert rt.get(big.remote(), timeout=60) == "ran"


def test_retry_exceptions_via_direct_plane(rt):
    """App-level retryable failure on a leased worker hands the remaining
    budget to the head path."""

    @rt.remote
    def flaky(key):
        from ray_tpu.core.context import ctx

        if ctx.client.kv_put(f"dp-flaky:{key}", b"1", overwrite=False):
            raise RuntimeError("first attempt fails")
        return "ok"

    @rt.remote
    def nop():
        return 1

    rt.get([nop.remote() for _ in range(8)])
    time.sleep(0.8)
    rt.get(nop.remote())
    assert rt.get(
        flaky.options(max_retries=2, retry_exceptions=True).remote("x"),
        timeout=60,
    ) == "ok"


# --------------------------------------------------- degraded cluster paths


@pytest.mark.chaos
def test_lease_revocation_on_drain_leaves_no_orphans():
    """SIGTERM drain of a node holding leased slots: the head revokes the
    leases, in-flight direct tasks drain or fall back, and every submitted
    task completes — no orphans (the PR's drain acceptance)."""
    from ray_tpu.cluster_utils import Cluster

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    c = Cluster(head_num_cpus=0)  # tasks can only run on the added node
    try:
        n = c.add_node(num_cpus=2, drain_grace_s=4.0)

        @ray_tpu.remote
        def work(i):
            time.sleep(0.05)
            return i

        # Warm leases onto the node's workers.
        ray_tpu.get([work.remote(i) for i in range(4)], timeout=90)
        time.sleep(0.5)
        refs = [work.remote(i) for i in range(30)]
        time.sleep(0.1)  # some in flight when the preemption lands
        c.preempt_node(n)
        assert sorted(ray_tpu.get(refs, timeout=120)) == list(range(30))
        deadline = time.monotonic() + 30
        revoked = 0.0
        while time.monotonic() < deadline and revoked == 0.0:
            revoked = _metric("ray_tpu_lease_revocations_total")
            time.sleep(0.25)
        assert revoked > 0, "drain never revoked the node's leases"
    finally:
        c.shutdown()


def test_dataplane_force_disabled_env_flag():
    """RT_DIRECT_CALLS=0 + RT_TASK_LEASES=0: no dataplane at all — every
    call takes the head-mediated path and still works (the fallback
    correctness acceptance, in miniature; the full suite runs under this
    flag in CI via the same env)."""
    script = r"""
import ray_tpu
ray_tpu.init(num_cpus=2)
from ray_tpu.core.context import ctx
assert ctx.client._dataplane is None

@ray_tpu.remote
def nop():
    return 1

@ray_tpu.remote
class A:
    def ping(self):
        return 2

assert ray_tpu.get([nop.remote() for _ in range(20)]) == [1] * 20
a = A.remote()
assert ray_tpu.get([a.ping.remote() for _ in range(20)]) == [2] * 20
ray_tpu.shutdown()
print("DISABLED-OK")
"""
    env = dict(os.environ, RT_DIRECT_CALLS="0", RT_TASK_LEASES="0",
               JAX_PLATFORMS="cpu")
    env.pop("RT_ADDRESS", None)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "DISABLED-OK" in proc.stdout
