"""Regression tests for control-plane fault-tolerance semantics:

- blocked-worker resource release (nested gets deeper than the pool cap)
- actor max_task_retries across worker death (in-flight call survives restart)
- large-arg object lifetime (no shm leak after the task finishes)
- placement-group pending queue + ready()
- health-check reaping of wedged workers; idle-worker reaping
- collective group re-initialization under the same name (fresh incarnation)

Models the reference's python/ray/tests/test_failure*.py and
test_placement_group*.py coverage.
"""

import os
import pickle
import signal
import time

import pytest

import ray_tpu
from ray_tpu import exceptions


def _fresh(**kw):
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(**kw)
    return ray_tpu


@pytest.fixture
def rt2():
    """Tiny worker pool: forces the blocked-worker paths."""
    rt = _fresh(num_cpus=2)
    yield rt
    ray_tpu.shutdown()


def test_nested_get_beyond_worker_cap(rt2):
    """Recursive fan deeper than the pool cap must not deadlock: a worker
    blocked in get releases its CPU so a replacement can run the child."""

    @ray_tpu.remote
    def nest(depth):
        if depth == 0:
            return 1
        return 1 + ray_tpu.get(nest.remote(depth - 1))

    assert ray_tpu.get(nest.remote(5), timeout=60) == 6


def test_blocked_wait_releases_resources(rt2):
    @ray_tpu.remote
    def child():
        return "c"

    @ray_tpu.remote
    def parent():
        refs = [child.remote() for _ in range(3)]
        ready, _ = ray_tpu.wait(refs, num_returns=3, timeout=30)
        return len(ready)

    assert ray_tpu.get(parent.remote(), timeout=60) == 3


def test_actor_task_retry_on_worker_death(rt2):
    """An in-flight actor call survives the actor's worker dying when
    max_task_retries allows: it is requeued and re-executed after restart."""

    @ray_tpu.remote(max_restarts=1, max_task_retries=1)
    class Slow:
        def work(self, t):
            time.sleep(t)
            return "done"

    a = Slow.remote()
    ray_tpu.get(a.work.remote(0))  # actor is up
    ref = a.work.remote(2.0)
    time.sleep(0.3)  # the call is in flight now
    ray_tpu.kill(a, no_restart=False)
    assert ray_tpu.get(ref, timeout=60) == "done"


def test_actor_calls_queue_during_restart(rt2):
    """Calls submitted while the actor restarts queue transparently instead
    of failing (reference: client-side queueing during RESTARTING)."""

    @ray_tpu.remote(max_restarts=2)
    class Crasher:
        def crash(self):
            os._exit(1)

        def ping(self):
            return "pong"

    a = Crasher.remote()
    assert ray_tpu.get(a.ping.remote()) == "pong"
    with pytest.raises(
        (exceptions.WorkerCrashedError, exceptions.ActorDiedError)
    ):
        ray_tpu.get(a.crash.remote())
    # The actor is now RESTARTING (or already restarted).  A call submitted
    # here must queue transparently and resolve without a caller retry loop.
    assert ray_tpu.get(a.ping.remote(), timeout=60) == "pong"


def test_large_arg_object_freed_after_task():
    rt = _fresh(num_cpus=2)
    try:
        import numpy as np

        @ray_tpu.remote
        def consume(arr):
            return int(arr.sum())

        big = np.ones(512 * 1024, dtype=np.uint8)  # > inline threshold
        assert ray_tpu.get(consume.remote(big)) == 512 * 1024
        from ray_tpu.core.context import ctx

        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            stats = ctx.client.call("store_stats")
            if stats["num_objects"] == 0:
                break
            time.sleep(0.1)
        assert stats["num_objects"] == 0, f"leaked args object: {stats}"
    finally:
        ray_tpu.shutdown()


def test_placement_group_queues_until_feasible():
    rt = _fresh(num_cpus=4)
    try:
        pg1 = ray_tpu.placement_group([{"CPU": 4}])
        assert pg1.ready(timeout=5)
        pg2 = ray_tpu.placement_group([{"CPU": 4}])  # busy: queues
        assert not pg2.ready(timeout=0.3)
        ray_tpu.remove_placement_group(pg1)
        assert pg2.ready(timeout=10)
        # Doesn't fit the current node set: warns and stays pending until
        # nodes join (reference: gcs_placement_group_manager pending queue).
        with pytest.warns(UserWarning, match="does not fit"):
            pg3 = ray_tpu.placement_group([{"CPU": 64}])
        assert not pg3.ready(timeout=0.3)
        ray_tpu.remove_placement_group(pg3)
    finally:
        ray_tpu.shutdown()


def test_health_check_reaps_wedged_worker():
    rt = _fresh(
        num_cpus=2,
        system_config={
            "health_check_period_s": 0.2,
            "health_check_failure_threshold": 3,
            "default_task_max_retries": 0,
        },
    )
    try:

        @ray_tpu.remote(max_retries=0)
        def wedge():
            os.kill(os.getpid(), signal.SIGSTOP)  # freeze the whole process
            return "unreachable"

        with pytest.raises(exceptions.WorkerCrashedError):
            ray_tpu.get(wedge.remote(), timeout=30)
    finally:
        ray_tpu.shutdown()


def test_idle_workers_reaped_and_respawned():
    rt = _fresh(
        num_cpus=2,
        system_config={"idle_worker_killing_time_s": 0.5},
    )
    try:

        @ray_tpu.remote
        def f():
            return os.getpid()

        ray_tpu.get([f.remote() for _ in range(2)])
        from ray_tpu.core.context import ctx

        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            workers = ctx.client.call("list_state", {"kind": "workers"})["items"]
            if not workers:
                break
            time.sleep(0.2)
        assert not workers, f"idle workers not reaped: {workers}"
        # Demand respawns the pool.
        assert isinstance(ray_tpu.get(f.remote(), timeout=30), int)
    finally:
        ray_tpu.shutdown()


def test_actor_died_error_pickle_roundtrip():
    err = exceptions.ActorDiedError("ab" * 16, "it crashed")
    err2 = pickle.loads(pickle.dumps(err))
    assert err2.actor_id_hex == "ab" * 16
    assert err2.cause == "it crashed"
    assert str(err2) == str(err)


def test_collective_group_reinit_fresh_incarnation():
    """Re-creating a collective group under the same name (elastic restart)
    must not consume the previous incarnation's KV keys."""
    rt = _fresh(num_cpus=4)
    try:

        @ray_tpu.remote
        class Member:
            def setup(self, world, rank, name):
                from ray_tpu import collective

                collective.init_collective_group(
                    world, rank, group_name=name, timeout=30
                )
                return rank

            def reduce(self, value):
                import numpy as np

                from ray_tpu import collective

                return collective.allreduce(
                    np.array([value], dtype=np.float64), group_name="elastic"
                )[0]

        for generation, (a_val, b_val) in enumerate([(1, 2), (10, 20)]):
            m0, m1 = Member.remote(), Member.remote()
            ray_tpu.get(
                [m0.setup.remote(2, 0, "elastic"), m1.setup.remote(2, 1, "elastic")]
            )
            r0, r1 = ray_tpu.get(
                [m0.reduce.remote(a_val), m1.reduce.remote(b_val)]
            )
            assert r0 == r1 == a_val + b_val, f"incarnation {generation}"
            ray_tpu.kill(m0)
            ray_tpu.kill(m1)
            time.sleep(0.3)
    finally:
        ray_tpu.shutdown()


def test_head_kill9_restores_actors_and_pending_pg(tmp_path):
    """Head durability v2: SIGKILL the head process mid-workload, restart
    with the same state path — the KV, named actors, a reserved placement
    group AND a still-pending (infeasible) placement group all survive
    (reference: gcs_table_storage.h tables replayed from Redis on GCS
    restart; raylets re-register and bundles re-place)."""
    import subprocess
    import sys

    state = str(tmp_path / "head.state")
    script = f"""
import os, time, pickle
import ray_tpu
ray_tpu.init(num_cpus=2, system_config={{"head_state_path": {state!r}}})
from ray_tpu.core.context import ctx

@ray_tpu.remote
class Durable:
    def __init__(self, tag):
        self.tag = tag
    def get_tag(self):
        return self.tag

d = Durable.options(name="kill9-actor", lifetime="detached").remote("v9")
assert ray_tpu.get(d.get_tag.remote(), timeout=30) == "v9"

# A named actor whose ctor arg lives in the object store: NOT restorable
# after restart — must yield an explanatory tombstone, not a bare miss.
big_arg = ray_tpu.put(list(range(50_000)))  # too big to inline
Durable.options(name="kill9-lost", lifetime="detached").remote(big_arg)

# A submitted job: its status/entrypoint rows live in the durable KV.
from ray_tpu.job_submission import JobSubmissionClient
job_id = JobSubmissionClient().submit_job(
    entrypoint="python -c 'print(42)'", job_id="kill9-job")

# Task churn so the timeline has pre-restart events.
@ray_tpu.remote
def noop(i):
    return i
assert sorted(ray_tpu.get([noop.remote(i) for i in range(20)],
                          timeout=30)) == list(range(20))

# One satisfiable PG and one that can't fit until the cluster grows.
ok_pg = ray_tpu.placement_group([{{"CPU": 1}}], strategy="PACK",
                                lifetime="detached")
assert ok_pg.ready(timeout=30)
big_pg = ray_tpu.placement_group([{{"CPU": 64}}], strategy="PACK",
                                 lifetime="detached")
ctx.client.kv_put("kill9-ok-pg", pickle.dumps(ok_pg))
ctx.client.kv_put("kill9-big-pg", pickle.dumps(big_pg))
# The kv_puts marked the snapshot dirty; the periodic persist flushes it
# (the event tail rides the same snapshot).
time.sleep(3)  # let the periodic persist flush the dirty snapshot
print("READY", flush=True)
time.sleep(30)  # killed long before this expires
"""
    env = {k: v for k, v in os.environ.items() if k != "RT_ADDRESS"}
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.Popen(
        [sys.executable, "-c", script], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    # Wait for the workload to be up, then SIGKILL the head (same process).
    deadline = time.time() + 120
    ready = False
    while time.time() < deadline:
        line = proc.stdout.readline()
        if "READY" in line:
            ready = True
            break
        if line == "" and proc.poll() is not None:
            break  # child died during startup: don't spin on EOF
    if not ready:
        proc.kill()
        err = proc.stderr.read()
        raise AssertionError(f"driver never became ready; stderr:\n{err}")
    proc.send_signal(signal.SIGKILL)
    proc.wait(timeout=30)
    time.sleep(2)  # orphan workers exit on connection loss

    rt = _fresh(num_cpus=2, system_config={"head_state_path": state})
    try:
        from ray_tpu.core.context import ctx

        ok_pg = pickle.loads(ctx.client.kv_get("kill9-ok-pg"))
        big_pg = pickle.loads(ctx.client.kv_get("kill9-big-pg"))
        # Named actor was re-created from its persisted spec.
        deadline = time.time() + 30
        tag = None
        while time.time() < deadline:
            try:
                a = rt.get_actor("kill9-actor")
                tag = rt.get(a.get_tag.remote(), timeout=30)
                break
            except Exception:
                time.sleep(0.3)
        assert tag == "v9"
        # The feasible PG re-reserved bundles on the restarted node set.
        assert ok_pg.ready(timeout=30)
        # The infeasible PG is STILL PENDING (not lost, not satisfied).
        assert not big_pg.ready(timeout=2)

        # Durable control plane v3 --------------------------------------
        # (a) The job table (KV-backed) survives: status + entrypoint.
        from ray_tpu.job_submission import JobSubmissionClient

        jc = JobSubmissionClient()
        assert jc.get_job_status("kill9-job") in (
            "PENDING", "RUNNING", "SUCCEEDED", "FAILED")
        assert (ctx.client.kv_get("job:kill9-job:entrypoint")
                == b"python -c 'print(42)'")
        # (b) The recent task timeline survives, with a restart marker
        #     sorting after the pre-kill events.
        events = ctx.client.call("list_state", {"kind": "timeline"})["items"]
        kinds = [e["kind"] for e in events]
        assert "head_restarted" in kinds
        assert any(k != "head_restarted"
                   for k in kinds[:kinds.index("head_restarted")]), (
            "no pre-restart events survived")
        # (c) The shm-arg actor was NOT restorable — and says why.
        with pytest.raises(ValueError, match="lost in head restart"):
            rt.get_actor("kill9-lost")
    finally:
        rt.shutdown()


# ---------------------------------------------------------------------------
# Preemption-aware elastic training: drain protocol, peer-replicated
# in-memory checkpoints, elastic gang resize (driven by PreemptionInjector).
# ---------------------------------------------------------------------------


def _elastic_train_loop(config):
    """SPMD-shaped loop: step counter state, periodic + drain-triggered
    checkpoints, world size reported every round.  Rank 0 drops marker
    files so the test can fire chaos at a known training phase."""
    import json
    import os
    import tempfile
    import time as _time

    from ray_tpu import train

    sess = train.get_context()
    total = config["total_steps"]
    start = 0
    ckpt = train.get_checkpoint()
    if ckpt is not None:
        with open(os.path.join(ckpt.path, "state.json")) as f:
            start = json.load(f)["step"]
    for step in range(start + 1, total + 1):
        _time.sleep(config.get("step_time_s", 0.05))
        if sess.get_world_rank() == 0:
            marker = config.get("marker")
            if (marker and step >= config.get("marker_step", 3)
                    and not os.path.exists(marker)):
                with open(marker, "w") as f:
                    f.write(str(step))
            marker2 = config.get("marker2")
            if (marker2 and sess.get_world_size() == config.get(
                    "marker2_world", 0) and not os.path.exists(marker2)):
                with open(marker2, "w") as f:
                    f.write(str(step))
        drain = train.should_checkpoint()
        metrics = {"step": step, "world_size": sess.get_world_size(),
                   "drain_save": drain}
        every = config.get("ckpt_every", 1)
        if drain or step % every == 0 or step == total:
            d = tempfile.mkdtemp(prefix="loop_ckpt_")
            with open(os.path.join(d, "state.json"), "w") as f:
                json.dump({"step": step}, f)
            train.report(
                metrics, checkpoint=train.Checkpoint.from_directory(d)
            )
        else:
            train.report(metrics)


def _wait_for_file(path, timeout_s=90.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline and not os.path.exists(path):
        time.sleep(0.05)
    return os.path.exists(path)


def test_node_drain_state_and_lease_exclusion():
    """SIGTERM on a node daemon: the head marks it DRAINING (visible in
    nodes()), stops placing new work on it while it is still alive, and
    the node leaves the cluster after its grace window."""
    from ray_tpu.cluster_utils import Cluster

    c = Cluster(head_num_cpus=2)
    try:
        n = c.add_node(num_cpus=2, drain_grace_s=3.0)
        c.preempt_node(n)
        deadline = time.monotonic() + 10
        draining = False
        while time.monotonic() < deadline and not draining:
            draining = any(
                node["node_id"] == n.hex and node.get("draining")
                for node in ray_tpu.nodes()
            )
            time.sleep(0.05)
        assert draining, "preempted node never reported DRAINING"

        @ray_tpu.remote
        def where():
            return os.environ["RT_NODE_ID"]

        refs = [
            where.options(scheduling_strategy="SPREAD").remote()
            for _ in range(6)
        ]
        assert n.hex not in set(ray_tpu.get(refs, timeout=60)), \
            "new leases landed on a draining node"
        # After the grace window the daemon exits and the node leaves.
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if not any(node["node_id"] == n.hex for node in ray_tpu.nodes()):
                break
            time.sleep(0.1)
        assert not any(node["node_id"] == n.hex for node in ray_tpu.nodes())
    finally:
        c.shutdown()


@pytest.mark.chaos
def test_preemption_drain_checkpoint_and_elastic_downsize(tmp_path):
    """Acceptance: SIGTERM-preempt a node mid-training.  The gang
    checkpoints inside the grace window (ahead of its periodic cadence),
    the run resumes from that drain checkpoint at a step strictly later
    than the last periodic disk save (there is none), at a smaller world
    size, and completes."""
    import threading

    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.train import (CheckpointConfig, DataParallelTrainer,
                               FailureConfig, RunConfig, ScalingConfig)
    from ray_tpu.util.chaos import PreemptionInjector

    seed = int(os.environ.get("RT_CHAOS_SEED", "0"))
    marker = str(tmp_path / "started")
    c = Cluster(head_num_cpus=0)  # the gang can only live on added nodes
    try:
        for _ in range(2):
            c.add_node(num_cpus=2, drain_grace_s=2.0)
        inj = PreemptionInjector(c, seed=seed, max_preemptions=1)

        def fire():
            if _wait_for_file(marker):
                inj.preempt_one()

        t = threading.Thread(target=fire, daemon=True)
        t.start()
        trainer = DataParallelTrainer(
            _elastic_train_loop,
            train_loop_config={
                "total_steps": 30, "ckpt_every": 1000, "step_time_s": 0.1,
                "marker": marker, "marker_step": 3,
            },
            scaling_config=ScalingConfig(
                num_workers=4, min_workers=2, elastic_wait_s=60.0
            ),
            run_config=RunConfig(
                storage_path=str(tmp_path / "run"),
                failure_config=FailureConfig(max_failures=3),
                checkpoint_config=CheckpointConfig(memory_ckpt_every_k=1),
            ),
        )
        result = trainer.fit()
        t.join(timeout=10)
        assert result.error is None, f"training failed: {result.error}"
        assert inj.preemptions == 1
        hist = result.metrics_history
        steps = [m["step"] for m in hist]
        assert result.metrics["step"] == 30  # full run completed
        assert any(m.get("drain_save") for m in hist), \
            "no drain-triggered checkpoint round observed"
        bounds = [i for i in range(1, len(steps)) if steps[i] <= steps[i - 1]]
        assert bounds, "run never restarted (preemption had no effect)"
        resume_step = steps[bounds[0]]
        # Periodic cadence is 1000 => the last periodic disk checkpoint is
        # step 0; resuming past step 1 proves the drain save was used.
        assert resume_step > 1, "restart rewound to step 1: drain save lost"
        worlds = [m["world_size"] for m in hist]
        assert worlds[0] == 4
        assert set(worlds[bounds[0]:]) == {2}, \
            f"gang did not downsize to min feasible: {set(worlds[bounds[0]:])}"
    finally:
        c.shutdown()


@pytest.mark.chaos
def test_inmemory_peer_checkpoint_recovery_unannounced_kill(tmp_path):
    """SIGKILL a node (no drain notice): the new gang restores from the
    peer-replicated in-memory checkpoints at a step strictly later than
    the last periodic disk checkpoint (disk cadence 10, kill ~step 13)."""
    import random as _random
    import threading

    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.train import (CheckpointConfig, DataParallelTrainer,
                               FailureConfig, RunConfig, ScalingConfig)

    seed = int(os.environ.get("RT_CHAOS_SEED", "0"))
    marker = str(tmp_path / "started")
    c = Cluster(head_num_cpus=0)
    try:
        for _ in range(2):
            c.add_node(num_cpus=2)
        rng = _random.Random(seed)

        def fire():
            if _wait_for_file(marker):
                victim = rng.choice(list(c.nodes))
                c.remove_node(victim, graceful=False)  # crash, not drain

        t = threading.Thread(target=fire, daemon=True)
        t.start()
        trainer = DataParallelTrainer(
            _elastic_train_loop,
            train_loop_config={
                "total_steps": 45, "ckpt_every": 1, "step_time_s": 0.1,
                "marker": marker, "marker_step": 12,
            },
            scaling_config=ScalingConfig(
                num_workers=4, min_workers=2, elastic_wait_s=60.0
            ),
            run_config=RunConfig(
                storage_path=str(tmp_path / "run"),
                failure_config=FailureConfig(max_failures=3),
                checkpoint_config=CheckpointConfig(
                    memory_ckpt_every_k=1, disk_ckpt_every_k=10
                ),
            ),
        )
        result = trainer.fit()
        t.join(timeout=10)
        assert result.error is None, f"training failed: {result.error}"
        hist = result.metrics_history
        steps = [m["step"] for m in hist]
        worlds = [m["world_size"] for m in hist]
        assert result.metrics["step"] == 45
        # In-memory recovery loses (at most) the round in flight, so steps
        # may not rewind at all — the restart shows as the world shrinking.
        bounds = [i for i in range(1, len(worlds))
                  if worlds[i] != worlds[i - 1]]
        assert bounds, "run never restarted (kill had no effect)"
        restored = steps[bounds[0]] - 1
        # Disk checkpoints exist only at multiples of 10; the in-memory
        # replicas must have carried the run strictly past them.
        assert restored > 10, f"restored step {restored}: memory replicas lost"
        assert restored % 10 != 0, \
            f"restored step {restored} is a disk-cadence step, not a replica"
        # The restore point is durably marked as replica-tier recovery:
        # either collected peer replicas ("memory_checkpoint") or the
        # driver-held copy of a disk-skipped replica round
        # ("held_checkpoint" — wins when the kill lands before the next
        # replication round).
        import glob
        import json

        metas = []
        for p in glob.glob(
            str(tmp_path / "run" / "*" / "checkpoints" / "*"
                / ".metadata.json")
        ):
            with open(p) as f:
                metas.append(json.load(f))
        assert any(m.get("memory_checkpoint") or m.get("held_checkpoint")
                   for m in metas), metas
    finally:
        c.shutdown()


@pytest.mark.chaos
@pytest.mark.slow
def test_elastic_downsize_then_upsize_across_two_failures(tmp_path):
    """Two failures, opposite capacity moves: a preemption shrinks the gang
    to min feasible; after the cluster backfills, the next failure's
    restart grows it back to num_workers."""
    import threading

    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.train import (CheckpointConfig, DataParallelTrainer,
                               FailureConfig, RunConfig, ScalingConfig)

    marker = str(tmp_path / "started")
    marker2 = str(tmp_path / "downsized")
    c = Cluster(head_num_cpus=0)
    try:
        a = c.add_node(num_cpus=2, drain_grace_s=2.0)
        b = c.add_node(num_cpus=2, drain_grace_s=2.0)

        def orchestrate():
            if not _wait_for_file(marker):
                return
            c.preempt_node(a)  # announced preemption: downsize follows
            if not _wait_for_file(marker2):
                return
            c.add_node(num_cpus=4)  # autoscaler-style backfill
            time.sleep(1.0)
            c.remove_node(b, graceful=False)  # second failure: upsize

        t = threading.Thread(target=orchestrate, daemon=True)
        t.start()
        trainer = DataParallelTrainer(
            _elastic_train_loop,
            train_loop_config={
                "total_steps": 80, "ckpt_every": 1, "step_time_s": 0.1,
                "marker": marker, "marker_step": 3,
                "marker2": marker2, "marker2_world": 2,
            },
            scaling_config=ScalingConfig(
                num_workers=4, min_workers=2, elastic_wait_s=60.0
            ),
            run_config=RunConfig(
                storage_path=str(tmp_path / "run"),
                failure_config=FailureConfig(max_failures=5),
                checkpoint_config=CheckpointConfig(memory_ckpt_every_k=1),
            ),
        )
        result = trainer.fit()
        t.join(timeout=30)
        assert result.error is None, f"training failed: {result.error}"
        assert result.metrics["step"] == 80
        worlds = [m["world_size"] for m in result.metrics_history]
        assert worlds[0] == 4, "first gang not at full size"
        assert 2 in worlds, "no elastic downsize happened"
        assert worlds[-1] == 4, \
            f"no upsize after backfill: final world {worlds[-1]}"
        # Progress was preserved across both failures: at every gang
        # re-formation (world-size change) the run resumed past step 1
        # (checkpoints carried), and steps never rewind more than the one
        # round that was in flight when the failure hit.
        steps = [m["step"] for m in result.metrics_history]
        bounds = [i for i in range(1, len(worlds))
                  if worlds[i] != worlds[i - 1]]
        assert len(bounds) >= 2, f"expected two restarts, saw {len(bounds)}"
        assert all(steps[i] > 1 for i in bounds), "a restart rewound to 1"
        assert all(steps[i] >= steps[i - 1] for i in range(1, len(steps))), \
            "step progress regressed across a restart"
    finally:
        c.shutdown()


def test_idempotent_rpc_retry_with_jittered_backoff():
    """Satellite: idempotent head reads retry transient connection errors;
    mutating RPCs surface the first failure untouched."""
    import threading
    from collections import deque

    from ray_tpu.core import client as client_mod

    calls = {"n": 0}

    class FlakyRpc:
        closed = False  # transient failures, connection itself stays up

        def call(self, method, body=None, timeout=60.0):
            calls["n"] += 1
            if calls["n"] < 3:
                raise client_mod.ConnectionLost("transient blip")
            return {"items": []}

    c = client_mod.Client.__new__(client_mod.Client)
    c.rpc = FlakyRpc()
    c._bg_exc = None
    c._bg_futs = deque()
    c._bg_lock = threading.Lock()
    c._put_batch = []
    c._put_batch_lock = threading.Lock()
    c._submit_batch = []
    c._submit_batch_lock = threading.Lock()

    t0 = time.monotonic()
    assert c.call("list_state", {"kind": "nodes"}) == {"items": []}
    assert calls["n"] == 3  # two transient failures absorbed
    assert time.monotonic() - t0 >= 0.05  # backoff actually slept

    calls["n"] = -10_000  # would "succeed" only after many retries
    with pytest.raises(client_mod.ConnectionLost):
        c.call("submit_task", {"task_id": b"x"})  # mutating: no retry
    assert calls["n"] == -9_999  # exactly one attempt


def test_serve_replica_retry_budget_unary_and_streaming(monkeypatch):
    """Satellite: REPLICA_RETRY_BUDGET bounds replica-death retries on both
    paths and each consumed retry is counted in metrics."""
    from ray_tpu import exceptions as exc
    from ray_tpu.serve import handle as handle_mod
    from ray_tpu.util.metrics import get_counter

    monkeypatch.setattr(
        handle_mod.ray_tpu, "get",
        lambda ref, timeout=None: (_ for _ in ()).throw(
            exc.ActorDiedError("ab" * 16, "replica died")),
    )
    counter = get_counter(
        "ray_tpu_serve_replica_retries_total",
        "Requests re-routed after a replica death", tag_keys=("path",),
    )

    def counted(path):
        return sum(
            row["value"] for row in counter._snapshot()
            if row["tags"].get("path") == path
        )

    unary0, stream0 = counted("unary"), counted("streaming")
    retries = {"n": 0}

    def retry():
        retries["n"] += 1
        return object()

    resp = handle_mod.DeploymentResponse(object(), None, retry)
    with pytest.raises(exc.ActorDiedError):
        resp.result(timeout=1)
    assert retries["n"] == handle_mod.REPLICA_RETRY_BUDGET - 1
    assert counted("unary") - unary0 == handle_mod.REPLICA_RETRY_BUDGET - 1

    # Streaming: retries only before the first item, same budget.
    class DeadGen:
        def __iter__(self):
            return self

        def __next__(self):
            raise exc.ActorDiedError("cd" * 16, "replica died")

    retries["n"] = 0
    gen = handle_mod.DeploymentResponseGenerator(
        DeadGen(), None, lambda: (retries.__setitem__("n", retries["n"] + 1),
                                  DeadGen())[1]
    )
    with pytest.raises(exc.ActorDiedError):
        list(gen)
    assert retries["n"] == handle_mod.REPLICA_RETRY_BUDGET - 1
    assert counted("streaming") - stream0 == \
        handle_mod.REPLICA_RETRY_BUDGET - 1


def test_checkpoint_pack_unpack_roundtrip(tmp_path):
    from ray_tpu.train.checkpoint import pack_directory, unpack_directory

    src = tmp_path / "src"
    (src / "sub").mkdir(parents=True)
    (src / "state.json").write_text('{"step": 7}')
    (src / "sub" / "opt.bin").write_bytes(b"\x00\x01\x02")
    blob = pack_directory(str(src))
    dest = tmp_path / "dest"
    unpack_directory(blob, str(dest))
    assert (dest / "state.json").read_text() == '{"step": 7}'
    assert (dest / "sub" / "opt.bin").read_bytes() == b"\x00\x01\x02"


def test_non_detached_pg_freed_on_driver_disconnect():
    """A placement group without lifetime="detached" dies with its creating
    connection, releasing its reservation (reference: PGs are job-scoped
    unless detached)."""
    import subprocess
    import sys

    rt = _fresh(num_cpus=2)
    try:
        from ray_tpu.core.context import ctx

        addr = os.environ.get("RT_ADDRESS")
        script = """
import ray_tpu
ray_tpu.init()  # attaches via RT_ADDRESS
pg = ray_tpu.placement_group([{"CPU": 2}])
assert pg.ready(timeout=30)
print("HELD", flush=True)
"""
        env = dict(os.environ)
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
        out = subprocess.run([sys.executable, "-c", script], env=env,
                             capture_output=True, text=True, timeout=120)
        assert "HELD" in out.stdout, out.stderr
        # The second driver exited without remove_placement_group: its
        # reservation must come back, or this PG can never be placed.
        pg = rt.placement_group([{"CPU": 2}])
        assert pg.ready(timeout=30), "disconnect did not free the PG"
        assert addr  # sanity: the subprocess really attached to our head
    finally:
        rt.shutdown()
