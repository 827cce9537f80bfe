"""Core + object-plane microbenchmark.

Role-equivalent to the reference's `ray microbenchmark`
(reference: python/ray/_private/ray_perf.py:93, timing harness
ray_microbenchmark_helpers.py:15) plus the release many_tasks /
object_store scalability probes (release/benchmarks/).

Prints one JSON line per metric:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
where vs_baseline divides by the reference's published number for the same
shape of operation (BASELINE.md; m4.16xlarge-class release logs 2.9.3).
Ends with a human-readable gap table on stderr and writes BENCH_CORE.json.

A host-only run: it holds JAX to the CPU backend and says so in every row;
none of its numbers is a device number.

Run:  python bench_core.py            (full suite, ~2-3 min)
      python bench_core.py --quick    (shorter reps for smoke)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# A host-only run: the control plane, not JAX, is under test.  Nothing here
# needs a chip, so this process and every process it starts (clusters,
# client fleets, the --rllib learners) are held to the CPU backend, and
# every row says so.  This parent imports no JAX itself.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("RT_PRESTART_WORKERS", "8")

import numpy as np

import ray_tpu

# Reference numbers from BASELINE.md (release_logs/2.9.3/microbenchmark.json
# and benchmarks/many_tasks.json).
BASELINE = {
    "single_client_get_small": 10182.0,       # gets/s
    "single_client_put_small": 5545.0,        # puts/s
    "single_client_put_gib": 20.88,           # GiB/s
    "single_client_tasks_sync": 1007.0,       # round-trips/s
    "single_client_tasks_async": 8444.0,      # submits+drain/s
    "actor_calls_sync_1_1": 2033.0,           # calls/s
    "actor_calls_async_1_1": 8886.0,          # calls/s
    "actor_calls_async_n_n": 27667.0,         # calls/s
    "actor_creation_rate": 580.1,             # actors/s (10k-actor run)
    "pg_create_remove": 796.6,                # ops/s
    "scheduling_throughput": 588.9,           # tasks/s (many_tasks)
    # 1 GiB broadcast to 50 nodes took 20.24 s => each node sustained at
    # least 1/20.24 GiB/s pulling its copy (object_store.json).
    "cross_node_pull_gib": 1.0 / 20.24,
    # Multi-client rows (microbenchmark.json multi_client_*).
    "multi_client_put_gib": 35.88,
    "multi_client_tasks_async": 25166.0,
}

RESULTS = []


def settle():
    """Wait for in-flight worker-process boots to finish so CPU contention
    from a previous section doesn't skew this one's numbers."""
    from ray_tpu.core.context import ctx

    deadline = time.time() + 15
    while time.time() < deadline:
        try:
            nodes = ctx.client.call("list_state", {"kind": "nodes"})["items"]
            if sum(n.get("pending_spawns", 0) for n in nodes) == 0:
                break
        except Exception:
            break
        time.sleep(0.25)
    time.sleep(0.3)


def timeit(name, fn, multiplier=1, min_time=1.0, warmup=1):
    """ops/s of fn, where one fn() call == `multiplier` operations."""
    settle()
    for _ in range(warmup):
        fn()
    reps = 0
    start = time.perf_counter()
    while True:
        fn()
        reps += 1
        elapsed = time.perf_counter() - start
        if elapsed >= min_time:
            break
    rate = reps * multiplier / elapsed
    record(name, rate, "ops/s")
    return rate


def record(name, value, unit, **extra):
    base = BASELINE.get(name)
    entry = {
        "metric": name,
        "value": round(value, 2),
        "unit": unit,
        "vs_baseline": round(value / base, 3) if base else None,
        "platform": "cpu (host-only run: no accelerator was used)",
        **extra,
    }
    RESULTS.append(entry)
    print(json.dumps(entry), flush=True)


def head_dispatch_count() -> float:
    """Head-side task-dispatch counter (the decentralization probe: direct
    actor calls and leased submissions must leave it flat)."""
    from ray_tpu.core.context import ctx

    try:
        rows = ctx.client.call("list_state", {"kind": "metrics"})["items"]
        for r in rows:
            if r["name"] == "ray_tpu_scheduler_tasks_dispatched_total":
                return float(r["value"])
    except Exception:
        pass
    return 0.0


def timeit_dataplane(name, fn, multiplier=1, min_time=1.0, warmup=1):
    """timeit + a ``head_rpcs_per_call`` column: head dispatch-counter
    delta over the timed window divided by operations — ~0 when the
    dataplane carries the traffic, ~1 when every call transits the head."""
    settle()
    for _ in range(warmup):
        fn()
    reps = 0
    d0 = head_dispatch_count()
    start = time.perf_counter()
    while True:
        fn()
        reps += 1
        elapsed = time.perf_counter() - start
        if elapsed >= min_time:
            break
    d1 = head_dispatch_count()
    rate = reps * multiplier / elapsed
    record(name, rate, "ops/s",
           head_rpcs_per_call=round((d1 - d0) / (reps * multiplier), 4))
    return rate


def bench_single_node(quick: bool):
    mt = 0.4 if quick else 1.2

    @ray_tpu.remote
    def nop():
        return b"ok"

    @ray_tpu.remote
    class Srv:
        def ping(self):
            return b"ok"

        async def aping(self):
            return b"ok"

    # -- object plane, small ops
    ref = ray_tpu.put(0)
    timeit("single_client_get_small", lambda: ray_tpu.get(ref), min_time=mt)
    timeit("single_client_put_small", lambda: ray_tpu.put(0), min_time=mt)

    # -- object plane, bandwidth (1 GiB total per rep in 256 MiB puts).
    # Warmup reps populate the store's warm-segment pool: steady-state put
    # bandwidth is the number that matters (first-touch tmpfs page faults
    # dominate cold puts; the reference's plasma arena has the same warmup).
    arr = np.zeros(256 * 1024 * 1024, dtype=np.uint8)

    def put_gib():
        refs = [ray_tpu.put(arr) for _ in range(4)]
        del refs

    for _ in range(2):
        put_gib()
        time.sleep(0.8)  # frees -> cooling -> pool
    # Stage attribution (core/object_store.py put-path accounting): the
    # measured loop's wall splits into named stages — the committed
    # baseline the zero-copy object-plane redesign (ROADMAP item 3) must
    # move.  Written next to BENCH_CORE.json as PUT_STAGES.json.
    from ray_tpu.core import object_store as _ostore

    _ostore.reset_put_stages()
    n, t0 = 0, time.perf_counter()
    while time.perf_counter() - t0 < (2.0 if quick else 5.0):
        put_gib()
        n += 1
    put_wall = time.perf_counter() - t0
    record("single_client_put_gib", n / put_wall, "GiB/s")
    stages = _ostore.put_stage_snapshot()
    attributed = sum(v["seconds"] for v in stages.values())
    table = {
        "row": "single_client_put_gib",
        "wall_s": round(put_wall, 4),
        "attributed_s": round(attributed, 4),
        "attributed_frac": round(attributed / put_wall, 4),
        "stages": {
            k: {"seconds": round(v["seconds"], 4), "bytes": v["bytes"],
                "count": v["count"],
                "frac_of_wall": round(v["seconds"] / put_wall, 4)}
            for k, v in sorted(stages.items())
        },
    }
    with open(os.path.join(os.path.dirname(__file__),
                           "PUT_STAGES.json"), "w") as f:
        json.dump(table, f, indent=1)
    print(f"  put-stage attribution: {table['attributed_frac']:.0%} of "
          f"{put_wall:.1f}s wall -> PUT_STAGES.json", file=sys.stderr)

    big_ref = ray_tpu.put(arr)

    def get_gib():
        for _ in range(4):
            ray_tpu.get(big_ref)

    n, t0 = 0, time.perf_counter()
    while time.perf_counter() - t0 < (1.0 if quick else 3.0):
        get_gib()
        n += 1
    record("single_client_get_gib", n / (time.perf_counter() - t0), "GiB/s")
    del big_ref, arr

    # -- tasks
    timeit("single_client_tasks_sync",
           lambda: ray_tpu.get(nop.remote()), min_time=mt)
    timeit_dataplane("single_client_tasks_async",
                     lambda: ray_tpu.get([nop.remote() for _ in range(100)]),
                     multiplier=100, min_time=mt)

    # -- actors
    a = Srv.remote()
    ray_tpu.get(a.ping.remote())
    timeit("actor_calls_sync_1_1", lambda: ray_tpu.get(a.ping.remote()),
           min_time=mt)
    timeit_dataplane("actor_calls_async_1_1",
                     lambda: ray_tpu.get([a.ping.remote()
                                          for _ in range(100)]),
                     multiplier=100, min_time=mt)

    servers = [Srv.remote() for _ in range(4)]
    ray_tpu.get([s.ping.remote() for s in servers])

    def n_n():
        refs = []
        for s in servers:
            refs.extend(s.ping.remote() for _ in range(50))
        ray_tpu.get(refs)

    timeit_dataplane("actor_calls_async_n_n", n_n, multiplier=200,
                     min_time=mt)

    # -- actor creation rate (reference: many_actors.json measures
    # creation at scale).  Creation only is timed; the kill churn and its
    # connection teardown settle OUTSIDE the window — timing back-to-back
    # create+kill cycles let a prior cycle's teardown (and, worst case, a
    # 10s spawn-slot reclaim) land inside the next cycle's measurement,
    # swinging reps 4-49/s.
    n_create = 20 if quick else 60
    rates = []
    for _ in range(2 if quick else 3):
        t0 = time.perf_counter()
        handles = [Srv.remote() for _ in range(n_create)]
        ray_tpu.get([h.ping.remote() for h in handles], timeout=120)
        rates.append(n_create / (time.perf_counter() - t0))
        for h in handles:
            ray_tpu.kill(h)
        settle()
        time.sleep(1.0)
    rates.sort()
    record("actor_creation_rate", rates[len(rates) // 2], "ops/s")

    # -- placement groups
    def pg_cycle():
        pg = ray_tpu.placement_group([{"CPU": 1}], strategy="PACK")
        pg.ready(timeout=5)
        ray_tpu.remove_placement_group(pg)

    timeit("pg_create_remove", pg_cycle, min_time=mt)

    # -- scheduling throughput: a burst of tasks through the full scheduler
    n_tasks = 200 if quick else 1000
    t0 = time.perf_counter()
    ray_tpu.get([nop.remote() for _ in range(n_tasks)])
    record("scheduling_throughput", n_tasks / (time.perf_counter() - t0),
           "tasks/s")

    # -- compiled DAG: two-actor pipeline over shm channels, zero
    # control-plane hops per call (reference: compiled_dag_node.py; no
    # published per-call number, so vs_baseline is null).
    from ray_tpu.dag import InputNode, enable_compiled_dags

    @enable_compiled_dags
    @ray_tpu.remote(max_concurrency=2)
    class Stage:
        def apply(self, x):
            return x

    s1, s2 = Stage.remote(), Stage.remote()
    with InputNode() as inp:
        dag = s2.apply.bind(s1.apply.bind(inp)).experimental_compile()
    try:
        dag.execute(1)
        timeit("compiled_dag_calls", lambda: dag.execute(1), min_time=mt)
    finally:
        dag.teardown()
        for s in (s1, s2):
            ray_tpu.kill(s)


def bench_cross_node(quick: bool):
    """Cross-node object pull bandwidth through the node-daemon object plane."""
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(head_num_cpus=2)
    try:
        cluster.add_node(num_cpus=2)

        @ray_tpu.remote(scheduling_strategy="SPREAD", num_cpus=1)
        def make_big(mib):
            import numpy as np
            return np.zeros(mib * 1024 * 1024, dtype=np.uint8)

        mib = 64 if quick else 256
        # Produce on both nodes (SPREAD), wait for seal, then time ONLY the
        # transfer of the copies that live on the other node — production
        # cost (cold remote-store writes) must not pollute the number.
        from ray_tpu.core.context import ctx

        refs = [make_big.remote(mib) for _ in range(2)]
        ray_tpu.wait(refs, num_returns=len(refs), timeout=300)
        descs = ctx.client.get_raw([r.object_id for r in refs])
        n_remote = sum(
            1 for d in descs
            if d.get("node_id") and d["node_id"] != ctx.client.node_id.binary()
        )
        t0 = time.perf_counter()
        vals = ray_tpu.get(refs)
        dt = time.perf_counter() - t0
        if n_remote == 0:
            print("cross_node_pull_gib: no remote copy produced; skipping",
                  file=sys.stderr)
        else:
            record("cross_node_pull_gib", n_remote * mib / 1024.0 / dt,
                   "GiB/s")
        del vals, refs
    finally:
        cluster.shutdown()


_MULTI_CLIENT_SCRIPT = r'''
import json, sys, time
import numpy as np
import ray_tpu

rank, nclients, put_reps, task_reps = map(int, sys.argv[1:5])
ray_tpu.init()  # attaches to the parent's cluster via RT_ADDRESS
from ray_tpu.core.context import ctx

def barrier(tag, timeout=120.0):
    ctx.client.kv_put(f"mc:{tag}:{rank}", b"1")
    deadline = time.monotonic() + timeout
    while len(ctx.client.kv_keys(f"mc:{tag}:")) < nclients:
        if time.monotonic() > deadline:
            raise TimeoutError(f"barrier {tag}: a peer never arrived")
        time.sleep(0.005)

blob = np.random.default_rng(rank).integers(
    0, 256, 1 << 20, dtype=np.uint8).tobytes()
barrier("puts")
t0 = time.perf_counter()
refs = [ray_tpu.put(blob) for _ in range(put_reps)]
put_dt = time.perf_counter() - t0
put_gib = put_reps / 1024.0 / put_dt
del refs

@ray_tpu.remote
def nop():
    return b"ok"

ray_tpu.get(nop.remote(), timeout=120)  # warm a worker
# Warm the task lease: keep submitting until this client holds a live
# direct slot (or times out into the head path) so the barrier-aligned
# window measures steady-state submission, not lease acquisition.
dp = ctx.client._dataplane
deadline = time.monotonic() + 6
while dp is not None and time.monotonic() < deadline:
    ray_tpu.get([nop.remote() for _ in range(4)], timeout=120)
    with dp._lock:
        if any(not s.dead and not s.revoked
               for p in dp._pools.values() for s in p.slots):
            break
    time.sleep(0.25)
barrier("tasks")
t0 = time.perf_counter()
task_refs = [nop.remote() for _ in range(task_reps)]
ray_tpu.get(task_refs, timeout=300)
task_dt = time.perf_counter() - t0
print(json.dumps({"put_gib": put_gib, "tasks_async": task_reps / task_dt}),
      flush=True)
ray_tpu.shutdown()
'''


def bench_multi_client(quick: bool):
    """N concurrent driver processes sharing one head — the reference's
    multi-client sections (reference: ray_perf.py multi_client_put_gigabytes
    / n-client task submission; release_logs 2.9.3 microbenchmark.json).
    Aggregate throughput = sum of per-client rates over the overlapped
    (KV-barrier-aligned) window; this is the first falsifiable datapoint
    for PERF_CEILINGS.md's single-core scaling hypothesis."""
    import subprocess

    nclients = 4
    put_reps = 16 if quick else 64       # 1 MiB puts per client
    task_reps = 128 if quick else 512
    env = dict(os.environ)  # RT_ADDRESS points at the live head
    d0 = head_dispatch_count()
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _MULTI_CLIENT_SCRIPT, str(i),
             str(nclients), str(put_reps), str(task_reps)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        )
        for i in range(nclients)
    ]
    rows = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            # A dead peer leaves survivors spinning in the KV barrier
            # (bounded child-side too): kill, skip the section, and let
            # the rest of the bench (and BENCH_CORE.json) proceed.
            p.kill()
            out, err = p.communicate()
            print("# multi-client worker timed out (killed)",
                  file=sys.stderr)
            continue
        if p.returncode != 0:
            print(f"# multi-client worker failed:\n{err[-2000:]}",
                  file=sys.stderr)
            continue
        rows.append(json.loads(out.strip().splitlines()[-1]))
    if len(rows) == nclients:
        record("multi_client_put_gib",
               sum(r["put_gib"] for r in rows), "GiB/s")
        # Dispatch-counter delta spans the whole section (incl. each
        # client's warmup call), so ~0 still reads "the task traffic never
        # transited the head".
        d1 = head_dispatch_count()
        record("multi_client_tasks_async",
               sum(r["tasks_async"] for r in rows), "tasks/s",
               head_rpcs_per_call=round(
                   (d1 - d0) / (nclients * task_reps), 4))
    else:
        print(f"# multi-client section incomplete: {len(rows)}/{nclients}",
              file=sys.stderr)


def bench_rllib(quick: bool):
    """PPO sample+update throughput (BASELINE north star: RLlib PPO
    env-steps/s; reference harness rllib/benchmarks/ppo)."""
    from ray_tpu.rllib import PPOConfig

    import jax

    print(f"# rllib learner backend: {jax.default_backend()}",
          file=sys.stderr)
    algo = (PPOConfig()
            .env_runners(num_env_runners=2, num_envs_per_env_runner=8,
                         rollout_fragment_length=128)
            .build())
    try:
        algo.train()  # compile + warmup
        rates = []
        for _ in range(3 if quick else 10):
            r = algo.train()
            rates.append(r["env_steps_per_sec"])
            print(f"# ppo iter: sps={r['env_steps_per_sec']:.0f} "
                  f"sample={r['time_sample_s']:.2f}s "
                  f"learn={r['time_learn_s']:.2f}s", file=sys.stderr)
        record("ppo_env_steps_per_sec",
               float(np.median(rates)), "steps/s")
    finally:
        algo.stop()

    from ray_tpu.rllib import ImpalaConfig

    algo = (ImpalaConfig()
            .env_runners(num_env_runners=2, num_envs_per_env_runner=8,
                         rollout_fragment_length=64,
                         num_inflight_per_runner=2)
            .build())
    try:
        algo.train()  # compile + warmup
        sps, ups = [], []
        for _ in range(3 if quick else 10):
            r = algo.train()
            sps.append(r["env_steps_per_sec"])
            ups.append(r["learner_updates_per_sec"])
            print(f"# impala iter: sps={r['env_steps_per_sec']:.0f} "
                  f"ups={r['learner_updates_per_sec']:.1f} "
                  f"stale={r['mean_weight_staleness']:.2f}",
                  file=sys.stderr)
        record("impala_env_steps_per_sec",
               float(np.median(sps)), "steps/s")
        record("impala_learner_updates_per_sec",
               float(np.median(ups)), "updates/s")
    finally:
        algo.stop()

    from ray_tpu.rllib import MultiAgentPPOConfig

    algo = (MultiAgentPPOConfig()
            .environment("MultiAgentCartPole", num_agents=4)
            .multi_agent(
                policies=["shared"],
                policy_mapping_fn=lambda a: "shared",
            )
            .env_runners(num_env_runners=2, rollout_fragment_length=256)
            .build())
    try:
        algo.train()  # compile + warmup
        rates = []
        for _ in range(3 if quick else 10):
            r = algo.train()
            rates.append(r["env_steps_per_sec"])
            print(f"# multi-agent ppo iter: "
                  f"sps={r['env_steps_per_sec']:.0f}", file=sys.stderr)
        record("multi_agent_env_steps_per_sec",
               float(np.median(rates)), "steps/s")
    finally:
        algo.stop()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--skip-multinode", action="store_true")
    ap.add_argument("--rllib", action="store_true")
    ap.add_argument("--repeat", type=int, default=1,
                    help="run the single-node section N times and report "
                    "per-metric medians (control-plane numbers on small "
                    "hosts swing +-30%% run to run)")
    args = ap.parse_args()

    # No prestart spares here: A/B on this host shows the burst benchmark
    # is fork-ceiling-bound either way (PERF_CEILINGS.md), and hardwiring
    # the feature would confound the numbers it claims to improve.
    ray_tpu.init(num_cpus=8)
    bench_single_node(args.quick)
    ray_tpu.shutdown()
    for _ in range(args.repeat - 1):
        time.sleep(5)  # let the previous fleet fully exit
        ray_tpu.init(num_cpus=8)
        bench_single_node(args.quick)
        ray_tpu.shutdown()
    if args.repeat > 1:
        # Collapse to per-metric medians, preserving first-seen order.
        import statistics

        by_name: dict = {}
        order = []
        for r in RESULTS:
            if r["metric"] not in by_name:
                order.append(r["metric"])
            by_name.setdefault(r["metric"], []).append(r)
        RESULTS[:] = []
        for name in order:
            rows = by_name[name]
            med = statistics.median(r["value"] for r in rows)
            base = rows[0]["vs_baseline"]
            rows[0]["value"] = round(med, 2)
            if base is not None:
                ref = BASELINE[name] if name in BASELINE else None
                if ref:
                    rows[0]["vs_baseline"] = round(med / ref, 3)
            rows[0]["runs"] = len(rows)
            RESULTS.append(rows[0])

    # Multi-client section: its own cluster so the client fleet doesn't
    # inherit a drained worker pool.
    time.sleep(5)
    ray_tpu.init(num_cpus=8)
    try:
        bench_multi_client(args.quick)
    finally:
        ray_tpu.shutdown()

    if args.rllib:
        # Fresh cluster after the old one's worker fleet fully exits:
        # leftover process churn skews env-runner scheduling.
        time.sleep(5)
        ray_tpu.init(num_cpus=8)
        bench_rllib(args.quick)
        ray_tpu.shutdown()

    if not args.skip_multinode:
        bench_cross_node(args.quick)

    with open(os.path.join(os.path.dirname(__file__), "BENCH_CORE.json"),
              "w") as f:
        json.dump(RESULTS, f, indent=1)

    print("\n== gap vs reference (BASELINE.md) ==", file=sys.stderr)
    for r in RESULTS:
        if r["vs_baseline"] is not None:
            print(f"  {r['metric']:<28} {r['value']:>12.1f} {r['unit']:<7} "
                  f"{r['vs_baseline']:>8.2f}x of reference", file=sys.stderr)


if __name__ == "__main__":
    main()
