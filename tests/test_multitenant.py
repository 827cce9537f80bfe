"""Multi-tenant serving plane tests: refcounted KV pages, radix prefix
cache with copy-on-write, batched LoRA multiplexing in the one compiled
decode program, weighted-fair admission with per-tenant shed, rendezvous
replica affinity, and the SLO-driven scale decision.

Reference analog: vLLM automatic-prefix-caching + multi-LoRA tests and
serve's model-multiplex routing tests — correctness here is token-exact
parity against the uncached / merged-weights reference, not throughput.
"""

import json
import time
import urllib.request

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve

# Same geometry as test_serve_engine so every engine in the process hits
# the same compiled decode program (the compile-count assertions below
# depend on it).
GEOMETRY = dict(batch_slots=4, page_size=8, max_prompt_len=16,
                max_new_tokens_cap=32)


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


@pytest.fixture(scope="module")
def engine():
    eng = _tiny_engine()
    eng.warmup()
    yield eng
    eng.shutdown()


# ---------------------------------------------------------- page refcounts


def test_page_allocator_refcounts():
    """share/free discipline: a shared page survives its first free,
    double-free and share-after-free fail loudly."""
    from ray_tpu.models.paged import PageAllocator

    al = PageAllocator(8)
    pages = al.alloc(2)
    assert al.free_count == 6
    al.share([pages[0]])
    assert al.refs(pages[0]) == 2
    assert al.shared_count == 1
    al.free([pages[0]])          # one owner left: page stays allocated
    assert al.free_count == 6
    assert al.shared_count == 0
    al.free([pages[0]])          # last owner: back on the free list
    assert al.free_count == 7
    with pytest.raises(AssertionError, match="double free"):
        al.free([pages[0]])
    with pytest.raises(AssertionError, match="unallocated"):
        al.share([pages[0]])
    al.free([pages[1]])
    assert al.free_count == al.total == 8


# ------------------------------------------------------------ prefix cache


def test_prefix_cache_hit_and_cow_parity(engine):
    """Cached-prefix decode (full-page hit AND mid-page COW divergence)
    must be token-exact against greedy decoding by the full forward pass
    — reusing frozen KV pages is an optimization, never an
    approximation."""
    from greedy_ref import greedy_tokens
    from ray_tpu.models.paged import trace_count

    def ref(prompt, n):
        return greedy_tokens(engine.model_config, engine.params, prompt, n)

    engine.clear_prefix_cache()
    cache_before = engine.stats()["prefix_cache"]
    decode_before = trace_count("decode")

    prompt = list(range(2, 14))           # 12 tokens -> one full 8-page
    cold = list(engine.submit(prompt, max_new_tokens=6))
    assert cold == ref(prompt, 6)

    # Full-page hit: same prompt skips the cached page's prefill.
    warm = list(engine.submit(prompt, max_new_tokens=6))
    assert warm == cold

    # COW divergence INSIDE the cached page: first 5 tokens shared, then
    # a different tail.  The engine must copy the cached page and keep
    # only the 5 overlapping positions.
    fork = prompt[:5] + [91, 92, 93, 94, 95, 96, 97]
    forked = list(engine.submit(fork, max_new_tokens=6))
    assert forked == ref(fork, 6)

    st = engine.stats()
    cache = st["prefix_cache"]
    assert cache["hits"] - cache_before["hits"] >= 2
    assert st["prefill_prefix_traces"] >= 1
    # The cached-prefix paths never retraced the decode program.
    assert trace_count("decode") == decode_before
    engine.clear_prefix_cache()


def test_prefix_cache_metrics_emitted(engine):
    """The new catalog rows are real series: a cache hit moves the hits
    counter and the shared-pages gauge was set."""
    from ray_tpu.util.metrics import BUILTIN_METRICS, get_counter, get_gauge

    for name in ("ray_tpu_serve_prefix_cache_hits_total",
                 "ray_tpu_serve_prefix_cache_pages_shared",
                 "ray_tpu_serve_adapter_evictions_total",
                 "ray_tpu_serve_tenant_shed_total"):
        assert name in BUILTIN_METRICS, name

    hits = get_counter("ray_tpu_serve_prefix_cache_hits_total")
    before = sum(hits._values.values())
    engine.clear_prefix_cache()
    prompt = list(range(30, 42))
    list(engine.submit(prompt, max_new_tokens=2))
    list(engine.submit(prompt, max_new_tokens=2))   # hit
    assert sum(hits._values.values()) > before
    gauge = get_gauge("ray_tpu_serve_prefix_cache_pages_shared")
    assert gauge._values  # set at least once by the prefill path
    engine.clear_prefix_cache()


def test_free_list_balances_with_cache_hits_and_cancels(engine):
    """Churn with shared-prefix traffic AND mid-stream cancels: every
    sequence ref comes back, and after draining the tree the free list
    is exactly full with zero shared pages."""
    engine.clear_prefix_cache()
    alloc = engine.allocator
    prompt = list(range(50, 62))          # 12 tokens, shares one page
    for round_ in range(4):
        streams = [engine.submit(prompt, max_new_tokens=4)
                   for _ in range(3)]
        victim = engine.submit(prompt, max_new_tokens=32)
        next(victim)
        victim.cancel()
        for s in streams:
            assert len(list(s)) == 4
    deadline = time.time() + 10
    while time.time() < deadline:
        engine.clear_prefix_cache()
        if alloc.free_count == alloc.total:
            break
        time.sleep(0.05)
    assert alloc.free_count == alloc.total
    assert alloc.shared_count == 0


# ----------------------------------------------------------- batched LoRA


def test_adapter_mix_parity_and_one_decode_program(engine):
    """Requests on different adapters decode IN THE SAME BATCH and each
    matches the reference with that adapter's weights merged into the
    base — and the whole mix reuses the one compiled decode program."""
    from greedy_ref import greedy_tokens
    from ray_tpu.models.llama import lora_merge
    from ray_tpu.models.paged import trace_count
    from ray_tpu.serve.engine import random_lora

    cfg = engine.model_config
    rank = engine.config.lora_rank
    engine.register_adapter("a1", lambda: random_lora(cfg, 1, rank=rank))
    engine.register_adapter("a2", lambda: random_lora(cfg, 2, rank=rank))

    decode_before = trace_count("decode")
    prompt = [5, 7, 11]
    streams = {
        None: engine.submit(prompt, max_new_tokens=6),
        "a1": engine.submit(prompt, max_new_tokens=6, adapter="a1"),
        "a2": engine.submit(prompt, max_new_tokens=6, adapter="a2"),
    }
    got = {k: list(s) for k, s in streams.items()}

    for name, seed in (("a1", 1), ("a2", 2)):
        merged = lora_merge(cfg, engine.params,
                            random_lora(cfg, seed, rank=rank))
        assert got[name] == greedy_tokens(cfg, merged, prompt, 6), name
    assert got[None] == greedy_tokens(cfg, engine.params, prompt, 6)
    # Adapter identity is per-slot DATA: no retrace for any mix.
    assert trace_count("decode") == decode_before
    st = engine.stats()["adapters"]
    assert st["loads"] >= 2


def test_adapter_pool_lru_eviction_and_pinning():
    """Host-side pool discipline: pinned residents are never evicted,
    LRU unpinned residents are, release/re-register misuse fails loudly."""
    import jax.numpy as jnp

    from ray_tpu.models import LlamaConfig
    from ray_tpu.serve.adapter_pool import AdapterNotFoundError, AdapterPool
    from ray_tpu.serve.engine import random_lora

    cfg = LlamaConfig.tiny(remat=False, dtype=jnp.float32)
    pool = AdapterPool(cfg, max_adapters=2, rank=4)
    for name, seed in (("a", 1), ("b", 2), ("c", 3)):
        pool.register(name, lambda s=seed: random_lora(cfg, s, rank=4))

    with pytest.raises(AdapterNotFoundError):
        pool.acquire("never-registered")
    assert pool.acquire(None) == pool.zero_slot

    slot_a = pool.acquire("a")
    pool.acquire("b")
    # Both slots pinned: a third adapter cannot enter.
    assert not pool.can_acquire("c")
    with pytest.raises(RuntimeError, match="pinned"):
        pool.acquire("c")
    # Unpinning "a" makes it the LRU eviction victim.
    pool.release("a")
    assert pool.can_acquire("c")
    assert pool.acquire("c") == slot_a
    assert pool.resident("c") and pool.resident("b")
    assert not pool.resident("a")
    assert pool.evictions == 1
    # Misuse fails loudly.
    with pytest.raises(AssertionError, match="unpinned"):
        pool.release("a")
    with pytest.raises(RuntimeError, match="pinned"):
        pool.register("b", lambda: random_lora(cfg, 9, rank=4))
    pool.release("b")
    assert pool.register("b", lambda: random_lora(cfg, 9, rank=4))
    assert not pool.resident("b")


# -------------------------------------------------- weighted-fair admission


def test_weighted_fair_shed_targets_heaviest_tenant():
    """Overload sheds the heaviest tenant's NEWEST queued request: a
    light (high-weight) tenant's burst survives a heavy tenant's backlog,
    and per-tenant counters plus the tenant-tagged metric record it."""
    from ray_tpu.serve.engine import EngineOverloadedError
    from ray_tpu.util.metrics import get_counter

    eng = _tiny_engine(max_queue=2)
    try:
        shed_metric = get_counter("ray_tpu_serve_tenant_shed_total",
                                  tag_keys=("tenant",))
        metric_before = sum(shed_metric._values.values())
        busy = []
        for _ in range(eng.config.batch_slots):
            s = eng.submit([1] * 8, max_new_tokens=32)
            next(s)
            busy.append(s)
        free_1 = eng.submit([2], max_new_tokens=1, tenant="free",
                            weight=1.0)
        free_2 = eng.submit([2], max_new_tokens=1, tenant="free",
                            weight=1.0)
        # Queue is now full; the GOLD submit overflows it — the shed
        # victim must be free's newest request, not gold's.
        gold = eng.submit([3], max_new_tokens=1, tenant="gold",
                          weight=10.0)
        with pytest.raises(EngineOverloadedError):
            list(free_2)
        assert len(list(gold)) == 1
        assert len(list(free_1)) == 1
        for s in busy:
            list(s)
        tenants = eng.stats()["tenants"]
        assert tenants["free"]["shed"] == 1
        assert tenants["free"]["submitted"] == 2
        assert tenants["free"]["completed"] == 1
        assert tenants["gold"]["shed"] == 0
        assert tenants["gold"]["completed"] == 1
        assert sum(shed_metric._values.values()) > metric_before
        assert any("free" in str(k) for k in shed_metric._values)
    finally:
        eng.shutdown()


def test_submitter_is_its_own_victim_when_heaviest():
    """Single-tenant overload keeps the old synchronous contract: the
    overflowing submit raises instead of landing the error elsewhere."""
    from ray_tpu.serve.engine import EngineOverloadedError

    eng = _tiny_engine(max_queue=1)
    try:
        busy = []
        for _ in range(eng.config.batch_slots):
            s = eng.submit([1] * 8, max_new_tokens=32)
            next(s)
            busy.append(s)
        queued = eng.submit([2], max_new_tokens=1)
        with pytest.raises(EngineOverloadedError):
            eng.submit([2], max_new_tokens=1)
        assert len(list(queued)) == 1
        for s in busy:
            list(s)
    finally:
        eng.shutdown()


def test_slo_signals_shape(engine):
    """The controller's autoscaling input: queue/TTFT snapshot with real
    observations after traffic."""
    list(engine.submit([4, 5, 6], max_new_tokens=3))
    sig = engine.slo_signals()
    assert sig["batch_slots"] == engine.config.batch_slots
    assert sig["ttft_count"] > 0
    assert sig["ttft_p90_s"] > 0
    assert sig["ttft_p90_s"] >= sig["ttft_p50_s"]
    assert isinstance(sig["queue_depth"], int)


# ----------------------------------------------------- rendezvous affinity


def test_rendezvous_minimal_remap():
    """Adding a replica moves ONLY the models that land on the new one;
    removing a replica leaves every survivor's assignment alone.  (The
    crc32-modulus router reshuffled nearly everything on any change.)"""
    from ray_tpu.serve.multiplex import pick_replica_for_model

    ids4 = [101, 102, 103, 104]
    models = [f"model-{i}" for i in range(200)]
    before = {m: ids4[pick_replica_for_model(m, ids4)] for m in models}
    assert len(set(before.values())) == 4  # all replicas used

    ids5 = ids4 + [105]
    after = {m: ids5[pick_replica_for_model(m, ids5)] for m in models}
    moved = [m for m in models if before[m] != after[m]]
    assert moved, "new replica got no models"
    assert all(after[m] == 105 for m in moved)      # moves go ONLY to new
    assert len(moved) < len(models) * 0.45          # ~1/5 expected

    ids3 = [101, 102, 104]
    for m in models:
        if before[m] != 103:
            assert ids3[pick_replica_for_model(m, ids3)] == before[m]


def test_handle_affinity_survives_scale_event():
    """Regression for the modulus-affinity bug: a scale event mid-traffic
    (controller appends a replica; existing stable ids keep their
    positions) must NOT re-route models between surviving replicas —
    every warm replica-side cache stays warm."""
    from ray_tpu.serve.handle import DeploymentHandle

    def assign(replicas, replica_ids, models):
        out = {}
        for m in models:
            h = DeploymentHandle("d", multiplexed_model_id=m)
            h._replicas = replicas
            h._replica_ids = replica_ids
            out[m] = replica_ids[h._pick()]
        return out

    models = [f"m{i}" for i in range(64)]
    before = assign(["r1", "r2"], [7, 11], models)
    # Mid-traffic scale-up: a third replica joins with a fresh stable id.
    after = assign(["r1", "r2", "r3"], [7, 11, 23], models)
    moved = [m for m in models if before[m] != after[m]]
    assert all(after[m] == 23 for m in moved), (
        "a model moved between SURVIVING replicas on scale-up")
    assert len(moved) < len(models) // 2
    # Without stable ids in the table the handle falls back to list
    # positions (still a valid index, just without the stability win).
    h = DeploymentHandle("d", multiplexed_model_id="m0")
    h._replicas = ["r1", "r2"]
    h._replica_ids = []
    assert h._pick() in (0, 1)


def test_scale_decision_slo_paths():
    """Pure autoscale math: either-signal breach scales up, scale-down
    needs both signals idle, bounds are respected."""
    from ray_tpu.serve.controller import _scale_decision

    # Queue breach alone.
    assert _scale_decision(1, 1, 4, per_queue=5, target_q=2) == 2
    # TTFT breach with an EMPTY queue still scales up (the engine's
    # batch is the bottleneck, not its queue).
    assert _scale_decision(2, 1, 4, 0.0, 2,
                           ttft_p90=1.0, target_ttft=0.25) == 3
    # Both comfortably idle: scale down.
    assert _scale_decision(3, 1, 4, 0.5, 2,
                           ttft_p90=0.05, target_ttft=0.25) == 2
    # Queue idle but TTFT not comfortably idle: hold.
    assert _scale_decision(2, 1, 4, 0.5, 2,
                           ttft_p90=0.2, target_ttft=0.25) == 2
    # Bounds.
    assert _scale_decision(4, 1, 4, 99, 2) == 4
    assert _scale_decision(1, 1, 4, 0, 2) == 1
    # No TTFT signal: plain queue-pressure behavior.
    assert _scale_decision(2, 1, 4, 0.1, 2) == 1


# --------------------------------------------------------- serve plumbing


@pytest.fixture
def rt():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    yield ray_tpu
    serve.shutdown()
    ray_tpu.shutdown()


def test_http_tenant_header_and_replica_ids(rt):
    """X-RT-Tenant rides into the deployment as the ``tenant`` kwarg (an
    explicit body tenant wins), and the controller's routing table
    carries position-aligned stable replica ids."""

    @serve.deployment(num_replicas=2)
    def echo(**kwargs):
        return kwargs

    serve.run(echo.bind(), name="echo")
    from ray_tpu.serve.controller import get_or_create_controller

    table = ray_tpu.get(
        get_or_create_controller().routing_table.remote(), timeout=30)
    ids = table["replica_ids"]["echo"]
    assert len(ids) == len(table["deployments"]["echo"]) == 2
    assert len(set(ids)) == 2

    port = serve.start_http()
    try:
        def post(body, headers):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/echo",
                data=json.dumps(body).encode(), headers=headers)
            with urllib.request.urlopen(req, timeout=60) as resp:
                return json.loads(resp.read())

        assert post({"x": 1}, {"X-RT-Tenant": "acme"}) == \
            {"x": 1, "tenant": "acme"}
        assert post({"x": 1, "tenant": "inline"},
                    {"X-RT-Tenant": "acme"}) == \
            {"x": 1, "tenant": "inline"}
        assert post({"x": 2}, {}) == {"x": 2}
    finally:
        serve.stop_http()
