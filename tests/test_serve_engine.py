"""Continuous-batching LLM engine tests: paged cache parity, per-step
admission, page lifecycle, admission control, compile stability, and the
serve streaming/cancellation integration.

Reference analog: vLLM-style engine tests + serve/tests/test_streaming —
the decode loop admits BETWEEN steps, pages free-list balances after any
workload, and one compiled program serves every admission mix.
"""

import json
import time
import urllib.request

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve

# Shared engine geometry: every engine below compiles the SAME decode
# shape (slots x page-table width), so the per-process jit cache is hit
# across tests and the compile-count assertions stay meaningful.
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
    eng.warmup()  # compile decode + every prefill bucket up front
    yield eng
    eng.shutdown()


def test_paged_decode_matches_the_full_forward_pass(engine):
    """The paged engine's greedy decode must match greedy decoding by the
    plain full forward pass token for token (same params, same layer,
    pages instead of recomputing the sequence)."""
    from greedy_ref import greedy_tokens

    prompt = [5, 7, 11]
    toks = list(engine.submit(prompt, max_new_tokens=6))
    assert toks == greedy_tokens(engine.model_config, engine.params,
                                 prompt, 6)
    # Greedy decode is deterministic across engine runs.
    assert list(engine.submit(prompt, max_new_tokens=6)) == toks


@pytest.mark.parametrize("n_heads,n_kv_heads", [(4, 4), (4, 2), (4, 1)],
                         ids=["mha", "gqa", "mqa"])
def test_paged_parity_for_every_kv_grouping(monkeypatch, n_heads,
                                            n_kv_heads):
    """One pool layout and one grouped contraction serve MHA (a group of
    one), GQA and MQA: in float32 the engine's tokens equal the full
    forward pass's (``greedy_ref``) through a cold prefill, a full-page prefix hit, a
    prefix hit that diverges mid-page (copy-on-write), and decode across a
    page edge; and the first token of each path has the plain full
    forward's best logit to within 1e-5."""
    import jax
    import jax.numpy as jnp

    import ray_tpu.models.paged as paged_mod
    from greedy_ref import greedy_tokens, logits_after
    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    cfg = LlamaConfig(vocab_size=512, d_model=128, n_layers=2,
                      n_heads=n_heads, n_kv_heads=n_kv_heads, d_ff=256,
                      max_seq=256, remat=False, dtype=jnp.float32)
    params = jax.jit(llama_init, static_argnums=0)(
        cfg, jax.random.PRNGKey(1))
    copies, real_copy = [], paged_mod.copy_page

    def counted_copy(pools, src, dst):
        copies.append(int(dst))
        return real_copy(pools, src, dst)

    monkeypatch.setattr(paged_mod, "copy_page", counted_copy)

    def ref(prompt, n):
        return greedy_tokens(cfg, params, prompt, n)

    def best_logit_gap(context, token):
        logits = np.asarray(logits_after(cfg, params, context))
        return float(logits.max() - logits[token])

    eng = InferenceEngine(cfg, params,
                          EngineConfig(max_queue=16, **GEOMETRY))
    try:
        # 12 tokens on 8-token pages: one page frozen into the prefix
        # cache, and 6 new tokens write positions 12..17, over the edge
        # at 16 into a third page.
        prompt = list(range(2, 14))
        cold = list(eng.submit(prompt, max_new_tokens=6))
        assert cold == ref(prompt, 6)
        assert best_logit_gap(prompt, cold[0]) <= 1e-5         # prefill
        assert best_logit_gap(prompt + cold[:1], cold[1]) <= 1e-5  # decode
        hits = eng.stats()["prefix_cache"]["hits"]
        assert list(eng.submit(prompt, max_new_tokens=6)) == cold
        assert eng.stats()["prefix_cache"]["hits"] == hits + 1 and not copies
        # Five tokens shared with the cached page, then another tail: the
        # page is copied and only its first five positions are kept.
        fork = prompt[:5] + [91, 92, 93, 94, 95, 96, 97]
        forked = list(eng.submit(fork, max_new_tokens=6))
        assert forked == ref(fork, 6) and forked != cold
        assert best_logit_gap(fork, forked[0]) <= 1e-5   # suffix prefill
        assert eng.stats()["prefix_cache"]["hits"] == hits + 2
        assert len(copies) == 1
        eng.clear_prefix_cache()
        assert eng.allocator.free_count == eng.allocator.total
    finally:
        eng.shutdown()


def test_admission_mid_stream_stalls_at_most_one_step(engine):
    """A sequence admitted mid-stream joins the running batch between
    decode steps: the running sequence keeps emitting one token per step
    (its step indices stay consecutive), and the newcomer finishes long
    before the long request — the continuous-batching property."""
    a = engine.submit([1, 2, 3, 4], max_new_tokens=24)
    next(a)  # A admitted and decoding
    b = engine.submit([9, 9], max_new_tokens=4)
    b_toks = list(b)
    list(a)
    assert len(b_toks) == 4
    # A emitted one token per decode step throughout B's admission,
    # prefill, and decode — deltas of exactly 1 mean B's prefill stalled
    # A by at most the one inter-step gap it rode in on.
    deltas = [y - x for x, y in zip(a.steps[1:], a.steps[2:])]
    assert deltas and all(d == 1 for d in deltas), a.steps
    # B ran INSIDE A's window (admitted after A started, done before A).
    assert a.steps[0] <= b.steps[0] <= b.steps[-1] < a.steps[-1]


# --- One decode step in flight: the loop dispatches step n+1 before it reads
# step n while membership stands still.  Whatever the loop overlaps, every
# request's tokens are the full forward pass's.


def _ref(engine, prompt, n):
    from greedy_ref import greedy_tokens

    return greedy_tokens(engine.model_config, engine.params, prompt, n)


def _settle(engine, timeout_s=10.0):
    """Wait until the loop is idle: nothing in a slot, nothing in flight."""
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if engine._inflight is None and not any(engine.slots):
            return
        time.sleep(0.02)
    raise AssertionError("the engine loop did not come to rest")


def test_run_ahead_wastes_no_step_at_a_token_budgets_end(engine):
    """A request that ends by ``max_tokens``: the host knows the count, so
    no step is dispatched behind the last one (n tokens: one from the
    prefill, n - 1 decode steps) and the tokens are the reference's."""
    _settle(engine)
    before, prompt = engine.step_count, [5, 7, 11, 13]
    assert list(engine.submit(prompt, max_new_tokens=10)) \
        == _ref(engine, prompt, 10)
    _settle(engine)
    assert engine.step_count - before == 9


def test_stop_token_with_a_step_in_flight_drops_that_steps_token(engine):
    """A stop the host cannot foresee: the step behind the stop token was
    already dispatched, its token goes to nobody, and the request beside it
    reads on undisturbed."""
    _settle(engine)
    prompt, other = [1, 3, 5, 7], [8, 6, 2, 12, 10]
    want = _ref(engine, prompt, 12)
    stop_at = 4
    assert want[stop_at] not in want[:stop_at]
    before = engine.step_count
    got = list(engine.submit(prompt, max_new_tokens=12,
                             stop_token=want[stop_at]))
    assert got == want[:stop_at + 1]
    _settle(engine)
    # One token from the prefill, stop_at from decode steps, one step wasted.
    assert engine.step_count - before == stop_at + 1
    a = engine.submit(other, max_new_tokens=14)
    b = engine.submit(prompt, max_new_tokens=12, stop_token=want[stop_at])
    assert list(b) == want[:stop_at + 1]
    assert list(a) == _ref(engine, other, 14)
    engine.clear_prefix_cache()
    assert engine.allocator.free_count == engine.allocator.total


def test_cancel_with_a_step_in_flight_leaves_the_others_tokens(engine):
    """A cancel frees its slot under the step in flight; the request beside
    it and the one admitted into the freed slot read the reference's."""
    _settle(engine)
    keep, gone, late = [2, 4, 6, 8, 10], [9, 7, 5], [1, 3, 5, 7]
    a = engine.submit(keep, max_new_tokens=20)
    b = engine.submit(gone, max_new_tokens=30)
    assert [next(b) for _ in range(3)] == _ref(engine, gone, 3)
    b.cancel()
    c = engine.submit(late, max_new_tokens=8)
    assert list(c) == _ref(engine, late, 8)
    assert list(a) == _ref(engine, keep, 20)
    rest = list(b)  # what was emitted before the cancel took effect
    assert len(rest) < 27 and rest == _ref(engine, gone, 30)[3:3 + len(rest)]
    _settle(engine)
    engine.clear_prefix_cache()
    assert engine.allocator.free_count == engine.allocator.total


def test_admission_while_running_ahead_reads_the_step_in_flight_first(engine):
    """A request arriving while the loop runs ahead: the step in flight is
    read before the newcomer's prefill is dispatched, and both streams are
    the reference's."""
    _settle(engine)
    first, second = [4, 8, 12, 3], [11, 2]
    a = engine.submit(first, max_new_tokens=24)
    head = [next(a) for _ in range(5)]  # decoding, a step in flight
    b = engine.submit(second, max_new_tokens=9)
    assert list(b) == _ref(engine, second, 9)
    assert head + list(a) == _ref(engine, first, 24)


def test_page_boundary_crossed_on_a_step_dispatched_ahead(engine):
    """Pages are reserved at admission, so a step dispatched ahead writes
    over a page edge (8-token pages: positions 5..20 cross 8 and 16) with
    nothing from the host."""
    _settle(engine)
    prompt = [6, 1, 9, 2, 7]
    assert list(engine.submit(prompt, max_new_tokens=16)) \
        == _ref(engine, prompt, 16)


def test_sampled_tokens_equal_the_serial_loops_under_one_key():
    """The PRNG key advances on the device once a dispatch, so the order of
    keys is the order of dispatch: with temperature > 0 the loop that runs
    ahead samples what a loop that never does (``_may_run_ahead`` held
    false from the test) samples under the same seed."""
    runs = []
    for serial in (False, True):
        eng = _tiny_engine()
        if serial:
            eng._may_run_ahead = lambda: False
        try:
            runs.append([list(eng.submit(p, max_new_tokens=12,
                                         temperature=0.9))
                         for p in ([5, 7, 11], [2, 3], [13, 1, 4, 9])])
            assert eng.stats()["steps"] == 3 * 11
        finally:
            eng.shutdown()
    assert runs[0] == runs[1]
    assert len({tuple(r) for r in runs[0]}) == 3  # sampled, not constant


def test_running_ahead_traces_no_new_program(engine):
    """A step dispatched ahead takes the last step's device outputs where a
    serial step may take uploaded mirrors: the same shapes and dtypes, so
    the recompile sentinel's counts do not move."""
    from ray_tpu.models.paged import trace_counts

    _settle(engine)
    before = trace_counts()
    streams = [engine.submit([3 + i, 5, 8], max_new_tokens=6 + 3 * i)
               for i in range(5)]
    assert [len(list(s)) for s in streams] == [6, 9, 12, 15, 18]
    assert trace_counts() == before


def test_shutdown_with_a_step_in_flight_frees_every_page():
    """``shutdown()`` reads or drops the step in flight before slots go:
    the stream errors loudly, every page is back, no thread is left."""
    eng = _tiny_engine()
    s = eng.submit([1, 2, 3], max_new_tokens=32)
    assert len([next(s) for _ in range(4)]) == 4  # running ahead by now
    eng.shutdown()
    assert not eng._thread.is_alive()
    assert eng._inflight is None
    assert eng.allocator.free_count == eng.allocator.total
    with pytest.raises(RuntimeError, match="shut down"):
        list(s)


def test_page_free_list_balances_after_churn(engine):
    """Completion, cancellation, and shutdown-free paths all return pages:
    after N churn rounds the free list must be exactly full."""
    alloc = engine.allocator
    for round_ in range(5):
        streams = [engine.submit([1 + round_, 2, 3], max_new_tokens=6)
                   for _ in range(6)]
        cancelled = engine.submit([7, 7], max_new_tokens=32)
        next(cancelled)
        cancelled.cancel()
        for s in streams:
            assert len(list(s)) == 6
    deadline = time.time() + 10
    while time.time() < deadline and alloc.free_count != alloc.total:
        time.sleep(0.05)
    assert alloc.free_count == alloc.total
    assert engine.stats()["cancelled"] >= 5


def test_overload_sheds_typed_error_and_counts(engine):
    """Admission control: a full wait queue sheds NEW arrivals with the
    typed error, serves everything already admitted/queued, and counts
    the sheds."""
    from ray_tpu.serve.engine import EngineOverloadedError
    from ray_tpu.util.metrics import get_counter

    small = _tiny_engine(max_queue=2)
    try:
        counter = get_counter("ray_tpu_serve_engine_shed_total")
        before_metric = sum(counter._values.values())
        busy = []
        for _ in range(small.config.batch_slots):
            s = small.submit([1] * 8, max_new_tokens=32)
            next(s)  # in a slot and decoding before the next submit
            busy.append(s)
        queued = [small.submit([2], max_new_tokens=1) for _ in range(2)]
        with pytest.raises(EngineOverloadedError):
            for _ in range(small.config.max_queue + 4):
                small.submit([3], max_new_tokens=1)
        for s in busy + queued:
            assert len(list(s)) > 0  # admitted work still completes
        assert small.stats()["shed"] >= 1
        assert sum(counter._values.values()) > before_metric
        # Page-size prompts leave frozen pages in the prefix cache by
        # design; after draining it the free list must balance exactly.
        small.clear_prefix_cache()
        assert small.allocator.free_count == small.allocator.total
    finally:
        small.shutdown()


def test_one_compiled_decode_program_for_any_mix(engine):
    """The compile-count contract: after the programs exist, no admission
    mix (occupancy, lengths, churn, cancellation) retraces the decode
    step — batch slots, page tables, and lengths are DATA."""
    from ray_tpu.models.paged import trace_count

    # Prior tests exercised the engine; programs exist.
    decode_before = trace_count("decode")
    prefill_before = trace_count("prefill")
    assert decode_before >= 1
    streams = [engine.submit([1], max_new_tokens=3),
               engine.submit([2, 3, 4, 5, 6, 7, 8, 9], max_new_tokens=9),
               engine.submit([4, 5], max_new_tokens=1)]
    mid = engine.submit([8] * 12, max_new_tokens=5)
    for s in streams:
        list(s)
    list(mid)
    c = engine.submit([6], max_new_tokens=17)
    next(c)
    c.cancel()
    assert trace_count("decode") == decode_before
    assert trace_count("prefill") == prefill_before


def test_prefill_bucket_wider_than_worst_case_footprint():
    """The page table must cover the largest prefill BUCKET, not just the
    worst-case sequence: padded prefill positions index the table, and a
    clamped out-of-range gather would silently overwrite a real page.
    max_prompt 20 / cap 4 / page 8 -> worst case 3 pages but bucket 32
    needs 4 table entries."""
    import jax
    import jax.numpy as jnp

    from greedy_ref import greedy_tokens
    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    cfg = LlamaConfig.tiny(remat=False, dtype=jnp.float32)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    eng = InferenceEngine(cfg, params, EngineConfig(
        batch_slots=2, page_size=8, max_prompt_len=20,
        max_new_tokens_cap=4, max_queue=4))
    try:
        assert eng.maxp == 4
        prompt = list(range(2, 20))  # 18 tokens -> the 32 bucket
        toks = list(eng.submit(prompt, max_new_tokens=4))
        assert toks == greedy_tokens(cfg, params, prompt, 4)
        eng.clear_prefix_cache()  # drop cached prompt pages
        assert eng.allocator.free_count == eng.allocator.total
    finally:
        eng.shutdown()


def test_llm_server_refuses_the_cpu_on_a_host_with_chips(monkeypatch):
    """Serving a real model from the CPU backend on a TPU host is an error,
    not a default; `tiny` (tests, demos) serves anywhere, and llm_app hands
    the chip request to the deployment's ordinary actor options."""
    from ray_tpu.serve.engine import LLMServer, llm_app

    monkeypatch.setenv("RT_TPU_CHIPS", "4")
    with pytest.raises(RuntimeError, match=r"ray_actor_options=.*num_tpus"):
        LLMServer(model="b1")  # raises before a single weight is made
    app = llm_app(model="b1", ray_actor_options={"num_tpus": 1})
    assert app.deployment.to_spec(app)["resources"] == {"TPU": 1}
    assert llm_app().deployment.to_spec(llm_app())["resources"] == {}


def test_model_failure_fails_streams_not_the_loop(monkeypatch):
    """A model-call failure mid-decode surfaces on the affected streams
    (not silent stalls), pages return, the pool is rebuilt, and the loop
    keeps serving; shutdown mid-generation errors instead of truncating."""
    import ray_tpu.models.paged as paged_mod

    eng = _tiny_engine()
    try:
        assert len(list(eng.submit([1, 2, 3], max_new_tokens=4))) == 4
        real = paged_mod.paged_decode_step
        calls = {"n": 0}

        def boom(*a, **kw):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("injected device failure")
            return real(*a, **kw)

        monkeypatch.setattr(paged_mod, "paged_decode_step", boom)
        with pytest.raises(RuntimeError, match="injected"):
            list(eng.submit([4, 5], max_new_tokens=6))
        # Recovered: fresh pool, balanced free list, still serving.
        assert len(list(eng.submit([1, 2, 3], max_new_tokens=4))) == 4
        assert eng.allocator.free_count == eng.allocator.total
    finally:
        eng.shutdown()

    eng2 = _tiny_engine()
    s = eng2.submit([1], max_new_tokens=16)
    next(s)
    eng2.shutdown()
    with pytest.raises(RuntimeError, match="shut down"):
        list(s)


@pytest.fixture
def rt():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    yield ray_tpu
    serve.shutdown()
    ray_tpu.shutdown()


def test_engine_request_span_tree(engine):
    """A traced engine request emits the queue -> prefill -> decode span
    tree (parented to the submitter's context) with bucket attr on the
    prefill and token count + TTFT on the decode span — per-request
    latency attribution derivable from spans alone.  Untraced requests
    emit nothing."""
    from ray_tpu.util import tracing

    # Untraced submissions (no ambient context) must stay span-free.
    tracing.drain_buffered()
    for _ in engine.submit([5, 7], max_new_tokens=2):
        pass
    assert [s for s in tracing.drain_buffered()
            if str(s.get("name", "")).startswith("engine:")] == []

    with tracing.trace("req_root", force=True) as root:
        stream = engine.submit([5, 7, 11], max_new_tokens=4)
        toks = list(stream)
    assert len(toks) == 4
    spans = [s for s in tracing.drain_buffered()
             if s.get("trace_id") == root["trace_id"]]
    by_name = {s["name"]: s for s in spans}
    assert {"engine:queue", "engine:prefill",
            "engine:decode"} <= set(by_name), sorted(by_name)
    for name in ("engine:queue", "engine:prefill", "engine:decode"):
        assert by_name[name]["parent_id"] == root["span_id"]
    prefill = by_name["engine:prefill"]
    assert prefill["attrs"]["prompt_len"] == 3
    assert prefill["attrs"]["bucket"] >= 3  # padded to a bucket
    decode = by_name["engine:decode"]
    assert decode["attrs"]["tokens"] == 4
    assert decode["attrs"]["reason"] == "complete"
    assert decode["attrs"]["ttft_s"] > 0
    # TTFT is reconstructable from the tree: queue start -> prefill end.
    assert prefill["end"] - by_name["engine:queue"]["start"] > 0
    # Stage ordering holds on the wall clock.
    assert by_name["engine:queue"]["start"] <= prefill["start"] \
        <= decode["start"]


@pytest.mark.slow
def test_serve_request_connected_trace_tree(rt):
    """Acceptance (slow gate — a fresh llm app deploy + compiles): one
    sampled serve request produces a SINGLE connected span tree spanning
    ingress -> handle -> replica -> engine (queue/prefill/decode),
    reconstructable from the head's span plane by trace id — the
    X-RT-Trace-Id the HTTP ingress returns.  Engine-stage completeness is
    held in tier-1 by test_engine_request_span_tree, so tier-1 keeps the
    cheap propagation tests while this covers the full serve path."""
    from ray_tpu.core.context import ctx
    from ray_tpu.util import trace_analysis

    handle = serve.run(serve.llm_app(
        engine=dict(GEOMETRY, max_queue=8), name="llmtr"))
    del handle  # requests go through the HTTP ingress below
    port = serve.start_http()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/llmtr",
            data=json.dumps({"prompt_tokens": [5, 7, 11],
                             "max_new_tokens": 3}).encode(),
            headers={"Accept": "text/event-stream",
                     "X-RT-Force-Trace": "1"})
        with urllib.request.urlopen(req, timeout=180) as resp:
            trace_id = resp.headers.get("X-RT-Trace-Id")
            resp.read()
        assert trace_id, "ingress did not return X-RT-Trace-Id"

        want = {"ingress:llmtr", "handle:llmtr", "replica:llmtr",
                "task:ServeReplica.handle_request_streaming",
                "engine:queue", "engine:prefill", "engine:decode"}
        deadline = time.time() + 30
        spans = []
        while time.time() < deadline:
            spans = ctx.client.call(
                "list_state",
                {"kind": "traces", "trace_id": trace_id})["items"]
            if want <= {s["name"] for s in spans}:
                break
            time.sleep(0.3)
        names = {s["name"] for s in spans}
        assert want <= names, sorted(names)
        # SINGLE connected tree: exactly one root, the ingress span.
        ids = {s["span_id"] for s in spans}
        roots = [s for s in spans if s.get("parent_id") not in ids]
        assert [s["name"] for s in roots] == ["ingress:llmtr"], roots
        # The critical path reaches the engine's decode stage and the
        # stage breakdown attributes prefill + decode time.
        path = trace_analysis.critical_path(spans)
        assert path[0]["name"] == "ingress:llmtr"
        assert any(r["name"] == "engine:decode" for r in path)
        stages = trace_analysis.stage_breakdown(spans)
        assert stages.get("prefill", 0) > 0
        assert stages.get("decode", 0) > 0
    finally:
        serve.stop_http()


def test_llm_app_streams_and_cancels_through_serve(rt):
    """The engine behind the full serve stack: handle streaming, SSE
    ingress, and a mid-stream handle cancel that frees the replica's
    pages (the decode loop sees the consumer vanish)."""
    handle = serve.run(serve.llm_app(
        engine=dict(GEOMETRY, max_queue=8), name="llm"))

    toks = list(handle.options(stream=True).remote([5, 7, 11], 5))
    assert len(toks) == 5 and all(isinstance(t, int) for t in toks)
    assert list(handle.options(stream=True).remote([5, 7, 11], 5)) == toks

    port = serve.start_http()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/llm",
            data=json.dumps({"prompt_tokens": [5, 7, 11],
                             "max_new_tokens": 3}).encode(),
            headers={"Accept": "text/event-stream"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            frames = [json.loads(ln[5:])
                      for ln in resp.read().decode().splitlines()
                      if ln.startswith("data:")
                      and ln[5:].strip() != "null"]
        assert frames == toks[:3]
    finally:
        serve.stop_http()

    # Mid-stream cancel: the replica-side generator is closed, the
    # engine evicts the sequence, and every page returns to the pool.
    stream = handle.options(stream=True).remote([1, 2], 32)
    it = iter(stream)
    next(it), next(it)
    stream.cancel()
    deadline = time.time() + 20
    while time.time() < deadline:
        st = handle.options("stats").remote().result()
        if st["free_pages"] == st["total_pages"] and not st["active_seqs"]:
            break
        time.sleep(0.2)
    assert st["free_pages"] == st["total_pages"], st
    assert st["cancelled"] >= 1
    # One compiled decode program served the whole test.
    assert st["decode_traces"] == 1
