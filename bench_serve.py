"""Benchmark: continuous-batching LLM serving under open-loop traffic.

Writes BENCH_SERVE.json: sustained tokens/s, p50/p99 TTFT and ITL at a
sweep of offered loads, and goodput under 2x overload — CONTINUOUS
batching (per-step admission into a paged KV cache) vs WHOLE-REQUEST
batching (gang admission, drain to completion) on the same model, same
kernels, same traffic.

The traffic generator is OPEN-LOOP (reference methodology: serving
benchmarks drive Poisson arrivals independent of completions, so queueing
under saturation is visible instead of hidden by closed-loop self-pacing):
arrivals ~ Poisson(rate), prompt/output lengths drawn from configurable
mixes.  Offered loads are fractions of the measured continuous-mode
saturation capacity, so rows are comparable across boxes.

A host-only run: a d=384 float32 model on the CPU backend, to compare
batching POLICIES.  It builds engines in this process and later starts a
cluster whose replica is another process, so neither may need a chip: JAX
is held to the CPU here and in every child, and the report says so.  None
of its numbers is a device number.

Usage:
    python bench_serve.py            # full sweep -> BENCH_SERVE.json
    python bench_serve.py --smoke    # small counts, no artifact rewrite
                                     # unless --out is given
"""

from __future__ import annotations

import argparse
import json
import os
import threading
import time
from typing import Dict, List, Optional

import numpy as np

os.environ["JAX_PLATFORMS"] = "cpu"  # host-only run, see the docstring

# Length mixes (tokens).  Outputs are deliberately long-tailed: the gap
# between continuous and whole-request batching IS the tail (a gang drains
# at the pace of its longest member while short sequences hold dead slots).
PROMPT_MIX = (4, 8, 12, 16)
OUTPUT_MIX = (4, 8, 16, 128)

ENGINE_KW = dict(batch_slots=8, page_size=16, max_prompt_len=16,
                 max_new_tokens_cap=128, max_queue=16)

# Shared-prefix geometry (G2): prompts must span MULTIPLE pages for the
# radix cache to have anything page-aligned to reuse, so this row trades
# page size down and prompt length up.  It runs LAST — a second decode
# geometry means a second compiled program, and the G1 rows' single-
# compile assertions must not see it.
PREFIX_KW = dict(batch_slots=8, page_size=8, max_prompt_len=48,
                 max_new_tokens_cap=32, max_queue=16)


def _build_engine(mode: str, seed: int = 0, engine_kw: Optional[Dict] = None):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    # Bigger than `tiny` on purpose: the decode step must dominate the
    # loop's Python overhead or the batching-policy gap washes out in
    # per-token bookkeeping noise on small CPU boxes.
    cfg = LlamaConfig(vocab_size=2048, d_model=384, n_layers=6,
                      n_heads=8, n_kv_heads=4, d_ff=1152, max_seq=256,
                      remat=False, dtype=jnp.float32)
    params = llama_init(cfg, jax.random.PRNGKey(seed))
    eng = InferenceEngine(
        cfg, params,
        EngineConfig(mode=mode, **(engine_kw or ENGINE_KW)), seed=seed)
    eng.warmup()
    return eng


def _pct(vals: List[float], q: float) -> Optional[float]:
    if not vals:
        return None
    return float(np.percentile(np.asarray(vals), q))


def run_load(engine, rate_rps: float, n_requests: int,
             seed: int = 0) -> Dict:
    """Offer ``n_requests`` at Poisson(rate_rps); returns the row dict.

    No consumer thread per request: the engine never blocks on consumers
    (emission queues are unbounded), so streams are drained AFTER the
    run and TTFT/ITL come from the engine's own emission timestamps.
    On a 2-vCPU box, a thread-per-request harness measures mostly its
    own GIL scheduling — and punishes the higher-throughput mode more
    (more tokens/s = more consumer wakeups), skewing the comparison."""
    from ray_tpu.serve.engine import EngineOverloadedError

    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate_rps, size=n_requests)
    prompts = rng.choice(PROMPT_MIX, size=n_requests)
    outs = rng.choice(OUTPUT_MIX, size=n_requests)
    streams = []
    shed = 0
    t0 = time.perf_counter()
    next_t = t0
    for i in range(n_requests):
        next_t += gaps[i]
        delay = next_t - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        prompt = rng.integers(1, 400, size=int(prompts[i]))
        try:
            streams.append(engine.submit(prompt,
                                         max_new_tokens=int(outs[i])))
        except EngineOverloadedError:
            shed += 1
    reqs = []
    for stream in streams:
        for _tok in stream:  # drains; engine has already timestamped
            pass
        reqs.append(stream._req)
    done = [r for r in reqs if r.first_token_t is not None]
    wall = max(r.last_token_t for r in done) - t0 if done else 0.0
    total_tokens = sum(r.generated for r in done)
    ttfts = [r.first_token_t - r.submit_t for r in done]
    itls = [d for r in done for d in r.itls]
    return {
        "offered_rps": round(rate_rps, 3),
        "requests": n_requests,
        "shed": shed,
        "completed": len(done),
        "wall_s": round(wall, 3),
        # Goodput: tokens of non-shed requests per second of wall — the
        # "did overload collapse it" number.
        "tokens_per_s": round(total_tokens / wall, 1) if wall > 0 else 0.0,
        "p50_ttft_s": _pct(ttfts, 50),
        "p99_ttft_s": _pct(ttfts, 99),
        "p50_itl_s": _pct(itls, 50),
        "p99_itl_s": _pct(itls, 99),
    }


def measure_capacity(engine, n_requests: int, seed: int = 0) -> Dict:
    """Saturation probe: CLOSED-LOOP — enough concurrent submitters to
    keep every batch slot occupied for the whole window, so the tail
    drain of an open-loop burst doesn't dilute the measured rate.

    Lengths ROTATE through the mixes instead of sampling: a whole-request
    gang's duration is its LONGEST member, so a randomly drawn gang's
    capacity swings severalfold on composition luck — the rotation holds
    every gang representative (each length appears equally), which is
    what makes the continuous/whole-request capacity ratio reproducible
    on a noisy box."""
    workers = engine.config.batch_slots + 8
    iters = max(1, n_requests // workers)
    rng = np.random.default_rng(seed)
    tokens = [0]
    lock = threading.Lock()

    def loop(widx: int):
        wrng = np.random.default_rng(seed * 1000 + widx)
        got = 0
        for it in range(iters):
            prompt = wrng.integers(
                1, 400, size=int(PROMPT_MIX[(widx + it) % len(PROMPT_MIX)]))
            stream = engine.submit(
                prompt,
                max_new_tokens=int(OUTPUT_MIX[(widx + it)
                                              % len(OUTPUT_MIX)]))
            got += sum(1 for _ in stream)
        with lock:
            tokens[0] += got

    t0 = time.perf_counter()
    threads = [threading.Thread(target=loop, args=(w,), daemon=True)
               for w in range(workers)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=600)
    wall = time.perf_counter() - t0
    return {"tokens_per_s": round(tokens[0] / wall, 1),
            "requests": workers * iters, "wall_s": round(wall, 3)}


def bench_serve_path(n_requests: int = 16) -> Dict:
    """Tokens/s through the FULL serve stack (replica actor + streaming
    returns + handle), to bound the per-token serving overhead vs the
    bare engine."""
    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=4)
    try:
        handle = serve.run(serve.llm_app(
            engine=dict(mode="continuous", **ENGINE_KW), warmup=True))
        stream_handle = handle.options(stream=True)
        tokens = [0]
        lock = threading.Lock()

        def consume(n_out):
            got = sum(1 for _ in stream_handle.remote([5, 7, 11], n_out))
            with lock:
                tokens[0] += got

        rng = np.random.default_rng(0)
        outs = rng.choice(OUTPUT_MIX, size=n_requests)
        t0 = time.perf_counter()
        threads = [threading.Thread(target=consume, args=(int(o),),
                                    daemon=True) for o in outs]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=300)
        wall = time.perf_counter() - t0
        return {"requests": n_requests,
                "tokens_per_s": round(tokens[0] / wall, 1),
                "wall_s": round(wall, 3)}
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def assert_trace_completeness(engine) -> Dict:
    """Drive ONE force-sampled request through the engine and assert its
    span tree contains every expected stage (queue -> prefill -> decode)
    with TTFT reconstructable from the spans alone.  A propagation
    regression (engine stops capturing the submitter's context, a stage
    span vanishes) fails the slow gate here instead of surviving until
    someone eyeballs a timeline.  Raises SystemExit on failure."""
    from ray_tpu.util import tracing

    tracing.drain_buffered()  # isolate this request's spans
    n_tokens = 4
    with tracing.trace("bench:request", force=True) as root:
        stream = engine.submit([3, 5, 7], max_new_tokens=n_tokens)
        for _ in stream:
            pass
    spans = [s for s in tracing.drain_buffered()
             if s.get("trace_id") == root["trace_id"]]
    by_name = {s["name"]: s for s in spans}
    missing = {"engine:queue", "engine:prefill",
               "engine:decode"} - set(by_name)
    if missing:
        raise SystemExit(
            f"trace completeness check FAILED: stages missing from the "
            f"span tree: {sorted(missing)} (got {sorted(by_name)})")
    for name in ("engine:queue", "engine:prefill", "engine:decode"):
        if by_name[name].get("parent_id") != root["span_id"]:
            raise SystemExit(
                f"trace completeness check FAILED: {name} span not "
                "parented into the request trace")
    decode = by_name["engine:decode"]
    if (decode.get("attrs") or {}).get("tokens") != n_tokens:
        raise SystemExit(
            "trace completeness check FAILED: decode span token count "
            f"{(decode.get('attrs') or {}).get('tokens')} != {n_tokens}")
    ttft_s = by_name["engine:prefill"]["end"] - by_name["engine:queue"]["start"]
    if not ttft_s > 0:
        raise SystemExit(
            "trace completeness check FAILED: TTFT not reconstructable "
            f"from spans (got {ttft_s})")
    return {"stages": sorted(by_name), "ttft_s": round(ttft_s, 6)}


def assert_step_records(engine) -> Dict:
    """Drive ONE request through the engine and assert the flight
    recorder captured it: records exist for this engine, every record
    carries the full field set, and at least one decode step shows the
    admitted sequence occupying a slot.  A recorder regression (ring
    stops filling, a field dropped, silent drops) fails the slow gate
    here instead of surviving until a post-mortem needs the black box.
    Raises SystemExit on failure."""
    from ray_tpu.util import steprec

    steprec.drain_buffered()  # isolate this request's records
    dropped0 = steprec.dropped_total()
    stream = engine.submit([3, 5, 7], max_new_tokens=4)
    for _ in stream:
        pass
    # The final step's record lands AFTER its tokens are consumable:
    # collect until a decoded record shows up (bounded).
    recs: List[Dict] = []
    deadline = time.perf_counter() + 2.0
    while time.perf_counter() < deadline:
        recs += [r for r in steprec.drain_buffered()
                 if r.get("engine") == engine.engine_id]
        if any(r.get("occupancy", 0) > 0 for r in recs):
            break
        time.sleep(0.05)
    if not recs:
        raise SystemExit(
            "step-record check FAILED: no flight-recorder records for "
            f"engine {engine.engine_id}")
    required = {"t", "engine", "step", "wall_s", "stall_s", "occupancy",
                "slots", "admitted", "evicted", "shed", "queued",
                "pages_used", "pages_free", "pages_shared", "prefix_hits",
                "adapter_pins", "tenants"}
    for r in recs:
        missing = required - set(r)
        if missing:
            raise SystemExit(
                "step-record check FAILED: record missing fields "
                f"{sorted(missing)}")
    decoded = [r for r in recs if r["occupancy"] > 0]
    if not decoded:
        raise SystemExit(
            "step-record check FAILED: no record shows the admitted "
            "sequence occupying a slot")
    if sum(r["admitted"] for r in recs) < 1:
        raise SystemExit(
            "step-record check FAILED: the admission never recorded")
    if steprec.dropped_total() != dropped0:
        raise SystemExit(
            "step-record check FAILED: records dropped during an idle "
            "single-request run")
    return {"records": len(recs), "steps_decoded": len(decoded),
            "admitted": int(sum(r["admitted"] for r in recs))}


def run_recorder_overhead(n_requests: int, seed: int = 0) -> Dict:
    """Recorder-on vs recorder-off decode throughput on identical
    closed-loop traffic.  The recorder's contract is <= 2% step overhead
    (one dict append per step; no device work); ``overhead_frac`` is the
    tracked number.  The hard gate is deliberately loose (25%) — a
    2-vCPU CI box cannot hold a 2% assertion without flaking, but a
    blowup means the record path grew device syncs or lock contention
    and must fail loudly."""
    caps: Dict[str, Dict] = {}
    for on in (True, False):
        eng = _build_engine("continuous", seed=seed,
                            engine_kw=dict(ENGINE_KW, step_record=on))
        try:
            caps["on" if on else "off"] = measure_capacity(
                eng, n_requests, seed=seed)
        finally:
            eng.shutdown()
    overhead = (caps["off"]["tokens_per_s"]
                / max(caps["on"]["tokens_per_s"], 1e-9)) - 1.0
    if overhead > 0.25:
        raise SystemExit(
            f"recorder-overhead row FAILED: flight recorder cost "
            f"{overhead:.1%} of decode throughput (contract: ~2%)")
    return {"recorder_on": caps["on"], "recorder_off": caps["off"],
            "overhead_frac": round(max(0.0, overhead), 4)}


def run_adapter_mix(n_requests: int, seed: int = 0) -> Dict:
    """Multi-LoRA traffic: requests rotate across the base model and six
    registered adapters (more adapters than device slots, so the pool
    must evict under load) in waves that decode TOGETHER in one batch.
    The row's contract: the adapter mix is per-slot DATA — the single
    compiled decode program from the earlier rows serves every mix, or
    this raises SystemExit."""
    from ray_tpu.serve.engine import random_lora

    eng = _build_engine("continuous", seed=seed)
    try:
        cfg, rank = eng.model_config, eng.config.lora_rank
        names = [f"lora{i}" for i in range(6)]
        for i, name in enumerate(names):
            eng.register_adapter(
                name, lambda s=i + 1: random_lora(cfg, s, rank=rank))
        choices = [None] + names
        rng = np.random.default_rng(seed)
        tokens = 0
        t0 = time.perf_counter()
        wave = eng.config.batch_slots
        for base in range(0, n_requests, wave):
            streams = []
            for i in range(base, min(base + wave, n_requests)):
                prompt = rng.integers(
                    1, 400, size=int(PROMPT_MIX[i % len(PROMPT_MIX)]))
                streams.append(eng.submit(
                    prompt,
                    max_new_tokens=int(OUTPUT_MIX[i % len(OUTPUT_MIX)]),
                    adapter=choices[i % len(choices)]))
            for s in streams:
                tokens += sum(1 for _ in s)
        wall = time.perf_counter() - t0
        st = eng.stats()
        if st["decode_traces"] != 1:
            raise SystemExit(
                f"adapter-mix row retraced the decode program "
                f"({st['decode_traces']} traces) — adapter ids must stay "
                "per-slot data")
        eng.clear_prefix_cache()
        return {
            "requests": n_requests,
            "adapters": len(names),
            "adapter_slots": eng.config.max_adapters,
            "tokens_per_s": round(tokens / wall, 1),
            "wall_s": round(wall, 3),
            "adapter_loads": st["adapters"]["loads"],
            "adapter_evictions": st["adapters"]["evictions"],
            "decode_traces": st["decode_traces"],
            "free_list_balanced": (
                eng.allocator.free_count == eng.allocator.total),
        }
    finally:
        eng.shutdown()


def run_tenant_overload(cap_rps: float, n_requests: int,
                        seed: int = 0) -> List[Dict]:
    """Two tenants (gold weight 4, free weight 1) offer EQUAL open-loop
    traffic at 1x and 2x capacity.  Overload must degrade PER TENANT:
    weighted-fair admission sheds the free tier's queue tail while gold's
    latency holds — a global FIFO would punish both equally.  Raises
    SystemExit when the shed distribution inverts at 2x."""
    from ray_tpu.serve.engine import EngineOverloadedError

    tenants = (("gold", 4.0), ("free", 1.0))
    rows = []
    for lvl in (1.0, 2.0):
        eng = _build_engine("continuous", seed=seed)
        try:
            rng = np.random.default_rng(seed)
            rate = cap_rps * lvl
            gaps = rng.exponential(1.0 / rate, size=n_requests)
            prompts = rng.choice(PROMPT_MIX, size=n_requests)
            outs = rng.choice(OUTPUT_MIX, size=n_requests)
            streams: Dict[str, list] = {t: [] for t, _ in tenants}
            shed = {t: 0 for t, _ in tenants}
            offered = {t: 0 for t, _ in tenants}
            t0 = time.perf_counter()
            next_t = t0
            for i in range(n_requests):
                next_t += gaps[i]
                delay = next_t - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                tname, weight = tenants[i % len(tenants)]
                offered[tname] += 1
                prompt = rng.integers(1, 400, size=int(prompts[i]))
                try:
                    streams[tname].append(eng.submit(
                        prompt, max_new_tokens=int(outs[i]),
                        tenant=tname, weight=weight))
                except EngineOverloadedError:
                    shed[tname] += 1
            per_tenant = {}
            for tname, weight in tenants:
                done = []
                for s in streams[tname]:
                    try:
                        for _tok in s:
                            pass
                    except EngineOverloadedError:
                        shed[tname] += 1
                        continue
                    done.append(s._req)
                done = [r for r in done if r.first_token_t is not None]
                ttfts = [r.first_token_t - r.submit_t for r in done]
                per_tenant[tname] = {
                    "weight": weight,
                    "offered": offered[tname],
                    "completed": len(done),
                    "shed": shed[tname],
                    "p50_ttft_s": _pct(ttfts, 50),
                    "p99_ttft_s": _pct(ttfts, 99),
                }
            eng.clear_prefix_cache()
            rows.append({
                "load_level": lvl,
                "offered_rps": round(rate, 3),
                "tenants": per_tenant,
                "free_list_balanced": (
                    eng.allocator.free_count == eng.allocator.total),
                "decode_traces": eng.stats()["decode_traces"],
            })
        finally:
            eng.shutdown()
    over = rows[-1]["tenants"]
    if over["free"]["shed"] < over["gold"]["shed"]:
        raise SystemExit(
            "tenant-overload row FAILED: weighted-fair shed fell on the "
            f"high-weight tenant (gold shed {over['gold']['shed']}, free "
            f"shed {over['free']['shed']})")
    return rows


def run_shared_prefix(n_requests: int, seed: int = 0) -> Dict:
    """Fleet-shares-a-system-prompt traffic: every prompt starts with the
    same 24 tokens (3 full pages under G2) plus a random tail.  The radix
    cache must serve the prefix from frozen pages — hit rate > 0.5 — and
    cached decode must be TOKEN-EXACT vs the cold path, or this raises
    SystemExit.  Runs under its own geometry, so trace assertions are
    delta-based against the row's own warmup."""
    from ray_tpu.models.paged import trace_count

    eng = _build_engine("continuous", seed=seed, engine_kw=PREFIX_KW)
    try:
        ps = eng.config.page_size
        rng = np.random.default_rng(seed)
        prefix = [int(t) for t in rng.integers(1, 400, size=3 * ps)]

        # Token-exact parity: the same prompt cold (no cached pages) and
        # warm (prefix + COW source cached) must decode identically.
        eng.clear_prefix_cache()
        probe = prefix + [int(t) for t in rng.integers(1, 400, size=8)]
        cold = list(eng.submit(probe, max_new_tokens=8))
        warm = list(eng.submit(probe, max_new_tokens=8))
        if warm != cold:
            raise SystemExit(
                f"shared-prefix row FAILED: cached decode diverged from "
                f"cold decode ({warm} != {cold})")
        eng.clear_prefix_cache()

        # Warm the tree with ONE request before the open fire: admission
        # looks prefixes up when requests enter slots, so a full first
        # wave would all miss together (nothing has prefilled yet) and
        # understate steady-state reuse.
        list(eng.submit(prefix + [7], max_new_tokens=2))

        hits_0 = eng.stats()["prefix_cache"]["hits"]
        lookups_0 = eng.stats()["prefix_cache"]["lookups"]
        decode_traces_0 = trace_count("decode")
        tokens = 0
        t0 = time.perf_counter()
        wave = eng.config.batch_slots
        for base in range(0, n_requests, wave):
            streams = []
            for i in range(base, min(base + wave, n_requests)):
                tail = [int(t) for t in rng.integers(1, 400, size=8)]
                streams.append(eng.submit(prefix + tail, max_new_tokens=8))
            for s in streams:
                tokens += sum(1 for _ in s)
        wall = time.perf_counter() - t0
        st = eng.stats()
        cache = st["prefix_cache"]
        looked = cache["lookups"] - lookups_0
        hit_rate = (cache["hits"] - hits_0) / max(1, looked)
        if hit_rate <= 0.5:
            raise SystemExit(
                f"shared-prefix row FAILED: cache hit rate {hit_rate:.2f} "
                "<= 0.5 on shared-prefix traffic")
        if trace_count("decode") != decode_traces_0:
            raise SystemExit(
                "shared-prefix row retraced the decode program mid-traffic")
        shared_peak = st["shared_pages"]
        eng.clear_prefix_cache()
        return {
            "requests": n_requests,
            "prefix_tokens": len(prefix),
            "engine": PREFIX_KW,
            "tokens_per_s": round(tokens / wall, 1),
            "wall_s": round(wall, 3),
            "cache_hit_rate": round(hit_rate, 3),
            "prefix_traces": st["prefill_prefix_traces"],
            "pages_shared_end": shared_peak,
            "parity": "token_exact",
            "free_list_balanced": (
                eng.allocator.free_count == eng.allocator.total),
        }
    finally:
        eng.shutdown()


def main(argv=None) -> Dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small counts; skips the serve-path row")
    ap.add_argument("--out", default=None,
                    help="artifact path (default BENCH_SERVE.json unless "
                         "--smoke)")
    args = ap.parse_args(argv)

    if args.smoke:
        # Smoke mode doubles as the recompile gate: every engine warmup
        # below arms the sentinel (devtools.jitguard), so a post-warmup
        # retrace of any paged program aborts the bench with the arg
        # delta instead of quietly skewing the numbers.
        os.environ.setdefault("RT_DEBUG_JIT", "1")

    n_cap = 24 if args.smoke else 64
    n_row = 16 if args.smoke else 64
    levels = (1.0, 2.0) if args.smoke else (0.5, 1.0, 2.0)

    import jax

    dev = jax.devices()[0]
    report: Dict = {"metric": "serve_engine_bench",
                    "host_only": "CPU backend; no accelerator was used",
                    "device": {"platform": dev.platform,
                               "kind": dev.device_kind,
                               "count": len(jax.devices())},
                    "engine": ENGINE_KW,
                    "prompt_mix": list(PROMPT_MIX),
                    "output_mix": list(OUTPUT_MIX),
                    "modes": {}, "capacity": {}}

    # SUSTAINED capacity per mode, closed-loop (saturation held for the
    # whole window).  This is the headline comparison: the ratio of the
    # two capacities under identical traffic is robust to this box's
    # scheduling noise where absolute open-loop rates are not.  Two
    # trials, best-of (interference can only slow a trial down).
    caps: Dict[str, float] = {}
    for mode in ("continuous", "whole_request"):
        eng = _build_engine(mode)
        if mode == "continuous":
            # Trace-completeness gate (cheap: one 4-token request on the
            # already-built engine): propagation regressions fail the
            # bench, and therefore the slow CI gate, loudly.
            report["trace_check"] = assert_trace_completeness(eng)
            # Flight-recorder gate: the same engine must have recorded
            # the request step-by-step (observability regressions fail
            # here, not in a post-mortem).
            report["step_record_check"] = assert_step_records(eng)
        trials = [measure_capacity(eng, n_cap, seed=t) for t in range(2)]
        caps[mode] = max(t["tokens_per_s"] for t in trials)
        report["capacity"][mode] = {
            "tokens_per_s": caps[mode], "trials": trials}
        eng.shutdown()
    cap_tok_s = caps["continuous"]
    mean_tokens = float(np.mean(OUTPUT_MIX))
    cap_rps = cap_tok_s / mean_tokens

    # Open-loop sweep: identical Poisson traffic for both modes at
    # fractions of CONTINUOUS capacity — the TTFT/ITL-vs-load curves and
    # the 2x-overload goodput row.
    for mode in ("continuous", "whole_request"):
        rows = []
        for lvl in levels:
            eng = _build_engine(mode)
            row = run_load(eng, rate_rps=cap_rps * lvl,
                           n_requests=n_row, seed=42)
            row["load_level"] = lvl
            row["free_list_balanced"] = (
                eng.allocator.free_count == eng.allocator.total)
            row["decode_traces"] = eng.stats()["decode_traces"]
            eng.shutdown()
            rows.append(row)
        report["modes"][mode] = rows

    # Multi-tenant serving plane rows: batched-LoRA mixes and weighted-
    # fair tenants reuse the G1 geometry (single-compile assertions hold
    # across them); the shared-prefix row runs LAST under G2.
    n_mix = 16 if args.smoke else 48
    n_ten = 16 if args.smoke else 48
    n_pfx = 12 if args.smoke else 32
    report["multi_tenant"] = {
        "adapter_mix": run_adapter_mix(n_mix),
        "tenant_overload": run_tenant_overload(cap_rps, n_ten),
        "shared_prefix": run_shared_prefix(n_pfx),
    }

    # Observability cost row: recorder-on vs recorder-off capacity on
    # identical closed-loop traffic (contract: ~2% step overhead).
    report["recorder_overhead"] = run_recorder_overhead(
        16 if args.smoke else 32)

    def _at(mode, lvl):
        return next(r for r in report["modes"][mode]
                    if r["load_level"] == lvl)

    sat = 1.0 if 1.0 in levels else levels[0]
    c_sat, w_sat = _at("continuous", sat), _at("whole_request", sat)
    c_over = _at("continuous", levels[-1])
    report["summary"] = {
        "continuous_tokens_per_s": caps["continuous"],
        "whole_request_tokens_per_s": caps["whole_request"],
        "continuous_over_whole_request": round(
            caps["continuous"] / max(caps["whole_request"], 1e-9), 2),
        "continuous_p99_ttft_s": c_sat["p99_ttft_s"],
        "whole_request_p99_ttft_s": w_sat["p99_ttft_s"],
        # Overload posture: goodput at 2x vs 1x offered load (graceful =
        # stays near 1.0 while shedding the excess).
        "overload_goodput_ratio": round(
            c_over["tokens_per_s"] / max(c_sat["tokens_per_s"], 1e-9), 2),
        "overload_shed": c_over["shed"],
        "recorder_overhead_frac":
            report["recorder_overhead"]["overhead_frac"],
        "adapter_mix_tokens_per_s":
            report["multi_tenant"]["adapter_mix"]["tokens_per_s"],
        "prefix_cache_hit_rate":
            report["multi_tenant"]["shared_prefix"]["cache_hit_rate"],
        "tenant_2x_p99_ttft_s": {
            t: rec["p99_ttft_s"]
            for t, rec in report["multi_tenant"]["tenant_overload"][-1]
            ["tenants"].items()
        },
        "tenant_2x_shed": {
            t: rec["shed"]
            for t, rec in report["multi_tenant"]["tenant_overload"][-1]
            ["tenants"].items()
        },
    }

    if not args.smoke:
        report["serve_path"] = bench_serve_path()

    out = args.out or (None if args.smoke else "BENCH_SERVE.json")
    if out:
        with open(out, "w") as f:
            json.dump(report, f, indent=2)
    print(json.dumps(report["summary"]))
    return report


if __name__ == "__main__":
    main()
