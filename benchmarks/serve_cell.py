"""The parent's side of a serving run: deploy the benchmark's deployment
through ``serve.run``, offer the cell's traffic through
``handle.options(stream=True)``, time every token at the client, and gather
what the per-layer readers need.  Never imports JAX.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

from . import traffic as gen
from .arith import median

APP = "bench_llm"
READY_TIMEOUT_S = 1100.0
CALL_TIMEOUT_S = 300.0


class _Sample:
    """One request as the client saw it."""

    __slots__ = ("index", "due", "sent", "tokens", "times", "want", "error",
                 "cancelled", "prompt")

    def __init__(self, index: int, due: float, req: Dict[str, Any]):
        self.index, self.due, self.sent = index, due, 0.0
        self.prompt, self.want = req["prompt"], req["max_new"]
        self.tokens: List[int] = []
        self.times: List[float] = []
        self.error: Optional[str] = None
        self.cancelled = False


def _stream_one(stream, s: _Sample, temperature: float,
                stop: Optional[threading.Event]) -> None:
    s.sent = time.perf_counter()
    try:
        g = stream.remote(s.prompt, s.want, temperature)
        for tok in g:
            s.times.append(time.perf_counter())
            s.tokens.append(tok)
            if stop is not None and stop.is_set():
                s.cancelled = True
                g.cancel()
                break
    except Exception as e:  # noqa: BLE001 — a failed request is a result
        s.error = f"{type(e).__name__}: {e}"


class Served:
    """A deployed replica of the benchmark's deployment, as the parent
    holds it: the streaming handle and plain calls of its methods."""

    def __init__(self, handle, t_run: float):
        self.handle, self.t_run = handle, t_run
        self.info: Dict[str, Any] = {}  # bench_info() once it is up
        self.stream = handle.options(stream=True)

    def call(self, method: str, *args):
        return self.handle.options(method).remote(*args).result(
            timeout=CALL_TIMEOUT_S)


def deploy(cell: Dict[str, Any], *, seed: int, platform: str, num_tpus: int,
           fail_phase: str, log, phase) -> Served:
    """``serve.run`` of the benchmark's deployment with the cell's engine;
    returns once the replica has compiled (or read from the cache) every
    program it will run."""
    from ray_tpu import serve
    from ray_tpu.serve.api import Deployment

    from .deployment import BenchLLMServer

    tr, model = cell["traffic"], cell["model"]
    phase("worker_grant")
    t_run = time.time()
    dep = Deployment(
        BenchLLMServer, APP, num_replicas=1,
        max_concurrent_queries=int(tr["max_concurrent_queries"]),
        ray_actor_options={"num_tpus": num_tpus} if num_tpus else None)
    handle = serve.run(
        dep.bind(model=model, engine=tr["engine"], seed=seed,
                 platform=platform, fail_phase=fail_phase),
        timeout=READY_TIMEOUT_S)
    served = Served(handle, t_run)
    served.info = info = served.call("bench_info")
    st = info["stats"]
    log({"phase": "ready", "ready_s": time.time() - t_run,
         "worker_start_s": info["devices_ready_wall"] - t_run,
         "init_s": st["init_s"], "warmup_compile_s": st["warmup_s"],
         "compile_cache": info["compile_cache"], "device": info["device"],
         "replica_pid": st["pid"]})
    return served


def offer(served: Served, tr: Dict[str, Any], model: Dict[str, Any], *,
          seed: int, seconds: float, trace: bool, phase) -> Dict[str, Any]:
    """Offer the traffic of ``tr`` for its ramp and then ``seconds``, time
    every token at the client, and return the window's samples with the
    engine's step records of the same window."""
    from ray_tpu.core.context import ctx as rt_ctx

    stream, call = served.stream, served.call
    temperature = float(tr.get("temperature", 0.0))
    phase("warmup")
    kind = tr["kind"]
    ramp = float(tr.get("ramp_s", 0.0))
    horizon = ramp + seconds
    if kind == "serve_open":
        n = max(1, round(tr["rate_rps"] * horizon))
        due = gen.arrivals(tr["rate_rps"], n, tr.get("schedule_seed", 0))
    else:
        # More than a window can consume; clients draw until it closes.
        n = int(tr["clients"] + 8 * horizon + 64)
        due = [0.0] * n
    reqs = gen.requests(tr, n, seed, model["vocab_size"])
    samples = [_Sample(i, d, r) for i, (d, r) in enumerate(zip(due, reqs))]
    stop = threading.Event()
    threads: List[threading.Thread] = []
    t0 = time.perf_counter() + 0.05
    w0, w1 = t0 + ramp, t0 + horizon
    marks: Dict[str, Any] = {}
    traced: Dict[str, Any] = {}

    def edge_marks() -> None:
        """Marks at the window's edges, and the trace inside it, from a
        thread of their own so that no request waits for them."""
        time.sleep(max(0.0, w0 - time.perf_counter()))
        marks["w0"] = call("mark")
        if trace:
            import tempfile

            trace_s = min(float(tr.get("trace_s", 5.0)), seconds * 0.8)
            time.sleep(min(1.0, seconds * 0.1))
            marks["trace_dir"] = tempfile.mkdtemp(prefix="bench_trace_")
            call("trace_start", marks["trace_dir"])
            time.sleep(trace_s)
            marks["trace_window_s"] = call("trace_stop")
        time.sleep(max(0.0, w1 - time.perf_counter()))
        marks["w1"] = call("mark")

    marker = threading.Thread(target=edge_marks, name="bench-marks",
                              daemon=True)
    marker.start()
    if kind == "serve_open":
        for s in samples:
            s.due = t0 + s.due
            time.sleep(max(0.0, s.due - time.perf_counter()))
            if time.perf_counter() >= w1:
                break
            th = threading.Thread(target=_stream_one, daemon=True,
                                  args=(stream, s, temperature, None))
            th.start()
            threads.append(th)
    else:
        it = iter(samples)
        lock = threading.Lock()

        def client() -> None:
            while not stop.is_set():
                with lock:
                    s = next(it, None)
                if s is None:
                    return
                s.due = time.perf_counter()
                _stream_one(stream, s, temperature, stop)

        for _ in range(int(tr["clients"])):
            th = threading.Thread(target=client, daemon=True)
            th.start()
            threads.append(th)
        time.sleep(max(0.0, w1 - time.perf_counter()))
    try:
        phase("measure")
    finally:
        stop.set()
    for th in threads:
        th.join(timeout=CALL_TIMEOUT_S)
    marker.join(timeout=CALL_TIMEOUT_S)
    if any(th.is_alive() for th in threads) or marker.is_alive():
        raise RuntimeError("a client thread did not finish")
    sent = [s for s in samples if s.sent]

    # --------------------------------------------------------- reduction
    window_tokens = sum(1 for s in sent for t in s.times if w0 <= t < w1)
    gaps = [b - a for s in sent for a, b in zip(s.times, s.times[1:])
            if w0 <= b < w1]
    in_window = [s for s in sent if w0 <= s.due < w1]
    ttft = [(s.times[0] - s.due) if s.times else seconds
            for s in in_window]
    first_in_window = [s.times[0] - s.sent for s in sent
                       if s.times and w0 <= s.times[0] < w1]
    late = [s.sent - s.due for s in sent]
    failed = [s for s in sent if s.error is not None
              or (not s.cancelled and len(s.tokens) != s.want)]
    vocab = model["vocab_size"]
    bad_ids = [s.index for s in sent
               if any(not (isinstance(t, int) and 0 <= t < vocab)
                      for t in s.tokens)]

    flush = call("flush_step_records")
    time.sleep(0.3)  # the batched RPC to the head is asynchronous
    rows = rt_ctx.client.call(
        "list_state", {"kind": "engine_steps"})["items"]
    # The replica shares this machine's wall clock: the window's edges as
    # this process timed them, not as a (possibly late) mark call did.
    w0_wall = time.time() - (time.perf_counter() - w0)
    steps = [r for row in rows for r in row["records"]
             if w0_wall <= r["t"] < w0_wall + seconds]
    if "trace_dir" in marks:  # read the trace now, outside the window
        traced.update(call("trace_reduce"))
    engine_ttft = call("engine_ttfts", marks["w0"]["ttft_seen"],
                       marks["w1"]["ttft_seen"])
    reasons: List[str] = []
    if failed:
        reasons.append(f"{len(failed)} of {len(sent)} requests failed, "
                       f"first: {failed[0].error or 'wrong token count'}")
    if bad_ids:
        reasons.append(f"token ids outside the vocabulary in requests "
                       f"{bad_ids[:5]}")
    if flush["dropped"]:
        reasons.append(f"{flush['dropped']} step records were dropped")
    if not steps:
        reasons.append("no engine step record fell inside the window")
    return {
        "kind": kind, "seconds": seconds, "attempted": len(sent),
        "failed": len(failed), "reasons": reasons,
        "window_tokens": window_tokens, "itl_s": gaps, "ttft_s": ttft,
        "client_first_token_s": first_in_window,
        "engine_ttft_s": engine_ttft, "steps": steps, "trace": traced,
        "window_wall": w0_wall,
        "trace_dir": marks.get("trace_dir"),
        "requests_in_window": len(in_window),
        "generator_late_s": late,
        "cancelled": sum(s.cancelled for s in sent),
        "drain_s": time.perf_counter() - w1,
    }


def compare(served: Served, tr: Dict[str, Any], model: Dict[str, Any], *,
            seed: int, phase) -> Dict[str, Any]:
    """Outside the window: seeded prompts, one to a prefill bucket, through
    a cold prefill and again through the cached prefix, each held to the
    plain reference token by token; then the engine's own invariants."""
    import numpy as np

    stream, call = served.stream, served.call
    vocab = model["vocab_size"]
    phase("compare")
    check = tr["check"]

    rng = np.random.default_rng([gen.seed32(seed), 4])
    reasons: List[str] = []
    picked = []
    for n_prompt in check["prompt_lens"]:
        prompt = rng.integers(1, vocab, size=n_prompt).tolist()
        call("clear_prefix_cache")
        cold = list(stream.remote(prompt, check["new_tokens"], 0.0))
        cached = list(stream.remote(prompt, check["new_tokens"], 0.0))
        picked += [{"prompt": prompt, "output": cold, "path": "cold"},
                   {"prompt": prompt, "output": cached, "path": "cached"}]
    short = [p for p in picked if len(p["output"]) != check["new_tokens"]]
    if short:
        reasons.append(f"{len(short)} check requests returned too few "
                       f"tokens")
        gaps_ref: List[List[float]] = []
    else:
        gaps_ref = call("reference_gaps",
                        [{"prompt": p["prompt"], "output": p["output"]}
                         for p in picked])
    worst = max((g for row in gaps_ref for g in row), default=0.0)
    if not worst <= check["logit_tol"]:
        reasons.append(f"an emitted token's reference logit is {worst} "
                       f"under the reference's best (tolerance "
                       f"{check['logit_tol']})")
    call("clear_prefix_cache")
    deadline = time.time() + 15.0
    while True:
        st = call("stats")
        idle = not st["active_seqs"] and not st["queued"]
        if (idle and st["free_pages"] == st["total_pages"]) \
                or time.time() > deadline:
            break
        time.sleep(0.2)
        call("clear_prefix_cache")
    if st["decode_traces"] != 1:
        reasons.append(f"decode program traced {st['decode_traces']} times")
    if st["free_pages"] != st["total_pages"] or st["active_seqs"]:
        reasons.append(f"free list not whole after clear_prefix_cache: "
                       f"{st['free_pages']} of {st['total_pages']}")
    return {"reasons": reasons, "reference_gap_max": worst,
            "shed": st["shed"], "device": call("bench_info")["device"]}


def run_serve(cell: Dict[str, Any], *, seed: int, seconds: float,
              trace: bool, platform: str, num_tpus: int, fail_phase: str,
              log, phase) -> Dict[str, Any]:
    """One serving run.  ``phase(name)`` marks the phase an exception
    belongs to; ``log`` prints an earlier line."""
    tr, model = cell["traffic"], cell["model"]
    served = deploy(cell, seed=seed, platform=platform, num_tpus=num_tpus,
                    fail_phase=fail_phase, log=log, phase=phase)
    m = offer(served, tr, model, seed=seed, seconds=seconds, trace=trace,
              phase=phase)
    c = compare(served, tr, model, seed=seed, phase=phase)
    late = m.pop("generator_late_s")
    log({"phase": "samples", "requests_sent": m["attempted"],
         "requests_in_window": m["requests_in_window"],
         "tokens_in_window": m["window_tokens"],
         "gaps_in_window": len(m["itl_s"]),
         "ttft_ms_p50": 1e3 * median(m["ttft_s"]) if m["ttft_s"] else None,
         "itl_ms_p50": 1e3 * median(m["itl_s"]) if m["itl_s"] else None,
         "generator_late_ms_p50": 1e3 * median(late),
         "generator_late_ms_max": 1e3 * max(late),
         "shed": c["shed"], "step_records_in_window": len(m["steps"]),
         "reference_gap_max": c["reference_gap_max"],
         "cancelled": m["cancelled"], "drain_s": m["drain_s"]})
    info = served.info
    m["reasons"] += c["reasons"]
    m.update(
        worker_start_s=info["devices_ready_wall"] - served.t_run,
        warmup_compile_s=info["stats"]["warmup_s"], device=c["device"],
        holder_pid=info["stats"]["pid"])
    return m
