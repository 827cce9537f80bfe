"""Continuous-batching LLM inference engine behind serve.

Role-equivalent to the Ray Serve LLM stack's engine loop (reference: Ray
Serve's LLM deployments wrap a continuous-batching engine; PAPER.md L7
names model multiplexing + streaming as the serve capability surface).
The engine turns a replica from a request router into an inference loop:

- ONE decode program (``models/paged.py``) serves every admission mix —
  batch slots, page tables, and lengths are data, so after warmup the
  loop never recompiles.
- Queued sequences are admitted into free batch slots BETWEEN decode
  steps; a prefill runs as its own (bucketed) program, so running
  sequences stall by at most one step per admission.
- The loop keeps ONE decode step in flight.  A step's tokens, lengths
  and PRNG key advance on the device and every page a sequence can
  reach was reserved at admission, so while membership stands still
  step n+1 needs nothing the host learns from step n: the loop
  dispatches n+1 first and then reads, emits and records n while the
  chip runs n+1 (``_run_step``).  It engages by what the loop observes
  (nothing admitted, no control op, mirrors clean, no token budget
  ending at step n) and otherwise reads the step in flight before
  anything else is dispatched: prefills, uploads, control ops and
  evictions by budget only ever meet a quiet device.  A stop the host
  cannot foresee (a stop token, a cancel) wastes the one step already
  dispatched: its token is dropped and its K/V row lies past the
  sequence's length in a page that slot had reserved.
- Finished/cancelled sequences are evicted between steps and their pages
  return to the free list; the page pool's worst-case footprint is
  reserved at admission, so decode can never die of page exhaustion
  mid-flight.
- Admission control sheds with a typed :class:`EngineOverloadedError`
  when the wait queue exceeds its bound — goodput holds under overload
  instead of collapsing into unbounded queueing.
- Tokens stream out per-request as they decode (the deployment's sync
  generator feeds serve's existing per-item streaming path: handles,
  HTTP SSE, gRPC server-streaming); a consumer that disappears cancels
  the request and frees its pages mid-flight.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
import queue as _queue
import sys
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from ..core.context import direct_stream_counts
from ..util.profiling import annotation

#: Engine identity within one process: step records carry
#: ``"<pid>.<seq>"`` so the head's per-engine rings stay distinct when a
#: process hosts several engines (bench harnesses, tests).
_ENGINE_SEQ = itertools.count()

#: Phases of the loop.  Each is one ``util.profiling.annotation``: a host
#: span on the profiler's clock under this name, and (where the table in
#: ``_record_step`` gives it a key) seconds on the step record.
PH_ADMIT = "rt:engine/admit"        # the locked section of _loop
PH_IDLE = "rt:engine/idle"          # _wake.wait with nothing to do
PH_PREFILL = "rt:engine/prefill"    # all of _prefill, one request
PH_PREFILL_WAIT = "rt:engine/prefill_wait"  # blocked in int(first)
PH_UPLOAD = "rt:engine/upload"      # host mirrors to the device
PH_DISPATCH = "rt:engine/dispatch"  # enqueue of the decode program
PH_READBACK = "rt:engine/readback"  # blocked on the step's tokens
PH_EMIT = "rt:engine/emit"          # the per-slot pass after it
PH_RECORD = "rt:engine/record"      # _record_step


class EngineOverloadedError(Exception):
    """Typed admission-control shed: the engine's wait queue is full.

    Callers see this at submit time (the request never held pages or a
    slot); clients should back off and retry — the standard overload
    contract (reference: Serve's backpressure returns 503)."""


@dataclasses.dataclass
class EngineConfig:
    """Sizing knobs for one replica's engine.

    ``page_table_width`` (MAXP) and the pool size derive from the prompt
    and output caps so admission's worst-case reservation always fits a
    fresh pool: ``num_pages = 0`` auto-sizes to ``batch_slots`` times the
    per-sequence worst case."""

    batch_slots: int = 8
    page_size: int = 16
    max_prompt_len: int = 64
    max_new_tokens_cap: int = 128
    num_pages: int = 0            # 0 -> batch_slots * pages_per_seq
    max_queue: int = 32           # admission bound: beyond this, shed
    stream_timeout_s: float = 120.0
    # Multi-tenant plane.  max_adapters/lora_rank shape the device
    # adapter pool and are PART of the decode signature — engines that
    # should share one compiled program must agree on them (like the
    # geometry above).  prefix_cache toggles the radix tree over the
    # paged KV; ttft_window sizes the recent-TTFT deque feeding the
    # controller's SLO autoscaling.
    max_adapters: int = 4
    lora_rank: int = 8
    prefix_cache: bool = True
    ttft_window: int = 64
    # A prompt longer than this is prefilled in chunks of it: consecutive
    # calls of the suffix program, each over what the ones before it
    # cached.  It is the largest prefill bucket (the buckets stop there
    # instead of doubling up to the prompt cap); 0: none, every prompt is
    # one bucket.  A deployment sets it where a prompt cap's own bucket
    # would not fit: the one-bucket program scores [H, S, S] in float32
    # (3.5 GB at 28 heads of 5632 rows), and a window layer's ring is the
    # window PLUS one chunk, so the chunk is also what the rings cost in
    # memory (16 slots x 6 layers x 2048 rows: 0.4 GB of the cell's 1.2).
    prefill_chunk: int = 0
    # Flight recorder (util/steprec.py): one fixed-size record per decode
    # step into the bounded per-process ring.  Off-hot-path by design
    # (host counters only, no device sync).  step_window sizes the recent
    # step-wall / stall deques feeding slo_signals jitter + stall
    # pressure.
    step_record: bool = True
    step_window: int = 256

    @property
    def pages_per_seq(self) -> int:
        # The page table must cover BOTH the worst-case sequence AND the
        # largest prefill bucket: padded prefill positions index the
        # table, and jit clamps an out-of-range gather to the last entry
        # — which would silently corrupt a real page.
        worst = math.ceil(
            (self.max_prompt_len + self.max_new_tokens_cap)
            / self.page_size)
        return max(worst, self.prefill_buckets()[-1] // self.page_size)

    @property
    def pool_pages(self) -> int:
        return self.num_pages or self.batch_slots * self.pages_per_seq

    def prefill_buckets(self) -> List[int]:
        """Padded prompt lengths (one compile each): page-size multiples
        doubling up to the prompt cap, or to ``prefill_chunk``."""
        cap = self.max_prompt_len
        if 0 < self.prefill_chunk < cap:
            cap = self.prefill_chunk
            if cap % self.page_size:
                raise ValueError("prefill_chunk is not whole pages")
        out, b = [], self.page_size
        while b < cap:
            out.append(b)
            b *= 2
        out.append(max(b, cap))
        return out


class _Request:
    __slots__ = (
        "req_id", "prompt", "max_new", "temperature", "stop_token",
        "out_q", "cancelled", "finished", "pages", "page_table",
        "length", "generated", "submit_t", "first_token_t",
        "last_token_t", "slot",
        "trace_ctx", "submit_wall", "admit_t",
        "tenant", "weight", "adapter", "adapter_slot", "match",
        "cow_ref", "cache_hit_len", "wake_s", "wake_seen",
    )

    def __init__(self, req_id: int, prompt: np.ndarray, max_new: int,
                 temperature: float, stop_token: Optional[int]):
        self.req_id = req_id
        self.prompt = prompt
        self.max_new = max_new
        self.temperature = temperature
        self.stop_token = stop_token
        self.out_q: "_queue.Queue" = _queue.Queue()
        self.cancelled = threading.Event()
        self.finished = False
        self.pages: List[int] = []
        self.page_table: Optional[np.ndarray] = None
        self.length = 0
        self.generated = 0
        # Stage stamps, all time.perf_counter(): submit, admission into a
        # slot, first token.  The step record's first_tokens entry and the
        # request's spans are both computed from these; submit_wall is the
        # one wall-clock anchor that places the spans beside other
        # processes' (see wall()).
        self.submit_t = time.perf_counter()
        self.submit_wall = time.time()
        self.admit_t = 0.0
        self.first_token_t: Optional[float] = None
        # Both at emission in the loop, not where a consumer took the
        # token: their distance over the tokens between is the mean gap.
        self.last_token_t: Optional[float] = None
        # The seconds this request's tokens waited for its consumer, from
        # their emit pass's start to TokenStream.__next__ handing them on:
        # summed by the consuming thread alone (one writer, no lock), and
        # how much of the sum the loop has put on a step record, which is
        # the loop's to write while the request holds a slot and the
        # consumer's once its stream has ended.
        self.wake_s = 0.0
        self.wake_seen = 0.0
        self.slot = -1
        # Tracing: the submitter's span context (None when the request
        # arrived untraced/unsampled — then the engine emits nothing).
        self.trace_ctx: Optional[Dict[str, str]] = None
        # Multi-tenant plane: fair-queue identity, the adapter this
        # sequence decodes with (None = base model), and the prefix-cache
        # plan pinned at admission (match + the extra COW-source ref held
        # until the page is copied).
        self.tenant = "default"
        self.weight = 1.0
        self.adapter: Optional[str] = None
        self.adapter_slot = -1
        self.match = None
        self.cow_ref: Optional[int] = None
        self.cache_hit_len = 0

    def wall(self, t: float) -> float:
        """The wall clock at this request's perf_counter stamp ``t``."""
        return self.submit_wall + (t - self.submit_t)


class _Step(NamedTuple):
    """A decode step that was dispatched and whose tokens the host has not
    read: its number, the device array of its tokens, the request each
    slot decoded for when it was dispatched, whether it went out before
    the step ahead of it was read, and how many of its live slots' pages
    were another live slot's too (``InferenceEngine._shared_dups``)."""

    number: int
    tokens: Any
    owners: List[Optional[_Request]]
    ahead: bool
    shared_dups: int = 0


class _Account:
    """The step record being filled: opened by the first ``_run_step``
    after the last record closed, closed by ``_record_step``."""

    __slots__ = ("t0", "phases", "stall_s", "first_tokens", "evicted0",
                 "shed0", "compiles0", "traces0")

    def __init__(self, engine: "InferenceEngine"):
        from ..models.paged import trace_counts
        from ..util import devmem

        self.t0 = time.perf_counter()
        self.phases: Dict[str, float] = {}
        self.stall_s = 0.0
        self.first_tokens: List[Dict[str, Any]] = []
        self.evicted0 = engine._evicted_total
        self.shed0 = engine.shed
        self.compiles0 = devmem.compile_count()
        self.traces0 = trace_counts()


class _Starved:
    """The loop's account of the seconds it left the chip without work:
    from ``since`` (``time.perf_counter()`` where a blocking read returned
    and nothing else the engine dispatched was unread; None while the chip
    has work, or the loop nothing to give it) until the next program call
    returns.  They are charged by the loop phase in which they passed, from
    the phases' own stamps, and wait here for the record that closes next:
    ``_record_step`` takes them as ``idle_s`` is taken from ``_gap_acct``."""

    __slots__ = ("since", "by_phase", "stretch")

    def __init__(self):
        self.since: Optional[float] = None
        self.by_phase: Dict[str, float] = {}
        #: Charged since the chip last got work, of what waits here.
        self.stretch = 0.0

    def free(self, t: float) -> None:
        """Nothing the engine dispatched is unread since ``t``."""
        if self.since is None:
            self.since = t

    def spend(self, name: str, t0: float, t1: float) -> None:
        """The loop was in phase ``name`` from ``t0`` to ``t1``: what of it
        the chip stood still is that phase's, and what lay in no phase
        before it is ``between``'s."""
        since = self.since
        if since is None or t1 <= since:
            return
        by = self.by_phase
        if t0 > since:
            by["between"] = by.get("between", 0.0) + t0 - since
            since = t0
        by[name] = by.get(name, 0.0) + t1 - since
        self.stretch += t1 - self.since
        self.since = t1

    def stop(self) -> float:
        """A program call has returned (or the loop has nothing to run):
        the seconds charged since the last such moment, of those that wait
        for the open record."""
        self.since = None
        charged, self.stretch = self.stretch, 0.0
        return charged

    def take(self) -> Dict[str, float]:
        """What waits, for the record being closed."""
        out, self.by_phase = self.by_phase, {}
        self.stretch = 0.0
        return out


class _Held:
    """Who had the loop thread: its CPU clock (``time.thread_time()``)
    beside the wall's, so that a record's period splits into the seconds
    the thread ran and those it did not (it waited for the chip, for the
    interpreter lock, for ``self._lock`` or for a core).  ONE read of the
    thread's clock and one of the process's where a record ends, no more:
    a kernel may answer a CPU clock by a trap (the chip machines' sandboxed
    one: 6.3 us a read where this repo's own answers in 0.4, and the thread
    that asks holds the interpreter), and a read at every phase stamp cost
    the widest cell 2.4% of its tokens a second (builder's chip runs,
    PR 54).  Such a kernel's clocks also advance by TICKS (10 ms there), so
    one record reads no CPU or a whole tick: sums and means over records
    are unbiased and are what to read.  The loop thread's alone: a thread's
    CPU clock is its own."""

    __slots__ = ("cpu", "proc", "proc_idle")

    def __init__(self):
        #: The thread's and the PROCESS's CPU clocks where the last record
        #: closed (a thread's starts at 0 with the thread), and what of the
        #: process's has passed since then while the loop idled.
        self.cpu = 0.0
        self.proc = time.process_time()
        self.proc_idle = 0.0

    def take(self) -> Tuple[float, float]:
        """For the record that ends now: the thread's CPU seconds since
        the last record ended, and the process's over the same stretch
        less the idling."""
        cpu, proc = time.thread_time(), time.process_time()
        out = (cpu - self.cpu, proc - self.proc - self.proc_idle)
        self.cpu, self.proc, self.proc_idle = cpu, proc, 0.0
        return out


class _Phase(annotation):
    """A phase of the loop that the chip may stand still in: a
    ``util.profiling.annotation`` that hands its two stamps to the
    engine's :class:`_Starved`."""

    __slots__ = ("starved",)

    def __init__(self, name: str, account: Optional[dict],
                 starved: _Starved):
        super().__init__(name, account)
        self.starved = starved

    def __exit__(self, *exc) -> None:
        super().__exit__(*exc)
        self.starved.spend(self.name, self.t0, self.t1)


class TokenStream:
    """Per-request token iterator; the consumer side of the engine's
    emission queue.  ``cancel()`` (or closing the iterating generator)
    releases the request's slot and pages at the next step boundary."""

    def __init__(self, engine: "InferenceEngine", req: _Request):
        self._engine = engine
        self._req = req
        self.steps: List[int] = []   # decode-step index of each token
        # The ``wake`` hop is timed only where a step record takes it.
        self._timed = engine.config.step_record

    def __iter__(self):
        return self

    def __next__(self) -> int:
        req = self._req
        try:
            item = req.out_q.get(
                timeout=self._engine.config.stream_timeout_s)
        except _queue.Empty:
            self.cancel()
            raise RuntimeError(
                "engine stream stalled past stream_timeout_s") from None
        kind, payload = item[0], item[1]
        if kind == "tok":
            if self._timed:  # this thread has the token: its hop's end
                req.wake_s += time.perf_counter() - item[3]
            self.steps.append(item[2])
            return int(payload)
        # The stream's end.  The loop took its last look at this request
        # before it put this item: what it had not seen is handed over
        # (nothing with the step record off: no record would take it).
        late = req.wake_s - req.wake_seen
        if late:
            req.wake_seen = req.wake_s
            self._engine._wake_late.append(late)
        if kind == "err":
            raise payload
        raise StopIteration  # ("done", reason)

    def cancel(self) -> None:
        self._engine.cancel(self._req)


class InferenceEngine:
    """One replica's decode loop: host-side sequence/slot state machine
    around the jitted paged programs.  The loop runs on a dedicated
    daemon thread; ``submit()`` is called from any number of request
    threads and only touches the wait queue under the lock — pools,
    allocator, and slot arrays belong to the loop thread alone."""

    def __init__(self, model_config, params, config: EngineConfig,
                 seed: int = 0):
        import jax

        from ..devtools import jitguard
        from ..models.paged import (PAGED_PROGRAMS, PageAllocator,
                                    counter_keys, kv_layers, ring_entries,
                                    routing_keys, shares_walked_pages,
                                    state_bytes, state_layers)
        from ..util.metrics import get_counter, get_gauge, get_histogram

        # A fresh engine means fresh geometry: re-registering stands the
        # paged programs' armed baselines down (recompile sentinel) until
        # this engine's own warmup() re-arms — an un-warmed engine's cold
        # traces are a compile phase, not hot-path recompiles.
        for prog in PAGED_PROGRAMS:
            jitguard.register_program(prog)
        self.model_config = model_config
        self.params = params
        self.config = config
        cfg = config
        self.maxp = cfg.pages_per_seq
        self.scratch = cfg.pool_pages  # scratch page index
        # Two kinds of cache where the model has window layers: those
        # keep a ring of the window plus one prefill chunk (never more
        # than a whole sequence) in a pool of their own, and slot s owns
        # that pool's pages [s * ring, (s + 1) * ring): a ring is as long
        # for every sequence, so there is nothing to allocate or to
        # refuse.  The others keep every page of a sequence, as every
        # layer of a model without a pattern does.
        self._kv_layers = tuple(len(k) for k in kv_layers(model_config))
        self.ring = min(self.maxp, ring_entries(
            model_config, cfg.page_size, cfg.prefill_buckets()[-1]))
        self.ring_scratch = cfg.batch_slots * self.ring
        # A third kind where the model has recurrent layers (gated
        # delta-rule, state-space): a state a layer, which slot s owns as
        # it owns its ring (the pools' ``S`` and ``conv``, indexed by the
        # slot).  An admission's first prefill call starts it from zeros
        # on the device; nothing here allocates or clears it.
        self._state_layers = len(state_layers(model_config))
        self._state_bytes = state_bytes(model_config)  # a slot's
        self.allocator = PageAllocator(cfg.pool_pages)
        self.pools = self._new_pools()
        self._routing_keys = routing_keys(model_config)
        # For ``kv_rows_distinct``: how many decoding slots hold each page,
        # and over the pages held by several, the holders past the first.
        self._page_holders: Dict[int, int] = {}
        self._shared_dups = 0
        # Multi-tenant plane: device-resident LoRA slots + the radix
        # prefix tree over the page pool.  Both are owned by the loop
        # thread like the allocator.
        from .adapter_pool import AdapterPool
        from .prefix_cache import RadixPrefixCache

        self.adapter_pool = AdapterPool(
            model_config, max_adapters=cfg.max_adapters,
            rank=cfg.lora_rank)
        # A radix node is one page, valid for every layer: false of a
        # window layer's ring page, so such a model runs without it; and a
        # node holds no recurrent state at its depth, so a model with
        # recurrent layers does too.
        self._cache_off = ("window layers" if self.ring else
                           "recurrent layers" if self._state_layers
                           else None)
        self._cache: Optional[RadixPrefixCache] = (
            RadixPrefixCache(cfg.page_size)
            if cfg.prefix_cache and not self._cache_off else None)
        if cfg.prefix_cache and self._cache_off:
            print(f"engine: the model has {self._cache_off}, whose "
                  f"{'pages' if self.ring else 'state'} the prefix cache "
                  f"cannot share: prefix_cache is off",
                  file=sys.stderr, flush=True)
        # Only the prefix cache puts one page into two slots' tables: where
        # it can, and the decode program walks its pages, the program is
        # compiled in the form that walks a shared run once.
        self._shared_walk = self._cache is not None \
            and shares_walked_pages(model_config)
        #: The names of the counters behind the decode step's tokens.
        self._counter_keys = counter_keys(model_config, self._shared_walk)
        self._adapter_evictions_seen = 0
        # ONE device-resident PRNG key threads through every prefill and
        # decode call (each program splits and returns the successor):
        # host-side fold_in per step costs more than the decode math.
        # Sampling is therefore seeded per ENGINE, not per request.
        self._d_key = jax.random.PRNGKey(seed)
        b = cfg.batch_slots
        self.slots: List[Optional[_Request]] = [None] * b
        # Host mirrors are the rebuild source; the device copies below are
        # what decode consumes.  Admission/eviction/prefill mutate the
        # mirrors and mark them dirty; steady-state decode advances
        # tokens/lengths ON DEVICE and never re-uploads.
        self._page_tables = np.full((b, self.maxp), self.scratch, np.int32)
        # The rings never change hands, so their tables go up once.
        self._ring_tables = np.arange(
            self.ring_scratch, dtype=np.int32).reshape(b, self.ring)
        self._d_ring_tables = (jax.numpy.asarray(self._ring_tables)
                               if self.ring else None)
        self._seq_lens = np.zeros((b,), np.int32)
        # The decode program appends its counters to the tokens it returns
        # (paged.counter_keys): the mirror has their room, so that what
        # goes up has the shape of what comes back.
        self._tokens = np.zeros((b + len(self._counter_keys),), np.int32)
        self._active = np.zeros((b,), bool)
        self._temps = np.zeros((b,), np.float32)
        self._adapter_slots = np.full((b,), self.adapter_pool.zero_slot,
                                      np.int32)
        self._dirty = True
        #: The decode step dispatched and not yet read (one at most).
        self._inflight: Optional[_Step] = None
        self._d_tokens = self._d_page_tables = None
        self._d_seq_lens = self._d_active = self._d_temps = None
        self._d_adapter_slots = None
        self.step_count = 0
        self._req_counter = 0
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        # Weighted-fair admission: one FIFO per tenant, picked by lowest
        # virtual finish time (classic WFQ — a tenant's vtime advances by
        # cost/weight per admitted request, clamped to the global vclock
        # so idle tenants can't bank unbounded credit).
        self._queues: Dict[str, List[_Request]] = {}
        self._vtime: Dict[str, float] = {}
        self._vclock = 0.0
        self._tenants: Dict[str, Dict[str, Any]] = {}
        # Control ops (adapter registration, cache clear) marshalled onto
        # the loop thread: it owns the pools the ops touch.
        self._control: List[Any] = []
        self._stop = False
        self.completed = 0
        self.shed = 0
        self.cancelled_count = 0
        # Instruments hoisted off the request path (registry lock).
        self._m_tokens = get_counter(
            "ray_tpu_gen_tokens_total",
            "Decoded tokens emitted by the inference engine")
        self._m_prefill = get_counter(
            "ray_tpu_gen_prefill_tokens_total",
            "Prompt tokens prefilled into the paged KV cache")
        self._m_pages = get_gauge(
            "ray_tpu_gen_kv_pages_in_use",
            "KV cache pages currently allocated to sequences",
            tag_keys=("pid",))
        self._m_queue = get_gauge(
            "ray_tpu_serve_engine_queue_depth",
            "Requests waiting for a batch slot", tag_keys=("pid",))
        self._m_active = get_gauge(
            "ray_tpu_serve_engine_active_seqs",
            "Sequences decoding in batch slots", tag_keys=("pid",))
        self._m_shed = get_counter(
            "ray_tpu_serve_engine_shed_total",
            "Requests rejected by admission control (overload)")
        self._m_completed = get_counter(
            "ray_tpu_serve_engine_completed_total",
            "Requests decoded to completion")
        self._m_cancelled = get_counter(
            "ray_tpu_serve_engine_cancelled_total",
            "Requests cancelled mid-flight (pages reclaimed)")
        self._m_ttft = get_histogram(
            "ray_tpu_serve_engine_ttft_seconds",
            "Submit-to-first-token latency",
            boundaries=(0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10))
        self._m_itl = get_histogram(
            "ray_tpu_serve_engine_itl_seconds",
            "Inter-token latency during decode",
            boundaries=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                        0.25, 1))
        self._m_pc_hits = get_counter(
            "ray_tpu_serve_prefix_cache_hits_total",
            "Prompts whose prefill reused cached KV prefix pages")
        self._m_pc_shared = get_gauge(
            "ray_tpu_serve_prefix_cache_pages_shared",
            "KV pages currently held by more than one owner",
            tag_keys=("pid",))
        self._m_adapter_evict = get_counter(
            "ray_tpu_serve_adapter_evictions_total",
            "LoRA adapters evicted from the device-resident pool")
        self._m_tenant_shed = get_counter(
            "ray_tpu_serve_tenant_shed_total",
            "Requests shed by weighted-fair admission, by tenant",
            tag_keys=("tenant",))
        self._m_stall = get_counter(
            "ray_tpu_engine_stall_seconds_total",
            "Decode-loop seconds spent stalled on admission prefills")
        # Recent TTFTs feeding the controller's SLO autoscaling signal.
        import collections

        self._ttft_recent = collections.deque(maxlen=cfg.ttft_window)
        import os

        self._pid_tags = {"pid": str(os.getpid())}
        # Flight recorder: engine identity + per-step deltas and the
        # recent step-wall / stall windows behind slo_signals jitter.
        self.engine_id = f"{os.getpid()}.{next(_ENGINE_SEQ)}"
        self._step_walls = collections.deque(maxlen=max(16, cfg.step_window))
        self._stall_events = collections.deque(
            maxlen=max(16, cfg.step_window))  # (wall_time, stall_s)
        self._evicted_total = 0
        # The loop's own time account (rides on the step record, so
        # step_record switches it): the idle seconds between two records,
        # the record being filled (_rec), and where the previous record
        # ended (t0 + wall_s, as recorded).
        self._gap_acct: Optional[Dict[str, float]] = (
            {} if cfg.step_record else None)
        self._acct: Optional[_Account] = None
        self._prev_end = round(time.perf_counter(), 6)
        self._starved = _Starved()
        self._held = _Held()
        # A token's way out (_record_step): the wake seconds the loop has
        # taken off live requests since the last record, those that ended
        # streams handed over (a deque: appended once a request, by its
        # consumer, and drained by every record), and the process's
        # direct-stream counters as the last record read them.
        self._wake_s = 0.0
        self._wake_late: "collections.deque[float]" = collections.deque()
        self._stream_counts = direct_stream_counts()
        # Device-memory attribution: the engine owns the big allocations,
        # so it names them for util/devmem snapshots.  Weights bytes are
        # static; pool/adapter lambdas chase the live arrays (donation
        # replaces them every step).
        from ..util import devmem

        self._weights_bytes = sum(
            int(getattr(x, "nbytes", 0))
            for x in jax.tree_util.tree_leaves(params))
        devmem.register_pool("model_weights", lambda: self._weights_bytes)
        devmem.register_pool("kv_pool", lambda: sum(
            int(a.nbytes) for a in self.pools.values()))
        devmem.register_pool("adapter_pool", lambda: sum(
            int(a.nbytes) for a in self.adapter_pool.arrays.values()))
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="llm-engine")
        self._thread.start()

    def _new_pools(self):
        from ..models.paged import init_paged_pools

        return init_paged_pools(self.model_config, self.config.pool_pages,
                                self.config.page_size, self.ring_scratch,
                                self.config.batch_slots)

    def _hold_pages(self, pages: List[int], by: int) -> None:
        """A slot starts (``by`` 1) or stops (-1) decoding over ``pages``.
        Only where the decode step counts its rows and the prefix cache
        can share a page is there anything to keep."""
        if self._cache is None or "kv_rows_live" not in self._counter_keys:
            return
        holders = self._page_holders
        for p in pages:
            was = holders.get(p, 0)
            # Past the first holder, each one reads rows another reads too.
            self._shared_dups += max(0, was + by - 1) - max(0, was - 1)
            if was + by:
                holders[p] = was + by
            else:
                del holders[p]

    def _ring_pages_held(self) -> int:
        """Ring pages that hold a live sequence's rows: a sequence fills
        its slot's ring up to its own length and no further."""
        return sum(min(self.ring, len(r.pages))
                   for r in self.slots if r is not None)

    def _pages_used(self) -> int:
        return self.allocator.used_count + self._ring_pages_held()

    # ------------------------------------------------------------- client API

    def submit(self, prompt_tokens, max_new_tokens: int = 16,
               temperature: float = 0.0,
               stop_token: Optional[int] = None,
               adapter: Optional[str] = None,
               tenant: str = "default",
               weight: float = 1.0) -> TokenStream:
        """Queue one sequence; returns its token stream.

        ``adapter`` names a registered LoRA (None = base model);
        ``tenant``/``weight`` place the request in weighted-fair
        admission.  Overload sheds the HEAVIEST tenant's newest queued
        request with :class:`EngineOverloadedError` — when that is the
        submitter itself the error raises here, otherwise it lands on
        the victim's stream.  A light tenant is never shed by a heavy
        one's burst."""
        prompt = np.asarray(prompt_tokens, np.int32).reshape(-1)
        if prompt.size == 0 or prompt.size > self.config.max_prompt_len:
            raise ValueError(
                f"prompt length {prompt.size} outside (0, "
                f"{self.config.max_prompt_len}]")
        max_new = min(int(max_new_tokens), self.config.max_new_tokens_cap)
        if max_new <= 0:
            raise ValueError("max_new_tokens must be positive")
        if weight <= 0:
            raise ValueError("tenant weight must be positive")
        if adapter is not None and not self.adapter_pool.has(adapter):
            raise KeyError(f"adapter {adapter!r} is not registered")
        need = math.ceil((prompt.size + max_new) / self.config.page_size)
        if need > self.allocator.total:
            raise ValueError(
                f"request needs {need} KV pages but the pool holds only "
                f"{self.allocator.total} — raise EngineConfig.num_pages")
        with self._lock:
            if self._stop:
                raise RuntimeError("engine is shut down")
            self._req_counter += 1
            req = _Request(self._req_counter, prompt, max_new,
                           float(temperature), stop_token)
            req.tenant = tenant
            req.weight = float(weight)
            req.adapter = adapter
            rec = self._tenant_rec(tenant)
            rec["weight"] = float(weight)
            rec["submitted"] += 1
            # Capture the submitter's trace context (the replica's
            # execution span in the serve path): the loop thread emits
            # this request's queue/prefill/decode spans against it.
            from ..util import tracing

            req.trace_ctx = tracing.context_for_submit()
            self._queues.setdefault(tenant, []).append(req)
            victim: Optional[_Request] = None
            if self._queued_total() > self.config.max_queue:
                victim = self._shed_locked()
            self._m_queue.set(self._queued_total(), tags=self._pid_tags)
            self._wake.notify()
            if victim is req:
                raise EngineOverloadedError(
                    f"engine queue full ({self.config.max_queue} "
                    f"waiting); tenant {tenant!r} is the heaviest")
            if victim is not None:
                victim.finished = True
                victim.out_q.put((
                    "err", EngineOverloadedError(
                        f"shed by weighted-fair admission (tenant "
                        f"{victim.tenant!r} heaviest at overload)"),
                    self.step_count))
        return TokenStream(self, req)

    def _tenant_rec(self, tenant: str) -> Dict[str, Any]:
        rec = self._tenants.get(tenant)
        if rec is None:
            rec = self._tenants[tenant] = {
                "submitted": 0, "completed": 0, "shed": 0,
                "cancelled": 0, "weight": 1.0,
            }
        return rec

    def _queued_total(self) -> int:
        return sum(len(q) for q in self._queues.values())

    @staticmethod
    def _req_cost(req: _Request) -> float:
        # Token work (prefill + worst-case decode) as the fair-share unit.
        return float(req.prompt.size + req.max_new)

    def _shed_locked(self) -> _Request:
        """Pick the victim: the tenant with the largest queued work per
        unit weight loses its NEWEST queued request (tail drop — oldest
        requests are closest to their SLO deadline)."""
        heaviest, load = None, -1.0
        for t, q in self._queues.items():
            if not q:
                continue
            w = max(self._tenants[t]["weight"], 1e-9)
            l = sum(self._req_cost(r) for r in q) / w
            if l > load:
                heaviest, load = t, l
        victim = self._queues[heaviest].pop()
        rec = self._tenants[heaviest]
        rec["shed"] += 1
        self.shed += 1
        self._m_shed.inc(1)
        self._m_tenant_shed.inc(1, tags={"tenant": heaviest})
        return victim

    def cancel(self, req: _Request) -> None:
        """Idempotent; a finished request is a no-op.  Pages return to
        the free list at the loop's next step boundary."""
        req.cancelled.set()
        with self._lock:
            self._wake.notify()

    def shutdown(self) -> None:
        with self._lock:
            self._stop = True
            self._wake.notify()
        self._thread.join(timeout=10)
        from ..util import devmem, steprec

        for name in ("model_weights", "kv_pool", "adapter_pool"):
            devmem.unregister_pool(name)
        steprec.dump_black_box(force=True)  # graceful exits get a fresh box

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            queued = self._queued_total()
            tenants = {
                t: dict(rec, queued=len(self._queues.get(t, [])))
                for t, rec in self._tenants.items()
            }
        active = sum(1 for s in self.slots if s is not None)
        from ..models.moe import grouped_form
        from ..models.paged import (decode_attention_form,
                                    prefill_attention_form,
                                    recurrent_decode_form,
                                    recurrent_prefill_form, trace_count)

        return {
            "steps": self.step_count,
            "active_seqs": active,
            "queued": queued,
            # Of both kinds of cache together (a model without window
            # layers has the one); the ring's own under "window_pages".
            "free_pages": (self.allocator.free_count
                           + self.ring_scratch - self._ring_pages_held()),
            "total_pages": self.allocator.total + self.ring_scratch,
            "shared_pages": self.allocator.shared_count,
            "completed": self.completed,
            "shed": self.shed,
            "cancelled": self.cancelled_count,
            "decode_traces": trace_count("decode"),
            "prefill_traces": trace_count("prefill"),
            "prefill_prefix_traces": trace_count("prefill_prefix"),
            "tenants": tenants,
            "prefix_cache": (self._cache.stats()
                             if self._cache is not None else None),
            # Why it is off where the configuration asked for it.
            "prefix_cache_off": (self._cache_off
                                 if self.config.prefix_cache else None),
            # The recurrent state of a model with recurrent layers:
            # a slot's own, in the pools beside the pages.
            "state": ({"layers": self._state_layers,
                       "slot_bytes": self._state_bytes,
                       "total_bytes": (self._state_bytes
                                       * self.config.batch_slots)}
                      if self._state_layers else None),
            # The form the decode program steps that state in: "kernel"
            # (ops/ssm_decode.py: one pass over the pool where it lies) or
            # "jnp" (the module's recurrent form on a layer's slice).
            "recurrent_decode": recurrent_decode_form(self.model_config),
            # And the form the prefill programs carry it over a call's rows
            # in: "kernel" (ops/ssm_scan.py: one call a layer, the real
            # rows only) or "scan" (the module's chunk form, the bucket).
            "recurrent_prefill": recurrent_prefill_form(self.model_config),
            "window_pages": ({"ring_entries": self.ring,
                              "free": (self.ring_scratch
                                       - self._ring_pages_held()),
                              "total": self.ring_scratch}
                             if self.ring else None),
            # The form a routed model's decode program holds its experts'
            # products in: "stream" (ops/grouped_ffn.py) or "ragged_dot";
            # and its prefill programs, by their largest bucket: "rows"
            # (the same file's row-block kernel) or "ragged_dot".
            "grouped_ffn": (
                grouped_form(self.model_config, self.config.batch_slots)
                if "experts_hit" in self._counter_keys else None),
            "grouped_ffn_prefill": (
                grouped_form(self.model_config,
                             self.config.prefill_buckets()[-1])
                if "experts_hit" in self._counter_keys else None),
            # The form the decode program attends its cache in: "walk" (a
            # kernel over the live pages: ops/latent_decode.py,
            # ops/paged_decode.py) or "gather".
            # "walk+shared": a run of pages that several slots hold is
            # fetched once for all of them (the prefix cache is on).
            "decode_attention": decode_attention_form(self.model_config,
                                                      self._shared_walk),
            # And the prefills: "walk" (ops/paged_prefill.py: a block of
            # query rows over the live pages, no scores in HBM; every call
            # then goes through the suffix program, a first one at 0).
            "prefill_attention": prefill_attention_form(self.model_config),
            "adapters": self.adapter_pool.stats(),
        }

    #: Window over which admission-stall seconds are summed for the
    #: autoscaler's stall-pressure signal.
    STALL_WINDOW_S = 30.0

    def slo_signals(self) -> Dict[str, Any]:
        """Queue-depth / TTFT snapshot for the controller's SLO-driven
        autoscaling (cheap: host counters plus a tiny sort), extended
        with the step ring's stall and jitter signals: seconds the decode
        loop spent stalled on admission prefills inside the last
        ``STALL_WINDOW_S``, and decode-step p99 jitter (p99 - p50 step
        wall).  The autoscaler reacts to stall pressure even while TTFT
        still holds — a saturated engine stalls before it breaches."""
        ttfts = sorted(self._ttft_recent)

        def pct(vals: List[float], p: float) -> float:
            if not vals:
                return 0.0
            return vals[min(len(vals) - 1, int(p * len(vals)))]

        with self._lock:
            queued = self._queued_total()
            tenant_queues = {t: len(q)
                             for t, q in self._queues.items() if q}
        now = time.time()
        stall_s = sum(s for (t, s) in list(self._stall_events)
                      if now - t <= self.STALL_WINDOW_S)
        walls = sorted(self._step_walls)
        p50, p99 = pct(walls, 0.50), pct(walls, 0.99)
        return {
            "queue_depth": queued,
            "active_seqs": sum(1 for s in self.slots if s is not None),
            "batch_slots": self.config.batch_slots,
            "ttft_p50_s": pct(ttfts, 0.50),
            "ttft_p90_s": pct(ttfts, 0.90),
            "ttft_count": len(ttfts),
            "completed": self.completed,
            "shed": self.shed,
            "stall_s_window": stall_s,
            "stall_window_s": self.STALL_WINDOW_S,
            "stall_frac": min(1.0, stall_s / self.STALL_WINDOW_S),
            "step_p50_s": p50,
            "step_p99_s": p99,
            "step_jitter_p99_s": max(0.0, p99 - p50),
            "tenant_queues": tenant_queues,
        }

    def _run_on_loop(self, fn, timeout: float = 30.0):
        """Run ``fn`` on the loop thread (it owns pools/cache/adapters)
        and return its result.  Raises what ``fn`` raised."""
        done = threading.Event()
        box: Dict[str, Any] = {}

        def task():
            try:
                box["r"] = fn()
            except BaseException as e:  # noqa: BLE001 — re-raised below
                box["e"] = e
            finally:
                done.set()

        with self._lock:
            if self._stop:
                raise RuntimeError("engine is shut down")
            self._control.append(task)
            self._wake.notify()
        if not done.wait(timeout):
            raise TimeoutError("engine loop did not run control op")
        if "e" in box:
            raise box["e"]
        return box.get("r")

    def register_adapter(self, name: str, source: Any) -> None:
        """Register (or replace) a LoRA adapter.  Replacement drops any
        resident copy AND the adapter's prefix-cache tree — its cached V
        deltas are stale."""

        from ..models import block

        if block.is_latent(self.model_config):
            raise ValueError(
                "a model with latent attention takes no LoRA adapter: the "
                "deltas on wq and wv have no counterpart among its "
                "projections")

        def do():
            self.adapter_pool.register(name, source)
            if self._cache is not None:
                self._cache.drop_adapter(name, self.allocator)

        self._run_on_loop(do)

    def clear_prefix_cache(self) -> int:
        """Release every cache-held page ref (tests/bench drain to a
        balanced free list; compiled programs stay warm)."""
        if self._cache is None:
            return 0
        return self._run_on_loop(
            lambda: self._cache.clear(self.allocator))

    def warmup(self) -> List[Dict[str, Any]]:
        """Compile the decode program and every prefill bucket up front
        (one dummy sequence per bucket) so serving traffic never pays a
        trace.  Returns one row a program warmed: its wall seconds and,
        from JAX's own monitoring events (``devmem.compile_totals``
        differenced around it), the seconds tracing, lowering and in the
        backend's compile, and whether the persistent cache served it."""
        from ..util import devmem

        rows: List[Dict[str, Any]] = []

        def warm(program: str, bucket: Optional[int], fn) -> None:
            before, t0 = devmem.compile_totals(), time.perf_counter()
            fn()
            wall_s = time.perf_counter() - t0
            d = {k: v - before[k]
                 for k, v in devmem.compile_totals().items()}
            rows.append({
                "program": program, "bucket": bucket,
                "wall_s": round(wall_s, 3),
                "trace_s": round(d["trace_s"], 3),
                "lower_s": round(d["lower_s"], 3),
                "compile_s": round(d["compile_s"], 3),
                "cache_hit": bool(d["cache_hits"]
                                  and not d["cache_misses"]),
                "cache_read_s": round(d["cache_read_s"], 3),
            })

        def run(prompt, max_new: int) -> None:
            for _ in self.submit(prompt, max_new_tokens=max_new):
                pass

        # A fresh engine's warmup is a legitimate compile phase: stand
        # the sentinel down while it traces (a previous engine in this
        # process may have armed with different geometry), re-arm below.
        from ..devtools import jitguard
        jitguard.disarm()
        buckets = self.config.prefill_buckets()
        # The first token comes from PREFILL — the decode program only
        # compiles once a second token is needed.
        warm("prefill", buckets[0], lambda: run([1], 1))
        warm("decode", None, lambda: run([1], 2))
        for bucket in buckets[1:]:
            n = min(bucket, self.config.max_prompt_len)
            if self._cache is not None:
                # The previous bucket's ones-prompt cached its pages; a
                # hit here would route to the suffix path and skip the
                # cold prefill compile this bucket exists to pay.
                self.clear_prefix_cache()
            warm("prefill", bucket,
                 lambda n=n: run(np.ones((n,), np.int32), 1))
        cached = self._cache is not None \
            and self.config.max_prompt_len >= self.config.page_size
        if cached:
            # Re-run the largest prompt: it hits the pages the line above
            # cached, compiling the COW copy + suffix-prefill path too.
            n = self.config.max_prompt_len
            warm("prefix_hit", n,
                 lambda: run(np.ones((n,), np.int32), 1))
            self.clear_prefix_cache()
        # One control op per program: each waits for ONE cold compile,
        # under the bound a cold prefill gets through its stream (at
        # 1B-parameter size a bucket compiles for tens of seconds).
        import functools

        compile_s = self.config.stream_timeout_s
        if cached or self.config.max_prompt_len > buckets[-1]:
            # The re-run traces the prefix path only for the ONE suffix
            # bucket (and COW divergence) its geometry happens to hit —
            # compile every suffix bucket and the COW copy explicitly
            # (dummy tokens into the scratch page; page 0 onto itself)
            # so no real prefix hit after warmup pays a trace.  A prompt
            # past the largest bucket runs the same program, a chunk a
            # call, and its last chunk can be of any bucket.
            def _warm_suffix_bucket(b):
                import jax.numpy as jnp

                from ..models.paged import paged_prefill_prefix
                pt = jnp.full((self.maxp,), self.scratch, jnp.int32)
                ring = jnp.full((self.ring,), self.ring_scratch,
                                jnp.int32) if self.ring else None
                zero = jnp.asarray(0, jnp.int32)
                # Slot 0's state is written: its next admission's first
                # call starts from zeros whatever is there.
                _, self._d_key, self.pools = paged_prefill_prefix(
                    self.model_config, self.params, self.pools,
                    self.adapter_pool.arrays, jnp.zeros((1, b), jnp.int32),
                    zero, jnp.asarray(1, jnp.int32), pt, zero,
                    jnp.asarray(0.0, jnp.float32), self._d_key, ring,
                    zero if self._state_layers else None)

            for b in buckets:
                warm("prefill_prefix", b, lambda b=b: self._run_on_loop(
                    functools.partial(_warm_suffix_bucket, b), compile_s))
        if cached:
            def _warm_cow_copy():
                import jax.numpy as jnp

                from ..models.paged import copy_page
                zero = jnp.asarray(0, jnp.int32)
                self.pools = copy_page(self.pools, zero, zero)

            warm("copy_page", None,
                 lambda: self._run_on_loop(_warm_cow_copy, compile_s))
        # Compile the adapter-load path too (zero payload into the zero
        # slot): the first real LoRA registration after warmup must be an
        # execution, not a fresh trace.
        warm("adapter_load", None, lambda: self._run_on_loop(
            self.adapter_pool.warmup_compile, self.config.stream_timeout_s))
        # Recompile sentinel (RT_DEBUG_JIT=1): freeze every program's
        # trace count — decode, each prefill bucket, the COW/suffix path,
        # adapter loads — so any post-warmup trace raises RecompileError
        # at the stray call site instead of silently paying a compile in
        # the step loop.  No-op when the env flag is off.
        jitguard.arm()
        return rows

    # ---------------------------------------------------------------- loop

    def _bucket_len(self, n: int) -> int:
        for b in self.config.prefill_buckets():
            if b >= n:
                return b
        return self.config.prefill_buckets()[-1]

    def _pick_tenant_locked(self) -> Optional[str]:
        """Lowest-virtual-time tenant with queued work (WFQ pick)."""
        best, best_v = None, None
        for t, q in self._queues.items():
            if not q:
                continue
            v = max(self._vtime.get(t, 0.0), self._vclock)
            if best_v is None or v < best_v:
                best, best_v = t, v
        return best

    def _admit_locked(self) -> List[_Request]:
        """Move queued requests into free slots (called under the lock).
        A request is admitted whenever a slot AND its pages are free.
        Tenants are drained in weighted-fair order; each admission pins
        its prefix-cache match (refcounted shares) and allocates only the
        pages the cache can't cover, evicting cold cache leaves first
        when the pool runs dry."""
        admitted: List[_Request] = []
        for slot in range(self.config.batch_slots):
            if self.slots[slot] is not None:
                continue
            tenant = self._pick_tenant_locked()
            if tenant is None:
                continue
            req = self._queues[tenant][0]
            if not self.adapter_pool.can_acquire(req.adapter):
                break  # every adapter slot pinned: wait for an eviction
            need_total = math.ceil((req.prompt.size + req.max_new)
                                   / self.config.page_size)
            match = None
            shared: List[int] = []
            if self._cache is not None:
                match = self._cache.lookup(req.adapter, req.prompt)
                shared = match.pages
                # Pin the match BEFORE any cache eviction below can free
                # the very pages it names.
                self._cache.claim(match, self.allocator)  # rt-owns: prefix_claim
            need = need_total - len(shared)
            pages = self.allocator.alloc(need)
            if pages is None and self._cache is not None:
                deficit = need - self.allocator.free_count
                if self._cache.evict_leaves(deficit, self.allocator):
                    pages = self.allocator.alloc(need)
            if pages is None:
                if match is not None:  # roll the claim back
                    held = list(shared)
                    if match.cow_src is not None:
                        held.append(match.cow_src)
                    if held:
                        self.allocator.free(held)
                break  # pool pressure: leave queued, retry next step
            self._queues[tenant].pop(0)
            # Reserve (pin) the adapter slot NOW, host-only: requests
            # admitted in this same round must see each other's pins, or
            # a wave of distinct adapters could over-commit the slots the
            # can_acquire check saw free.  Weights load at prefill.
            req.adapter_slot = self.adapter_pool.reserve(req.adapter)
            v_start = max(self._vtime.get(tenant, 0.0), self._vclock)
            w = max(req.weight, 1e-9)
            self._vtime[tenant] = v_start + self._req_cost(req) / w
            self._vclock = v_start
            req.admit_t = time.perf_counter()
            req.pages = shared + pages
            req.match = match
            if match is not None and match.cow_src is not None:
                req.cow_ref = match.cow_src
            req.cache_hit_len = match.prefix_len if match else 0
            pt = np.full((self.maxp,), self.scratch, np.int32)
            pt[:need_total] = req.pages
            req.page_table = pt
            req.slot = slot
            self.slots[slot] = req
            admitted.append(req)
        if admitted:
            self._m_queue.set(self._queued_total(), tags=self._pid_tags)
        return admitted

    def _emit_req_span(self, req: _Request, name: str, start: float,
                       end: float, **attrs) -> None:
        """One request-stage span (queue / prefill / decode), parented to
        the submitter's context.  Buffered emission (util/tracing ring) —
        the decode loop never pays a head RPC for tracing."""
        if req.trace_ctx is None or start <= 0:
            return
        from ..util import tracing

        tracing.emit_span(
            tracing.make_span(req.trace_ctx, name, start, end, **attrs))

    def _evict(self, slot: int, reason: str) -> None:
        req = self.slots[slot]
        assert req is not None
        # Decode-lifetime span: first token -> eviction.  Token count,
        # TTFT, and mean ITL ride as attrs so per-request latency
        # attribution is derivable from the span tree alone.
        self._emit_req_span(
            req, "engine:decode",
            req.wall(req.first_token_t or req.admit_t),
            time.time(), tokens=req.generated, reason=reason,
            ttft_s=round(req.first_token_t - req.submit_t, 6)
            if req.first_token_t is not None else None,
            mean_itl_s=round((req.last_token_t - req.first_token_t)
                             / (req.generated - 1), 6)
            if req.generated > 1 else None)
        if self._active[slot]:
            self._hold_pages(req.pages, -1)
        self.allocator.free(req.pages)  # refcounted: shared prefix
        req.pages = []                  # pages may stay cached
        if req.cow_ref is not None:     # evicted before the COW copy ran
            self.allocator.free([req.cow_ref])
            req.cow_ref = None
        if req.adapter_slot >= 0:
            self.adapter_pool.release(req.adapter)
            req.adapter_slot = -1
        req.finished = True
        self._evicted_total += 1
        self.slots[slot] = None
        self._page_tables[slot, :] = self.scratch
        self._seq_lens[slot] = 0
        self._tokens[slot] = 0
        self._active[slot] = False
        self._temps[slot] = 0.0
        self._adapter_slots[slot] = self.adapter_pool.zero_slot
        self._dirty = True
        rec = self._tenant_rec(req.tenant)
        if reason == "cancelled":
            self.cancelled_count += 1
            rec["cancelled"] += 1
            self._m_cancelled.inc(1)
        elif reason in ("complete", "stop"):
            self.completed += 1
            rec["completed"] += 1
            self._m_completed.inc(1)
        self._take_wake(req)  # the last look: the stream's end follows
        if reason == "shutdown":
            # Loudly: a truncated generation must not look complete.
            req.out_q.put(("err", RuntimeError(
                "engine shut down mid-generation"), self.step_count))
        else:
            req.out_q.put(("done", reason, self.step_count))

    def _prefill(self, req: _Request) -> None:
        """Run one admitted sequence's prompt through the bucketed
        prefill programs and emit its first token (TTFT point).  A
        prefix-cache hit copies the COW page (mid-page divergence) and
        prefills only the uncached suffix; a prompt longer than the
        largest bucket goes through in chunks."""
        # This request's own account: the prefill's phases go on its
        # first_tokens entry, not among the step's.
        acct: Dict[str, float] = {}
        t0 = time.perf_counter()
        bucket, chunks, routing = self._prefill_body(req, acct)
        n, prefix_len = int(req.prompt.size), int(req.cache_hit_len)
        entry = {
            "queue_s": round(req.admit_t - req.submit_t, 6),
            "prefill_s": round(acct[PH_PREFILL], 6),
            "prefill_wait_s": round(acct[PH_PREFILL_WAIT], 6),
            "ttft_s": round(req.first_token_t - req.submit_t, 6),
            "prompt": n, "bucket": bucket, "cached": prefix_len,
            "chunks": chunks,
            # What this admission left the chip standing still, of the
            # open record's starved seconds: up to its first call's return
            # here, after its first token in _dispatch_step.
            "starved_s": round(acct["starved"], 6),
            **routing,  # a routed model's counters of this prefill
        }
        rec = self._rec()
        if rec is not None:
            rec.first_tokens.append(entry)
        # The request's spans, from the same stamps as the entry: queue
        # wait (submit -> admission into a batch slot) and the prefill;
        # bucket and cached-prefix attrs make padding waste and cache
        # effectiveness readable straight off the trace.
        if req.trace_ctx is not None:
            self._emit_req_span(req, "engine:queue", req.submit_wall,
                                req.submit_wall + entry["queue_s"],
                                prompt_len=n)
            start = req.wall(t0)
            self._emit_req_span(req, "engine:prefill", start,
                                start + entry["prefill_s"], bucket=bucket,
                                prompt_len=n, cached_prefix=prefix_len)

    def _prefill_body(self, req: _Request, acct: Dict[str, float]
                      ) -> Tuple[int, int, Dict[str, int]]:
        """The work of :meth:`_prefill`; returns the padded rows run, the
        program calls they took (the chunks) and, of a model whose FFN is
        routed, the prefill's routing counters.  ``PH_PREFILL`` is
        annotated once a call: the first holds the adapter's weights and a
        prefix hit's page copy too, the last the wait for the first token
        and the bookkeeping after it."""
        import jax.numpy as jnp

        from ..models.paged import (attn_pairs, paged_prefill,
                                    paged_prefill_prefix,
                                    prefill_attention_form,
                                    recurrent_rows_walked)

        n = int(req.prompt.size)
        prefix_len = int(req.cache_hit_len)
        # What is not cached, a chunk (the largest bucket) at a time: each
        # call writes its rows' K/V and attends over what is cached before
        # them; the last one's token is the request's first.
        chunk = self.config.prefill_buckets()[-1]
        starts = list(range(prefix_len, n, chunk))
        rows, walked, firsts, routing = 0, 0, [], {}
        # Where the prefills walk the live pages, a prompt's first rows are
        # a suffix behind nothing: the same kernel at a first position of
        # 0, so one program a bucket serves both, and the cold program
        # (its dense scores) is never compiled there.
        walks = prefill_attention_form(self.model_config) == "walk"
        for start in starts:
            end = min(start + chunk, n)
            with self._phase(PH_PREFILL, acct) as phase:
                if start == prefix_len:  # the first call's share
                    self._prefill_prepare(req)
                    aid = jnp.asarray(req.adapter_slot, jnp.int32)
                    temp = jnp.asarray(req.temperature, jnp.float32)
                    table = jnp.asarray(req.page_table)
                    ring = jnp.asarray(self._ring_tables[req.slot]) \
                        if self.ring else None
                    state = jnp.asarray(req.slot, jnp.int32) \
                        if self._state_layers else None
                s_pad = self._bucket_len(end - start)
                rows += s_pad
                if self._state_layers:
                    walked += recurrent_rows_walked(self.model_config,
                                                    s_pad, end - start)
                toks = np.zeros((1, s_pad), np.int32)
                toks[0, :end - start] = req.prompt[start:end]
                if start or walks:
                    first, self._d_key, self.pools = paged_prefill_prefix(
                        self.model_config, self.params, self.pools,
                        self.adapter_pool.arrays, jnp.asarray(toks),
                        jnp.asarray(start, jnp.int32),
                        jnp.asarray(end, jnp.int32), table, aid, temp,
                        self._d_key, ring, state)
                else:
                    first, self._d_key, self.pools = paged_prefill(
                        self.model_config, self.params, self.pools,
                        self.adapter_pool.arrays, jnp.asarray(toks),
                        jnp.asarray(end, jnp.int32), table, aid, temp,
                        self._d_key, ring, state)
                if start == prefix_len:  # the chip has work again
                    self._starved.spend(PH_PREFILL, phase.t0,
                                        time.perf_counter())
                    acct["starved"] = self._starved.stop()
                firsts.append(first)
                if end == n:
                    routing = self._finish_prefill(req, firsts, acct)
        if walks:
            # The pairs the kernel's real rows could see, over the calls
            # (what paged_prefill_roofline.swa and
            # latent_prefill_roofline.mla divide their seconds by).
            routing["attn_pairs"] = sum(
                attn_pairs(self.model_config, start, min(start + chunk, n))
                for start in starts)
        if self._state_layers:
            # What the recurrent layers' chunk form went over: the real
            # positions, and the rows behind them that the form in use
            # WALKED all the same, as the model code says (the scan: the
            # calls' buckets, each row a position's step that leaves the
            # state alone; the kernel of a TPU's state-space layers: none).
            routing["scan_rows"] = n - prefix_len
            routing["scan_rows_padded"] = walked - (n - prefix_len)
        return rows, len(starts), routing

    def _prefill_prepare(self, req: _Request) -> None:
        """Before a request's first prefill call: the adapter's weights,
        and the private copy of a prefix hit's divergent page."""
        import jax.numpy as jnp

        from ..models.paged import copy_page

        # Admission reserved (pinned) the slot; materialize the weights
        # if this is the adapter's first use since eviction.
        self.adapter_pool.ensure_loaded(req.adapter)
        ev = self.adapter_pool.evictions
        if ev > self._adapter_evictions_seen:
            self._m_adapter_evict.inc(ev - self._adapter_evictions_seen)
            self._adapter_evictions_seen = ev
        n, prefix_len = int(req.prompt.size), int(req.cache_hit_len)
        if prefix_len > 0:
            match = req.match
            if match.cow_src is not None:
                # Private copy of the divergent page, then drop the
                # claim's extra ref on the source.
                dest = int(req.page_table[len(match.pages)])
                self.pools = copy_page(
                    self.pools, jnp.asarray(match.cow_src, jnp.int32),
                    jnp.asarray(dest, jnp.int32))
                self.allocator.free([req.cow_ref])
                req.cow_ref = None
            self._m_pc_hits.inc(1)
        self._m_prefill.inc(n - prefix_len)  # only the work actually done

    def _finish_prefill(self, req: _Request, firsts: List[Any],
                        acct: Dict[str, float]) -> Dict[str, int]:
        """Wait for the last prefill call of ``req`` (``firsts``: what each
        of its calls returned), stream its token and hand the slot to the
        decode step; returns the calls' routing counters, summed (the
        heaviest load: the largest)."""
        with annotation(PH_PREFILL_WAIT, acct) as wait:
            outs = [np.asarray(f).reshape(-1) for f in firsts]  # rt-sync-ok: THE prefill readback — the first token must reach the host to stream it
        self._starved.free(wait.t1)  # the step in flight was read before
        first = int(outs[-1][0])
        counters = np.stack([o[1:] for o in outs])
        routing = dict(zip(self._routing_keys, counters.sum(0).tolist()))
        if routing:
            heaviest = self._routing_keys.index("expert_load_max")
            routing["expert_load_max"] = int(counters[:, heaviest].max())
        n = int(req.prompt.size)
        # Cache every fully-frozen prompt page (decode appends past the
        # prompt, so pages wholly inside it never change again).
        if self._cache is not None:
            full = n // self.config.page_size
            if full > 0:
                self._cache.insert(
                    req.adapter, req.prompt[:full * self.config.page_size],
                    [int(p) for p in req.page_table[:full]],
                    self.allocator)
            self._m_pc_shared.set(self.allocator.shared_count,
                                  tags=self._pid_tags)
        now = time.perf_counter()
        req.length = n
        req.first_token_t = now
        req.last_token_t = now
        ttft = now - req.submit_t
        self._m_ttft.observe(ttft)
        self._ttft_recent.append(ttft)
        slot = req.slot
        self._page_tables[slot] = req.page_table
        self._seq_lens[slot] = n
        self._tokens[slot] = first
        self._active[slot] = True
        self._hold_pages(req.pages, 1)
        self._temps[slot] = req.temperature
        self._adapter_slots[slot] = req.adapter_slot
        self._dirty = True
        self._m_tokens.inc(1)
        self._emit_token(req, first, self.step_count, now)
        return routing

    def _take_wake(self, req: _Request) -> None:
        """Onto the open record: what ``req``'s consumer has added to its
        wake seconds since the loop last looked."""
        wake_s = req.wake_s
        self._wake_s += wake_s - req.wake_seen
        req.wake_seen = wake_s

    def _emit_token(self, req: _Request, token: int, step: int,
                    emitted: float) -> None:
        """Hand ``token`` to ``req``'s stream.  ``emitted`` is the
        ``time.perf_counter()`` at which the pass that emits it began (the
        token was on the host): it rides with the token, and the consumer
        ends the ``wake`` hop against it."""
        req.generated += 1
        req.out_q.put(("tok", token, step, emitted))
        if req.stop_token is not None and token == req.stop_token:
            self._evict(req.slot, "stop")
        elif req.generated >= req.max_new:
            self._evict(req.slot, "complete")

    def _fail_inflight(self, exc: BaseException) -> None:
        """A model-call failure must not kill the loop thread silently:
        every in-flight request gets the error on its stream, pages
        return to the free list, and the pools are rebuilt (a failed
        donated call may have invalidated them).  Queued requests stay
        queued — they retry against the fresh pool."""
        self._quiesce()
        self._acct = None  # its seconds fall to the next record's between_s
        now_wall = time.time()
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            self._emit_req_span(
                req, "engine:decode",
                req.wall(req.first_token_t or req.admit_t or req.submit_t),
                now_wall, tokens=req.generated, reason="error",
                error=repr(exc)[:200])
            self.allocator.free(req.pages)
            req.pages = []
            if req.cow_ref is not None:
                self.allocator.free([req.cow_ref])
                req.cow_ref = None
            if req.adapter_slot >= 0:
                self.adapter_pool.release(req.adapter)
                req.adapter_slot = -1
            req.finished = True
            self.slots[slot] = None
            self._take_wake(req)
            req.out_q.put(("err", exc, self.step_count))
        # The pools are rebuilt below, so every cached KV page and every
        # resident adapter slot is garbage: drop the tree's refs and
        # reset the adapter pool (the registry survives; adapters reload
        # on next acquire).
        if self._cache is not None:
            self._cache.clear(self.allocator)
        self.adapter_pool.reset()
        self._page_tables[:] = self.scratch
        self._seq_lens[:] = 0
        self._tokens[:] = 0
        self._active[:] = False
        self._page_holders.clear()  # no slot decodes: no page is held
        self._shared_dups = 0
        self._temps[:] = 0.0
        self._adapter_slots[:] = self.adapter_pool.zero_slot
        self._dirty = True
        self.pools = self._new_pools()

    def _phase(self, name: str, account: Optional[dict] = None) -> _Phase:
        return _Phase(name, account, self._starved)

    def _loop(self) -> None:
        while True:
            with self._phase(PH_ADMIT) as phase, self._lock:
                if self._inflight is None:  # the loop's start, after idling
                    self._starved.free(phase.t0)
                if self._stop:
                    break
                control, self._control = self._control, []
                # Reap cancellations first: queued cancels just drop,
                # in-flight cancels free pages before admission looks at
                # the pool.
                reaped = False
                for q in self._queues.values():
                    keep = []
                    for r in q:
                        if r.cancelled.is_set():
                            self.cancelled_count += 1
                            self._tenant_rec(r.tenant)["cancelled"] += 1
                            self._m_cancelled.inc(1)
                            r.out_q.put(
                                ("done", "cancelled", self.step_count))
                            reaped = True
                        else:
                            keep.append(r)
                    q[:] = keep
                if reaped:
                    self._m_queue.set(self._queued_total(),
                                      tags=self._pid_tags)
                for slot, req in enumerate(self.slots):
                    if req is not None and req.cancelled.is_set():
                        self._evict(slot, "cancelled")
                # A slot freed under the step in flight (a stop token, a
                # cancel) is still written for its previous owner by that
                # step: nothing is admitted this round, _run_step reads
                # the step, and the next round admits.
                admitted = ([] if self._inflight is not None and self._dirty
                            else self._admit_locked())
                active = sum(1 for s in self.slots if s is not None)
                if not admitted and active == 0 and not control \
                        and self._inflight is None:
                    self._m_active.set(0, tags=self._pid_tags)
                    self._m_pages.set(self._pages_used(),
                                      tags=self._pid_tags)
                    # Nothing to run is not starvation: this turn's seconds
                    # are idle_s, and nobody's.
                    self._starved.stop()
                    proc = time.process_time()
                    with annotation(PH_IDLE, self._gap_acct):
                        self._wake.wait(timeout=0.05)
                    self._held.proc_idle += time.process_time() - proc
                    continue
            # Model work runs OUTSIDE the lock: pools/slot arrays belong
            # to this thread; submit() only appends to the wait queue.
            # Control ops (adapter registration, cache clear) run here
            # for the same reason, and with no step in flight.
            try:
                if control and self._inflight is not None:
                    step, self._inflight = self._inflight, None
                    self._finish_step(step)
                for task in control:
                    task()
                self._run_step(admitted)
            except Exception as e:  # noqa: BLE001 — fail streams, not
                self._fail_inflight(e)  # the loop thread
        # Shutdown: fail queued + in-flight requests loudly, and unblock
        # any control-op waiters.  The device is quiet before a slot goes.
        self._quiesce()
        with self._lock:
            pending = [r for q in self._queues.values() for r in q]
            for q in self._queues.values():
                q.clear()
            control, self._control = self._control, []
            self._m_queue.set(0, tags=self._pid_tags)
        for task in control:
            task()
        for req in pending:
            req.out_q.put(("err", RuntimeError(
                "engine shut down before admission"), self.step_count))
        for slot, req in enumerate(self.slots):
            if req is not None:
                self._evict(slot, "shutdown")

    def _rec(self) -> Optional[_Account]:
        """The step record being filled (None with ``step_record`` off),
        opened here if the last one was closed."""
        if self._acct is None and self.config.step_record:
            self._acct = _Account(self)
        return self._acct

    def _quiesce(self) -> None:
        """Let go of the step in flight, once the device is done with it;
        nobody gets its tokens."""
        step, self._inflight = self._inflight, None
        if step is not None:
            try:
                np.asarray(step.tokens)  # rt-sync-ok: shutdown or failure, the device must be quiet before slots and pools go
            except Exception:  # noqa: BLE001 — its failure is being handled
                pass
        self._starved.free(time.perf_counter())

    def _may_run_ahead(self) -> bool:
        """Whether the step after the one in flight can be dispatched
        before that one is read: membership stands as the device has it
        (``_dirty`` false) and no slot's token budget ends at the step in
        flight, which the host knows by count.  Its pages were reserved at
        admission, so the step needs nothing else from the host."""
        return not self._dirty and all(
            req is None or req.max_new - req.generated > 1
            for req in self.slots)

    def _run_step(self, admitted: List[_Request]) -> None:
        """One turn of the loop: at most one decode step dispatched and at
        most one read.  With a step in flight and membership standing
        still, the next step goes out first and the chip runs it while
        this turn reads, emits and records the one before.  Otherwise the
        step in flight is read first; what it evicted is admission's to
        refill, so with nothing to prefill the turn ends there.  With
        nothing in flight (the serial case) the turn prefills what was
        admitted, uploads the mirrors and dispatches, and the next turn
        reads."""
        self._rec()  # a record opens where its first turn begins
        step, self._inflight = self._inflight, None
        if step is not None:
            if not admitted and self._may_run_ahead():
                self._inflight = self._dispatch_step(ahead=True)
            self._finish_step(step)
            if not admitted:
                return
        rec = self._rec()
        for req in admitted:
            pf0 = time.perf_counter()
            self._prefill(req)
            if rec is not None:
                rec.stall_s += time.perf_counter() - pf0
        if any(s is not None for s in self.slots):
            self._inflight = self._dispatch_step(ahead=False)
        elif rec is not None and admitted:  # prefills that ended at once
            with self._phase(PH_RECORD) as phase:
                self._record_step(rec, None, {}, phase)
        else:
            self._acct = None  # an empty turn (a control op's) is no record

    def _dispatch_step(self, ahead: bool) -> _Step:
        """Enqueue one decode step for every slot; its tokens stay on the
        device until ``_finish_step``.  ``ahead``: the step before it has
        not been read (then nothing is dirty and nothing is uploaded)."""
        import jax.numpy as jnp

        from ..models.paged import paged_decode_step

        rec = self._rec()
        phases = rec.phases if rec is not None else None
        self.step_count += 1
        if self._dirty:
            # Membership changed since the last step: re-upload the
            # host mirrors.  Steady-state decode skips this — tokens,
            # lengths, and the PRNG key advance on device.
            with self._phase(PH_UPLOAD, phases):
                self._d_tokens = jnp.asarray(self._tokens)
                self._d_page_tables = jnp.asarray(self._page_tables)
                self._d_seq_lens = jnp.asarray(self._seq_lens)
                self._d_active = jnp.asarray(self._active)
                self._d_temps = jnp.asarray(self._temps)
                self._d_adapter_slots = jnp.asarray(self._adapter_slots)
                self._dirty = False
        with self._phase(PH_DISPATCH, phases):
            (self._d_tokens, self._d_seq_lens, self._d_key,
             self.pools) = paged_decode_step(
                self.model_config, self.params, self.pools,
                self.adapter_pool.arrays,
                self._d_tokens, self._d_page_tables, self._d_seq_lens,
                self._d_active, self._d_temps, self._d_adapter_slots,
                self._d_key, self._d_ring_tables, shared=self._shared_walk)
        # The chip has work again.  What it stood still since the last
        # admission's first token is that admission's.
        since_token = self._starved.stop()
        if since_token and rec is not None and rec.first_tokens:
            entry = rec.first_tokens[-1]
            entry["starved_s"] = round(entry["starved_s"] + since_token, 6)
        return _Step(self.step_count, self._d_tokens, list(self.slots),
                     ahead, self._shared_dups)

    def _finish_step(self, step: _Step) -> None:
        """Read a dispatched step's tokens, hand each to the request its
        slot decoded for, and close the record."""
        rec = self._rec()
        phases = rec.phases if rec is not None else None
        with annotation(PH_READBACK, phases) as readback:
            toks = np.asarray(step.tokens)  # rt-sync-ok: THE decode-step readback — one batched token fetch per step
        if self._inflight is None:  # no step was dispatched ahead of it
            self._starved.free(readback.t1)
        with self._phase(PH_EMIT, phases) as phase:
            now = phase.t0
            routing = dict(zip(
                self._counter_keys,
                toks[self.config.batch_slots:].tolist()))
            gaps, emitted = [], 0
            for slot, req in enumerate(step.owners):
                if req is None or self.slots[slot] is not req:
                    continue  # stopped under this step: the token is dropped
                self._seq_lens[slot] += 1
                req.length += 1
                self._tokens[slot] = toks[slot]
                if req.last_token_t is not None:
                    gaps.append(now - req.last_token_t)
                req.last_token_t = now
                emitted += 1
                if rec is not None:
                    self._take_wake(req)
                self._emit_token(req, int(toks[slot]), step.number, now)
            # Each instrument once a step, not once a token: a lock each.
            if emitted:
                self._m_tokens.inc(emitted)
                self._m_itl.observe_many(gaps)
        self._m_active.set(
            sum(1 for s in self.slots if s is not None),
            tags=self._pid_tags)
        self._m_pages.set(self._pages_used(),
                          tags=self._pid_tags)
        if rec is not None:
            with self._phase(PH_RECORD) as phase:
                self._record_step(rec, step, routing, phase)

    def _record_step(self, acct: _Account, step: Optional[_Step],
                     routing: Dict[str, int], phase: _Phase) -> None:
        """Close ``acct`` into one flight-recorder record: of ``step``,
        which was just read, or (None) of prefills that left no sequence to
        decode.  Called on the loop thread; everything here is host
        bookkeeping (the decode result was already synced for token
        emission).

        The records tile the loop's time: ``t0`` (``time.perf_counter()``
        where the record's first ``_run_step`` began) less the previous
        record's ``t0 + wall_s`` is ``between_s + idle_s``, and inside
        ``wall_s`` the phases ``stall_s`` (the admission prefills, itemised
        a request in ``first_tokens``), ``upload_s``, ``dispatch_s``,
        ``readback_s`` and ``emit_s`` leave only the gauges and, where the
        step was dispatched in one turn and read in the next, the locked
        section between them.  A phase's seconds go to the record that was
        open when it ran: with a step in flight, ``dispatch_s`` is the
        next step's dispatch, ``readback_s`` the time still blocked on the
        chip, and ``wall_s + between_s`` the step's period.  ``ahead`` is
        1 when the step was dispatched before the one ahead of it was
        read.

        ``starved_s`` is what of ``wall_s + between_s`` the chip had no work
        of the engine's (:class:`_Starved`), ``starved`` the same by the
        phase it passed in (``between``: in none), and ``traced`` is 1 on
        a record closed while a profiler session was open, so that a reader
        of a device trace takes the records of the traced seconds.

        Who had the loop thread (:class:`_Held`), over the same period
        ``wall_s + between_s``: ``cpu_s`` is the seconds the thread ran
        (its own CPU clock, read where a record ends), and ``wait_s`` what
        is left of the period once those and the chip's are taken: the
        thread neither ran nor waited for the chip (the interpreter lock,
        ``self._lock``, a core).  The chip's are ``readback_s`` and, of a
        prefill, ``prefill_wait``, which is booked on its ``first_tokens``
        entry (``prefill_wait_s``): so on the record's own numbers ``cpu_s
        + wait_s + readback_s`` plus the entries' ``prefill_wait_s`` IS
        ``wall_s + between_s``, to the rounding (the thread's few CPU
        microseconds inside a wait for the chip are in ``cpu_s``).  Not by
        phase: a clock read at every phase stamp cost more than it told,
        and what it told is in ``PERF.md`` section 5.  Under a CPU clock
        that ticks, every one of these is a sample: see :class:`_Held`.
        ``proc_cpu_s`` is the PROCESS's CPU seconds over the period: near
        the period itself, one core's worth of interpreter is saturated
        (a bound: the runtime's own threads count too).

        A token's way out, over the items whose hop ENDED in the period:
        ``wake_s`` from the start of the pass that emitted a token to its
        stream's thread having it (``TokenStream.__next__``), and the
        process's direct-stream counters (``core.context``), differenced:
        ``store_s`` from there to the item's append, ``pull_s`` from the
        append to the reply that carries it, ``tokens_out`` the items such
        replies carried and ``pull_waiting`` those appended while a pull
        already waited for them (all 0 where no worker pulls the streams)."""
        import jax

        from ..models.paged import trace_counts
        from ..util import devmem, steprec

        self._acct = None
        t0, stall_s = acct.t0, acct.stall_s
        end = time.perf_counter()
        wall_s = end - t0
        cpu_s, proc_cpu_s = self._held.take()
        while self._wake_late:
            self._wake_s += self._wake_late.popleft()
        wake_s, self._wake_s = self._wake_s, 0.0
        streams0 = self._stream_counts
        streams = direct_stream_counts()
        self._stream_counts = streams
        # The record ends here, inside its own last phase: what the chip
        # stands still in the rest of it is the next record's.
        self._starved.spend(PH_RECORD, phase.t0, end)
        starved = {name.rpartition("/")[2]: round(s, 6)
                   for name, s in self._starved.take().items()}
        now = time.time()
        if step is not None:
            self._step_walls.append(wall_s)
        if stall_s > 0:
            self._stall_events.append((now, stall_s))
            self._m_stall.inc(stall_s)
        # Compile observability: a trace-count bump inside this step means
        # this step's wall paid the compile — attribute it by program.
        for prog, n in trace_counts().items():
            if n > acct.traces0.get(prog, 0):
                devmem.record_compile(prog, wall_s)
        with self._lock:
            queued = self._queued_total()
            tenants = {t: len(q) for t, q in self._queues.items() if q}
        # Rounded first, then differenced: the tiling identity holds on
        # the record's own numbers, not only on the clock's.
        t0, wall_s = round(t0, 6), round(wall_s, 6)
        idle_s = round(self._gap_acct.pop(PH_IDLE, 0.0), 6)
        between_s = round(t0 - self._prev_end - idle_s, 6)
        self._prev_end = t0 + wall_s
        first_tokens, phases = acct.first_tokens, acct.phases
        # Who had the loop thread: what of the period it neither ran nor
        # waited for the chip, on the record's own (rounded) numbers.
        cpu_s = round(cpu_s, 6)
        readback_s = round(phases.get(PH_READBACK, 0.0), 6)
        wait_s = round(wall_s + between_s - cpu_s - readback_s - sum(
            e["prefill_wait_s"] for e in first_tokens), 6)
        rec = {
            "t": round(now, 3),
            "engine": self.engine_id,
            "step": step.number if step is not None else self.step_count,
            "ahead": int(step is not None and step.ahead),
            "t0": t0,
            "wall_s": wall_s,
            "stall_s": round(stall_s, 6),
            "between_s": between_s,
            "idle_s": idle_s,
            "upload_s": round(phases.get(PH_UPLOAD, 0.0), 6),
            "dispatch_s": round(phases.get(PH_DISPATCH, 0.0), 6),
            "readback_s": readback_s,
            "emit_s": round(phases.get(PH_EMIT, 0.0), 6),
            "starved_s": round(sum(starved.values(), 0.0), 6),
            "starved": {name: s for name, s in starved.items() if s},
            "cpu_s": cpu_s,
            "wait_s": wait_s,
            "proc_cpu_s": round(proc_cpu_s, 6),
            "tokens_out": streams["items"] - streams0["items"],
            "wake_s": round(wake_s, 6),
            "store_s": round(streams["store_s"] - streams0["store_s"], 6),
            "pull_s": round(streams["pull_s"] - streams0["pull_s"], 6),
            "pull_waiting": streams["waiting"] - streams0["waiting"],
            "first_tokens": first_tokens,
            "occupancy": sum(1 for s in self.slots if s is not None),
            "slots": self.config.batch_slots,
            "admitted": len(first_tokens),
            "evicted": self._evicted_total - acct.evicted0,
            "shed": self.shed - acct.shed0,
            "queued": queued,
            "pages_used": self.allocator.used_count,
            "pages_free": self.allocator.free_count,
            "pages_shared": self.allocator.shared_count,
            "prefix_hits": sum(1 for e in first_tokens if e["cached"]),
            "adapter_pins": self.adapter_pool.pinned_count,
            "tenants": tenants,
            # Only a model whose FFN is routed, or one with window
            # layers, has these (of the decode step; a prefill's routing
            # counters are on its first_tokens entry).
            **routing,
        }
        if self._state_layers and step is not None:
            # What the step's program read and wrote of recurrent state:
            # every slot's, live or not (a masked write still moves it).
            rec["state_bytes"] = (2 * self._state_bytes
                                  * self.config.batch_slots)
        if "kv_rows_live" in routing:
            # The live rows counted once a physical page: slots that share
            # prefix pages (always whole and wholly live) read the same
            # rows, and any program computing these tokens reads them at
            # least once.
            rec["kv_rows_distinct"] = routing["kv_rows_live"] - (
                self._kv_layers[0] * self.config.page_size
                * step.shared_dups)
        if self.ring:
            # Pages held for live sequences, a layer's each: by the two
            # kinds of cache, and what one pool in which every layer kept
            # every page would hold for the same sequences.
            whole, window = self._kv_layers
            rec.update(
                pages_global=whole * self.allocator.used_count,
                pages_window=window * self._ring_pages_held(),
                pages_uniform=(whole + window) * self.allocator.used_count)
        # Which step recompiled, for every jitted program of the process
        # (trace_counts above knows the three paged ones).
        compiles = devmem.compile_count() - acct.compiles0
        if compiles:
            rec["compiles"] = compiles
        if jax.profiler.TraceAnnotation.is_enabled():
            rec["traced"] = 1
        steprec.record_step(rec)


# ------------------------------------------------------------ serve binding


_MODEL_BUILDERS = {
    "tiny": lambda: _tiny_config(),
    "b1": lambda: _b1_config(),
}


def register_model(name: str, builder) -> None:
    """Make ``llm_app(model=name)`` serve the model whose configuration
    object (a ``LlamaConfig`` or a ``MoEConfig``) the zero-argument
    ``builder`` returns.  Called in the process that builds the
    ``LLMServer``: the replica, e.g. from a deployment subclass's
    ``__init__``."""
    _MODEL_BUILDERS[name] = builder


def _tiny_config():
    import jax.numpy as jnp

    from ..models import LlamaConfig

    return LlamaConfig.tiny(remat=False, dtype=jnp.float32)


def _b1_config():
    import jax.numpy as jnp

    from ..models import LlamaConfig

    return LlamaConfig.b1(remat=False, dtype=jnp.bfloat16)


def random_lora(model_config, seed: int, rank: int = 8,
                alpha: float = 16.0):
    """A deterministic nonzero LoRA for tests/bench/demo adapters
    (``lora_init`` zeroes the B matrices, which would make every adapter
    a no-op; serving wants adapters that visibly change the logits)."""
    import jax
    import jax.numpy as jnp

    from ..models.llama import lora_init

    lora = lora_init(model_config, jax.random.PRNGKey(seed), rank=rank,
                     alpha=alpha)
    base = jax.random.PRNGKey(seed ^ 0x5BD1)
    for i, layer in enumerate(lora["layers"]):
        kq, kv = jax.random.split(jax.random.fold_in(base, i))
        layer["wq_lora_b"] = (
            jax.random.normal(kq, layer["wq_lora_b"].shape, jnp.float32)
            * 0.05).astype(layer["wq_lora_b"].dtype)
        layer["wv_lora_b"] = (
            jax.random.normal(kv, layer["wv_lora_b"].shape, jnp.float32)
            * 0.05).astype(layer["wv_lora_b"].dtype)
    return lora


def _setup_table(setup: Dict[str, Any]) -> str:
    """``LLMServer.stats()["setup"]`` as lines for the replica's log."""
    cols = ("program", "bucket", "wall_s", "trace_s", "lower_s",
            "compile_s", "cache_hit", "cache_read_s")
    lines = [f"weights_s={setup['weights_s']} pools_s={setup['pools_s']}",
             " ".join(cols)]
    lines += [" ".join(str(row[c]) for c in cols)
              for row in setup["programs"]]
    return "\n  ".join(lines)


class LLMServer:
    """The deployment callable: one engine per replica, tokens streamed
    through serve's per-item streaming path (handle iterators, HTTP SSE,
    gRPC server-streaming).  A consumer that disconnects mid-stream
    closes the generator, which cancels the request and frees its pages.

    Multi-tenant: ``adapter=`` picks a registered LoRA (defaulting to the
    ambient multiplexed model id, so ``multiplexed_model_id`` routing
    composes with the engine's batched adapters), ``tenant``/``weight``
    feed weighted-fair admission."""

    def __init__(self, model: str = "tiny",
                 engine: Optional[dict] = None, seed: int = 0,
                 warmup: bool = False,
                 adapters: Optional[Dict[str, Any]] = None):
        import jax

        from .. import accelerators

        backend = jax.default_backend()
        chips = accelerators.num_chips()
        if model != "tiny" and backend != "tpu" and chips:
            raise RuntimeError(
                f"model {model!r} would be served from the {backend!r} "
                f"backend on a host with {chips} TPU chip(s): the replica "
                f"was granted none.  Ask for one through the deployment's "
                f"actor options: llm_app(..., ray_actor_options="
                f"{{'num_tpus': 1}})")
        t0 = time.perf_counter()
        from ..models import init_and_apply

        cfg = _MODEL_BUILDERS[model]()
        init, self._apply = init_and_apply(cfg)
        params = jax.block_until_ready(init(cfg, jax.random.PRNGKey(seed)))
        tw = time.perf_counter()
        self.engine = InferenceEngine(
            cfg, params, EngineConfig(**(engine or {})), seed=seed)
        jax.block_until_ready(self.engine.pools)
        tp = time.perf_counter()
        for name, spec in (adapters or {}).items():
            self.load_adapter(name, spec)
        t1 = time.perf_counter()
        programs = self.engine.warmup() if warmup else []
        #: Set-up seconds: params and pools onto the device, then (with
        #: ``warmup``) the compile of every program the engine will run.
        self._init_s = t1 - t0
        self._warmup_s = time.perf_counter() - t1
        #: The same, itemised: weights, the engine with its KV pools, and
        #: one row a program warmed (InferenceEngine.warmup).
        self._setup = {"weights_s": round(tw - t0, 3),
                       "pools_s": round(tp - tw, 3), "programs": programs}
        if programs:  # once, into the replica's log
            print(f"set-up of {model}: {_setup_table(self._setup)}",
                  file=sys.stderr, flush=True)
            stats = self.engine.stats()
            if stats["grouped_ffn"]:
                print("the decode step's grouped products: "
                      f"{stats['grouped_ffn']}, the prefills': "
                      f"{stats['grouped_ffn_prefill']}",
                      file=sys.stderr, flush=True)
            print(f"the decode step's attention: {stats['decode_attention']}"
                  f", the prefills': {stats['prefill_attention']}",
                  file=sys.stderr, flush=True)
            if stats["recurrent_decode"]:
                print("the decode step's recurrent layers: "
                      f"{stats['recurrent_decode']}",
                      file=sys.stderr, flush=True)

    def load_adapter(self, name: str, source: Any = None) -> str:
        """Register a LoRA adapter on this replica's engine.  ``source``
        is packed arrays / a lora pytree / an object-plane ref / a
        zero-arg builder, or an int seed (a deterministic random adapter
        — handy for tests and bench)."""
        if isinstance(source, int):
            seed = source
            cfg = self.engine.model_config
            rank = self.engine.config.lora_rank
            source = lambda: random_lora(cfg, seed, rank=rank)  # noqa: E731
        self.engine.register_adapter(name, source)
        return name

    def __call__(self, prompt_tokens, max_new_tokens: int = 16,
                 temperature: float = 0.0,
                 stop_token: Optional[int] = None,
                 adapter: Optional[str] = None,
                 tenant: str = "default", weight: float = 1.0):
        if adapter is None:
            # serve.multiplexed routing: the handle's multiplexed_model_id
            # arrives via the replica's contextvar.
            from .multiplex import get_multiplexed_model_id

            adapter = get_multiplexed_model_id() or None
        stream = self.engine.submit(
            prompt_tokens, max_new_tokens=max_new_tokens,
            temperature=temperature, stop_token=stop_token,
            adapter=adapter, tenant=tenant, weight=weight)
        try:
            for tok in stream:
                yield tok
        finally:
            # Reached on completion AND on GeneratorExit (client gone,
            # task cancelled): idempotent, frees pages mid-flight.
            stream.cancel()

    def stats(self) -> Dict[str, Any]:
        """Engine counters plus what this replica's process runs on, as
        JAX reports it here."""
        import os

        import jax

        from ..devtools import jitguard

        dev = jax.devices()[0]
        return dict(
            self.engine.stats(),
            pid=os.getpid(),
            vocab_size=self.engine.model_config.vocab_size,
            device={"platform": dev.platform, "kind": dev.device_kind,
                    "count": len(jax.devices())},
            init_s=self._init_s, warmup_s=self._warmup_s,
            setup=self._setup, sentinel_armed=jitguard.armed())

    def clear_prefix_cache(self) -> int:
        """Drop every cached prefix page (returns how many the cache let
        go): afterwards an idle engine's free list holds the whole pool."""
        return self.engine.clear_prefix_cache()

    def reference_logits(self, prompt_tokens,
                         candidates=()) -> Dict[str, Any]:
        """Next-token logits after ``prompt_tokens`` from the plain full
        forward pass (the model's own: no cache, no pages, no buckets) over
        this replica's weights: the best token, its logit, and the logits
        of ``candidates``.  What a deployment check holds the engine's
        greedy token to, within the dtype's rounding."""
        import jax
        import jax.numpy as jnp

        toks = jnp.asarray(prompt_tokens, jnp.int32)[None]
        logits = np.asarray(jax.jit(self._apply, static_argnums=0)(
            self.engine.model_config, self.engine.params, toks)[0, -1])
        return {"argmax": int(logits.argmax()), "max": float(logits.max()),
                "logits": [float(logits[int(c)]) for c in candidates]}

    def engine_metrics(self) -> Dict[str, Any]:
        """SLO signal snapshot for the controller's autoscaler."""
        return self.engine.slo_signals()


def llm_app(model: str = "tiny", engine: Optional[dict] = None,
            num_replicas: int = 1, name: str = "llm", seed: int = 0,
            warmup: bool = False,
            adapters: Optional[Dict[str, Any]] = None,
            ray_actor_options: Optional[dict] = None):
    """Build a servable LLM application:
    ``serve.run(llm_app(...))`` then stream tokens via
    ``handle.options(stream=True).remote([1, 2, 3], 16)`` or POST with
    ``Accept: text/event-stream``.  ``adapters`` maps adapter name to an
    int seed (random adapter) or weight source, registered on every
    replica at startup.  ``ray_actor_options`` are the deployment's:
    ``{"num_tpus": 1}`` gives each replica a chip, and on a host with
    chips every model but ``tiny`` refuses to start without one."""
    from .api import Deployment

    dep = Deployment(LLMServer, name, num_replicas=num_replicas,
                     ray_actor_options=ray_actor_options)
    return dep.bind(model=model, engine=engine, seed=seed, warmup=warmup,
                    adapters=adapters)
