"""Paged (blocked) KV cache + decode/prefill programs for the serve engine.

Role-equivalent to vLLM-style PagedAttention as surfaced by Ray Serve's LLM
stack (reference: the Ray Serve LLM APIs run a continuous-batching engine
whose KV cache is a pool of fixed-size pages).  TPU-first shape:

- ONE preallocated KV pool per replica: ``[L, P+1, page, H_kv, D]`` per
  k/v — a token's ``[H_kv, D]`` row is contiguous, which is how it is
  written (one row per token) and how it is read (a table's pages
  reshape to ``[MAXP*page, H_kv, D]`` with no transpose), so the donated
  pools keep one layout from argument to result and no program copies
  them.  Page ``P`` is a scratch page that absorbs writes from inactive
  batch slots and padded prompt tail positions, so every program runs
  with fully static shapes and no data-dependent control flow.  The
  page axis is the second; what lies inside a page only
  ``init_paged_pools``, ``_page_size``, ``_write_rows`` and
  ``_attend_pages`` know.
- A host-side free-list allocator hands pages to sequences; per-sequence
  PAGE TABLES (``[MAX_PAGES]`` int32, scratch-filled past the allocated
  prefix) are plain arrays, so ONE compiled decode program serves any
  admission mix — slot occupancy, page placement, and lengths are data.
- The decode step gathers each slot's pages into a linear view and masks
  by sequence length (the standard static-shape TPU decode recipe: score
  the whole gather, mask the unwritten tail — no dynamic slicing).  The
  gather is the only copy of K/V a layer makes: queries are grouped by
  KV head and contracted against the gathered view in the pool's dtype
  with float32 accumulation (no upcast, transposed or GQA-repeated K/V
  is ever materialised).

Compile counts are observable via ``trace_count()`` — the jitted bodies
bump a counter when TRACED (python executes only at trace time), which is
how tests assert the engine never recompiles after warmup.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from ..ops.rotary import apply_rotary, rope_frequencies
from . import block
from .llama import LlamaConfig

Params = Any
PagedPools = Dict[str, jax.Array]  # {"k": [L, P+1, page, H_kv, D], "v": ...}

# jit-trace counters per program name; a bump means XLA compiled a new
# specialization (python bodies only run while tracing).  The counters
# live in the devtools.jitguard registry (shared with the rllib learner
# updates and armed as a recompile sentinel under RT_DEBUG_JIT=1); the
# names below are kept as aliases so devmem snapshots and the engine's
# ``decode_traces`` assertions read unchanged.
from ..devtools import jitguard as _jitguard

PAGED_PROGRAMS = ("decode", "prefill", "prefill_prefix", "page_copy",
                  "adapter_load")
for _prog in PAGED_PROGRAMS:
    _jitguard.register_program(_prog)


def trace_count(name: str) -> int:
    """Times the named program (``"decode"`` / ``"prefill"``) was traced."""
    return _jitguard.count(name)


def trace_counts() -> Dict[str, int]:
    """Snapshot of every program's trace count (devmem/compile
    observability: a nonzero delta between snapshots means XLA compiled
    a new specialization in that window)."""
    return _jitguard.counts()


def _bump(name: str, **arrays: Any) -> None:
    _jitguard.bump(name, _jitguard.signature_of(arrays) if arrays else None)


def init_paged_pools(config: LlamaConfig, num_pages: int,
                     page_size: int) -> PagedPools:
    """One pool pair for the whole replica; index ``num_pages`` is the
    scratch page (writes routed there are never read)."""
    shape = (config.n_layers, num_pages + 1, page_size,
             config.n_kv_heads, config.head_dim)
    return {"k": jnp.zeros(shape, config.dtype),
            "v": jnp.zeros(shape, config.dtype)}


def _page_size(pools: PagedPools) -> int:
    return pools["k"].shape[2]


def _write_rows(pool: jax.Array, layer: int, page_idx: jax.Array,
                off: jax.Array, rows: jax.Array) -> jax.Array:
    """Write one token's K or V per row: rows [N, H_kv, D] land at
    ``(page_idx[n], off[n])`` of ``layer``.  The index arrays lead and are
    adjacent, so each row is one contiguous ``[H_kv, D]`` update and the
    donated pool is updated in place."""
    return pool.at[layer, page_idx, off].set(rows.astype(pool.dtype))


def _attend_pages(config: LlamaConfig, q: jax.Array, k_pool: jax.Array,
                  v_pool: jax.Array, layer: int, tables: jax.Array,
                  visible: jax.Array) -> jax.Array:
    """Attention of q [B, Q, H, D] over the pages of tables [B, MAXP]:
    visible [B, Q, MAXP*page] bool says which gathered positions a query
    may see (scratch and unwritten ones never).  Returns [B, Q, H*D].

    A table's pages reshape to a linear ``[B, MAXP*page, H_kv, D]`` view
    as gathered; queries are grouped ``[.., H_kv, n_rep, D]`` and
    contracted against it directly.  K and V stay in the pool's dtype and
    the products accumulate in float32: a bf16 x bf16 product is exact in
    float32, so this is what upcasting the gathered K first computed,
    without the float32 copy."""
    B, Q = q.shape[:2]
    n_rep = config.n_heads // config.n_kv_heads
    k_seq = k_pool[layer, tables].reshape(
        B, -1, config.n_kv_heads, config.head_dim)
    v_seq = v_pool[layer, tables].reshape(k_seq.shape)
    qg = q.reshape(B, Q, config.n_kv_heads, n_rep, config.head_dim)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k_seq,
                        preferred_element_type=jnp.float32) \
        * (config.head_dim ** -0.5)
    scores = jnp.where(visible[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_seq.dtype)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v_seq)
    return out.reshape(B, Q, -1)


#: What ``_with_routing`` appends, in its order: the step record's keys.
ROUTING_KEYS = ("experts_hit", "expert_pairs", "expert_load_max")


def routing_width(config) -> int:
    """How many int32 counters a program of ``config`` appends to the
    tokens it returns: ``len(ROUTING_KEYS)`` where the FFN is routed."""
    return len(ROUTING_KEYS) if block.is_routed(config) else 0


def _with_routing(toks: jax.Array, counts: List[Optional[jax.Array]]
                  ) -> jax.Array:
    """``toks`` [N] int32, followed (where the layers were routed) by the
    program's ``ROUTING_KEYS``: over all layers, the experts that got a
    token, the (token, expert) pairs routed, and the most tokens on one
    expert of one layer.  They ride in the array the engine reads back
    anyway, so they cost it no transfer of their own."""
    if counts[0] is None:
        return toks
    c = jnp.stack(counts)  # [L, E]
    return jnp.concatenate([toks, jnp.stack(
        [jnp.sum(c > 0, dtype=jnp.int32), jnp.sum(c), jnp.max(c)])])


def _first_token(tok: jax.Array, counts) -> jax.Array:
    """A prefill's result: the scalar token, or [token, *ROUTING_KEYS]
    where the layers were routed."""
    return tok[0] if counts[0] is None else _with_routing(tok, counts)


# ------------------------------------------------------- adapter pool

#: {"qa": [A+1, L, d, r], "qb": [A+1, L, r, d], "va": [A+1, L, d, r],
#:  "vb": [A+1, L, r, kv_out], "scale": [A+1]} — slot A is the permanent
#: zero adapter (scale 0), so base-model slots are just data too.
AdapterArrays = Dict[str, jax.Array]


def init_adapter_pool(config: LlamaConfig, max_adapters: int,
                      rank: int) -> AdapterArrays:
    """Device-resident pool of ``max_adapters`` LoRA slots plus one zero
    slot at index ``max_adapters``.  The pool's SHAPES are part of every
    decode/prefill signature, so loading, evicting, or remixing adapters
    never recompiles — only the per-slot ``adapter_ids`` data changes."""
    d = config.d_model
    kv_out = config.n_kv_heads * config.head_dim
    A, L = max_adapters + 1, config.n_layers
    return {
        "qa": jnp.zeros((A, L, d, rank), config.dtype),
        "qb": jnp.zeros((A, L, rank, d), config.dtype),
        "va": jnp.zeros((A, L, d, rank), config.dtype),
        "vb": jnp.zeros((A, L, rank, kv_out), config.dtype),
        "scale": jnp.zeros((A,), jnp.float32),
    }


def pack_lora(config: LlamaConfig, lora: Params) -> AdapterArrays:
    """Stack a ``lora_init``-style adapter (list of per-layer dicts) into
    the dense per-slot layout ``adapter_load`` writes into the pool."""
    ls = lora["layers"]
    return {
        "qa": jnp.stack([l["wq_lora_a"] for l in ls]).astype(config.dtype),
        "qb": jnp.stack([l["wq_lora_b"] for l in ls]).astype(config.dtype),
        "va": jnp.stack([l["wv_lora_a"] for l in ls]).astype(config.dtype),
        "vb": jnp.stack([l["wv_lora_b"] for l in ls]).astype(config.dtype),
        "scale": jnp.asarray(ls[0]["scale"], jnp.float32),
    }


@functools.partial(jax.jit, donate_argnums=(0,))
def adapter_load(adapters: AdapterArrays, slot: jax.Array,
                 packed: AdapterArrays) -> AdapterArrays:
    """Overwrite one pool slot in place (slot index is data; pool arrays
    are donated so load/evict churn never copies the resident set)."""
    _bump("adapter_load", slot=slot, qa=packed["qa"], scale=packed["scale"])
    return {name: adapters[name].at[slot].set(packed[name])
            for name in ("qa", "qb", "va", "vb", "scale")}


def _lora_delta_batched(h: jax.Array, a: jax.Array, b: jax.Array,
                        scale: jax.Array) -> jax.Array:
    """Per-slot low-rank delta: h [B, d], a [B, d, r], b [B, r, out],
    scale [B] -> [B, out].  Rank is tiny, so this is two skinny matmuls
    per projection — the price of serving any adapter mix in one
    program."""
    t = jnp.einsum("bd,bdr->br", h, a)
    return (jnp.einsum("br,bro->bo", t, b)
            * scale[:, None].astype(h.dtype))


def _lora_delta_seq(h: jax.Array, a: jax.Array, b: jax.Array,
                    scale: jax.Array) -> jax.Array:
    """One adapter over a sequence: h [S, d], a [d, r], b [r, out]."""
    return ((h @ a) @ b) * scale.astype(h.dtype)


def _adapter_lora(adapters: AdapterArrays, ids: jax.Array):
    """``lora(i, name, h)`` for layer ``i`` over the pool slots ``ids``:
    [B], a slot for each row of h [B, d] (the decode step), or a scalar,
    one adapter over a sequence h [S, d] (the prefills).  One gather per
    adapter array for the whole program: [B, L, ...] or [L, ...]."""
    qa, qb = adapters["qa"][ids], adapters["qb"][ids]
    va, vb = adapters["va"][ids], adapters["vb"][ids]
    scale = adapters["scale"][ids]
    delta = _lora_delta_batched if jnp.ndim(ids) else _lora_delta_seq

    def lora(i, name, h):
        a, b = (qa, qb) if name == "wq" else (va, vb)
        return delta(h, a[..., i, :, :], b[..., i, :, :], scale)
    return lora


class PageAllocator:
    """Refcounted free-list page allocator (host side; the engine
    serializes access).

    All-or-nothing ``alloc``: a sequence is admitted only when its whole
    worst-case footprint fits, so decode can never die of page exhaustion
    mid-flight — admission control happens at the boundary, not inside
    the loop.  ``share`` grows a page's refcount (prefix-cache reuse: the
    radix tree and every sequence reading a cached page each hold a ref);
    ``free`` releases one ref and only returns the page to the free list
    at zero.  Releasing a page nobody holds fails loudly (a page on two
    sequences corrupts both)."""

    def __init__(self, num_pages: int):
        self.total = num_pages
        self._free: List[int] = list(range(num_pages))
        self._refs: Dict[int, int] = {}

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return self.total - len(self._free)

    @property
    def shared_count(self) -> int:
        """Pages currently held by more than one owner."""
        return sum(1 for n in self._refs.values() if n > 1)

    def refs(self, page: int) -> int:
        return self._refs.get(page, 0)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n pages at refcount 1, or None when the pool can't cover them
        (caller queues or sheds — never partial)."""
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        return pages

    def share(self, pages: List[int]) -> None:
        """One more owner per page (must be live — sharing a freed page
        would resurrect a slot the free list already handed out)."""
        for p in pages:
            if p not in self._refs:
                raise AssertionError(f"share of unallocated KV page {p}")
            self._refs[p] += 1

    def free(self, pages: List[int]) -> None:
        """Release one ref per page; the page returns to the free list
        only when its last owner lets go."""
        for p in pages:
            n = self._refs.get(p)
            if n is None:
                raise AssertionError(f"double free of KV page {p}")
            if n == 1:
                del self._refs[p]
                self._free.append(p)
            else:
                self._refs[p] = n - 1


def _rotary_single(x: jax.Array, cos: jax.Array, sin: jax.Array,
                   pos: jax.Array) -> jax.Array:
    """RoPE for one position per batch slot: x [B, H, D], pos [B]."""
    c = cos[pos][:, None, :]  # [B, 1, D/2]
    s = sin[pos][:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * c - xf2 * s, xf2 * c + xf1 * s], axis=-1
    ).astype(x.dtype)


def _sample_tokens(logits: jax.Array, temps: jax.Array,
                   key: jax.Array) -> jax.Array:
    """Per-slot greedy/temperature sampling: logits [B, V], temps [B]
    (<= 0 means greedy)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe_t = jnp.where(temps > 0, temps, 1.0)[:, None]
    keys = jax.random.split(key, logits.shape[0])
    sampled = jax.vmap(jax.random.categorical)(keys, logits / safe_t)
    return jnp.where(temps > 0, sampled.astype(jnp.int32), greedy)


def _stack(config, params: Params, tokens: jax.Array, attend, lora,
           valid: jax.Array):
    """The decoder stack of a serving program over tokens [N]:
    ``attend(i, q, k, v)`` and ``lora(i, name, h)`` are ``block``'s
    closures with the layer's index in front (the pools and the adapter
    pool are indexed by it).  Returns (hidden [N, d], the layers' expert
    counts)."""
    hidden, _, counts = block.decoder_stack(
        config, params, tokens,
        lambda i, layer, x: block.decoder_layer(
            config, layer, x, functools.partial(attend, i),
            lora=functools.partial(lora, i), valid=valid))
    return hidden, counts


def _write_kv(pools: PagedPools, layer: int, page_idx: jax.Array,
              off: jax.Array, k: jax.Array, v: jax.Array) -> None:
    """``_write_rows`` of a layer's K and V, the dict's pools replaced."""
    pools["k"] = _write_rows(pools["k"], layer, page_idx, off, k)
    pools["v"] = _write_rows(pools["v"], layer, page_idx, off, v)


def decode_logits(config, params: Params, pools: PagedPools,
                  adapters: AdapterArrays, tokens: jax.Array,
                  page_tables: jax.Array, seq_lens: jax.Array,
                  active: jax.Array, adapter_ids: jax.Array):
    """``paged_decode_step`` up to its sampling: (logits [B, V] float32,
    pools, per-layer expert counts)."""
    B, maxp = page_tables.shape
    ps = _page_size(pools)
    pools = dict(pools)
    cos, sin = rope_frequencies(config.head_dim, maxp * ps,
                                config.rope_theta)
    page_idx = page_tables[jnp.arange(B), seq_lens // ps]  # [B]
    off = seq_lens % ps
    # The length mask removes scratch/unwritten positions: [B, 1, MAXP*ps].
    visible = jnp.arange(maxp * ps)[None, None, :] \
        <= seq_lens[:, None, None]

    def attend(i, q, k, v):  # one row a slot: [B, H, D]
        q = _rotary_single(q, cos, sin, seq_lens)
        k = _rotary_single(k, cos, sin, seq_lens)
        _write_kv(pools, i, page_idx, off, k, v)
        return _attend_pages(config, q[:, None], pools["k"], pools["v"], i,
                             page_tables, visible)[:, 0]

    x, counts = _stack(config, params, tokens[:B], attend,
                       _adapter_lora(adapters, adapter_ids), active)
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    return logits, pools, counts


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def paged_decode_step(config: LlamaConfig, params: Params,
                      pools: PagedPools, adapters: AdapterArrays,
                      tokens: jax.Array, page_tables: jax.Array,
                      seq_lens: jax.Array, active: jax.Array,
                      temps: jax.Array, adapter_ids: jax.Array,
                      key: jax.Array):
    """One decode step for every batch slot at once.

    tokens int32, the last sampled token of each slot in its first B
    entries (what this program returned last step goes back in as it is,
    so anything behind them is ignored), page_tables [B, MAXP]
    int32 (scratch index past each sequence's allocated prefix), seq_lens
    [B] int32 = tokens already cached (the new token is WRITTEN at
    position seq_lens and attends positions <= seq_lens), active [B]
    bool, temps [B] float32, adapter_ids [B] int32 pool-slot indices
    (the zero slot for base-model requests — per-slot adapters are DATA,
    so one compiled program serves any adapter mix).  Inactive slots
    pass seq_lens=0 and an all-scratch page table: their writes land on
    the scratch page and their sampled token is ignored host-side.
    Pools are donated and keep their layout through the program
    (``tests/test_chip_compile.py`` holds the compiled step to it), so
    steady-state decode never copies the cache: a layer writes B rows in
    place and reads one gather of the page tables.

    The PRNG key and the slot lengths advance ON DEVICE (returned
    alongside the tokens), so the serving loop's only per-step host
    traffic is downloading the [B] sampled tokens — host-side key
    folding measurably dominates step time otherwise.  Returns
    (next_tokens [B], new_seq_lens [B], new_key, pools); a model whose FFN
    is routed appends its ``ROUTING_KEYS`` counters to next_tokens
    (``_with_routing``)."""
    _bump("decode", tokens=tokens, page_tables=page_tables,
          seq_lens=seq_lens, temps=temps, adapter_ids=adapter_ids, key=key)
    logits, pools, counts = decode_logits(
        config, params, pools, adapters, tokens, page_tables, seq_lens,
        active, adapter_ids)
    key, sub = jax.random.split(key)
    toks = _sample_tokens(logits, temps, sub)
    new_lens = jnp.where(active, seq_lens + 1, 0)
    return _with_routing(toks, counts), new_lens, key, pools


def prefill_logits(config, params: Params, pools: PagedPools,
                   adapters: AdapterArrays, tokens: jax.Array,
                   length: jax.Array, page_table: jax.Array,
                   adapter_id: jax.Array):
    """``paged_prefill`` up to its sampling: (logits [1, V] float32 after
    the last real position, pools, per-layer expert counts)."""
    _, s_pad = tokens.shape
    ps = _page_size(pools)
    pools = dict(pools)
    n_rep = config.n_heads // config.n_kv_heads
    cos, sin = rope_frequencies(config.head_dim, s_pad, config.rope_theta)
    positions = jnp.arange(s_pad)
    page_idx = page_table[positions // ps]  # [S_pad]
    off = positions % ps
    causal = positions[None, :] <= positions[:, None]  # [S_pad, S_pad]

    def attend(i, q, k, v):  # [S_pad, H, D]: attention among the rows
        q = apply_rotary(q.transpose(1, 0, 2)[None], cos, sin)[0]
        k = apply_rotary(k.transpose(1, 0, 2)[None], cos, sin)[0]
        _write_kv(pools, i, page_idx, off, k.transpose(1, 0, 2), v)
        kr, vr = k, v.transpose(1, 0, 2)  # [H_kv, S_pad, D]
        if n_rep > 1:
            kr = jnp.repeat(kr, n_rep, axis=0)
            vr = jnp.repeat(vr, n_rep, axis=0)
        scores = jnp.einsum("hqd,hkd->hqk", q.astype(jnp.float32),
                            kr.astype(jnp.float32)) \
            * (config.head_dim ** -0.5)
        scores = jnp.where(causal[None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(vr.dtype)
        out = jnp.einsum("hqk,hkd->hqd", probs, vr)
        return out.transpose(1, 0, 2).reshape(s_pad, -1)

    x, counts = _stack(config, params, tokens[0], attend,
                       _adapter_lora(adapters, adapter_id),
                       positions < length)
    x_last = jnp.take(x, length - 1, axis=0)  # last REAL position
    logits = (x_last @ params["lm_head"]).astype(jnp.float32)[None]
    return logits, pools, counts


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def paged_prefill(config: LlamaConfig, params: Params, pools: PagedPools,
                  adapters: AdapterArrays, tokens: jax.Array,
                  length: jax.Array, page_table: jax.Array,
                  adapter_id: jax.Array, temp: jax.Array, key: jax.Array):
    """Prefill ONE sequence's prompt into its pages and sample the first
    token.

    tokens [1, S_pad] int32 (prompt padded to a bucket length — one
    compile per bucket, see the engine's bucket table), length scalar =
    real prompt length, page_table [MAXP], adapter_id scalar pool-slot
    index (data, like the decode step's).  Padded tail positions write
    through the page table like real ones (their garbage K/V is masked by
    length until decode overwrites it) or to the scratch page past the
    allocated prefix.  The key advances on device like the decode step's.
    Returns (first_token scalar, new_key, pools); a model whose FFN is
    routed returns [first_token, *ROUTING_KEYS] in its place."""
    _bump("prefill", tokens=tokens, page_table=page_table, temp=temp,
          key=key)
    logits, pools, counts = prefill_logits(
        config, params, pools, adapters, tokens, length, page_table,
        adapter_id)
    key, sub = jax.random.split(key)
    tok = _sample_tokens(logits, temp[None], sub)
    return _first_token(tok, counts), key, pools


def prefill_prefix_logits(config, params: Params, pools: PagedPools,
                          adapters: AdapterArrays, tokens: jax.Array,
                          prefix_len: jax.Array, length: jax.Array,
                          page_table: jax.Array, adapter_id: jax.Array):
    """``paged_prefill_prefix`` up to its sampling; returns what
    ``prefill_logits`` returns."""
    _, s_pad = tokens.shape
    maxp = page_table.shape[0]
    ps = _page_size(pools)
    scratch = pools["k"].shape[1] - 1
    pools = dict(pools)
    cos, sin = rope_frequencies(config.head_dim, maxp * ps,
                                config.rope_theta)
    positions = prefix_len + jnp.arange(s_pad)  # global positions
    valid = positions < length
    page_idx = jnp.where(
        valid, page_table[jnp.clip(positions // ps, 0, maxp - 1)], scratch)
    off = jnp.where(valid, positions % ps, 0)
    # Causal in global positions: [1, S_pad, MAXP*ps].
    visible = jnp.arange(maxp * ps)[None, None, :] \
        <= positions[None, :, None]

    def attend(i, q, k, v):  # [S_pad, H, D]
        # Per-row RoPE at global positions (suffix rows are not at 0).
        q = _rotary_single(q, cos, sin, positions)
        k = _rotary_single(k, cos, sin, positions)
        _write_kv(pools, i, page_idx, off, k, v)
        # Attend the WHOLE table (cached prefix + fresh suffix) like the
        # decode step, as a batch of one.
        return _attend_pages(config, q[None], pools["k"], pools["v"], i,
                             page_table[None], visible)[0]

    x, counts = _stack(config, params, tokens[0], attend,
                       _adapter_lora(adapters, adapter_id), valid)
    x_last = jnp.take(x, length - prefix_len - 1, axis=0)  # last real row
    logits = (x_last @ params["lm_head"]).astype(jnp.float32)[None]
    return logits, pools, counts


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def paged_prefill_prefix(config: LlamaConfig, params: Params,
                         pools: PagedPools, adapters: AdapterArrays,
                         tokens: jax.Array, prefix_len: jax.Array,
                         length: jax.Array, page_table: jax.Array,
                         adapter_id: jax.Array, temp: jax.Array,
                         key: jax.Array):
    """Prefill only the SUFFIX of a prompt whose first ``prefix_len``
    positions are already cached in this sequence's page table (radix
    prefix-cache hit; shared pages were written by an earlier identical
    prefill, the COW page by ``copy_page``).

    tokens [1, S_pad] int32 = prompt[prefix_len:] padded to a bucket,
    prefix_len / length scalars (length = FULL prompt length; both are
    data, so one compile per bucket serves every split point including
    mid-page COW divergence).  Suffix K/V is written through the page
    table at global positions ``prefix_len + row``; rows past the real
    suffix route to the scratch page (they may not even own a page).
    Queries then attend the full gathered table like the decode step —
    cached prefix plus fresh suffix — masked by global causal position.
    Returns what ``paged_prefill`` returns."""
    _bump("prefill_prefix", tokens=tokens, page_table=page_table,
          temp=temp, key=key)
    logits, pools, counts = prefill_prefix_logits(
        config, params, pools, adapters, tokens, prefix_len, length,
        page_table, adapter_id)
    key, sub = jax.random.split(key)
    tok = _sample_tokens(logits, temp[None], sub)
    return _first_token(tok, counts), key, pools


@functools.partial(jax.jit, donate_argnums=(0,))
def copy_page(pools: PagedPools, src: jax.Array,
              dst: jax.Array) -> PagedPools:
    """Copy one page's K/V across every layer (copy-on-write when a
    request diverges mid-page from a cached prefix).  src/dst are data —
    one compile covers every divergence."""
    _bump("page_copy", src=src, dst=dst)
    k, v = pools["k"], pools["v"]
    return {"k": k.at[:, dst].set(k[:, src]),
            "v": v.at[:, dst].set(v[:, src])}
