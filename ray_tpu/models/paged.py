"""Paged (blocked) KV cache + decode/prefill programs for the serve engine.

Role-equivalent to vLLM-style PagedAttention as surfaced by Ray Serve's LLM
stack (reference: the Ray Serve LLM APIs run a continuous-batching engine
whose KV cache is a pool of fixed-size pages).  TPU-first shape:

- ONE preallocated KV pool per replica and KIND of layer (below):
  ``[L, P+1, page, H_kv, D]`` per k/v — a token's ``[H_kv, D]`` row is contiguous, which is how it is
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

Two kinds of cache live side by side where the configuration has a layer
pattern (``block.layer_window``).  A layer that attends its sequence's
whole length keeps every page of it: pools ``k`` / ``v``, the page table
``[MAXP]``.  A WINDOW layer keeps a ring: pools ``kw`` / ``vw`` over the
window layers only, and a ring table of ``ring_entries(...)`` pages
(window + one prefill chunk) in which position ``p`` lives at entry
``(p // page) % entries``.  Nothing is freed while a sequence runs, every
program keeps static shapes, and which absolute position a gathered ring
slot holds is arithmetic on the last position written
(``_ring_positions``).  A configuration without a pattern has the one
kind, the one pool pair and the programs it always had.

A third kind where attention goes through a compressed latent
(``block.is_latent``): ONE pool ``kv`` of latent rows
``[L, P+1, page, latent_row_width]``, a token's ``[norm(c_kv) ; RoPE(k_r)]``
on a layer (``kv_lora_rank + qk_rope_head_dim`` numbers, then zeros up to
whole 128-lane tiles: ``latent_row_width``), behind the page tables the
whole-length kind uses (every layer keeps every page, so a radix node is
still one page valid for every layer).  The decode step and the prefill
over cached rows attend ABSORBED, in the latent (``_latent_attend``): the
row is the key of ONE KV head that all query heads share, and its first
``kv_lora_rank`` columns are that head's value; ``wkv_b`` is multiplied
into the queries and into the output, never into the cached rows.  The
cold prefill expands its own chunk's keys and values.

On a TPU the decode step of a latent model, and of a model with window
layers, does not gather: a Pallas kernel walks each slot's LIVE pages where
they lie and reads each once (``ops/latent_decode.py`` over the latent
pool; ``ops/paged_decode.py`` over K/V pairs, on the whole-length layers
and on the rings alike; ``_walks_live_pages`` chooses).  There the suffix
prefill's many query rows walk too (``ops/paged_prefill.py`` over K/V
pairs, ``ops/latent_prefill.py`` over the latent pool: a block of rows
against the live pages of the call's table or ring, online softmax, no score
matrix in HBM; the engine then sends a prompt's first rows through it as
well, a suffix behind nothing).  The gather form is their reference, and
what every other backend and a configuration of the one whole-length kind
take.

A fourth kind of per-sequence state where the configuration has gated
delta-rule layers (``block.is_kda``, ``models/kda.py``): no rows at all, but
a float32 matrix state a head and the last pre-activation rows of the
layer's convolutions, a SLOT's each (pools ``S`` and ``conv``, indexed
[KDA layer, slot]; ``kda.state_shapes``).  Nothing allocates it: a slot owns
its state as it owns its ring.  The decode step reads and writes every
active slot's; a cold prefill starts from zeros and leaves the state behind
its last real row in the slot it is told (``state_slot``), and each further
chunk of a chunked prompt takes the slot's state in and hands it on.  The
latent pool beside it holds rows for the latent layers only.

A fifth arrangement where the recurrent layers are selective state-space
layers (``block.is_ssm``, ``models/mamba.py``) beside ordinary attention
layers (``attn_layout`` ``"kv"``): the same two slot pools, of that module's
shapes (``S`` ``[layer, slot, N, I]`` float32, ``conv`` a row a slot), beside
the ONE K/V pool pair of the whole-length kind, over the attention layers
only.  ``block.recurrent`` names the module; its ``state_shapes``,
``decode_rows`` and ``prefill_rows`` are all this file asks of it; on a TPU
the decode step hands the state-space module the ``S`` pool whole with the
layer's place in it, and the module's kernel updates it where it lies
(``_steps_in_place``), and a prefill call's recurrence is one kernel a
layer over the call's real rows (``mamba._scans_on_chip``;
``recurrent_prefill_form`` names the form, ``recurrent_rows_walked`` counts
what it went over).

Compile counts are observable via ``trace_count()`` — the jitted bodies
bump a counter when TRACED (python executes only at trace time), which is
how tests assert the engine never recompiles after warmup.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from ..ops import latent_decode, paged_decode
from ..ops import latent_prefill as latent_prefill_op
from ..ops import paged_prefill as paged_prefill_op
from ..ops.rotary import apply_rotary, rope_frequencies
from . import block, mamba
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


def kv_layers(config):
    """(the layers that keep a sequence's whole length, the window layers):
    each a list of layer indices, in order.  A layer's place in its list is
    its index in that kind's pools.  A recurrent layer (gated delta-rule,
    state-space) keeps no rows and is in neither (``state_layers``)."""
    layers = range(config.n_layers)
    window = [i for i in layers if block.layer_window(config, i)]
    return [i for i in layers if i not in window
            and block.recurrent(config, i) is None], window


def state_layers(config) -> List[int]:
    """The recurrent layers, in order: a layer's place in the list is its
    index in the state pools ``S`` and ``conv``."""
    return [i for i in range(config.n_layers)
            if block.recurrent(config, i) is not None]


def _state_shapes(config, slots: int) -> Dict[str, Any]:
    """The state pools of ``config``'s recurrent layers for ``slots``
    sequences, by name (none without such layers)."""
    layers = state_layers(config)
    if not layers:
        return {}
    return block.recurrent(config).state_shapes(config, len(layers), slots)


def state_bytes(config, slots: int = 1) -> int:
    """The bytes of recurrent state ``slots`` sequences hold (0 without
    recurrent layers)."""
    return sum(math.prod(s.shape) * jnp.dtype(s.dtype).itemsize
               for s in _state_shapes(config, slots).values())


def _kv_slot(config, i: int):
    """Where layer ``i`` keeps its K/V: (``""`` or ``"w"``, the suffix of
    its kind's pool names, and its index in those pools)."""
    whole, window = kv_layers(config)
    return ("w", window.index(i)) if i in window else ("", whole.index(i))


def ring_entries(config, page_size: int, chunk: int) -> int:
    """Pages in a window layer's ring: the window, plus what one prefill
    chunk of ``chunk`` tokens (page-aligned) writes before it attends; 0
    for a configuration without window layers."""
    if not kv_layers(config)[1]:
        return 0
    return -(-config.window // page_size) + -(-chunk // page_size)


def latent_row_width(config) -> int:
    """The width of a latent pool's rows: ``kv_lora_rank +
    qk_rope_head_dim`` rounded up to whole tiles of 128 lanes (576 -> 640).
    A pool whose rows are not whole tiles gets a page-minor layout from the
    TPU compiler, which then copies the WHOLE pool to a row-minor one and
    back around every layer's write (4.8 GB of temporaries at the
    benchmark's geometry, compiled for a v5e); row-minor, the tiles hold
    the padding anyway."""
    return -(-(config.kv_lora_rank + config.qk_rope_head_dim) // 128) * 128


def init_paged_pools(config: LlamaConfig, num_pages: int,
                     page_size: int, window_pages: int = 0,
                     state_slots: int = 0) -> PagedPools:
    """One pool pair a kind of layer for the whole replica (of a latent
    model, the one pool ``kv`` of latent rows, over its latent layers); the
    last index (``num_pages``, ``window_pages``) of each is its scratch page
    (writes routed there are never read).  Beside them, where the
    configuration has recurrent layers, the state of ``state_slots``
    sequences (the module's ``state_shapes``), zeros."""
    whole, window = kv_layers(config)
    state = {}
    if state_layers(config):
        if state_slots <= 0:
            raise ValueError("a configuration with recurrent layers "
                             "keeps a state a slot: state_slots")
        state = {name: jnp.zeros(shape.shape, shape.dtype) for name, shape
                 in _state_shapes(config, state_slots).items()}
    if block.is_latent(config):
        return {"kv": jnp.zeros(
            (len(whole), num_pages + 1, page_size,
             latent_row_width(config)), config.dtype), **state}

    def pair(suffix, n_layers, pages):
        shape = (n_layers, pages + 1, page_size,
                 config.n_kv_heads, config.head_dim)
        return {"k" + suffix: jnp.zeros(shape, config.dtype),
                "v" + suffix: jnp.zeros(shape, config.dtype)}

    pools = pair("", len(whole), num_pages)
    if window:
        pools.update(pair("w", len(window), window_pages))
    return {**pools, **state}


def _whole(pools: PagedPools) -> jax.Array:
    """A pool behind the whole-length page tables: ``k``, or a latent
    model's ``kv``."""
    return pools["kv"] if "kv" in pools else pools["k"]


def _page_size(pools: PagedPools) -> int:
    return _whole(pools).shape[2]


def _write_rows(pool: jax.Array, layer: int, page_idx: jax.Array,
                off: jax.Array, rows: jax.Array) -> jax.Array:
    """Write one token's K or V per row: rows [N, H_kv, D] land at
    ``(page_idx[n], off[n])`` of ``layer``.  The index arrays lead and are
    adjacent, so each row is one contiguous ``[H_kv, D]`` update and the
    donated pool is updated in place."""
    return pool.at[layer, page_idx, off].set(rows.astype(pool.dtype))


#: The most float32 scores ``_attend_pages`` forms at once.  A prefill
#: chunk's whole matrix on a whole-length layer (28 heads x 2048 queries x
#: 15360 keys) is 3.5 GB; over this the queries are walked in blocks.
SCORE_BLOCK_BYTES = 512 * 2 ** 20


def _ring_positions(last: jax.Array, entries: int, ps: int) -> jax.Array:
    """The absolute position each slot of a gathered ring holds once
    position ``last`` [...] is written: [..., entries * ps].  Entry ``e``
    holds the newest page at or before ``last``'s whose number is ``e``
    modulo ``entries``; a negative result was never written, and one past
    ``last`` is what an older page left there."""
    j = jnp.arange(entries * ps)
    last_page = last[..., None] // ps
    page = last_page - (last_page - j // ps) % entries
    return page * ps + j % ps


def _attend_pages(config: LlamaConfig, q: jax.Array, k_pool: jax.Array,
                  v_pool: Optional[jax.Array], layer: int,
                  tables: jax.Array, visible: jax.Array) -> jax.Array:
    """Attention of q [B, Q, H, D] over the pages of tables [B, MAXP]:
    visible [B, Q, MAXP*page] bool says which gathered positions a query
    may see (scratch and unwritten ones never).  Returns [B, Q, H*D_v].

    A table's pages reshape to a linear ``[B, MAXP*page, H_kv, D]`` view
    as gathered; queries are grouped ``[.., H_kv, n_rep, D]`` and
    contracted against it directly.  K and V stay in the pool's dtype and
    the products accumulate in float32: a bf16 x bf16 product is exact in
    float32, so this is what upcasting the gathered K first computed,
    without the float32 copy.  Where the whole score matrix would pass
    ``SCORE_BLOCK_BYTES`` (a prefill chunk over a long table) the queries
    are walked in equal blocks, one at a time (``lax.map``).

    ``v_pool`` None: the latent pool (rows ``[.., D]``, no head axis).  A
    row is the key of one KV head that every query head shares, and its
    first ``kv_lora_rank`` columns are its value (one gather serves both);
    the scores are scaled for the width of an expanded head's q and k."""
    B, Q = q.shape[:2]
    n_kv = config.n_kv_heads if v_pool is not None else 1
    n_rep = config.n_heads // n_kv
    k_seq = k_pool[layer, tables].reshape(B, -1, n_kv, q.shape[-1])
    v_seq = v_pool[layer, tables].reshape(k_seq.shape) \
        if v_pool is not None else k_seq[..., :config.kv_lora_rank]
    qg = q.reshape(B, Q, n_kv, n_rep, q.shape[-1])

    def attend(qg, visible):
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k_seq,
                            preferred_element_type=jnp.float32) \
            * (config.head_dim ** -0.5)
        scores = jnp.where(visible[:, None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(v_seq.dtype)
        return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v_seq)

    blocks = 1
    while (B * config.n_heads * Q * k_seq.shape[1] * 4 // blocks
           > SCORE_BLOCK_BYTES and Q % (2 * blocks) == 0):
        blocks *= 2
    if blocks == 1:
        return attend(qg, visible).reshape(B, Q, -1)

    def split(x):  # [B, Q, ...] -> [blocks, B, Q / blocks, ...]
        return jnp.moveaxis(
            x.reshape(B, blocks, Q // blocks, *x.shape[2:]), 1, 0)

    out = jax.lax.map(lambda a: attend(*a), (split(qg), split(visible)))
    return jnp.moveaxis(out, 0, 1).reshape(B, Q, -1)


#: What ``_with_routing`` appends, in its order: the step record's keys.
ROUTING_KEYS = ("experts_hit", "expert_pairs", "expert_load_max")
#: Behind them where the program holds a share of the router's experts
#: (the three above count its OWN experts and the pairs that land on them):
#: the pairs the router made, here or elsewhere (real rows x ``top_k``).
SHARE_KEYS = ("expert_pairs_routed",)
#: What the decode step of a configuration with window layers, a latent
#: pool or recurrent layers appends behind them (``_with_kv_rows``): the
#: rows the program brought in (a gather's whole tables, or the pages a
#: kernel walks) and the rows a query could see.
KV_KEYS = ("kv_rows_read", "kv_rows_live")
#: Behind them where the decode program is compiled in the shared form
#: (``paged_decode_step(..., shared=True)``): the rows the walk slot by slot
#: would have fetched and this one did not, because a run of pages that
#: several slots hold was fetched once for all of them (0: nothing shared).
SHARED_KEYS = ("kv_rows_shared",)


def _counts_kv_rows(config) -> bool:
    """Rings, a latent pool, or rows of any kind beside recurrent layers."""
    return bool(kv_layers(config)[1]) or block.is_latent(config) \
        or bool(state_layers(config))


def routing_keys(config) -> tuple:
    """The names of the routing counters a program of ``config`` appends
    (``_with_routing``), in their order; none where no layer is routed."""
    if not block.is_routed(config):
        return ()
    share = config.router_width > config.n_experts
    return ROUTING_KEYS + (SHARE_KEYS if share else ())


def counter_keys(config, shared: bool = False) -> tuple:
    """The names of the int32 counters the decode program of ``config``
    (``shared``: in the shared form) appends to the tokens it returns, in
    their order (a prefill appends the ``routing_keys`` among them)."""
    return routing_keys(config) \
        + (KV_KEYS if _counts_kv_rows(config) else ()) \
        + (SHARED_KEYS if shared else ())


def routing_width(config, shared: bool = False) -> int:
    """How many int32 counters the decode program of ``config`` appends to
    the tokens it returns: ``len(counter_keys(config, shared))``."""
    return len(counter_keys(config, shared))


def _with_routing(config, toks: jax.Array,
                  counts: List[Optional[jax.Array]]) -> jax.Array:
    """``toks`` [N] int32, followed (where the layers were routed) by the
    program's ``routing_keys``: over all layers, the experts that got a
    token, the (token, expert) pairs routed to them, and the most tokens on
    one expert of one layer; where the program holds a share of the experts
    (``_moe_ffn`` then appends the pairs routed anywhere to a layer's
    counts), those summed too.  They ride in the array the engine reads back
    anyway, so they cost it no transfer of their own.  A dense layer among
    routed ones (its entry is None) has no experts to count."""
    counts = [c for c in counts if c is not None]
    if not counts:
        return toks
    c = jnp.stack(counts)  # [routed layers, E] (E + 1 of a share)
    more = []
    if c.shape[1] > config.n_experts:
        more, c = [jnp.sum(c[:, -1])], c[:, :-1]
    return jnp.concatenate([toks, jnp.stack(
        [jnp.sum(c > 0, dtype=jnp.int32), jnp.sum(c), jnp.max(c)] + more)])


def _walks_live_pages(config) -> bool:
    """Whether the programs of ``config`` attend through a kernel that walks
    the live pages in place of ``_attend_pages``' gather: on a TPU, a latent
    pool (the decode step's one query row a slot,
    ``ops.latent_decode_attention``, and the suffix prefill's many,
    ``ops.latent_prefill_attention``) or a model with window layers (the
    decode step, ``ops.paged_decode_attention``, and the suffix prefill,
    ``ops.paged_prefill_attention``: on its whole-length layers and its
    rings), or one whose K/V layers stand beside recurrent layers (the same
    two kernels on its few whole-length layers), which are the
    configurations whose decode program counts its rows.  The one place
    that chooses; ``_with_kv_rows`` counts by it."""
    kernel = latent_decode if block.is_latent(config) else paged_decode
    return _counts_kv_rows(config) and kernel.on_tpu()


def shares_walked_pages(config) -> bool:
    """Whether the decode program of ``config`` has a shared form (a run of
    pages that several slots hold walked once for all of them): where it
    walks a latent pool.  An engine asks for it
    (``paged_decode_step(..., shared=True)``) where its prefix cache can put
    one page into two tables, and nowhere else."""
    return block.is_latent(config) and _walks_live_pages(config)


def decode_attention_form(config, shared: bool = False) -> str:
    """The form the decode program of ``config`` attends its cache in, by
    name (``LLMServer.stats()["decode_attention"]``); ``shared``: as the
    engine compiles it (``shares_walked_pages``)."""
    if not _walks_live_pages(config):
        return "gather"
    return "walk+shared" if shared else "walk"


def prefill_attention_form(config) -> str:
    """The form the prefill calls of ``config`` attend in, by name
    (``LLMServer.stats()["prefill_attention"]``): the suffix prefill walks
    where the decode step does.  The engine then runs every call of a prompt
    through it, the first at ``prefix_len`` 0: ``prefill_logits`` (dense
    ``[H, S, S]`` scores, a latent model's chunk expanded) is for the
    configurations that gather."""
    return "walk" if _walks_live_pages(config) else "gather"


def attn_pairs(config, start: int, end: int) -> int:
    """The (query, key) pairs the real rows of one prefill call could see,
    summed over the layers that keep K/V rows: the rows at positions
    ``start .. end - 1`` see every position up to their own on a
    whole-length layer, at most the window's on a window layer.  Host
    arithmetic on Python ints (a first_tokens entry's ``attn_pairs``)."""
    whole, window = kv_layers(config)
    causal = (start + 1 + end) * (end - start) // 2  # sum of p + 1
    pairs = len(whole) * causal
    if window:
        w = config.window
        over = max(0, end - max(start, w))  # the rows that see w positions
        under = end - start - over          # the rows before the window fills
        pairs += len(window) * (
            over * w + (2 * start + 1 + under) * under // 2)
    return pairs


def _window_lo(config, seq_lens: jax.Array) -> jax.Array:
    """The first position a window layer's query at ``seq_lens`` sees."""
    return jnp.maximum(0, seq_lens - config.window + 1)


def _with_kv_rows(config, toks: jax.Array, page_tables: jax.Array,
                  ring_tables: Optional[jax.Array], seq_lens: jax.Array,
                  active: jax.Array, ps: int, runs=None) -> jax.Array:
    """``toks`` followed (where ``config`` has window layers, a latent
    pool or recurrent layers) by the decode step's ``KV_KEYS``, summed over
    the layers that keep rows and over slots:
    the rows of K (of the latent pool) the program brought in, and the rows
    a query could see (``len + 1`` on a whole-length layer, at most the
    window on a window layer, nothing in an empty slot).  What it brings in
    is every slot's whole table, live or not, where it gathers; where a
    kernel walks (``_walks_live_pages``), the pages it visits: of a
    whole-length table ``seq_lens // page + 1`` a slot, of a ring those from
    the window's first position to ``seq_lens``' (one of an empty slot).
    In the shared form (``runs``: the step's ``shared_runs``) a run's pages
    count once a pass, whatever its holders, and ``SHARED_KEYS`` follows:
    the rows that saved, so that the two sum to the per-slot walk's."""
    whole, window = kv_layers(config)
    if not _counts_kv_rows(config):
        return toks
    B = seq_lens.shape[0]
    if _walks_live_pages(config):
        last = seq_lens // ps
        read = ps * (len(whole) * jnp.sum(last + 1) + len(window) * jnp.sum(
            last - _window_lo(config, seq_lens) // ps + 1))
    else:
        read = B * ps * (
            len(whole) * page_tables.shape[1]
            + (len(window) * ring_tables.shape[1] if window else 0))
    rows = jnp.where(active, seq_lens + 1, 0)
    live = len(whole) * jnp.sum(rows) \
        + len(window) * jnp.sum(jnp.minimum(rows, config.window))
    counts = [jnp.asarray(read, jnp.int32), live.astype(jnp.int32)]
    if runs is not None:
        saved = (ps * len(whole) * runs.pages_saved).astype(jnp.int32)
        counts = [counts[0] - saved, counts[1], saved]
    return jnp.concatenate([toks, jnp.stack(counts)])


def _first_token(config, tok: jax.Array, counts) -> jax.Array:
    """A prefill's result: the scalar token, or [token, *routing_keys]
    where a layer was routed."""
    return tok[0] if all(c is None for c in counts) \
        else _with_routing(config, tok, counts)


# ------------------------------------------------------- adapter pool

#: {"qa": [A+1, L, d, r], "qb": [A+1, L, r, q_out], "va": [A+1, L, d, r],
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
    q_out = config.n_heads * config.head_dim
    kv_out = config.n_kv_heads * config.head_dim
    A, L = max_adapters + 1, config.n_layers
    return {
        "qa": jnp.zeros((A, L, d, rank), config.dtype),
        "qb": jnp.zeros((A, L, rank, q_out), config.dtype),
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
           valid: jax.Array, attend_state=None):
    """The decoder stack of a serving program over tokens [N]:
    ``attend(i, q, k, v)`` and ``lora(i, name, h)`` are ``block``'s
    closures with the layer's index in front (the pools and the adapter
    pool are indexed by it); ``attend_state(i, *projected, a)`` is the
    recurrent layers' (``_state_decode``, ``_state_prefill``).  Returns
    (hidden [N, d], the layers' expert counts)."""
    hidden, _, counts = block.decoder_stack(
        config, params, tokens,
        lambda i, layer, x: block.decoder_layer(
            config, layer, x, functools.partial(
                attend if block.recurrent(config, i) is None
                else attend_state, i),
            lora=functools.partial(lora, i), valid=valid,
            routed=block.is_routed(config, i),
            attn=block.layer_attn(config, i)))
    return hidden, counts


def _steps_in_place(config, i: Optional[int] = None) -> bool:
    """Whether the decode step hands recurrent layer ``i``'s module (with no
    layer named, the configuration's recurrent layers') the state POOL and
    the layer's place in it, to update where it lies: a state-space layer
    where ``mamba._steps_in_place`` says so (``ops.ssm_decode_step``).  A
    gated delta-rule layer's module takes its layer's slice."""
    return block.recurrent(config, i) is mamba \
        and mamba._steps_in_place(config)


def recurrent_decode_form(config) -> Optional[str]:
    """The form the decode program of ``config`` steps its recurrent layers'
    state in, by name (``LLMServer.stats()["recurrent_decode"]``):
    ``"kernel"`` (one pass over the pool where it lies) or ``"jnp"``; None
    without recurrent layers."""
    if not state_layers(config):
        return None
    return "kernel" if _steps_in_place(config) else "jnp"


def recurrent_prefill_form(config) -> Optional[str]:
    """The form the prefill programs of ``config`` carry their recurrent
    layers' state over a call's rows in, by name
    (``LLMServer.stats()["recurrent_prefill"]``): ``"kernel"`` (one call a
    layer, the state on the chip, the real rows only: a state-space layer
    where ``mamba._scans_on_chip`` says so) or ``"scan"`` (the module's
    chunk form over the whole bucket); None without recurrent layers."""
    layers = state_layers(config)
    if not layers:
        return None
    return "kernel" if block.recurrent(config, layers[0]) is mamba \
        and mamba._scans_on_chip(config) else "scan"


def recurrent_rows_walked(config, rows: int, real: int) -> int:
    """The positions the recurrent layers' chunk form steps the state over
    in ONE prefill call of ``rows`` rows, ``real`` of them real
    (``first_tokens[].scan_rows_padded`` is this less ``real``, over a
    prompt's calls): what the state-space module says (``rows_walked``);
    a gated delta-rule layer's chunk form goes over the bucket."""
    module = block.recurrent(config, state_layers(config)[0])
    return module.rows_walked(config, rows, real) if module is mamba \
        else rows


def _state_decode(config, pools: PagedPools, active: jax.Array, i: int,
                  *projected):
    """What the decode step does in recurrent layer ``i`` with the new rows'
    projections (``block.attention`` hands the closure what the layer's
    module projects, one row a slot, and the layer's weights last): every
    slot's state through the module's recurrent form (``decode_rows``), an
    inactive slot's (``active`` [B] false) left as it was, the state pools
    replaced.  A module that steps in place (``_steps_in_place``) is handed
    the state pool whole and returns it.  Returns what the module's
    ``output`` takes."""
    *projected, a = projected
    module = block.recurrent(config, i)
    layer = state_layers(config).index(i)
    in_place = _steps_in_place(config, i)
    S = None if in_place else pools["S"][layer]
    rows = pools["conv"][layer]

    def live(new, old):  # active [B] against [B, ...]
        return jnp.where(active[(slice(None),) + (None,) * (old.ndim - 1)],
                         new, old)

    if in_place:
        out, pools["S"], nxt = module.decode_rows(
            config, a, pools["S"], rows, *projected, layer=layer,
            active=active)
    else:
        out, new, nxt = module.decode_rows(config, a, S, rows, *projected)
        with jax.named_scope(module.SCOPE):  # the write is the recurrence's
            pools["S"] = pools["S"].at[layer].set(live(new, S))
    pools["conv"] = pools["conv"].at[layer].set(
        live(nxt.astype(rows.dtype), rows))
    return out


def _state_prefill(config, pools: PagedPools, slot: jax.Array, fresh,
                   valid: jax.Array, i: int, *projected):
    """What a prefill does in recurrent layer ``i`` with the projections of
    ONE sequence's rows ([S_pad, .], ``valid`` [S_pad] the real ones; the
    layer's weights last): the module's chunk form (``prefill_rows``) from
    zeros where ``fresh`` (True of the cold prefill; a scalar bool of a
    chunk's program), else from what ``slot`` holds (a chunk behind
    another), and the state behind the last real row left in the slot.
    Returns what the module's ``output`` takes."""
    *projected, a = projected
    layer = state_layers(config).index(i)
    S = jnp.where(fresh, 0, pools["S"][layer, slot])[None]
    rows = jnp.where(fresh, 0, pools["conv"][layer, slot])[None]
    out, new, nxt = block.recurrent(config, i).prefill_rows(
        config, a, S, rows, valid, *projected)
    pools["S"] = pools["S"].at[layer, slot].set(new[0])
    pools["conv"] = pools["conv"].at[layer, slot].set(
        nxt[0].astype(rows.dtype))
    return jax.tree.map(lambda t: t[0], out)  # the batch of one


def _write_kv(pools: PagedPools, layer: int, page_idx: jax.Array,
              off: jax.Array, **rows: jax.Array) -> None:
    """``_write_rows`` of a layer's new rows into the pools they are named
    for (``k`` and ``v`` of its kind, ``layer`` as ``_kv_slot`` gives it,
    or a latent model's ``kv``), the dict's pools replaced."""
    for name, new in rows.items():
        pools[name] = _write_rows(pools[name], layer, page_idx, off, new)


def _paged_attend(config, pools: PagedPools, i: int, q, k, v, *, whole,
                  ring, walk_lens: Optional[jax.Array] = None,
                  walk_rows: Optional[tuple] = None):
    """What the decode step and the suffix prefill do in layer ``i`` with
    q [B, Q, H, D] and the new rows' k, v [N, H_kv, D] (rotated by the
    caller where the layer has rotary): the rows written into the layer's
    kind of pool, and attention over that kind's tables.  ``whole`` and
    ``ring`` are each ``(page_idx [N], off [N], tables [B, T], visible
    [B, Q, T*page])``: the write indices and the read side of the
    whole-length table and of the window layers' ring (None for a
    configuration that has none).  Where ``_walks_live_pages``, a kernel
    walks the pages that hold what ``visible`` says, in place of the gather
    (``visible`` is then not read, and may be None): ``walk_lens`` [B] is
    the decode step's ``seq_lens`` (Q is 1); ``walk_rows`` a prefill's
    ``(first position, length)`` (B is 1: row ``r`` of q sits at ``first +
    r``, rows at or past ``length`` are padding)."""
    kind, slot = _kv_slot(config, i)
    page_idx, off, tables, visible = ring if kind else whole
    _write_kv(pools, slot, page_idx, off, **{"k" + kind: k, "v" + kind: v})
    with jax.named_scope("attn_window" if kind else "attn_global"):
        if walk_rows is not None:
            return paged_prefill_op.paged_prefill_attention(
                q[0], pools["k" + kind], pools["v" + kind], slot, tables[0],
                *walk_rows, window=config.window if kind else 0,
                sm_scale=config.head_dim ** -0.5).reshape(1, q.shape[1], -1)
        if walk_lens is None:
            return _attend_pages(config, q, pools["k" + kind],
                                 pools["v" + kind], slot, tables, visible)
        lo = _window_lo(config, walk_lens) if kind \
            else jnp.zeros_like(walk_lens)
        return paged_decode.paged_decode_attention(
            q[:, 0], pools["k" + kind], pools["v" + kind], slot, tables, lo,
            walk_lens, sm_scale=config.head_dim ** -0.5
        ).reshape(q.shape[0], 1, -1)


def _tile_padded(config, *parts: jax.Array) -> jax.Array:
    """``parts`` [..., w_i] side by side, then zeros up to
    ``latent_row_width``."""
    lead, width = parts[0].shape[:-1], sum(p.shape[-1] for p in parts)
    pad = jnp.zeros((*lead, latent_row_width(config) - width),
                    parts[0].dtype)
    return jnp.concatenate([*parts, pad], axis=-1)


def _latent_row(config, k_r: jax.Array, c: jax.Array, cos, sin,
                positions: jax.Array, rotary: bool = True) -> jax.Array:
    """What the latent pool keeps of the tokens at ``positions`` [N]:
    ``[c ; RoPE(k_r) ; 0]`` [N, latent_row_width], c [N, rank] normalised
    by the block, k_r [N, rope] the one rotary key the heads share
    (``rotary`` false, the layer's ``block.layer_rotary``: as it is)."""
    if rotary:
        k_r = _rotary_single(k_r[:, None], cos, sin, positions)[:, 0]
    return _tile_padded(config, c, k_r.astype(c.dtype))


def _latent_attend(config, pools: PagedPools, i: int, q, c, k_r, wkv_b, *,
                   cos, sin, positions, page_idx, off, tables, visible,
                   scope: str, walk_lens: Optional[jax.Array] = None,
                   walk_rows: Optional[tuple] = None, runs=None):
    """What the decode step and the suffix prefill do in layer ``i`` of a
    latent model with the new rows' q [N, H, nope + rope], latent c
    [N, rank] and rotary key k_r [N, rope] at ``positions`` [N] (N = B * Q
    rows of ``tables`` [B, MAXP] and ``visible`` [B, Q, MAXP*page]): the
    rows written into the latent pool, and attention over the table
    ABSORBED, never expanding a cached row.  With ``wkv_b``'s halves W_uk
    and W_uv: ``q_lat = q_n W_uk^T`` [N, H, rank], the scores are
    ``[q_lat ; RoPE(q_r) ; 0] . [c ; RoPE(k_r) ; 0]`` over the gathered rows, the
    values those rows' first ``rank`` columns, and the heads' outputs in
    the latent go through W_uv.  Where ``_walks_live_pages``, a kernel walks
    the live pages in place of the gather (``visible`` is then not read, and
    may be None): ``walk_lens`` [B] is the decode step's ``seq_lens`` (Q is
    1); ``walk_rows`` a prefill's ``(first position, length)`` (B is 1, as
    ``_paged_attend`` takes it); ``runs`` the decode step's shared runs
    (``latent_decode.shared_runs``, the shared form).  Returns [N, H * v]."""
    B = tables.shape[0]
    Q = q.shape[0] // B
    nope = config.qk_nope_head_dim
    rotary = block.layer_rotary(config, i)
    i = _kv_slot(config, i)[1]  # the layer's place among the latent ones
    _write_kv(pools, i, page_idx, off,
              kv=_latent_row(config, k_r, c, cos, sin, positions, rotary))
    w_uk, w_uv = block.latent_up(config, wkv_b)
    with jax.named_scope(scope):
        q_lat = jnp.einsum("nhd,chd->nhc", q[..., :nope], w_uk)
        q_r = q[..., nope:]
        if rotary:
            q_r = _rotary_single(q_r, cos, sin, positions)
        q_abs = _tile_padded(config, q_lat, q_r)
        if walk_lens is not None:  # Q is 1
            o_lat = latent_decode.latent_decode_attention(
                q_abs, pools["kv"], i, tables, walk_lens,
                rank=config.kv_lora_rank, sm_scale=config.head_dim ** -0.5,
                runs=runs)
        elif walk_rows is not None:  # B is 1
            o_lat = latent_prefill_op.latent_prefill_attention(
                q_abs, pools["kv"], i, tables[0], *walk_rows,
                rank=config.kv_lora_rank, sm_scale=config.head_dim ** -0.5)
        else:
            o_lat = _attend_pages(
                config, q_abs.reshape(B, Q, *q_abs.shape[1:]), pools["kv"],
                None, i, tables, visible)
        o_lat = o_lat.reshape(B * Q, config.n_heads, config.kv_lora_rank)
        return jnp.einsum("nhc,chd->nhd", o_lat, w_uv).reshape(B * Q, -1)


def decode_logits(config, params: Params, pools: PagedPools,
                  adapters: AdapterArrays, tokens: jax.Array,
                  page_tables: jax.Array, seq_lens: jax.Array,
                  active: jax.Array, adapter_ids: jax.Array,
                  ring_tables: Optional[jax.Array] = None, runs=None):
    """``paged_decode_step`` up to its sampling: (logits [B, V] float32,
    pools, per-layer expert counts).  ``runs``: the step's shared runs
    (``latent_decode.shared_runs``), handed to every latent layer's walk."""
    B, maxp = page_tables.shape
    ps = _page_size(pools)
    pools = dict(pools)
    cos, sin = rope_frequencies(block.rotary_dim(config), maxp * ps,
                                config.rope_theta)
    page_idx = page_tables[jnp.arange(B), seq_lens // ps]  # [B]
    off = seq_lens % ps
    # The length mask removes scratch/unwritten positions: [B, 1, MAXP*ps].
    visible = jnp.arange(maxp * ps)[None, None, :] \
        <= seq_lens[:, None, None]
    ring = None
    if ring_tables is not None:
        entries = ring_tables.shape[1]
        held = _ring_positions(seq_lens, entries, ps)  # [B, entries*ps]
        age = seq_lens[:, None] - held
        ring = (ring_tables[jnp.arange(B), (seq_lens // ps) % entries], off,
                ring_tables,
                ((held >= 0) & (age >= 0) & (age < config.window))[:, None])

    walk_lens = seq_lens if _walks_live_pages(config) else None

    def attend(i, q, k, v):  # one row a slot: [B, H, D]
        if block.layer_rotary(config, i):
            q = _rotary_single(q, cos, sin, seq_lens)
            k = _rotary_single(k, cos, sin, seq_lens)
        return _paged_attend(
            config, pools, i, q[:, None], k, v,
            whole=(page_idx, off, page_tables, visible), ring=ring,
            walk_lens=walk_lens)[:, 0]

    if block.is_latent(config):  # one row a slot: q [B, H, D], c, k_r
        attend = functools.partial(
            _latent_attend, config, pools, cos=cos, sin=sin,
            positions=seq_lens, page_idx=page_idx, off=off,
            tables=page_tables, visible=visible, scope="attn_latent",
            walk_lens=walk_lens, runs=runs)

    x, counts = _stack(config, params, tokens[:B], attend,
                       _adapter_lora(adapters, adapter_ids), active,
                       functools.partial(_state_decode, config, pools, active))
    logits = block.head(config, params, x)
    return logits, pools, counts


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,),
                   static_argnames=("shared",))
def paged_decode_step(config: LlamaConfig, params: Params,
                      pools: PagedPools, adapters: AdapterArrays,
                      tokens: jax.Array, page_tables: jax.Array,
                      seq_lens: jax.Array, active: jax.Array,
                      temps: jax.Array, adapter_ids: jax.Array,
                      key: jax.Array,
                      ring_tables: Optional[jax.Array] = None, *,
                      shared: bool = False):
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
    place and reads one gather of the page tables (on a TPU a latent
    model's, and a model's with window layers, the live pages where they
    lie: ``_walks_live_pages``).  ring_tables
    [B, entries] int32 are the window layers' rings (None for a
    configuration without them): those layers write and gather through
    them, that wide and no wider; the branch is taken layer by layer at
    trace time, so this stays the one decode program.

    The PRNG key and the slot lengths advance ON DEVICE (returned
    alongside the tokens), so the serving loop's only per-step host
    traffic is downloading the [B] sampled tokens — host-side key
    folding measurably dominates step time otherwise.  Returns
    (next_tokens [B], new_seq_lens [B], new_key, pools); behind
    next_tokens come the configuration's ``counter_keys``: a routed FFN's
    ``ROUTING_KEYS`` (``_with_routing``), window layers' ``KV_KEYS``
    (``_with_kv_rows``).

    ``shared`` (static; ``shares_walked_pages`` says whether ``config`` has
    the form) is what an engine whose prefix cache can put ONE page into
    several slots' tables asks for: the step finds the runs of pages that
    slots open with in common and every latent layer's walk fetches a run
    once for all its holders (``ops/latent_decode.py``); ``SHARED_KEYS``
    follows the counters.  Without it this is the program it was, text for
    text."""
    _bump("decode", tokens=tokens, page_tables=page_tables,
          seq_lens=seq_lens, temps=temps, adapter_ids=adapter_ids, key=key)
    runs = None
    if shared:  # found once, from what the step already has
        if not shares_walked_pages(config):
            raise ValueError("the decode program of this configuration has "
                             "no shared form on this backend")
        runs = latent_decode.shared_runs(
            page_tables, seq_lens, active, page=_page_size(pools),
            heads=config.n_heads)
    logits, pools, counts = decode_logits(
        config, params, pools, adapters, tokens, page_tables, seq_lens,
        active, adapter_ids, ring_tables, runs)
    key, sub = jax.random.split(key)
    toks = _sample_tokens(logits, temps, sub)
    new_lens = jnp.where(active, seq_lens + 1, 0)
    out = _with_kv_rows(config, _with_routing(config, toks, counts),
                        page_tables, ring_tables, seq_lens, active,
                        _page_size(pools), runs)
    return out, new_lens, key, pools


def prefill_logits(config, params: Params, pools: PagedPools,
                   adapters: AdapterArrays, tokens: jax.Array,
                   length: jax.Array, page_table: jax.Array,
                   adapter_id: jax.Array,
                   ring_table: Optional[jax.Array] = None,
                   state_slot: Optional[jax.Array] = None):
    """``paged_prefill`` up to its sampling: (logits [1, V] float32 after
    the last real position, pools, per-layer expert counts)."""
    _, s_pad = tokens.shape
    ps = _page_size(pools)
    pools = dict(pools)
    n_rep = config.n_heads // config.n_kv_heads
    cos, sin = rope_frequencies(block.rotary_dim(config), s_pad,
                                config.rope_theta)
    positions = jnp.arange(s_pad)
    page_idx = page_table[positions // ps]  # [S_pad]
    off = positions % ps
    causal = positions[None, :] <= positions[:, None]  # [S_pad, S_pad]
    if ring_table is not None:  # a bucket never laps the ring
        ring_idx = ring_table[(positions // ps) % ring_table.shape[0]]
        in_window = causal & (positions[:, None] - positions[None, :]
                              < config.window)

    def attend(i, q, k, v):  # [S_pad, H, D]: attention among the rows
        q, k = q.transpose(1, 0, 2), k.transpose(1, 0, 2)
        if block.layer_rotary(config, i):
            q = apply_rotary(q[None], cos, sin)[0]
            k = apply_rotary(k[None], cos, sin)[0]
        kind, slot = _kv_slot(config, i)
        _write_kv(pools, slot, ring_idx if kind else page_idx, off,
                  **{"k" + kind: k.transpose(1, 0, 2), "v" + kind: v})
        kr, vr = k, v.transpose(1, 0, 2)  # [H_kv, S_pad, D]
        if n_rep > 1:
            kr = jnp.repeat(kr, n_rep, axis=0)
            vr = jnp.repeat(vr, n_rep, axis=0)
        with jax.named_scope("attn_window" if kind else "attn_global"):
            scores = jnp.einsum("hqd,hkd->hqk", q.astype(jnp.float32),
                                kr.astype(jnp.float32)) \
                * (config.head_dim ** -0.5)
            scores = jnp.where((in_window if kind else causal)[None],
                               scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(vr.dtype)
            out = jnp.einsum("hqk,hkd->hqd", probs, vr)
        return out.transpose(1, 0, 2).reshape(s_pad, -1)

    def attend_latent(i, q, c, k_r, wkv_b):
        # The cold chunk EXPANDS its own rows' keys and values (nothing is
        # cached before them) and writes the latent rows for what follows.
        rotary = block.layer_rotary(config, i)
        row = _latent_row(config, k_r, c, cos, sin, positions, rotary)
        _write_kv(pools, _kv_slot(config, i)[1], page_idx, off, kv=row)
        nope, rank = config.qk_nope_head_dim, config.kv_lora_rank
        w_uk, w_uv = block.latent_up(config, wkv_b)
        with jax.named_scope("attn_latent_prefill"):
            k_n = jnp.einsum("sc,chd->shd", c, w_uk)
            v = jnp.einsum("sc,chd->shd", c, w_uv)
            q_r = q[..., nope:]
            if rotary:
                q_r = _rotary_single(q_r, cos, sin, positions)
            scores = (jnp.einsum("qhd,khd->hqk", q[..., :nope], k_n,
                                 preferred_element_type=jnp.float32)
                      + jnp.einsum("qhd,kd->hqk", q_r,
                                   row[:, rank:rank + q_r.shape[-1]],
                                   preferred_element_type=jnp.float32)) \
                * (config.head_dim ** -0.5)
            scores = jnp.where(causal[None], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
            return jnp.einsum("hqk,khd->qhd", probs, v).reshape(s_pad, -1)

    if block.is_latent(config):
        attend = attend_latent
    x, counts = _stack(config, params, tokens[0], attend,
                       _adapter_lora(adapters, adapter_id),
                       positions < length,
                       functools.partial(_state_prefill, config, pools,
                                         state_slot, True,
                                         positions < length))
    x_last = jnp.take(x, length - 1, axis=0)  # last REAL position
    logits = block.head(config, params, x_last)[None]
    return logits, pools, counts


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def paged_prefill(config: LlamaConfig, params: Params, pools: PagedPools,
                  adapters: AdapterArrays, tokens: jax.Array,
                  length: jax.Array, page_table: jax.Array,
                  adapter_id: jax.Array, temp: jax.Array, key: jax.Array,
                  ring_table: Optional[jax.Array] = None,
                  state_slot: Optional[jax.Array] = None):
    """Prefill ONE sequence's prompt into its pages and sample the first
    token.

    tokens [1, S_pad] int32 (prompt padded to a bucket length — one
    compile per bucket, see the engine's bucket table), length scalar =
    real prompt length, page_table [MAXP], adapter_id scalar pool-slot
    index (data, like the decode step's), ring_table [entries] the window
    layers' ring (None without them), state_slot a scalar: the slot whose
    recurrent state this sequence owns (None without gated delta-rule
    layers; the state starts from zeros here, whatever the slot held, and
    is left behind the last real row).  Padded tail positions write
    through the page table like real ones (their garbage K/V is masked by
    length until decode overwrites it) or to the scratch page past the
    allocated prefix.  The key advances on device like the decode step's.
    Returns (first_token scalar, new_key, pools); a model whose FFN is
    routed returns [first_token, *ROUTING_KEYS] in its place."""
    _bump("prefill", tokens=tokens, page_table=page_table, temp=temp,
          key=key)
    logits, pools, counts = prefill_logits(
        config, params, pools, adapters, tokens, length, page_table,
        adapter_id, ring_table, state_slot)
    key, sub = jax.random.split(key)
    tok = _sample_tokens(logits, temp[None], sub)
    return _first_token(config, tok, counts), key, pools


def prefill_prefix_logits(config, params: Params, pools: PagedPools,
                          adapters: AdapterArrays, tokens: jax.Array,
                          prefix_len: jax.Array, length: jax.Array,
                          page_table: jax.Array, adapter_id: jax.Array,
                          ring_table: Optional[jax.Array] = None,
                          state_slot: Optional[jax.Array] = None):
    """``paged_prefill_prefix`` up to its sampling; returns what
    ``prefill_logits`` returns."""
    _, s_pad = tokens.shape
    maxp = page_table.shape[0]
    ps = _page_size(pools)
    scratch = _whole(pools).shape[1] - 1
    pools = dict(pools)
    cos, sin = rope_frequencies(block.rotary_dim(config), maxp * ps,
                                config.rope_theta)
    positions = prefix_len + jnp.arange(s_pad)  # global positions
    valid = positions < length
    page_idx = jnp.where(
        valid, page_table[jnp.clip(positions // ps, 0, maxp - 1)], scratch)
    off = jnp.where(valid, positions % ps, 0)
    # Causal in global positions: [1, S_pad, MAXP*ps].
    visible = jnp.arange(maxp * ps)[None, None, :] \
        <= positions[None, :, None]
    ring = None
    if ring_table is not None:
        # The rows are written before they are read, so the ring has to
        # hold the window behind the first row and every row: what
        # ``ring_entries`` sizes it for, with ``prefix_len`` on a page's
        # edge.  A slot is seen by the rows at or behind what it holds,
        # inside their window.
        entries = ring_table.shape[0]
        last = jnp.minimum(prefix_len + s_pad, length) - 1
        held = _ring_positions(last, entries, ps)  # [entries*ps]
        age = positions[:, None] - held[None, :]
        ring = (jnp.where(valid, ring_table[(positions // ps) % entries],
                          pools["kw"].shape[1] - 1), off, ring_table[None],
                ((held >= 0) & (held <= last) & (age >= 0)
                 & (age < config.window))[None])

    walk_rows = (prefix_len, length) if _walks_live_pages(config) else None

    def attend(i, q, k, v):  # [S_pad, H, D]
        # Per-row RoPE at global positions (suffix rows are not at 0).
        if block.layer_rotary(config, i):
            q = _rotary_single(q, cos, sin, positions)
            k = _rotary_single(k, cos, sin, positions)
        # Attend the table (cached prefix + fresh suffix) like the decode
        # step, as a batch of one: gathered whole, or its live pages walked.
        return _paged_attend(
            config, pools, i, q[None], k, v,
            whole=(page_idx, off, page_table[None], visible), ring=ring,
            walk_rows=walk_rows)[0]

    if block.is_latent(config):  # q [S_pad, H, D], c, k_r: a batch of one
        attend = functools.partial(
            _latent_attend, config, pools, cos=cos, sin=sin,
            positions=positions, page_idx=page_idx, off=off,
            tables=page_table[None], visible=visible,
            scope="attn_latent_prefill", walk_rows=walk_rows)

    x, counts = _stack(config, params, tokens[0], attend,
                       _adapter_lora(adapters, adapter_id), valid,
                       functools.partial(_state_prefill, config, pools,
                                         state_slot, prefix_len == 0, valid))
    x_last = jnp.take(x, length - prefix_len - 1, axis=0)  # last real row
    logits = block.head(config, params, x_last)[None]
    return logits, pools, counts


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def paged_prefill_prefix(config: LlamaConfig, params: Params,
                         pools: PagedPools, adapters: AdapterArrays,
                         tokens: jax.Array, prefix_len: jax.Array,
                         length: jax.Array, page_table: jax.Array,
                         adapter_id: jax.Array, temp: jax.Array,
                         key: jax.Array,
                         ring_table: Optional[jax.Array] = None,
                         state_slot: Optional[jax.Array] = None):
    """Prefill only the SUFFIX of a prompt whose first ``prefix_len``
    positions are already cached in this sequence's page table (radix
    prefix-cache hit; shared pages were written by an earlier identical
    prefill, the COW page by ``copy_page``), or one CHUNK of a prompt
    longer than the largest bucket (the engine calls it with
    ``prefix_len`` advancing by the chunk and ``length`` the chunk's end).

    tokens [1, S_pad] int32 = prompt[prefix_len:] padded to a bucket,
    prefix_len / length scalars (length = FULL prompt length; both are
    data, so one compile per bucket serves every split point including
    mid-page COW divergence).  Suffix K/V is written through the page
    table at global positions ``prefix_len + row``; rows past the real
    suffix route to the scratch page (they may not even own a page).
    Queries then attend the table like the decode step — cached prefix
    plus fresh suffix — masked by global causal position; on a window
    layer, the ring (``ring_table``), masked by what each slot holds.  The
    table or ring is gathered whole (``_attend_pages``) or, on a TPU where
    the model has window layers or a latent pool (``_walks_live_pages``), its
    live pages are walked a block of query rows at a time
    (``ops/paged_prefill.py``, ``ops/latent_prefill.py``): the pages from the
    window of the block's first row to its last real row, and no score
    matrix in HBM.  A gated delta-rule layer takes the state of
    ``state_slot`` in (zeros where ``prefix_len`` is 0) and leaves the state
    behind this call's last real row there: a chunked prompt carries its
    state from chunk to chunk in the slot.
    Returns what ``paged_prefill`` returns."""
    _bump("prefill_prefix", tokens=tokens, page_table=page_table,
          temp=temp, key=key)
    logits, pools, counts = prefill_prefix_logits(
        config, params, pools, adapters, tokens, prefix_len, length,
        page_table, adapter_id, ring_table, state_slot)
    key, sub = jax.random.split(key)
    tok = _sample_tokens(logits, temp[None], sub)
    return _first_token(config, tok, counts), key, pools


@functools.partial(jax.jit, donate_argnums=(0,))
def copy_page(pools: PagedPools, src: jax.Array,
              dst: jax.Array) -> PagedPools:
    """Copy one page's K/V (a latent model's rows) across every layer
    (copy-on-write when a request diverges mid-page from a cached prefix).
    src/dst are data — one compile covers every divergence."""
    _bump("page_copy", src=src, dst=dst)
    return {name: pools[name].at[:, dst].set(pools[name][:, src])
            for name in ("k", "v", "kv") if name in pools}
