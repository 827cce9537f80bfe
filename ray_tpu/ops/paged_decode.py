"""Decode attention over paged K/V POOLS: a Pallas TPU kernel that walks each
slot's live pages where they lie, of a whole-length table or of a window
layer's ring, and reads every page once.

``models/paged.py`` keeps a token's K and V on a layer as one contiguous
``[H_kv, D]`` row each of the pools ``[L_kind, P+1, page, H_kv, D]``.  The
decode step has one query row a head and slot, ``q [B, H, D]``.  The gather
form (``paged._attend_pages``) brings every slot's WHOLE table of both pools
into fresh arrays and passes over them for the scores and again for the
values; here:

- the pools stay in HBM as they are (``pl.ANY``: no block of them is the
  pipeline's, so nothing copies or re-lays them; the kernel is handed a
  page as ``[page x H_kv, D]``, position-major as it lies, which the TPU's
  compiler makes a bitcast of the pool: rows of 128 lanes pack the same way
  whatever the tile's height); the layer, the page tables and the slots'
  first and last visible positions ``lo`` and ``hi`` are scalar-prefetch
  operands;
- position ``p`` lives in entry ``(p // page) % T`` of its slot's table of
  ``T`` entries.  That one rule is both kinds of cache: a whole-length table
  never reaches its modulus, a ring wraps by it (``paged._ring_positions``);
- slot ``b`` is one grid step.  It visits pages ``lo[b] // page ..
  hi[b] // page`` and no other, a block of ``_pages_per_block`` at a time:
  one DMA a page of K and one of V (a page is contiguous) into one half of a
  double buffer in VMEM, the next block's DMAs (the next slot's first block
  behind a slot's last) in flight while this block is multiplied.  A page
  outside the walk is neither fetched nor waited for; an empty slot
  (``lo = hi = 0``, an all-scratch table) costs one page;
- GQA without a repeat and without cutting the block a head (two KV heads'
  rows share a 32-bit word of a bfloat16 pool, so no DMA can part them):
  ALL the query heads meet all of a block's rows in one product; a score
  whose row is another KV head's than its query's group is masked like a
  position outside ``lo..hi``, so its probability is exactly 0 and the one
  value product over all rows adds nothing of another head.  ``H_kv`` times
  the operations the heads need, against pages that the DMAs bring no
  faster (alone on a v5e at Trinity-Mini's geometry: 81% of the HBM peak);
- online softmax over the blocks: running maximum, sum and accumulator in
  float32.  Operands in the pool's dtype, both products accumulated in
  float32, the probabilities cast to the pool's dtype before the value
  product: the arithmetic of ``_attend_pages``, which is this kernel's
  reference.

A block that the walk does not fill holds, past its live pages, what an
earlier block (another slot's) left in the buffer.  Those rows' scores are
masked, so they meet a probability of exactly 0, but 0 x NaN is NaN: the
pages of the VALUE buffer that this block did not fetch are zeroed before
the products (a masked score never reads its key), and one slot's rows
never reach another slot's output.

The kernel is ``paged_decode`` in the compiled program
(``mosaic:paged_decode.N`` in a device trace).  Off the TPU nothing here
runs unless a test asks for ``interpret``: ``models/paged.py`` chooses."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF
from .latent_decode import _sublanes, on_tpu  # noqa: F401 (paged asks it)

#: Bytes of K and V one block of the walk brings into one half of the double
#: buffer and multiplies at once: four pages of 128 x 4 x 128 bf16 K and V.
#: Measured alone on a v5e at that geometry: 0.25 MB 1.15 ms for five layers'
#: walks, 0.5 MB 0.85, 1 MB 0.765, 2 MB 0.79, 4 MB 0.86.
BLOCK_BYTES = 1 << 20


def _pages_per_block(k_pool: jax.Array, entries: int) -> int:
    page_bytes = 2 * math.prod(k_pool.shape[2:]) * k_pool.dtype.itemsize
    return max(1, min(entries, BLOCK_BYTES // page_bytes))


def check_geometry(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                   tables: jax.Array) -> None:
    """A ValueError naming what the kernel cannot take: it moves whole pages
    by DMA and multiplies them as they land, ``[page x H_kv, D]``, so a
    page's rows have to be whole sublane tiles and a head whole lane tiles."""
    if q.ndim != 3 or k_pool.ndim != 5 or k_pool.shape != v_pool.shape \
            or q.shape[-1] != k_pool.shape[-1] or tables.ndim != 2 \
            or tables.shape[0] != q.shape[0] \
            or not q.dtype == k_pool.dtype == v_pool.dtype:
        raise ValueError(
            f"paged decode attention takes q [B, H, D], pools "
            f"[L, P+1, page, H_kv, D] of K and of V and tables [B, T], of "
            f"one dtype: got {q.shape} {q.dtype}, {k_pool.shape} "
            f"{k_pool.dtype}, {v_pool.shape} {v_pool.dtype} and "
            f"{tables.shape}")
    page, n_kv, dim = k_pool.shape[2:]
    if q.shape[1] % n_kv or dim % 128 \
            or (page * n_kv) % _sublanes(k_pool.dtype):
        raise ValueError(
            f"paged decode attention needs whole groups of query heads, "
            f"heads of whole lane tiles and pages of whole sublane tiles: "
            f"{q.shape[1]} heads over {n_kv} KV heads, head_dim {dim} "
            f"(tiles of 128), page {page} x {n_kv} rows (tiles of "
            f"{_sublanes(k_pool.dtype)} rows of {k_pool.dtype})")


def _kernel(layer_ref, lo_ref, hi_ref, tables_ref, q_ref, k_ref, v_ref, o_ref,
            k_buf, v_buf, sems, half_ref, *, entries: int, page: int,
            n_rep: int, sm_scale: float):
    b, n_slots = pl.program_id(0), pl.num_programs(0)
    _, per_block, page_rows, dim = k_buf.shape
    n_kv = page_rows // page
    rows = per_block * page_rows
    layer = layer_ref[0]

    def each_page(slot, block, half, live, dead=None):
        """``live(copy)`` on the DMAs (of K, of V) of every page of
        ``slot``'s ``block`` that its walk visits into ``half`` of the
        buffers, ``dead(k)`` on the block's other pages."""
        first, last = lo_ref[slot] // page, hi_ref[slot] // page
        for k in range(per_block):
            p = first + block * per_block + k

            @pl.when(p <= last)
            def _(k=k, p=p):
                at = tables_ref[slot * entries + p % entries]
                for s, (pool, buf) in enumerate(((k_ref, k_buf),
                                                 (v_ref, v_buf))):
                    live(pltpu.make_async_copy(
                        pool.at[layer, at], buf.at[half, k],
                        sems.at[s, half]))

            if dead is not None and k:  # a block's first page is live
                pl.when(p > last)(functools.partial(dead, k))

    def start(slot, block, half):
        each_page(slot, block, half, lambda copy: copy.start())

    @pl.when(b == 0)
    def _():
        half_ref[0] = 0
        start(0, 0, 0)

    lo, hi = lo_ref[b], hi_ref[b]
    first = lo // page
    n_blocks = pl.cdiv(hi // page - first + 1, per_block)
    q = q_ref[...]  # [heads, D]
    heads = q.shape[0]
    # Column c of a block's scores is the row of position c // H_kv (from
    # the block's first) and KV head c % H_kv; query row h is of the group
    # h // n_rep (the padding's rows of the last, their outputs dropped).
    col = jax.lax.broadcasted_iota(jnp.int32, (heads, rows), 1)
    group = jnp.minimum(
        jax.lax.broadcasted_iota(jnp.int32, (heads, rows), 0) // n_rep,
        n_kv - 1)
    own = col % n_kv == group
    col_pos = first * page + col // n_kv

    def body(j, carry):
        m, l, acc = carry
        half = half_ref[0]
        other = 1 - half

        @pl.when(j + 1 < n_blocks)
        def _():
            start(b, j + 1, other)

        @pl.when((j + 1 == n_blocks) & (b + 1 < n_slots))
        def _():
            start(b + 1, 0, other)

        def zero(k):
            v_buf[half, k] = jnp.zeros((page_rows, dim), v_buf.dtype)

        each_page(b, j, half, lambda copy: copy.wait(), zero)
        half_ref[0] = other
        keys = k_buf[half].reshape(rows, dim)
        s = jax.lax.dot_general(
            q, keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [heads, rows]
        pos = col_pos + j * (per_block * page)
        s = jnp.where(own & (pos >= lo) & (pos <= hi), s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jnp.dot(
            p.astype(v_buf.dtype), v_buf[half].reshape(rows, dim),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(
        0, n_blocks, body,
        (jnp.full((heads, 1), NEG_INF, jnp.float32),
         jnp.zeros((heads, 1), jnp.float32),
         jnp.zeros((heads, dim), jnp.float32)))
    o_ref[...] = (acc / l).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, layer, tables: jax.Array,
                           lo: jax.Array, hi: jax.Array, *, sm_scale: float,
                           interpret: bool = False) -> jax.Array:
    """Attention of one query row a head and slot, q [B, H, D], over the
    pages of ``tables`` [B, T] in ``k_pool[layer]`` and ``v_pool[layer]``
    ([L, P+1, page, H_kv, D]): slot ``b`` sees the positions ``lo[b] <= p <=
    hi[b]``, position ``p`` in entry ``(p // page) % T`` of its table, the
    row at ``hi[b]`` being the one the step just wrote.  A whole-length
    layer passes ``lo = 0``; a ring layer the first position of its window
    and its ring table (of more entries than a window has pages, so no
    entry is visited twice).  Returns [B, H, D] in q's dtype.  A geometry
    the kernel cannot take raises (``check_geometry``)."""
    check_geometry(q, k_pool, v_pool, tables)
    B, H, D = q.shape
    page, n_kv = k_pool.shape[2:4]
    entries = tables.shape[1]
    # Whole sublane tiles of query rows; the padding's outputs are dropped.
    tile = _sublanes(q.dtype)
    heads = -(-H // tile) * tile
    q = jnp.pad(q, ((0, 0), (0, heads - H), (0, 0)))
    per_block = _pages_per_block(k_pool, entries)
    buffer = pltpu.VMEM((2, per_block, page * n_kv, D), k_pool.dtype)
    out = pl.pallas_call(
        functools.partial(_kernel, entries=entries, page=page,
                          n_rep=H // n_kv, sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((None, heads, D), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, heads, D), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                buffer, buffer,
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, heads, D), q.dtype),
        # A slot's last block starts the next slot's first: in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      lo.astype(jnp.int32), hi.astype(jnp.int32),
      tables.astype(jnp.int32).reshape(-1), q,
      *(pool.reshape(*pool.shape[:2], page * n_kv, D)
        for pool in (k_pool, v_pool)))
    return out[:, :H]
