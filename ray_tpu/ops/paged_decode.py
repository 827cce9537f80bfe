"""Decode attention over paged K/V POOLS: ``page_walk``'s walk of one query
row a slot, over the K/V pair, of a whole-length table or of a window
layer's ring.

``models/paged.py`` keeps a token's K and V on a layer as one contiguous
``[H_kv, D]`` row each of the pools ``[L_kind, P+1, page, H_kv, D]``.  The
decode step has one query row a head and slot, ``q [B, H, D]``.  The gather
form (``paged._attend_pages``) brings every slot's WHOLE table of both pools
into fresh arrays and passes over them for the scores and again for the
values; here slot ``b`` visits pages ``lo[b] // page .. hi[b] // page`` and no
other, a block of ``_pages_per_block`` at a time, and ALL the query heads
meet all of a block's rows in one product, another KV head's rows masked
(two KV heads' rows share a 32-bit word of a bfloat16 pool, so no DMA can
part them): ``H_kv`` times the operations the heads need, against pages that
the DMAs bring no faster (alone on a v5e at Trinity-Mini's geometry: 81% of
the HBM peak).

The kernel is ``paged_decode`` in the compiled program
(``mosaic:paged_decode.N`` in a device trace)."""

from __future__ import annotations

import math

import jax

from . import page_walk
from .page_walk import on_tpu  # noqa: F401 (paged asks it of this kind)

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
    page_walk.check_operands(
        "paged decode attention takes q [B, H, D], pools "
        "[L, P+1, page, H_kv, D] of K and of V and tables [B, T], of one "
        "dtype", q, (k_pool, v_pool), tables, slots=True)
    page, n_kv, dim = k_pool.shape[2:]
    page_walk.check_tiles(
        f"paged decode attention needs whole groups of query heads, heads "
        f"of whole lane tiles and pages of whole sublane tiles: "
        f"{q.shape[1]} heads over {n_kv} KV heads, head_dim {dim} (tiles of "
        f"128), page {page} x {n_kv} rows (tiles of "
        f"{page_walk.sublanes(k_pool.dtype)} rows of {k_pool.dtype})",
        k_pool, q.shape[1] % n_kv)


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
    return page_walk.walk_slots(
        "paged_decode", q, (k_pool, v_pool), layer, tables, lo, hi,
        rank=q.shape[-1],
        per_block=_pages_per_block(k_pool, tables.shape[1]),
        sm_scale=sm_scale, interpret=interpret)
