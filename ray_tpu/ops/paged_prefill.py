"""Prefill attention over paged K/V POOLS: ``page_walk``'s walk of a block of
query rows of one sequence, over the K/V pair, in which the query rows of one
prefill call (a prompt's chunk, a prefix hit's suffix, a prompt's first rows
at position 0) attend the live pages of their sequence's whole-length table
or window ring where they lie, and no array of the scores' shape reaches HBM.

Row ``r`` of ``q [S, H, D]`` sits at position ``p = first + r`` and sees
``max(0, p - window + 1) <= j <= p`` on a window layer, ``0 <= j <= p`` on a
whole-length one (``window`` 0).  The gather form (``paged._attend_pages``)
brings the WHOLE table into fresh arrays and forms float32 scores against all
of it (28 heads x 2048 rows x 15360 keys: 3.5 GB a layer and call, written
and read twice); here a block of ``BLOCK_ROWS`` query rows walks the pages
its real rows see and no other, ``BLOCK_KEYS`` positions at a time, a query
group against its OWN KV head's rows, parted in VMEM
(``page_walk._head_rows``).

The kernel is ``paged_prefill`` in the compiled program
(``mosaic:paged_prefill.N`` in a device trace); its call is jitted on its own
(``page_walk._call``), one trace a program and kind of layer."""

from __future__ import annotations

import jax

from . import page_walk

#: Query rows a grid step (fewer where the call has fewer), and positions of
#: K and V a block of its walk: the scores a KV head's group forms at once
#: are ``n_rep x BLOCK_ROWS x BLOCK_KEYS`` float32 in VMEM (3.7 MB at 7:1).
#: Measured on a v5e at both cells' geometries (PERF.md section 6, PR 44):
#: a chunk's layer takes 0.92 ms at 128 x 1024 where 256 x 256 takes 1.60;
#: the running maximum, sum and accumulator are rescaled once a block, so
#: few wide blocks beat many narrow ones until the scores spill (256 x 1024).
BLOCK_ROWS = 128
BLOCK_KEYS = 1024
#: What the kernel may hold in VMEM: the query block and its output, double
#: buffered, the float32 accumulators of every head and the scores' blocks.
VMEM_LIMIT_BYTES = 64 << 20


def _blocks(rows: int, page: int) -> tuple:
    """(query rows a grid step, pages a block of keys) for a call of
    ``rows`` query rows over pages of ``page`` positions."""
    return min(BLOCK_ROWS, rows), max(1, BLOCK_KEYS // page)


def check_geometry(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                   table: jax.Array) -> None:
    """A ValueError naming what the kernel cannot take: it moves whole pages
    by DMA and parts their KV heads by 32-bit words, so a page's rows have
    to be whole sublane tiles, a head whole lane tiles, the KV heads of a
    16-bit pool even in number (or one), and the query rows whole blocks of
    whole sublane tiles."""
    page_walk.check_operands(
        "paged prefill attention takes q [S, H, D], pools "
        "[L, P+1, page, H_kv, D] of K and of V and a table [T], of one "
        "dtype", q, (k_pool, v_pool), table)
    page, n_kv, dim = k_pool.shape[2:]
    tile = page_walk.sublanes(k_pool.dtype)
    packed = 4 // k_pool.dtype.itemsize
    rows = _blocks(q.shape[0], page)[0]
    page_walk.check_tiles(
        f"paged prefill attention needs whole groups of query heads, heads "
        f"of whole lane tiles, pages of whole sublane tiles whose KV heads "
        f"part by 32-bit words, and query rows in whole blocks of whole "
        f"tiles: {q.shape[1]} heads over {n_kv} KV heads, head_dim {dim} "
        f"(tiles of 128), page {page} x {n_kv} rows (tiles of {tile} rows "
        f"of {k_pool.dtype}, {packed} a word), {q.shape[0]} query rows in "
        f"blocks of {rows}",
        k_pool, q.shape[1] % n_kv or (n_kv > 1 and n_kv % packed)
        or q.shape[0] % rows or rows % tile)


def paged_prefill_attention(q: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, layer, table: jax.Array,
                            first, length, *, window: int = 0,
                            sm_scale: float,
                            interpret: bool = False) -> jax.Array:
    """Attention of the query rows of one prefill call, q [S, H, D], over
    the pages of ``table`` [T] in ``k_pool[layer]`` and ``v_pool[layer]``
    ([L, P+1, page, H_kv, D]), into which the call's own rows are already
    written: row ``r`` sits at position ``first + r`` and sees the positions
    ``j <= first + r`` (on a window layer, ``window`` > 0, those with ``j >
    first + r - window``), position ``j`` in entry ``(j // page) % T`` of
    the table.  A whole-length layer passes its page table; a ring layer its
    ring table, which holds the window behind ``first`` and the call's rows
    (``paged.ring_entries``).  Rows at or past ``length`` are padding: their
    outputs are finite and mean nothing.  Returns [S, H, D] in q's dtype.  A
    geometry the kernel cannot take raises (``check_geometry``)."""
    check_geometry(q, k_pool, v_pool, table)
    rows, per_block = _blocks(q.shape[0], k_pool.shape[2])
    return page_walk.walk_rows(
        "paged_prefill", q, (k_pool, v_pool), layer, table, first, length,
        rank=q.shape[-1], window=window, rows=rows, per_block=per_block,
        vmem_limit_bytes=VMEM_LIMIT_BYTES, sm_scale=sm_scale,
        interpret=interpret)
