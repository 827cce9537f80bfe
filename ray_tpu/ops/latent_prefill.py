"""Prefill attention over a LATENT paged pool: ``page_walk``'s walk of a
block of query rows of one sequence, over the latent kind, in which the query
rows of one prefill call (a prompt's chunk, a prefix hit's suffix, a prompt's
first rows at position 0) attend the live pages of their sequence's table
where they lie, and no array of the scores' shape reaches HBM.

A token's row is ``[c ; RoPE(k_r) ; 0]`` (``latent_decode.py``).  Row ``r`` of
the ABSORBED queries ``q [S, H, W]`` sits at position ``p = first + r`` and
sees ``0 <= j <= p``.  The gather form (``paged._attend_pages``) brings the
WHOLE table into a fresh array and forms float32 scores against all of it (20
heads x 2048 rows x 19200 keys: 3.1 GB a layer and call, written and read
twice); here a block of ``BLOCK_ROWS`` query rows with ALL heads is one grid
step: every head meets the same rows, so the block is one ``[H x rows, W]``
matrix against ``[keys, W]`` for the scores and against the same rows' first
``rank`` columns for the values (one fetch serves both), over pages ``0 .. hi
// page``, ``BLOCK_KEYS`` positions at a time.

The kernel is ``latent_prefill`` in the compiled program
(``mosaic:latent_prefill.N`` in a device trace); its call is jitted on its
own (``page_walk._call``), one trace a program."""

from __future__ import annotations

import jax

from . import page_walk

#: Query rows a grid step (fewer where the call has fewer), and positions of
#: the pool a block of its walk: the scores formed at once are ``H x
#: BLOCK_ROWS x BLOCK_KEYS`` float32 in VMEM (2.6 MB at 20 heads, 4.2 at 32),
#: the accumulator ``H x BLOCK_ROWS x rank`` (2.6 and 4.2 MB).  Measured on a
#: v5e (PERF.md section 6, PR 45), a 1024-row suffix behind 16384 cached rows
#: at 20 heads and a 2048-row chunk behind 2048 at 32: 4.70 and 3.47 ms a
#: layer here (81% and 64% of the MXU peak); 128 x 512 takes 4.54 and 4.96,
#: 64 x 1024 4.73 and 5.61, 64 x 2048 7.68 and 5.72: past some 1.3 M scores
#: a block (5 MB) the softmax's temporaries spill.  Rows of 64 also leave
#: less of a bucket's padding to walk than 128 (2.66 against 2.87 ms at 562
#: real rows of 1024).
BLOCK_ROWS = 64
BLOCK_KEYS = 512
#: What the kernel may hold in VMEM: the query block and its output, double
#: buffered, the float32 accumulator of every head and the scores' blocks.
VMEM_LIMIT_BYTES = 64 << 20


def _blocks(rows: int, page: int) -> tuple:
    """(query rows a grid step, pages a block of keys) for a call of
    ``rows`` query rows over pages of ``page`` positions."""
    return min(BLOCK_ROWS, rows), max(1, BLOCK_KEYS // page)


def check_geometry(q: jax.Array, kv: jax.Array, table: jax.Array,
                   rank: int) -> None:
    """A ValueError naming what the kernel cannot take: it moves whole pages
    by DMA and multiplies them as they land, so a page has to be whole
    sublane tiles, a row (and its value part) whole lane tiles, and the query
    rows whole blocks of whole sublane tiles."""
    page_walk.check_operands(
        "latent prefill attention takes q [S, H, W], a pool "
        "[L, P+1, page, W] of one dtype and a table [T]", q, (kv,), table)
    page, width = kv.shape[2:]
    tile = page_walk.sublanes(kv.dtype)
    rows = _blocks(q.shape[0], page)[0]
    page_walk.check_tiles(
        f"latent prefill attention needs pages of whole sublane tiles, rows "
        f"of whole lane tiles and query rows in whole blocks of whole "
        f"tiles: page {page} (tiles of {tile} rows of {kv.dtype}), row "
        f"width {width} and value width {rank} (tiles of 128), "
        f"{q.shape[0]} query rows in blocks of {rows}",
        kv, rank % 128 or not 0 < rank <= width
        or q.shape[0] % rows or rows % tile)


def latent_prefill_attention(q: jax.Array, kv: jax.Array, layer,
                             table: jax.Array, first, length, *, rank: int,
                             sm_scale: float,
                             interpret: bool = False) -> jax.Array:
    """Attention of the absorbed query rows of one prefill call, q
    [S, H, W], over the pages of ``table`` [T] in ``kv[layer]``
    ([L, P+1, page, W]), into which the call's own rows are already written:
    row ``r`` sits at position ``first + r`` and sees the positions ``j <=
    first + r``, position ``j`` in entry ``j // page`` of the table.  Rows
    at or past ``length`` are padding: their outputs are finite and mean
    nothing.  Returns the heads' outputs in the latent, [S, H, rank] in q's
    dtype.  A geometry the kernel cannot take raises (``check_geometry``)."""
    check_geometry(q, kv, table, rank)
    rows, per_block = _blocks(q.shape[0], kv.shape[2])
    return page_walk.walk_rows(
        "latent_prefill", q, (kv,), layer, table, first, length, rank=rank,
        window=0, rows=rows, per_block=per_block,
        vmem_limit_bytes=VMEM_LIMIT_BYTES, sm_scale=sm_scale,
        interpret=interpret)
