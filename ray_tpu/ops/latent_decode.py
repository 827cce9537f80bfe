"""Decode attention over a LATENT paged pool: ``page_walk``'s walk of one
query row a slot, over the latent kind.

What ``models/paged.py`` keeps of a token on a layer of a latent model is one
row ``[c ; RoPE(k_r) ; 0]`` (``paged.latent_row_width`` numbers): the key of
ONE KV head that every query head shares, whose first ``rank`` columns are
that head's value.  The decode step has one ABSORBED query row a head and
slot, ``q [B, H, W]``.  The gather form (``paged._attend_pages``) brings every
slot's whole table into a fresh array and passes over it for the scores and
again for the values; here slot ``b`` visits pages ``0 .. seq_lens[b] //
page`` of its table and no other, ``PAGES_PER_BLOCK`` at a time, and a block
in VMEM serves both products: the scores ``q . row`` over all ``W`` columns
(the padding is zero on both sides), the values the rows' first ``rank``
columns, ``position <= seq_lens[b]`` masked inside the last block.

The kernel is ``latent_decode`` in the compiled program
(``mosaic:latent_decode.N`` in a device trace).

Where the engine's prefix cache can put ONE page into several slots' tables
(``paged_decode_step(..., shared=True)``), the step finds the runs of pages
that slots open with in common (``shared_runs``, once a step) and a layer's
call has two parts: ``latent_decode_shared`` (``page_walk.walk_shared``)
fetches each run once and multiplies it against the stacked queries of all
its holders, and ``latent_decode`` walks each slot's own tail from the run's
end, going on from the softmax the pass left.  Same keys, same arithmetic;
a step with no run anywhere is the walk above, bit for bit."""

from __future__ import annotations

import jax

from . import page_walk
from .page_walk import on_tpu  # noqa: F401 (paged asks it of this kind)

#: Pages one block of the walk brings in and multiplies at once: 8 pages of
#: 128 x 640 bf16 are 1.3 MB a half of the double buffer.
PAGES_PER_BLOCK = 8
#: Of a shared pass: the stacked query rows multiplied at once (eight slots
#: of GLM-4.7-Flash's 20 heads: a group of eight, sixteen or all 32 slots
#: fills whole blocks) against a block of ``SHARED_PAGES_PER_BLOCK`` pages
#: (the scores formed at once are ``SHARED_ROWS x pages x page`` float32 in
#: VMEM, 1.3 MB), and what the kernel may hold there: the stacked queries,
#: the partials of every row, the double buffer and the scores' temporaries.
#: Measured on a v5e (PERF.md section 6, PR 56), a step's six layers, 32
#: slots of 20 heads over pages of 128 x 640 bf16, the per-slot walk beside
#: the two kernels with everything between them: all 32 slots on one run of
#: 128 pages 5.67 ms against 1.33 here (128 x 8: 1.46; 160 x 8: 1.39; 320 x
#: 16: 1.31; 640 x 16: 1.32); two groups of sixteen 3.00 against 0.92 (1.32
#: at 640 rows); sixteen PAIRS on runs of 32 pages 1.67 against 1.65 here,
#: 2.46 at 320 rows and 6.38 at 640 (a pass multiplies whole blocks of rows,
#: its members' or not).
SHARED_ROWS = 160
SHARED_PAGES_PER_BLOCK = 16
SHARED_VMEM_LIMIT_BYTES = 64 << 20


def check_geometry(q: jax.Array, kv: jax.Array, rank: int) -> None:
    """A ValueError naming what the kernel cannot take: it moves whole pages
    by DMA and multiplies them as they land, so a page has to be whole
    sublane tiles and a row (and its value part) whole lane tiles."""
    page_walk.check_operands(
        "latent decode attention takes q [B, H, W] and a pool "
        "[L, P+1, page, W] of one dtype", q, (kv,))
    page, width = kv.shape[2:]
    page_walk.check_tiles(
        f"latent decode attention needs pages of whole sublane tiles and "
        f"rows of whole lane tiles: page {page} (tiles of "
        f"{page_walk.sublanes(kv.dtype)} rows of {kv.dtype}), row width "
        f"{width} and value width {rank} (tiles of 128)",
        kv, rank % 128 or not 0 < rank <= width)


def _shared_rows(slots: int, heads: int) -> int:
    """The stacked query rows a block of a shared pass: ``SHARED_ROWS``, or
    all there are where they are fewer, in whole tiles of either dtype."""
    return min(SHARED_ROWS, -(-slots * heads // 16) * 16)


def shared_runs(page_tables: jax.Array, seq_lens: jax.Array,
                active: jax.Array, *, page: int,
                heads: int) -> page_walk.Runs:
    """The runs of pages that the slots of a decode step hold in common
    (``page_walk.shared_runs``), for every layer's call of the step."""
    return page_walk.shared_runs(
        page_tables, seq_lens, active, page=page, heads=heads,
        rows=_shared_rows(page_tables.shape[0], heads))


def latent_decode_attention(q: jax.Array, kv: jax.Array, layer,
                            page_tables: jax.Array, seq_lens: jax.Array, *,
                            rank: int, sm_scale: float,
                            interpret: bool = False,
                            runs: page_walk.Runs | None = None) -> jax.Array:
    """Attention of one absorbed query row a head and slot, q [B, H, W],
    over the live pages of ``page_tables`` [B, MAXP] in ``kv[layer]``
    ([L, P+1, page, W]): slot ``b`` sees positions ``<= seq_lens[b]``, the
    row at ``seq_lens[b]`` being the one the step just wrote.  Returns the
    heads' outputs in the latent, [B, H, rank] in q's dtype.  With ``runs``
    (``shared_runs`` of the same tables), a run of pages that several slots
    hold is fetched once for all of them and a slot walks its tail alone.
    A geometry the kernel cannot take raises (``check_geometry``)."""
    check_geometry(q, kv, rank)
    per_block = min(PAGES_PER_BLOCK, page_tables.shape[1])
    if runs is None:
        return page_walk.walk_slots(
            "latent_decode", q, (kv,), layer, page_tables, None, seq_lens,
            rank=rank, per_block=per_block, sm_scale=sm_scale,
            interpret=interpret)
    seed = page_walk.walk_shared(
        "latent_decode_shared", q, kv, layer, page_tables, runs, rank=rank,
        rows=_shared_rows(*q.shape[:2]),
        per_block=SHARED_PAGES_PER_BLOCK,
        vmem_limit_bytes=SHARED_VMEM_LIMIT_BYTES, sm_scale=sm_scale,
        interpret=interpret)
    return page_walk.walk_slots(
        "latent_decode", q, (kv,), layer, page_tables,
        runs.run * kv.shape[2], seq_lens, rank=rank, per_block=per_block,
        sm_scale=sm_scale, interpret=interpret, seed=seed)
