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
(``mosaic:latent_decode.N`` in a device trace)."""

from __future__ import annotations

import jax

from . import page_walk
from .page_walk import on_tpu  # noqa: F401 (paged asks it of this kind)

#: Pages one block of the walk brings in and multiplies at once: 8 pages of
#: 128 x 640 bf16 are 1.3 MB a half of the double buffer.
PAGES_PER_BLOCK = 8


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


def latent_decode_attention(q: jax.Array, kv: jax.Array, layer,
                            page_tables: jax.Array, seq_lens: jax.Array, *,
                            rank: int, sm_scale: float,
                            interpret: bool = False) -> jax.Array:
    """Attention of one absorbed query row a head and slot, q [B, H, W],
    over the live pages of ``page_tables`` [B, MAXP] in ``kv[layer]``
    ([L, P+1, page, W]): slot ``b`` sees positions ``<= seq_lens[b]``, the
    row at ``seq_lens[b]`` being the one the step just wrote.  Returns the
    heads' outputs in the latent, [B, H, rank] in q's dtype.  A geometry
    the kernel cannot take raises (``check_geometry``)."""
    check_geometry(q, kv, rank)
    return page_walk.walk_slots(
        "latent_decode", q, (kv,), layer, page_tables, None, seq_lens,
        rank=rank, per_block=min(PAGES_PER_BLOCK, page_tables.shape[1]),
        sm_scale=sm_scale, interpret=interpret)
