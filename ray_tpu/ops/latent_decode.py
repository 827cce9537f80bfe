"""Decode attention over a LATENT paged pool: a Pallas TPU kernel that walks
each slot's live pages where they lie and reads every page once.

What ``models/paged.py`` keeps of a token on a layer of a latent model is one
row ``[c ; RoPE(k_r) ; 0]`` (``paged.latent_row_width`` numbers): the key of
ONE KV head that every query head shares, whose first ``rank`` columns are
that head's value.  The decode step has one ABSORBED query row a head and
slot, ``q [B, H, W]``.  The gather form (``paged._attend_pages``) brings every
slot's whole table into a fresh array and passes over it for the scores and
again for the values; here:

- the pool ``[L, P+1, page, W]`` stays in HBM as it is (``pl.ANY``: no block
  of it is the pipeline's, so nothing copies or re-lays it); the layer, the
  slots' lengths and the page tables are scalar-prefetch operands;
- slot ``b`` is one grid step.  It visits pages ``0 .. seq_lens[b] // page``
  of its table and no other, ``PAGES_PER_BLOCK`` at a time: one DMA a page
  (a page is contiguous: ``page x W`` numbers) into one half of a double
  buffer in VMEM, the next block's DMAs (the next slot's first block behind
  a slot's last) in flight while this block is multiplied.  A page past the
  live length is neither fetched nor waited for; an empty slot (length 0, an
  all-scratch table) costs one page;
- a block in VMEM serves both products: the scores ``q . row`` over all ``W``
  columns (the padding is zero on both sides), the values the rows' first
  ``rank`` columns.  Online softmax over the blocks: running maximum, sum and
  accumulator in float32, ``position <= seq_lens[b]`` masked inside the last
  block.  Operands in the pool's dtype, both products accumulated in float32,
  the probabilities cast to the pool's dtype before the value product: the
  arithmetic of ``_attend_pages``, which is this kernel's reference.

A block that the live length does not fill holds, past its live pages, what
an earlier block (another slot's) left in the buffer.  Those rows' scores
are masked, so they meet a probability of exactly 0, but 0 x NaN is NaN:
the pages of the buffer that this block did not fetch are zeroed before the
products, and one slot's rows never reach another slot's output.

The kernel is ``latent_decode`` in the compiled program
(``mosaic:latent_decode.N`` in a device trace).  Off the TPU nothing here
runs unless a test asks for ``interpret``: ``models/paged.py`` chooses."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF, _on_tpu as on_tpu  # noqa: F401 (paged asks it)

#: Pages one block of the walk brings in and multiplies at once: 8 pages of
#: 128 x 640 bf16 are 1.3 MB a half of the double buffer.
PAGES_PER_BLOCK = 8


def _sublanes(dtype) -> int:
    """Rows of one tile of ``dtype`` (8 of float32, 16 of bfloat16)."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def check_geometry(q: jax.Array, kv: jax.Array, rank: int) -> None:
    """A ValueError naming what the kernel cannot take: it moves whole pages
    by DMA and multiplies them as they land, so a page has to be whole
    sublane tiles and a row (and its value part) whole lane tiles."""
    if kv.ndim != 4 or q.ndim != 3 or q.shape[-1] != kv.shape[-1] \
            or q.dtype != kv.dtype:
        raise ValueError(
            f"latent decode attention takes q [B, H, W] and a pool "
            f"[L, P+1, page, W] of one dtype: got {q.shape} {q.dtype} and "
            f"{kv.shape} {kv.dtype}")
    page, width = kv.shape[2:]
    if page % _sublanes(kv.dtype) or width % 128 or rank % 128 \
            or not 0 < rank <= width:
        raise ValueError(
            f"latent decode attention needs pages of whole sublane tiles "
            f"and rows of whole lane tiles: page {page} (tiles of "
            f"{_sublanes(kv.dtype)} rows of {kv.dtype}), row width {width} "
            f"and value width {rank} (tiles of 128)")


def _kernel(layer_ref, lens_ref, tables_ref, q_ref, kv_ref, o_ref,
            buf, sems, half_ref, *, max_pages: int, sm_scale: float):
    b, n_slots = pl.program_id(0), pl.num_programs(0)
    _, per_block, page, width = buf.shape
    rows = per_block * page
    rank = o_ref.shape[-1]
    layer = layer_ref[0]

    def each_page(slot, block, half, live, dead=None):
        """``live(copy)`` on the DMA of every live page of ``slot``'s
        ``block`` into ``half`` of the buffer, ``dead(k)`` on the others."""
        n_live = lens_ref[slot] // page + 1
        for k in range(per_block):
            p = block * per_block + k

            @pl.when(p < n_live)
            def _(k=k, p=p):
                live(pltpu.make_async_copy(
                    kv_ref.at[layer, tables_ref[slot * max_pages + p]],
                    buf.at[half, k], sems.at[half]))

            if dead is not None and k:  # a block's first page is live
                pl.when(p >= n_live)(functools.partial(dead, k))

    def start(slot, block, half):
        each_page(slot, block, half, lambda copy: copy.start())

    @pl.when(b == 0)
    def _():
        half_ref[0] = 0
        start(0, 0, 0)

    length = lens_ref[b]
    n_blocks = pl.cdiv(length // page + 1, per_block)
    q = q_ref[...]  # [H, W]

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
            buf[half, k] = jnp.zeros((page, width), buf.dtype)

        each_page(b, j, half, lambda copy: copy.wait(), zero)
        half_ref[0] = other
        block = buf[half].reshape(rows, width)
        s = jax.lax.dot_general(
            q, block, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [H, rows]
        pos = j * rows + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos <= length, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jnp.dot(
            p.astype(block.dtype), block[:, :rank],
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    heads = q.shape[0]
    m, l, acc = jax.lax.fori_loop(
        0, n_blocks, body,
        (jnp.full((heads, 1), NEG_INF, jnp.float32),
         jnp.zeros((heads, 1), jnp.float32),
         jnp.zeros((heads, rank), jnp.float32)))
    o_ref[...] = (acc / l).astype(o_ref.dtype)


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
    B, H, W = q.shape
    page = kv.shape[2]
    max_pages = page_tables.shape[1]
    # Whole sublane tiles of query rows; the padding's outputs are dropped.
    tile = _sublanes(q.dtype)
    heads = -(-H // tile) * tile
    q = jnp.pad(q, ((0, 0), (0, heads - H), (0, 0)))
    per_block = min(PAGES_PER_BLOCK, max_pages)
    out = pl.pallas_call(
        functools.partial(_kernel, max_pages=max_pages, sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((None, heads, W), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, heads, rank),
                                   lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, per_block, page, W), kv.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, heads, rank), q.dtype),
        # A slot's last block starts the next slot's first: in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_decode",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      seq_lens.astype(jnp.int32),
      page_tables.astype(jnp.int32).reshape(-1), q, kv)
    return out[:, :H]
