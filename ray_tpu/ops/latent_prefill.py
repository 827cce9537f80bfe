"""Prefill attention over a LATENT paged pool: a Pallas TPU kernel in which
the query rows of one prefill call (a prompt's chunk, a prefix hit's suffix,
a prompt's first rows at position 0) attend the live pages of their
sequence's table where they lie, and no array of the scores' shape reaches
HBM.

What ``models/paged.py`` keeps of a token on a latent layer is one row ``[c ;
RoPE(k_r) ; 0]`` (``paged.latent_row_width`` numbers): the key of ONE KV head
that every query head shares, whose first ``rank`` columns are that head's
value.  A call's rows are written into the pool ``[L, P+1, page, W]`` BEFORE
they are read, so the kernel reads pages only.  Row ``r`` of the ABSORBED
queries ``q [S, H, W]`` sits at position ``p = first + r`` and sees ``0 <= j
<= p``; position ``j`` lives in entry ``j // page`` of the table.  The gather
form (``paged._attend_pages``) brings the WHOLE table into a fresh array and
forms float32 scores against all of it (20 heads x 2048 rows x 19200 keys:
3.1 GB a layer and call, written and read twice); here:

- the pool stays in HBM as it is (``pl.ANY``); the layer, the table, the
  first position and the sequence's ``length`` are scalar-prefetch operands;
- a block of ``BLOCK_ROWS`` query rows with ALL heads is one grid step: every
  head meets the same rows, so the block is one ``[H x rows, W]`` matrix
  against ``[keys, W]`` for the scores and against the same rows' first
  ``rank`` columns for the values (one fetch serves both, as in
  ``latent_decode.py``).  Its rows that are real (before ``length``) see
  positions ``0 .. hi``, up to its last real row: it walks pages ``0 .. hi //
  page`` and no other, ``BLOCK_KEYS`` positions at a time, one DMA a page
  into one half of a double buffer, the next block's in flight while this
  one is multiplied.  A page after the block's last real row is neither
  fetched nor multiplied; a block wholly in the bucket's padding walks
  nothing;
- online softmax over the blocks: running maximum, sum and accumulator in
  float32.  Operands in the pool's dtype, both products accumulated in
  float32, the probabilities cast to the pool's dtype before the value
  product: the arithmetic of ``_attend_pages``, which is this kernel's
  reference.  A block of keys that ends at or before the query block's first
  row is seen whole by every row and skips the mask.

A block of keys that the walk does not fill holds, past its live pages, what
an earlier block left in the buffer: their scores are masked (they lie past
``hi``), and the buffer's pages this block did not fetch are zeroed, since 0
x NaN is NaN (``latent_decode.py`` has the same).  Rows at or past ``length``
(the bucket's padding) see what the last real row sees of the fetched pages:
finite, and dropped by the caller.

The kernel is ``latent_prefill`` in the compiled program
(``mosaic:latent_prefill.N`` in a device trace).  Its call is jitted on its
own (``_call``) and the layer is data, so a program's latent layers share one
trace and one lowering of it (``paged_prefill.py`` says what a call a layer
costs every start).  Off the TPU nothing here runs unless a test asks for
``interpret``: ``models/paged.py`` chooses."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF
from .latent_decode import _sublanes

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
    if q.ndim != 3 or kv.ndim != 4 or q.shape[-1] != kv.shape[-1] \
            or table.ndim != 1 or q.dtype != kv.dtype:
        raise ValueError(
            f"latent prefill attention takes q [S, H, W], a pool "
            f"[L, P+1, page, W] of one dtype and a table [T]: got {q.shape} "
            f"{q.dtype}, {kv.shape} {kv.dtype} and {table.shape}")
    page, width = kv.shape[2:]
    tile = _sublanes(kv.dtype)
    rows = _blocks(q.shape[0], page)[0]
    if page % tile or width % 128 or rank % 128 or not 0 < rank <= width \
            or q.shape[0] % rows or rows % tile:
        raise ValueError(
            f"latent prefill attention needs pages of whole sublane tiles, "
            f"rows of whole lane tiles and query rows in whole blocks of "
            f"whole tiles: page {page} (tiles of {tile} rows of {kv.dtype}), "
            f"row width {width} and value width {rank} (tiles of 128), "
            f"{q.shape[0]} query rows in blocks of {rows}")


def _kernel(layer_ref, span_ref, table_ref, q_ref, kv_ref, o_ref, buf, sems,
            m_ref, l_ref, acc_ref, *, entries: int, page: int,
            sm_scale: float):
    i = pl.program_id(0)
    heads, rows, width = q_ref.shape
    rank = o_ref.shape[-1]
    group = heads * rows
    per_block = buf.shape[1] // page
    keys = per_block * page
    layer = layer_ref[0]
    # The block's rows sit at p0 ..; those before ``length`` see 0 .. hi.
    p0 = span_ref[0] + i * rows
    hi = jnp.minimum(p0 + rows, span_ref[1]) - 1
    n_pages = jnp.minimum(hi // page + 1, entries)
    n_blocks = jnp.where(hi >= p0, pl.cdiv(n_pages, per_block), 0)

    def pages(block):
        """The pages of ``block`` the walk visits: a whole block's, or what
        is left for the last."""
        return jnp.minimum(per_block, n_pages - block * per_block)

    def each_page(block, half, then):
        """``then(copy)`` on the DMA of every page of ``block`` that the
        walk visits, into ``half`` of the buffer.  A loop, not unrolled: the
        kernel's text is traced and lowered once a program, and that is
        set-up time."""
        def one(k, _):
            at = table_ref[block * per_block + k]
            to = pl.ds(pl.multiple_of(k * page, page), page)
            then(pltpu.make_async_copy(kv_ref.at[layer, at],
                                       buf.at[half, to], sems.at[half]))
            return 0

        jax.lax.fori_loop(0, pages(block), one, 0)

    @pl.when(n_blocks > 0)
    def _():
        each_page(0, 0, lambda copy: copy.start())

    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    # Row x of the matrix is query row x % rows of the block (head x //
    # rows); a row past ``length`` sees what the last real row sees.
    seen_to = jnp.minimum(p0 + jax.lax.broadcasted_iota(
        jnp.int32, (group, 1), 0) % rows, hi)

    def body(j, _):
        half = j % 2

        @pl.when(j + 1 < n_blocks)
        def _():
            each_page(j + 1, 1 - half, lambda copy: copy.start())

        each_page(j, half, lambda copy: copy.wait())

        def zero(k, _):  # what the walk does not fill of the buffer
            to = pl.ds(pl.multiple_of(k * page, page), page)
            buf[half, to] = jnp.zeros((page, width), buf.dtype)
            return 0

        jax.lax.fori_loop(pages(j), per_block, zero, 0)
        c0 = j * keys  # the block's first position

        def attend(masked: bool):
            block = buf[half]
            s = jax.lax.dot_general(
                q_ref[...].reshape(group, width), block,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            if masked:
                at = c0 + jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1)
                s = jnp.where(at <= seen_to, s, NEG_INF)
            m = m_ref[...]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            m_ref[...] = m_new
            l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1,
                                                      keepdims=True)
            acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
                p.astype(block.dtype), block[:, :rank],
                preferred_element_type=jnp.float32)

        # Every row of the block sees the whole of these keys: they end at
        # or before its first row.
        whole = c0 + keys - 1 <= p0
        pl.when(whole)(functools.partial(attend, False))
        pl.when(jnp.logical_not(whole))(functools.partial(attend, True))
        return 0

    jax.lax.fori_loop(0, n_blocks, body, 0)
    l = l_ref[...]
    out = acc_ref[...] / jnp.where(l == 0, 1.0, l)  # a padding block's
    o_ref[...] = out.reshape(heads, rows, rank).astype(o_ref.dtype)


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
    return _call(jnp.asarray(layer, jnp.int32).reshape(1),
                 jnp.stack([jnp.asarray(first, jnp.int32),
                            jnp.asarray(length, jnp.int32)]),
                 table.astype(jnp.int32), q, kv, rows=rows,
                 per_block=min(per_block, table.shape[0]), rank=rank,
                 sm_scale=sm_scale, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("rows", "per_block", "rank",
                                             "sm_scale", "interpret"))
def _call(layer, span, table, q, kv, *, rows, per_block, rank, sm_scale,
          interpret):
    """The kernel's call, jitted on its own: the latent layers of a program
    then share ONE trace of the kernel and one lowering of it, where each
    call of its own costs every start its seconds, warm or cold (the layer
    is data; ``paged_prefill._call``)."""
    S, H, W = q.shape
    page = kv.shape[2]
    out = pl.pallas_call(
        functools.partial(_kernel, entries=table.shape[0], page=page,
                          sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S // rows,),
            in_specs=[
                pl.BlockSpec((H, rows, W), lambda i, *_: (0, i, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((H, rows, rank), lambda i, *_: (0, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, per_block * page, W), kv.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((H * rows, 1), jnp.float32),
                pltpu.VMEM((H * rows, 1), jnp.float32),
                pltpu.VMEM((H * rows, rank), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((H, S, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="latent_prefill",
    )(layer, span, table, q.transpose(1, 0, 2), kv)
    return out.transpose(1, 0, 2)
