"""Prefill attention over paged K/V POOLS: a Pallas TPU kernel in which the
query rows of one prefill call (a prompt's chunk, a prefix hit's suffix, a
prompt's first rows at position 0) attend the live pages of their sequence's
whole-length table or window ring where they lie, and no array of the
scores' shape reaches HBM.

``models/paged.py`` writes a call's K and V rows into the pools
``[L_kind, P+1, page, H_kv, D]`` BEFORE they are read, so the kernel reads
pages only.  Row ``r`` of ``q [S, H, D]`` sits at position ``p = first + r``
and sees ``max(0, p - window + 1) <= j <= p`` on a window layer, ``0 <= j <=
p`` on a whole-length one (``window`` 0); position ``j`` lives in entry ``(j
// page) % T`` of the table of ``T`` entries, ``paged_decode.py``'s one rule
for both kinds of cache.  The gather form (``paged._attend_pages``) brings
the WHOLE table into fresh arrays and forms float32 scores against all of
it (28 heads x 2048 rows x 15360 keys: 3.5 GB a layer and call, written and
read twice); here:

- the pools stay in HBM as they are (``pl.ANY``; a page is handed over as
  ``[page x H_kv, D]``, a bitcast of the pool); the layer, the table, the
  first position and the sequence's ``length`` are scalar-prefetch operands;
- a block of ``BLOCK_ROWS`` query rows is one grid step.  Its rows that are
  real (before ``length``) see positions ``lo .. hi``, from the window of
  its first row to its last real row: it walks pages ``lo // page .. hi //
  page`` and no other, ``BLOCK_KEYS`` positions at a time, one DMA a page of
  K and one of V into one half of a double buffer, the next block's in
  flight while this one is multiplied.  A page before the window of the
  block's first row or after its last real row is neither fetched nor
  multiplied; a block wholly in the bucket's padding walks nothing;
- GQA without a repeat and without the decode kernel's all-heads product
  (which at 2048 query rows would be ``H_kv`` times the MXU's work): a query
  group meets its OWN KV head's rows.  A page lands as it lies, position
  major with the KV heads' rows interleaved, and two heads' rows share a
  32-bit word of a bfloat16 pool, so no DMA can part them: the heads are
  parted in VMEM, by a strided load of the words and a shift (``_head_rows``);
- online softmax over the blocks: running maximum, sum and accumulator in
  float32, a KV head's at a time.  Operands in the pool's dtype, both
  products accumulated in float32, the probabilities cast to the pool's
  dtype before the value product: the arithmetic of ``_attend_pages``,
  which is this kernel's reference.  A block of keys that every row of the
  query block sees whole (behind the diagonal, inside the window) skips the
  mask.

A block of keys that the walk does not fill holds, past its live pages,
what an earlier block left in the buffer: their scores are masked (they lie
past ``hi``), and the VALUE buffer's pages this block did not fetch are
zeroed, since 0 x NaN is NaN (``paged_decode.py`` has the same).  Rows at or
past ``length`` (the bucket's padding) see what the last real row sees of the
fetched pages: finite, and dropped by the caller.

The kernel is ``paged_prefill`` in the compiled program
(``mosaic:paged_prefill.N`` in a device trace).  Its call is jitted on its
own (``_call``) and the layer is data, so the layers of one kind share one
trace and one lowering of it in a program: eighty calls of their own cost
every start of SmallThinker's cell 39 s, warm or cold (PERF.md, PR 44).  Off the TPU nothing here
runs unless a test asks for ``interpret``: ``models/paged.py`` chooses."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF
from .latent_decode import _sublanes

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
    if q.ndim != 3 or k_pool.ndim != 5 or k_pool.shape != v_pool.shape \
            or q.shape[-1] != k_pool.shape[-1] or table.ndim != 1 \
            or not q.dtype == k_pool.dtype == v_pool.dtype:
        raise ValueError(
            f"paged prefill attention takes q [S, H, D], pools "
            f"[L, P+1, page, H_kv, D] of K and of V and a table [T], of "
            f"one dtype: got {q.shape} {q.dtype}, {k_pool.shape} "
            f"{k_pool.dtype}, {v_pool.shape} {v_pool.dtype} and "
            f"{table.shape}")
    page, n_kv, dim = k_pool.shape[2:]
    tile = _sublanes(k_pool.dtype)
    packed = 4 // k_pool.dtype.itemsize
    rows = _blocks(q.shape[0], page)[0]
    if q.shape[1] % n_kv or dim % 128 or (page * n_kv) % tile \
            or (n_kv > 1 and n_kv % packed) \
            or q.shape[0] % rows or rows % tile:
        raise ValueError(
            f"paged prefill attention needs whole groups of query heads, "
            f"heads of whole lane tiles, pages of whole sublane tiles whose "
            f"KV heads part by 32-bit words, and query rows in whole blocks "
            f"of whole tiles: {q.shape[1]} heads over {n_kv} KV heads, "
            f"head_dim {dim} (tiles of 128), page {page} x {n_kv} rows "
            f"(tiles of {tile} rows of {k_pool.dtype}, {packed} a word), "
            f"{q.shape[0]} query rows in blocks of {rows}")


def _head_rows(buf, g: int, n_kv: int, keys: int):
    """KV head ``g``'s rows [keys, D] of a buffer ref [keys x n_kv, D] that
    holds whole pages as they lie (row ``j x n_kv + g`` is position ``j``'s,
    head ``g``'s).  Rows of 32 bits part by a strided load.  Two rows of 16
    bits share a word (the even row its low half): the words of the head's
    pair are loaded by stride and the half shifted into a float32's high
    bits, which IS the bfloat16's value."""
    if n_kv == 1:
        return buf[...]
    if buf.dtype.itemsize == 4:
        return buf[pl.ds(g, keys, stride=n_kv), :]
    if buf.dtype != jnp.bfloat16:
        raise NotImplementedError(f"a pool of {buf.dtype}")
    words = buf.bitcast(jnp.uint32)  # [keys x n_kv / 2, D]
    if n_kv == 2:
        w = words[...]
    else:
        w = words[pl.ds(g // 2, keys, stride=n_kv // 2), :]
    w = (w & jnp.uint32(0xFFFF0000)) if g % 2 else (w << 16)
    return pltpu.bitcast(w, jnp.float32).astype(jnp.bfloat16)


def _kernel(layer_ref, span_ref, table_ref, q_ref, k_ref, v_ref, o_ref,
            k_buf, v_buf, sems, m_ref, l_ref, acc_ref, *, entries: int,
            page: int, n_kv: int, window: int, sm_scale: float):
    i = pl.program_id(0)
    heads, rows, dim = q_ref.shape
    n_rep = heads // n_kv
    page_rows = page * n_kv
    per_block = k_buf.shape[1] // page_rows
    keys = per_block * page
    group = n_rep * rows
    layer = layer_ref[0]
    # The block's rows sit at p0 ..; those before ``length`` see lo .. hi.
    p0 = span_ref[0] + i * rows
    hi = jnp.minimum(p0 + rows, span_ref[1]) - 1
    lo = jnp.maximum(0, p0 - window + 1) if window else 0
    first, last = lo // page, hi // page
    n_blocks = jnp.where(hi >= p0, pl.cdiv(last - first + 1, per_block), 0)

    def pages(block):
        """The pages of ``block`` the walk visits: a whole block's, or what
        is left for the last."""
        return jnp.minimum(per_block, last - first + 1 - block * per_block)

    def each_page(block, half, then):
        """``then(copy)`` on the DMAs (of K, of V) of every page of
        ``block`` that the walk visits, into ``half`` of the buffers.  A
        loop, not unrolled: the kernel's text is traced and lowered once a
        program and kind of layer, and that is set-up time."""
        def one(k, _):
            at = table_ref[(first + block * per_block + k) % entries]
            to = pl.ds(pl.multiple_of(k * page_rows, page_rows), page_rows)
            for s, (pool, buf) in enumerate(((k_ref, k_buf), (v_ref, v_buf))):
                then(pltpu.make_async_copy(pool.at[layer, at],
                                           buf.at[half, to], sems.at[s, half]))
            return 0

        jax.lax.fori_loop(0, pages(block), one, 0)

    @pl.when(n_blocks > 0)
    def _():
        each_page(0, 0, lambda copy: copy.start())

    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    # Row x of a group is query row x % rows of the block (head x // rows of
    # the group); a row past ``length`` sees what the last real row sees.
    # ``rel`` + a block's first position: the key's position less the row's.
    rel = jax.lax.broadcasted_iota(jnp.int32, (group, keys), 1) \
        - jnp.minimum(p0 + jax.lax.broadcasted_iota(
            jnp.int32, (group, keys), 0) % rows, hi)

    def body(j, _):
        half = j % 2

        @pl.when(j + 1 < n_blocks)
        def _():
            each_page(j + 1, 1 - half, lambda copy: copy.start())

        each_page(j, half, lambda copy: copy.wait())

        def zero(k, _):  # what the walk does not fill of the VALUE buffer
            to = pl.ds(pl.multiple_of(k * page_rows, page_rows), page_rows)
            v_buf[half, to] = jnp.zeros((page_rows, dim), v_buf.dtype)
            return 0

        jax.lax.fori_loop(pages(j), per_block, zero, 0)
        c0 = (first + j * per_block) * page  # the block's first position

        def attend(masked: bool):
            for g in range(n_kv):
                q = q_ref[g * n_rep:(g + 1) * n_rep].reshape(group, dim)
                s = jax.lax.dot_general(
                    q, _head_rows(k_buf.at[half], g, n_kv, keys),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * sm_scale
                if masked:
                    d = rel + c0
                    seen = (d <= 0) & (d > -window) if window else d <= 0
                    s = jnp.where(seen, s, NEG_INF)
                m = m_ref[g]
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new)
                m_ref[g] = m_new
                l_ref[g] = alpha * l_ref[g] + jnp.sum(p, axis=-1,
                                                      keepdims=True)
                acc_ref[g] = alpha * acc_ref[g] + jnp.dot(
                    p.astype(v_buf.dtype),
                    _head_rows(v_buf.at[half], g, n_kv, keys),
                    preferred_element_type=jnp.float32)

        # Every row of the block sees the whole of these keys: they end at
        # or before the first row, and start inside the last row's window.
        whole = c0 + keys - 1 <= p0
        if window:
            whole &= c0 > hi - window
        pl.when(whole)(functools.partial(attend, False))
        pl.when(jnp.logical_not(whole))(functools.partial(attend, True))
        return 0

    jax.lax.fori_loop(0, n_blocks, body, 0)
    for g in range(n_kv):
        l = l_ref[g]
        out = acc_ref[g] / jnp.where(l == 0, 1.0, l)  # a padding block's
        o_ref[g * n_rep:(g + 1) * n_rep] = out.reshape(
            n_rep, rows, dim).astype(o_ref.dtype)


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
    return _call(jnp.asarray(layer, jnp.int32).reshape(1),
                 jnp.stack([jnp.asarray(first, jnp.int32),
                            jnp.asarray(length, jnp.int32)]),
                 table.astype(jnp.int32), q, k_pool, v_pool, rows=rows,
                 per_block=min(per_block, table.shape[0]), window=window,
                 sm_scale=sm_scale, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("rows", "per_block", "window",
                                             "sm_scale", "interpret"))
def _call(layer, span, table, q, k_pool, v_pool, *, rows, per_block, window,
          sm_scale, interpret):
    """The kernel's call, jitted on its own: the layers of one kind in a
    program (and a bucket's two programs) then share ONE trace of the
    kernel and one lowering of it a program, where each call of its own
    would cost 0.4 s of every start, warm or cold (the layer is data)."""
    S, H, D = q.shape
    page, n_kv = k_pool.shape[2:4]
    n_rep = H // n_kv
    buffer = pltpu.VMEM((2, per_block * page * n_kv, D), k_pool.dtype)
    out = pl.pallas_call(
        functools.partial(_kernel, entries=table.shape[0], page=page,
                          n_kv=n_kv, window=window, sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S // rows,),
            in_specs=[
                pl.BlockSpec((H, rows, D), lambda i, *_: (0, i, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((H, rows, D), lambda i, *_: (0, i, 0)),
            scratch_shapes=[
                buffer, buffer,
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((n_kv, n_rep * rows, 1), jnp.float32),
                pltpu.VMEM((n_kv, n_rep * rows, 1), jnp.float32),
                pltpu.VMEM((n_kv, n_rep * rows, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((H, S, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="paged_prefill",
    )(layer, span, table, q.transpose(1, 0, 2),
      *(pool.reshape(*pool.shape[:2], page * n_kv, D)
        for pool in (k_pool, v_pool)))
    return out.transpose(1, 0, 2)
