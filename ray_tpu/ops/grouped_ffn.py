"""The routed FFN with each hit expert's weights read from HBM once a call:
two Pallas TPU kernels of one arithmetic, for a step's rows and for a
prompt's, beside the ``ragged_dot`` form they are held to.

``models/moe.py`` sorts a call's (token, expert) pairs by expert: expert
``e``'s rows are ``offset[e] : offset[e] + counts[e]`` of ``xs [R, d]``, with
``offset`` the running sum of ``counts``.  The ``ragged_dot`` form
(``grouped_ffn_ragged``) makes three calls of a general grouped matmul, each
sweeping the weights, with ``h`` written to HBM and read back between.  In
both kernels ``w1``, ``w3 [E, d, f]`` and ``w2 [E, f, d]`` stay in HBM where
they lie (``pl.ANY``: nothing copies or re-lays them), ``counts`` is the one
scalar-prefetch operand, walked once for the hit experts and their offsets,
the next hit expert's weights are in flight while this one's are multiplied,
and an expert with no row is neither fetched nor waited for: the cost
follows the experts hit.  ``h`` never leaves VMEM.  An expert's rows are met
through the whole row tiles they lie in: the tile's rows are all multiplied
and the expert's own are SELECTED into the output, so nothing is gathered or
sliced off a tile edge and what a neighbour's row holds (a NaN behind the
last group) reaches no row but its own.

``grouped_ffn_stream`` (``ragged-dot-stream``), where an expert gets a
handful of rows (a decode step: the work is reading weights):

- the step's rows and its float32 output are whole in VMEM; a tile is 16
  rows of bfloat16;
- an expert's weights are cut along ``f`` into slabs of ``slab_width``
  columns of ``w1`` and ``w3`` and the same rows of ``w2``: a slab is three
  DMAs, a few MB, and whole in itself (``act(x w1) * (x w3)`` of those
  columns, times those rows of ``w2``, summed over the slabs in float32).
  Slabs are double-buffered (the next HIT expert's first slab behind an
  expert's last).  Rows past the last group are written as zeros.

``grouped_ffn_rows`` (``ragged-dot-rows``), where an expert gets a prompt's
rows (every prefill, the full forward; 377 MB of rows and output at
SmallThinker's chunk, and as many operations as bytes allow):

- the rows and the output stay in HBM too and pass in blocks of
  ``ROWS_BLOCK`` rows, double-buffered: a block's rows are brought in while
  the block before is multiplied, its output (summed in float32 in VMEM
  over the experts with a row in it) is sent off while the next is;
- a hit expert is resident WHOLE while its blocks pass (two experts in
  VMEM: GLM's, 19 MB each, which the stream cuts in two), so no partial
  output waits on a slab; ``holds_an_expert`` says whether that fits;
- a tile is as tall as the MXU wants, from the call's static row count
  (``rows_blocks``): 64 rows where an expert has that many on average;
- rows past ``sum(counts)`` are neither fetched nor multiplied, and blocks
  wholly behind it are not written: ``_moe_ffn`` selects zeros for them.

The arithmetic is the ``ragged_dot`` form's, which is the kernels'
reference: operands in the weights' dtype, each product accumulated in
float32, ``act(.) * (.)`` in float32 and cast to that dtype before the
``w2`` product, float32 out.

In a device trace the kernels are ``mosaic:ragged-dot-stream.N`` and
``mosaic:ragged-dot-rows.N``: the grouped products' reader finds both by
``mosaic:ragged-dot`` as it finds XLA's own ``ragged-dot-none``, the
stream's reader only the first.  The stream defines no gradient; the
row-block form's is the ``ragged_dot`` form's (a ``custom_vjp``).  Off the
TPU nothing here but that form runs unless a test asks for ``interpret``:
``models/moe.py`` chooses."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _on_tpu as on_tpu  # noqa: F401 (moe asks it)
from .page_walk import sublanes as _sublanes

LANES = 128
#: The most bytes one slab (its three blocks) may hold: two of them are the
#: double buffer.  An expert of 12 MB is then one slab, whole and contiguous;
#: alone on a v5e slabs of 6-19 MB all read at 89-92% of the HBM peak, the
#: whole expert 2% above its halves (PERF.md §6).
SLAB_BYTES = 13 << 20

#: Where a prompt's rows pass an expert: the most rows one product
#: multiplies at once, the most one DMA moves, and the VMEM the call may ask
#: for (of a v5e's 128 MiB).  Alone on a v5e tiles of 32 to 128 rows read
#: within a percent of each other where the DMAs bind (GLM's buckets, OLMoE's
#: 256: 88-90% of the HBM peak); where an expert has 128-192 rows
#: (SmallThinker's chunk, Trinity-Mini's, OLMoE's 1024) 64 is best by 1-5%
#: (the rows of other experts a ragged end multiplies), 16 and 256 a fifth
#: to a third slower; blocks of 512 gain nothing (PERF.md §6, PR 49).
ROWS_TILE = 64
ROWS_BLOCK = 256
ROWS_VMEM_BYTES = 100 << 20

ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def grouped_ffn_ragged(xs: jax.Array, w1: jax.Array, w3: jax.Array,
                       w2: jax.Array, counts: jax.Array, *,
                       act: str = "silu") -> jax.Array:
    """The three grouped products as three ``jax.lax.ragged_dot`` calls:
    the kernels' reference, their arithmetic to the letter, and the form of
    every backend that is not a TPU.  [R, d] float32."""
    def grouped(rows, w):
        return jax.lax.ragged_dot(rows, w, counts,
                                  preferred_element_type=jnp.float32)

    h = (ACTS[act](grouped(xs, w1)) * grouped(xs, w3)).astype(w1.dtype)
    return grouped(h, w2)


def slab_width(d: int, f: int, dtype) -> int:
    """Columns of ``w1`` / ``w3`` (rows of ``w2``) in one slab: the widest
    whole-lane-tile divisor of ``f`` whose three blocks fit ``SLAB_BYTES``
    (one lane tile where none does)."""
    per_column = 3 * d * jnp.dtype(dtype).itemsize
    widths = [w for w in range(LANES, f + 1, LANES) if f % w == 0]
    fitting = [w for w in widths if w * per_column <= SLAB_BYTES]
    return max(fitting) if fitting else LANES


def check_geometry(xs: jax.Array, w1: jax.Array, w3: jax.Array,
                   w2: jax.Array) -> None:
    """A ValueError naming what the kernel cannot take: it moves blocks of
    the weights by DMA and multiplies them as they land, so both widths
    have to be whole lane tiles."""
    E, d, f = w1.shape
    if w3.shape != (E, d, f) or w2.shape != (E, f, d) \
            or xs.ndim != 2 or xs.shape[1] != d \
            or not xs.dtype == w1.dtype == w3.dtype == w2.dtype:
        raise ValueError(
            f"the streamed routed FFN takes rows [R, d] and w1, w3 "
            f"[E, d, f], w2 [E, f, d] of one dtype: got {xs.shape} "
            f"{xs.dtype}, {w1.shape} {w1.dtype}, {w3.shape} {w3.dtype}, "
            f"{w2.shape} {w2.dtype}")
    if d % LANES or f % LANES:
        raise ValueError(
            f"the streamed routed FFN needs widths of whole lane tiles: "
            f"d {d}, f {f} (tiles of {LANES})")


def _hit_experts(counts_ref, hit_ref, off_ref):
    """One walk of ``counts``: the experts with a row and each one's first
    row into ``hit_ref`` / ``off_ref``; returns how many are hit and the
    rows they have together."""
    def scan(e, carry):
        n, off = carry
        c = counts_ref[e]

        @pl.when(c > 0)
        def _():
            hit_ref[n] = e
            off_ref[n] = off

        return n + (c > 0).astype(jnp.int32), off + c

    return jax.lax.fori_loop(0, counts_ref.shape[0], scan,
                             (jnp.int32(0), jnp.int32(0)))


def _ffn(x, w1_ref, w3_ref, w2_ref, act):
    """A tile of rows through the blocks of an expert that three buffers
    hold, each read where its product wants it: float32."""
    a = jnp.dot(x, w1_ref[...], preferred_element_type=jnp.float32)
    b = jnp.dot(x, w3_ref[...], preferred_element_type=jnp.float32)
    h = (act(a) * b).astype(x.dtype)
    return jnp.dot(h, w2_ref[...], preferred_element_type=jnp.float32)


def _kernel(counts_ref, xs_ref, w1_ref, w3_ref, w2_ref, y_ref,
            buf1, buf3, buf2, sems, hit_ref, off_ref, *, act):
    _, d, width = buf1.shape
    slabs = w1_ref.shape[2] // width
    tile = _sublanes(xs_ref.dtype)

    n_hit, _ = _hit_experts(counts_ref, hit_ref, off_ref)
    total = n_hit * slabs

    def copies(t, half):
        """The three DMAs of item ``t`` (hit expert ``t // slabs``, slab
        ``t % slabs``) into ``half`` of the buffers."""
        e = hit_ref[t // slabs]
        col = pl.multiple_of((t % slabs) * width, LANES)
        return (
            pltpu.make_async_copy(w1_ref.at[e, :, pl.ds(col, width)],
                                  buf1.at[half], sems.at[half]),
            pltpu.make_async_copy(w3_ref.at[e, :, pl.ds(col, width)],
                                  buf3.at[half], sems.at[half]),
            pltpu.make_async_copy(w2_ref.at[e, pl.ds(col, width), :],
                                  buf2.at[half], sems.at[half]))

    @pl.when(total > 0)
    def _():
        for c in copies(0, 0):
            c.start()

    y_ref[...] = jnp.zeros_like(y_ref)

    def item(t, _):
        half = t % 2

        @pl.when(t + 1 < total)
        def _():
            for c in copies(t + 1, 1 - half):
                c.start()

        for c in copies(t, half):
            c.wait()
        i = t // slabs
        off = off_ref[i]
        end = off + counts_ref[hit_ref[i]]

        def rows(r, _):
            r0 = pl.multiple_of(r * tile, tile)
            x = xs_ref[pl.ds(r0, tile), :]
            y = _ffn(x, buf1.at[half], buf3.at[half], buf2.at[half], act)
            row = r0 + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
            mine = (row >= off) & (row < end)
            y_ref[pl.ds(r0, tile), :] += jnp.where(mine, y, 0.0)

        jax.lax.fori_loop(off // tile, (end - 1) // tile + 1, rows, None)

    jax.lax.fori_loop(0, total, item, None)


def grouped_ffn_stream(xs: jax.Array, w1: jax.Array, w3: jax.Array,
                       w2: jax.Array, counts: jax.Array, *,
                       act: str = "silu",
                       interpret: bool = False) -> jax.Array:
    """``act(xs w1[e]) * (xs w3[e])`` times ``w2[e]`` for the rows of each
    expert ``e``: xs [R, d] sorted by expert, ``counts`` [E] int32 the rows
    each has.  Returns [R, d] float32; the rows past ``sum(counts)`` are
    zero.  A geometry the kernel cannot take raises (``check_geometry``)."""
    check_geometry(xs, w1, w3, w2)
    R, d = xs.shape
    E, _, f = w1.shape
    tile = _sublanes(xs.dtype)
    rows = -(-R // tile) * tile
    xs = jnp.pad(xs, ((0, rows - R), (0, 0)))
    width = slab_width(d, f, w1.dtype)
    item = jnp.dtype(w1.dtype).itemsize
    # Both halves of the three buffers, the rows and the output as the
    # pipeline holds them (twice), and room for the products' temporaries.
    vmem = 2 * 3 * d * width * item + 2 * rows * d * (item + 4) + (8 << 20)
    out = pl.pallas_call(
        functools.partial(_kernel, act=ACTS[act]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[
                pl.BlockSpec((rows, d), lambda i, *_: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((rows, d), lambda i, *_: (0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, d, width), w1.dtype),
                pltpu.VMEM((2, d, width), w3.dtype),
                pltpu.VMEM((2, width, d), w2.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((E,), jnp.int32),
                pltpu.SMEM((E,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        interpret=interpret,
        name="ragged-dot-stream",
    )(counts.astype(jnp.int32), xs, w1, w3, w2)
    return out[:R]


# ------------------------------------------------- a prompt's rows an expert


def rows_blocks(rows: int, experts: int, dtype) -> tuple:
    """``(tile, block)`` for ``rows`` sorted rows over ``experts``: the rows
    one product multiplies (as tall as the MXU wants where an expert has
    that many on average, a row tile of ``dtype`` at least), and the rows
    one DMA brings in and one takes out (``ROWS_BLOCK``, or the whole
    buffer in whole tiles where that is less).  The buffer is padded to
    whole blocks; a bucket's or a chunk's rows are whole blocks already."""
    tile = _sublanes(dtype)
    while tile < ROWS_TILE and tile * experts < rows:
        tile *= 2
    return tile, min(max(ROWS_BLOCK, tile), -(-rows // tile) * tile)


def _rows_vmem(d: int, f: int, dtype, tile: int, block: int) -> int:
    """What the row-block form holds in VMEM: two experts whole, two blocks
    of rows and of float32 output, a tile's products, and room to spare."""
    item = jnp.dtype(dtype).itemsize
    return (2 * 3 * d * f * item + 2 * block * d * (item + 4)
            + 2 * tile * (f * (8 + item) + 4 * d) + (8 << 20))


def holds_an_expert(d: int, f: int, dtype) -> bool:
    """Whether the row-block form fits: it keeps a hit expert's three
    matrices whole in VMEM while that expert's rows pass, beside the next
    expert's as they land."""
    return _rows_vmem(d, f, dtype, ROWS_TILE, ROWS_BLOCK) <= ROWS_VMEM_BYTES


def _rows_kernel(counts_ref, xs_ref, w1_ref, w3_ref, w2_ref, y_ref,
                 buf1, buf3, buf2, xbuf, ybuf, wsems, xsems, ysems,
                 hit_ref, off_ref, *, act, tile):
    block = xbuf.shape[1]
    n_hit, total = _hit_experts(counts_ref, hit_ref, off_ref)
    n_blocks = (total + block - 1) // block  # the blocks that hold a row

    def weights(i, half):
        """The three DMAs of hit expert ``i``, whole, into ``half``."""
        e = hit_ref[i]
        return (pltpu.make_async_copy(w1_ref.at[e], buf1.at[half],
                                      wsems.at[half]),
                pltpu.make_async_copy(w3_ref.at[e], buf3.at[half],
                                      wsems.at[half]),
                pltpu.make_async_copy(w2_ref.at[e], buf2.at[half],
                                      wsems.at[half]))

    def span(j):
        return pl.ds(pl.multiple_of(j * block, block), block)

    def rows_in(j):
        return pltpu.make_async_copy(xs_ref.at[span(j), :], xbuf.at[j % 2],
                                     xsems.at[j % 2])

    def rows_out(j):
        return pltpu.make_async_copy(ybuf.at[j % 2], y_ref.at[span(j), :],
                                     ysems.at[j % 2])

    @pl.when(n_hit > 0)
    def _():
        for c in weights(0, 0):
            c.start()
        rows_in(0).start()

    def expert(i, live):
        half = i % 2

        @pl.when(i + 1 < n_hit)
        def _():
            for c in weights(i + 1, 1 - half):
                c.start()

        for c in weights(i, half):
            c.wait()
        off = off_ref[i]
        end = off + counts_ref[hit_ref[i]]

        def rows_block(j, live):
            # The blocks are met in order, each by the experts with a row
            # in it one after another: ``live`` is the one whose output is
            # being summed.  Meeting the next one sends that output off,
            # asks for the rows behind these, and takes the buffer that the
            # block before last has left.
            @pl.when(j != live)
            def _():
                @pl.when(live >= 0)
                def _():
                    rows_out(live).start()

                rows_in(j).wait()

                @pl.when(j + 1 < n_blocks)
                def _():
                    rows_in(j + 1).start()

                @pl.when(j >= 2)
                def _():
                    rows_out(j - 2).wait()

                ybuf[j % 2] = jnp.zeros(ybuf.shape[1:], ybuf.dtype)

            first = j * block
            lo = jnp.maximum(off, first) - first
            hi = jnp.minimum(end, first + block) - first

            def rows(r, _):
                r0 = pl.multiple_of(r * tile, tile)
                x = xbuf[j % 2, pl.ds(r0, tile), :]
                y = _ffn(x, buf1.at[half], buf3.at[half], buf2.at[half], act)
                row = first + r0 + jax.lax.broadcasted_iota(
                    jnp.int32, (tile, 1), 0)
                mine = (row >= off) & (row < end)
                ybuf[j % 2, pl.ds(r0, tile), :] += jnp.where(mine, y, 0.0)

            jax.lax.fori_loop(lo // tile, (hi - 1) // tile + 1, rows, None)
            return j

        return jax.lax.fori_loop(off // block, (end - 1) // block + 1,
                                 rows_block, live)

    live = jax.lax.fori_loop(0, n_hit, expert, jnp.int32(-1))

    @pl.when(live >= 0)
    def _():
        rows_out(live).start()

        @pl.when(live >= 1)
        def _():
            rows_out(live - 1).wait()

        rows_out(live).wait()


@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def _rows_call(xs, w1, w3, w2, counts, *, act, interpret):
    """The row-block kernel's call, jitted on its own: the routed layers of
    a program share one trace and one lowering of it (a ``pallas_call`` is
    traced and lowered where it stands, before the compile cache is asked:
    PERF.md, PR 44)."""
    R, d = xs.shape
    E, _, f = w1.shape
    tile, block = rows_blocks(R, E, xs.dtype)
    rows = -(-R // block) * block
    xs = jnp.pad(xs, ((0, rows - R), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rows_kernel, act=ACTS[act], tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 4,
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((2, d, f), w1.dtype),
                pltpu.VMEM((2, d, f), w3.dtype),
                pltpu.VMEM((2, f, d), w2.dtype),
                pltpu.VMEM((2, block, d), xs.dtype),
                pltpu.VMEM((2, block, d), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((E,), jnp.int32),
                pltpu.SMEM((E,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_rows_vmem(d, f, w1.dtype, tile, block)),
        interpret=interpret,
        name="ragged-dot-rows",
    )(counts.astype(jnp.int32), xs, w1, w3, w2)
    return out[:R]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rows(xs, w1, w3, w2, counts, act, interpret):
    return _rows_call(xs, w1, w3, w2, counts, act=act, interpret=interpret)


def _rows_fwd(xs, w1, w3, w2, counts, act, interpret):
    return (_rows_call(xs, w1, w3, w2, counts, act=act, interpret=interpret),
            (xs, w1, w3, w2, counts))


def _rows_bwd(act, interpret, saved, g):
    *operands, counts = saved
    _, vjp = jax.vjp(
        lambda *ops: grouped_ffn_ragged(*ops, counts, act=act), *operands)
    return (*vjp(g), None)


_rows.defvjp(_rows_fwd, _rows_bwd)


def grouped_ffn_rows(xs: jax.Array, w1: jax.Array, w3: jax.Array,
                     w2: jax.Array, counts: jax.Array, *,
                     act: str = "silu", interpret: bool = False) -> jax.Array:
    """``grouped_ffn_stream``'s product where an expert has a prompt's rows:
    the same operands, [R, d] float32 out.  Rows past ``sum(counts)`` are
    zero as far as the last block that holds a row reaches, and NOT WRITTEN
    behind it (``rows_blocks``): the caller drops them.  Its gradient is the
    ``ragged_dot`` form's (``grouped_ffn_ragged``)."""
    check_geometry(xs, w1, w3, w2)
    return _rows(xs, w1, w3, w2, counts, act, interpret)
