"""The routed FFN where an expert gets a handful of rows: a Pallas TPU kernel
that streams each hit expert's weights from HBM once, in large blocks, and
does the three grouped products for the expert while the next blocks are in
flight.

``models/moe.py`` sorts a step's (token, expert) pairs by expert: expert
``e``'s rows are ``offset[e] : offset[e] + counts[e]`` of ``xs [R, d]``, with
``offset`` the running sum of ``counts``.  A decode step has one or two rows
an expert, so the work is reading weights.  The ``ragged_dot`` form
(``moe._moe_ffn``) makes three calls of a general grouped matmul; here:

- ``w1``, ``w3 [E, d, f]`` and ``w2 [E, f, d]`` stay in HBM where they lie
  (``pl.ANY``: nothing copies or re-lays them); ``counts`` is the one
  scalar-prefetch operand, and the kernel walks it once for the hit experts
  and their offsets;
- an expert's weights are cut along ``f`` into slabs of ``slab_width``
  columns of ``w1`` and ``w3`` and the same rows of ``w2``: a slab is three
  DMAs, a few MB, and whole in itself (``act(x w1) * (x w3)`` of those
  columns, times those rows of ``w2``, summed over the slabs in float32), so
  ``h`` never leaves VMEM.  Slabs are double-buffered: the next slab's DMAs
  (the next HIT expert's first slab behind an expert's last) are in flight
  while this one is multiplied.  An expert with no row is neither fetched
  nor waited for: the cost follows the experts hit;
- an expert's rows are met through the whole row tiles they lie in (16 rows
  of bfloat16): the tile's rows are all multiplied and the expert's own are
  SELECTED into the output, so nothing is gathered or sliced off a tile
  edge, one expert with every row is a loop over tiles, and what a
  neighbour's row holds (a NaN behind the last group) reaches no row but
  its own.  Rows past the last group are written as zeros.

The arithmetic is the ``ragged_dot`` form's, which is this kernel's
reference: operands in the weights' dtype, each product accumulated in
float32, ``act(.) * (.)`` in float32 and cast to that dtype before the
``w2`` product, float32 out.

The kernel is ``ragged-dot-stream`` in the compiled program
(``mosaic:ragged-dot-stream.N`` in a device trace: the grouped products'
readers find it by ``mosaic:ragged-dot`` as they find XLA's own).  It
defines no gradient.  Off the TPU nothing here runs unless a test asks for
``interpret``: ``models/moe.py`` chooses."""

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

ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


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


def _kernel(counts_ref, xs_ref, w1_ref, w3_ref, w2_ref, y_ref,
            buf1, buf3, buf2, sems, hit_ref, off_ref, *, act):
    n_experts = counts_ref.shape[0]
    _, d, width = buf1.shape
    slabs = w1_ref.shape[2] // width
    tile = _sublanes(xs_ref.dtype)

    def scan(e, carry):
        n, off = carry
        c = counts_ref[e]

        @pl.when(c > 0)
        def _():
            hit_ref[n] = e
            off_ref[n] = off

        return n + (c > 0).astype(jnp.int32), off + c

    n_hit, _ = jax.lax.fori_loop(0, n_experts, scan,
                                 (jnp.int32(0), jnp.int32(0)))
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
            a = jnp.dot(x, buf1[half], preferred_element_type=jnp.float32)
            b = jnp.dot(x, buf3[half], preferred_element_type=jnp.float32)
            h = (act(a) * b).astype(x.dtype)
            y = jnp.dot(h, buf2[half], preferred_element_type=jnp.float32)
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
