"""A selective state-space layer's recurrence over ONE sequence's chunk in
one call: a Pallas TPU kernel that keeps the ``[N, I]`` float32 state in
VMEM from the chunk's first position to its last real one, and walks the
real rows only.

``models/mamba.py:chunked`` carries the state from position to position with
a ``lax.scan``, and XLA writes it to HBM and reads it back every trip: 1.39
ms a layer for 2048 rows of Jamba2-3B's ``[16, 5120]`` alone on a v5e, 26
layers a call (PERF.md section 6, PR 61), some four times the arithmetic and
the streams past it, and a row that holds no token costs a full trip.  Here
the grid is (blocks of channels, blocks of ``POSITIONS_BLOCK`` positions),
positions innermost and in order.  A channel block's state is the call's
second OUTPUT block, which the pipeline leaves in VMEM while the position
blocks pass: read from HBM once, written once, and stepped where it lies, a
strip of ``STRIP`` channels at a time.  ``delta``, ``xs``, ``B`` and ``C``
stream past it, block by block, double-buffered; ``A = -exp(A_log)`` is made
once a channel block.

ONLY REAL ROWS ARE WALKED.  The count of real rows is a scalar-prefetch
operand: a position block at or past the last real row fetches nothing (its
index maps name the last real block again, which the pipeline has) and runs
no arithmetic, and the trips inside the last real block stop at the count.
``y`` of a row that was not walked is ZERO, written by the kernel: a padded
row's output goes on through the block's products and into the attention
layers' pages, and what lay in the buffer (a NaN) would not be masked by a
zero weight there.  Nothing a padded row holds is read.

The arithmetic is ``chunked``'s, in float32, which is the kernel's
reference, the trainer's form and every other backend's:
``H = exp(delta (x) A) . H + (delta . xs) (x) B``, ``y = sum_n H . C``; only
the order of the sum over N may differ.  ``delta . xs`` is formed here (no
``[T, I]`` array is written for it).  ``B`` and ``C`` come as
``[T, N, LANES]``, an entry across its lane tile: N on the sublanes, where
the state has it, so the kernel neither lays anything out again nor
broadcasts along the lanes (``[T, N, 1]``, as ``ssm_decode`` takes a step's,
pads to the same bytes in HBM, and the broadcast of a position's column
then costs a fifth of the call: section 6, PR 61).

The kernel is ``ssm_scan`` in the compiled program (``mosaic:ssm_scan.N`` in
a device trace).  Off the TPU nothing here runs unless a test asks for
``interpret``: ``models/mamba.py`` chooses (``_scans_on_chip``)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _on_tpu as on_tpu  # noqa: F401 (mamba asks it)
from .ssm_decode import LANES, SUBLANES, _divisor

#: Positions a grid step brings in and sends off; a chunk has to be whole
#: blocks of it (``takes_rows``: the engine's buckets 128..2048 all are).
#: Alone on a v5e at 2048 rows of [16, 5120], 26 layers a call (section 6,
#: PR 61): blocks of 64 / 128 / 256 read 0.381 / 0.385 / 0.413 ms a layer.
POSITIONS_BLOCK = 128
#: Channels a grid step holds the state of, at most (Jamba2-3B's 5120 whole:
#: ``B`` and ``C`` are fetched once a channel block).
CHANNELS_BLOCK = 5120
#: Channels of the state that pass the arithmetic at once (512 and 1024 read
#: the same; 256 is slower).
STRIP = 512
#: Positions a trip of the kernel's loop takes (4, 8 and 16 read the same);
#: the last real ones, fewer than that, take a trip each.
UNROLL = 8


def check_geometry(H: jax.Array, delta: jax.Array) -> None:
    """A ValueError naming what the kernel cannot take: the state of whole
    ``(8, 128)`` tiles, in float32 (``chunked``'s arithmetic), and a chunk
    of whole position blocks."""
    if H.ndim != 2 or delta.ndim != 2 or delta.shape[1] != H.shape[1]:
        raise ValueError(
            f"the state-space scan takes the state [N, I] and delta "
            f"[T, I]: got {H.shape} and {delta.shape}")
    n, i = H.shape
    if i % LANES:
        raise ValueError(
            f"the state-space scan needs channels of whole lane tiles: "
            f"I {i} (tiles of {LANES})")
    if n % SUBLANES:
        raise ValueError(
            f"the state-space scan needs a state of whole sublane tiles a "
            f"channel: N {n} (tiles of {SUBLANES})")
    if H.dtype != jnp.float32:
        raise ValueError(
            f"the state-space scan carries a float32 state: got {H.dtype}")
    if not takes_rows(delta.shape[0]):
        raise ValueError(
            f"the state-space scan needs a chunk of whole position blocks: "
            f"T {delta.shape[0]} (blocks of {POSITIONS_BLOCK})")


def takes(n: int, i: int, dtype) -> bool:
    """Whether ``check_geometry`` would pass a state of ``[n, i]``."""
    return i % LANES == 0 and n % SUBLANES == 0 and dtype == jnp.float32


def takes_rows(t: int) -> bool:
    """Whether ``check_geometry`` would pass a chunk of ``t`` rows."""
    return t > 0 and t % POSITIONS_BLOCK == 0


def _kernel(count_ref, h_ref, alog_ref, delta_ref, xs_ref, bm_ref, cm_ref,
            y_ref, out_ref, a_ref, *, strip, unroll):
    j = pl.program_id(1)
    block, width = delta_ref.shape
    # The rows of this block to walk: all of it, the head of the last real
    # block, none of a block behind it.
    real = jnp.clip(count_ref[0] - j * block, 0, block)

    @pl.when(j == 0)
    def _():
        a_ref[...] = -jnp.exp(alog_ref[...])
        out_ref[...] = h_ref[...]

    @pl.when(real < block)
    def _():  # the rows not walked; the real ones are written below
        y_ref[...] = jnp.zeros_like(y_ref)

    def across(col, lanes):  # [N, LANES], an entry a row: [N, lanes]
        return jnp.concatenate([col] * (lanes // LANES), axis=1)

    def step(t, carry):
        row = pl.ds(t, 1)
        bm, cm = bm_ref[t], cm_ref[t]
        for c in range(0, width, strip):
            lanes = min(strip, width - c)
            cols = pl.ds(c, lanes)
            d = delta_ref[row, cols]  # [1, lanes]
            h = jnp.exp(d * a_ref[:, cols]) * out_ref[:, cols] \
                + (d * xs_ref[row, cols]) * across(bm, lanes)
            out_ref[:, cols] = h
            y_ref[row, cols] = jnp.sum(h * across(cm, lanes), axis=0,
                                       keepdims=True)
        return carry

    def trip(g, carry):
        first = pl.multiple_of(g * unroll, unroll)
        for u in range(unroll):
            step(first + u, carry)
        return carry

    @pl.when(real > 0)
    def _():
        trips = real // unroll
        jax.lax.fori_loop(0, trips, trip, 0)
        jax.lax.fori_loop(trips * unroll, real, step, 0)


@functools.partial(jax.jit, static_argnames=("block", "channels", "strip",
                                             "unroll", "interpret"))
def _call(A_log, H, delta, xs, bm, cm, count, *, block, channels, strip,
          unroll, interpret):
    """The kernel's call, jitted on its own: the layers of a program share
    one trace and one lowering of it (a ``pallas_call`` is traced and
    lowered where it stands, before the compile cache is asked: PERF.md,
    PR 44)."""
    T, i = delta.shape
    n = H.shape[0]

    def last(count):  # the last block that holds a real row
        return jnp.maximum(count[0] - 1, 0) // block

    def held(j, count):  # a block that holds a real row: the last, again
        return jnp.minimum(j, last(count))

    wide = pl.BlockSpec((block, channels),
                        lambda c, j, count: (held(j, count), c))
    tall = pl.BlockSpec((block, n, LANES),
                        lambda c, j, count: (held(j, count), 0, 0))
    state = pl.BlockSpec((n, channels), lambda c, j, count: (0, c))
    # Both halves of delta, xs and y, of B and C, of the state in and out
    # and A_log; A; and room to spare.
    vmem = (6 * block * channels * 4 + 4 * block * n * LANES * 4
            + 7 * n * channels * 4 + (4 << 20))
    y, H = pl.pallas_call(
        functools.partial(_kernel, strip=strip, unroll=unroll),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(i // channels, T // block),
            in_specs=[state, state, wide, wide, tall, tall],
            out_specs=[
                pl.BlockSpec((block, channels), lambda c, j, count: (j, c)),
                state],
            scratch_shapes=[pltpu.VMEM((n, channels), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((T, i), jnp.float32),
                   jax.ShapeDtypeStruct((n, i), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name="ssm_scan",
    )(jnp.reshape(count, (1,)).astype(jnp.int32), H, A_log, delta, xs,
      *(jnp.broadcast_to(t[:, :, None], (T, n, LANES)) for t in (bm, cm)))
    return y, H


def ssm_scan_chunk(A_log: jax.Array, H: jax.Array, delta: jax.Array,
                   xs: jax.Array, bm: jax.Array, cm: jax.Array, count, *,
                   interpret: bool = False):
    """The first ``count`` of ONE sequence's ``T`` rows through a layer's
    recurrence behind the state ``H`` [N, I] float32: ``A_log`` [N, I]
    (``A = -exp(A_log)``), the step ``delta`` and the convolved ``xs``
    [T, I], the tokens' ``bm`` and ``cm`` [T, N], all float32.  Returns
    (y [T, I] float32 without the ``D`` skip, the state behind row
    ``count - 1``): ``y`` of a row at or past ``count`` is zero, and nothing
    such a row holds is read; a ``count`` of 0 returns ``H`` as it came.  A
    geometry the kernel cannot take raises (``check_geometry``)."""
    check_geometry(H, delta)
    i = H.shape[1]
    channels = LANES * _divisor(i // LANES, CHANNELS_BLOCK // LANES)
    return _call(A_log, H, delta, xs, bm, cm, jnp.asarray(count, jnp.int32),
                 block=POSITIONS_BLOCK, channels=channels,
                 strip=min(STRIP, channels), unroll=UNROLL,
                 interpret=interpret)
