"""A selective state-space layer's decode step in ONE pass over the slots'
state: a Pallas TPU kernel that reads each slot's ``[N, I]`` float32 state
once, writes it once where it lies, and returns ``y = H C`` from the same
pass.

``models/mamba.py:recurrent`` is plain ``jax.numpy``, and XLA splits it in
two: a fusion that reads a layer's states and writes them back, and a second
that reads the new states AGAIN for ``y = sum_n H C``: three passes where two
would do.  Here the state pool ``S [layers, slots, N, I]`` is the call's
operand AND its output (``input_output_aliases``: nothing copies it), the
layer a scalar-prefetch operand that the block index map reads, so one
lowering serves every layer of a program with the layer as data.  The grid
walks blocks of ``SLOTS_BLOCK`` slots, double-buffered in and out by the
pipeline; ``A = -exp(A_log)`` ``[N, I]`` is made once, in the first step,
and stays in VMEM.  A slot's state passes the arithmetic in strips of
``STRIP`` channels, so that a strip's intermediates stay in registers.

The arithmetic is ``recurrent``'s, in float32, which is the kernel's
reference and every other backend's form:
``H' = exp(delta (x) A) . H + (delta . xs) (x) B``, ``y = sum_n H' . C``;
only the order of the sum over N may differ.  ``B`` and ``C`` come as
``[slots, N, 1]``: N on the sublanes, where the state has it, so nothing is
laid out again in the kernel, and never broadcast to the state's own bytes.
An inactive slot's state is written back as it was read, to the bit, and
its ``y`` is zero; no slot's numbers meet another's, so what a dead slot
holds (a NaN) stays there.

The kernel is ``ssm_decode`` in the compiled program (``mosaic:ssm_decode.N``
in a device trace).  Off the TPU nothing here runs unless a test asks for
``interpret``: ``models/mamba.py`` chooses (``_steps_in_place``)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _on_tpu as on_tpu  # noqa: F401 (mamba asks it)

LANES, SUBLANES = 128, 8
#: Slots whose states one grid step brings in and sends off (the most; a
#: divisor of the slots).  Eight slots of Jamba2-3B's 328 KB are 2.6 MB, in
#: and out and both halves of each 10.5 MB.  Alone on a v5e at 128 slots of
#: [16, 5120], 26 layers a call (PERF.md section 6, PR 50): blocks of 1 / 4 /
#: 8 / 16 / 32 slots read 0.147 / 0.144 / 0.142 / 0.143 / 0.149 ms a layer;
#: the HBM gives a stream that is half reads and half writes 590-650 GB/s of
#: its 819 (XLA's own in-place fusion: 0.129), and neither more DMAs in
#: flight nor deeper buffers moved it.
SLOTS_BLOCK = 8
#: Channels of one slot's state that pass the arithmetic at once (256 to
#: 5120 read the same: the DMAs bind, not the arithmetic).
STRIP = 512


def check_geometry(S: jax.Array, delta: jax.Array) -> None:
    """A ValueError naming what the kernel cannot take: it moves whole
    ``[N, I]`` states by DMA and updates them tile by tile, so the channels
    have to be whole lane tiles, the state entries a channel whole sublane
    tiles, and the state float32 (``recurrent``'s arithmetic)."""
    if S.ndim != 4 or delta.shape != (S.shape[1], S.shape[3]):
        raise ValueError(
            f"the state-space decode step takes the state pool "
            f"[layers, slots, N, I] and delta [slots, I]: got {S.shape} and "
            f"{delta.shape}")
    n, i = S.shape[2:]
    if i % LANES:
        raise ValueError(
            f"the state-space decode step needs channels of whole lane "
            f"tiles: I {i} (tiles of {LANES})")
    if n % SUBLANES:
        raise ValueError(
            f"the state-space decode step needs a state of whole sublane "
            f"tiles a channel: N {n} (tiles of {SUBLANES})")
    if S.dtype != jnp.float32:
        raise ValueError(
            f"the state-space decode step updates a float32 state: got "
            f"{S.dtype}")


def takes(n: int, i: int, dtype) -> bool:
    """Whether ``check_geometry`` would pass a state of ``[n, i]`` a slot."""
    return i % LANES == 0 and n % SUBLANES == 0 and dtype == jnp.float32


def _divisor(n: int, most: int) -> int:
    """The largest divisor of ``n`` that is at most ``most``."""
    return max(d for d in range(1, min(n, most) + 1) if n % d == 0)


def _kernel(layer_ref, active_ref, s_ref, alog_ref, delta_ref, xs_ref,
            bm_ref, cm_ref, y_ref, out_ref, a_ref, *, strip):
    del layer_ref  # the index maps read it
    j = pl.program_id(0)
    block, _, width = s_ref.shape
    # The rows (delta, xs, y) come a block of slots at a time, or whole
    # where a block is less than a sublane tile (``_call``).
    first = j * block if delta_ref.shape[0] != block else 0

    @pl.when(j == 0)
    def _():
        a_ref[...] = -jnp.exp(alog_ref[...])

    for s in range(block):
        live = active_ref[j * block + s] != 0
        row = pl.ds(first + s, 1)

        @pl.when(live)
        def _():
            bm, cm = bm_ref[s], cm_ref[s]  # [N, 1]
            for c in range(0, width, strip):
                cols = pl.ds(c, min(strip, width - c))
                d = delta_ref[row, cols]  # [1, strip]
                dx = d * xs_ref[row, cols]
                h = jnp.exp(d * a_ref[:, cols]) * s_ref[s, :, cols] + dx * bm
                out_ref[s, :, cols] = h
                y_ref[row, cols] = jnp.sum(h * cm, axis=0, keepdims=True)

        @pl.when(jnp.logical_not(live))
        def _():
            out_ref[s] = s_ref[s]
            y_ref[row, :] = jnp.zeros((1, width), jnp.float32)


@functools.partial(jax.jit, static_argnames=("block", "strip", "interpret"))
def _call(S, layer, A_log, delta, xs, bm, cm, active, *, block, strip,
          interpret):
    """The kernel's call, jitted on its own with the layer as data: the
    layers of a program share one trace and one lowering of it (a
    ``pallas_call`` is traced and lowered where it stands, before the
    compile cache is asked: PERF.md, PR 44)."""
    _, B, n, i = S.shape
    # A block of fewer rows than a sublane tile cannot be cut from [B, I]:
    # the rows (delta, xs, y) are then whole in VMEM, fetched once.
    whole = block % SUBLANES != 0 and block != B

    def per_block(*tail):  # a block of slots of [B, ...]
        return pl.BlockSpec((block, *tail),
                            lambda j, *_: (j,) + (0,) * len(tail))

    def row_spec():
        return pl.BlockSpec((B, i), lambda j, *_: (0, 0)) if whole \
            else per_block(i)

    state = pl.BlockSpec((None, block, n, i),
                         lambda j, layer, *_: (layer[0], j, 0, 0))
    held = (B if whole else block) * i * 4
    # Both halves of the states in and out, A_log and A, the rows (delta,
    # xs, y), B and C as [N, 1] pads to lane tiles, and room to spare.
    vmem = (4 * block * n * i * 4 + 3 * n * i * 4 + 6 * held
            + 4 * block * n * LANES * 4 + (4 << 20))
    y, S = pl.pallas_call(
        functools.partial(_kernel, strip=strip),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B // block,),
            in_specs=[
                state,
                pl.BlockSpec((n, i), lambda j, *_: (0, 0)),
                row_spec(), row_spec(),
                per_block(n, 1), per_block(n, 1),
            ],
            out_specs=[row_spec(), state],
            scratch_shapes=[pltpu.VMEM((n, i), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, i), jnp.float32),
                   jax.ShapeDtypeStruct(S.shape, S.dtype)],
        input_output_aliases={2: 1},  # the pool, behind the two scalars
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        interpret=interpret,
        name="ssm_decode",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), active.astype(jnp.int32),
      S, A_log, delta, xs, bm[:, :, None], cm[:, :, None])
    return y, S


def ssm_decode_step(S: jax.Array, layer, A_log: jax.Array, delta: jax.Array,
                    xs: jax.Array, bm: jax.Array, cm: jax.Array,
                    active: jax.Array, *, interpret: bool = False):
    """One token a slot through layer ``layer`` of the state pool ``S``
    [layers, slots, N, I] float32: ``A_log`` [N, I] (``A = -exp(A_log)``),
    the step ``delta`` and the convolved ``xs`` [slots, I], the token's
    ``bm`` and ``cm`` [slots, N], all float32; ``active`` [slots] bool.
    Returns (y [slots, I] float32 without the ``D`` skip, the pool): the
    pool is the operand's own buffer (donate it), layer ``layer`` of an
    active slot replaced by ``exp(delta (x) A) . H + (delta . xs) (x) bm``,
    every other entry as it was, to the bit; an inactive slot's ``y`` is
    zero.  A geometry the kernel cannot take raises (``check_geometry``)."""
    check_geometry(S, delta)
    return _call(S, jnp.asarray(layer, jnp.int32), A_log, delta, xs, bm, cm,
                 active, block=_divisor(S.shape[1], SLOTS_BLOCK),
                 strip=min(STRIP, S.shape[3]), interpret=interpret)
