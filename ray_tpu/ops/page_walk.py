"""The walk of a sequence's live pages: what the four paged-attention kernels
(``latent_decode``, ``paged_decode``, ``latent_prefill``, ``paged_prefill``)
share, written once, over a ROW KIND and in two FORMS (and a third for runs
of pages that several slots hold).

A row kind says what a page of the cache holds and how a block of it is
multiplied:

- a K/V PAIR: two pools ``[L, P+1, page, H_kv, D]``, a token's K and V one
  contiguous ``[H_kv, D]`` row each.  A page is handed over as ``[page x
  H_kv, D]``, position-major with the KV heads' rows interleaved as it lies
  (a bitcast of the pool: rows of 128 lanes pack the same way whatever the
  tile's height), and a query group meets its own KV head's rows;
- a LATENT: one pool ``[L, P+1, page, W]``, a token's row the key of ONE KV
  head that every query head shares, whose first ``rank`` columns are that
  head's value.  That is the pair with one pool and one head: one fetch
  serves both products, and nothing parts or masks heads.

Here the pools are a tuple of one or two arrays and ``rank`` the value's
columns; what a kind does not need is left out where the kernel is traced,
not computed and thrown away.  Both forms:

- leave the pools in HBM as they are (``pl.ANY``: no block of them is the
  pipeline's, so nothing copies or re-lays them); the layer, the tables and
  the bounds of the walk are scalar-prefetch operands;
- find position ``p`` in entry ``(p // page) % T`` of its sequence's table of
  ``T`` entries (``_entry``).  That one rule is every kind of table: a
  whole-length table never reaches its modulus, a window layer's ring wraps
  by it (``paged._ring_positions``);
- visit the pages that hold visible positions and no other, a block of pages
  at a time: one DMA a page and pool (a page is contiguous) into one half of
  a double buffer in VMEM, a semaphore a pool and half, started and waited
  for through the same enumeration, the next block's DMAs in flight while
  this block is multiplied.  A page outside the walk is neither fetched nor
  waited for;
- zero the pages of the VALUE buffer that a block did not fetch.  They hold
  what an earlier block (another slot's) left there; their scores are masked,
  so they meet a probability of exactly 0, but 0 x NaN is NaN.  A masked
  score never reads its key, so only the buffer the values are read from is
  zeroed (a latent's one buffer is that);
- run the online softmax over the blocks (``_online_softmax``): running
  maximum, sum and accumulator in float32, operands in the pool's dtype, both
  products accumulated in float32, the probabilities cast to the pool's dtype
  before the value product: the arithmetic of ``paged._attend_pages``, which
  is every kernel's reference.

The forms are ``walk_slots`` (one query row a slot: a decode step) and
``walk_rows`` (a block of query rows of one sequence: a prefill call).
Where several slots of a decode step open with the SAME pages (a prefix
cache's), ``shared_runs`` finds those runs and ``walk_shared`` fetches each
once for the stacked query rows of all its holders (a latent pool's; the
pair's kind is not written), leaving a softmax that ``walk_slots`` goes on
from over each slot's own tail.  The four modules beside this one are
fronts: a kind, a form, the block sizes measured for them, a geometry check
in their own words and the name their ``pallas_call`` carries into the
compiled program.  Off the TPU nothing here
runs unless a test asks for ``interpret``: ``models/paged.py`` chooses."""

from __future__ import annotations

import functools
import itertools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF, _on_tpu as on_tpu  # noqa: F401 (the fronts')


def sublanes(dtype) -> int:
    """Rows of one tile of ``dtype`` (8 of float32, 16 of bfloat16)."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def check_operands(takes: str, q: jax.Array, pools: tuple,
                   tables: jax.Array | None = None,
                   slots: bool = False) -> None:
    """The shared half of a front's ``check_geometry``, its first sentence:
    a ValueError ``takes`` unless q has three axes, the pools the kind's (a
    pair's five, a latent's four) and one shape, q's rows the pools' width
    and dtype, and the tables one axis (two, one table a row of q, for
    ``slots``)."""
    pool = pools[0]
    if q.ndim != 3 or pool.ndim != 3 + len(pools) \
            or q.shape[-1] != pool.shape[-1] \
            or any(p.shape != pool.shape or p.dtype != q.dtype
                   for p in pools) \
            or tables is not None and (
                tables.ndim != 1 + slots
                or slots and tables.shape[0] != q.shape[0]):
        got = [f"{x.shape} {x.dtype}" for x in (q, *pools)]
        if tables is not None:
            got.append(f"{tables.shape}")
        raise ValueError(f"{takes}: got {', '.join(got[:-1])} and {got[-1]}")


def check_tiles(needs: str, pool: jax.Array, more: bool = False) -> None:
    """The shared half's second sentence: a ValueError ``needs`` unless a
    page's rows are whole sublane tiles and a row whole lane tiles (the walk
    moves whole pages by DMA and multiplies them as they land), or if
    ``more``, what the front's kind and form need besides."""
    if math.prod(pool.shape[2:-1]) % sublanes(pool.dtype) \
            or pool.shape[-1] % 128 or more:
        raise ValueError(needs)


def _kv_heads(pool: jax.Array) -> int:
    """``H_kv`` of a pool: a latent pool ``[L, P+1, page, W]`` has one."""
    return math.prod(pool.shape[3:-1])


def _flat(pool: jax.Array) -> jax.Array:
    """The pool as ``[L, P+1, page x H_kv, D]``: a latent pool is that."""
    return pool.reshape(*pool.shape[:2], -1, pool.shape[-1])


def _entry(tables_ref, page_no, entries: int, slot=None):
    """The pool's page that holds page ``page_no`` of a sequence's
    positions: entry ``page_no % T`` of its table (``slot``'s of a batch of
    tables)."""
    if slot is None:
        return tables_ref[page_no % entries]
    return tables_ref[slot * entries + page_no % entries]


def _page_copies(pools, bufs, sems, layer, at, half, to):
    """The DMAs of page ``at`` of ``layer``, one a pool, into ``to`` of
    ``half`` of the pool's buffer."""
    return [pltpu.make_async_copy(pool.at[layer, at], buf.at[half, to],
                                  sems.at[s, half])
            for s, (pool, buf) in enumerate(zip(pools, bufs))]


def _and(seen, also):
    """``seen & also``, where ``seen`` None is a condition a kind has not."""
    return also if seen is None else seen & also


class _Carry:
    """A loop's carry read and written as a scratch ref is, by ``[...]``."""

    def __init__(self, x):
        self.x = x

    def __getitem__(self, _):
        return self.x

    def __setitem__(self, _, x):
        self.x = x


def _online_softmax(s, m, l, acc, values, dtype) -> None:
    """One block of the online softmax: the running maximum ``m``, sum ``l``
    and accumulator ``acc`` (float32; scratch refs, or a loop's carry as
    ``_Carry``) after the float32 scores ``s`` [rows, keys] (masked ones
    ``NEG_INF``) and the block's value rows ``values()`` [keys, rank], the
    probabilities cast to ``dtype``, the pool's, before they meet them.
    Each is read where it is needed and written as soon as it is known: a
    prefill's accumulator is not held across the scores' exponentials."""
    m_old = m[...]
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_old - m_new)
    p = jnp.exp(s - m_new)
    m[...] = m_new
    l[...] = alpha * l[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc[...] = alpha * acc[...] + jnp.dot(
        p.astype(dtype), values(), preferred_element_type=jnp.float32)


def _refs(refs, n_scalars: int, n_pools: int) -> tuple:
    """A kernel's refs apart: the scalar-prefetch operands, q, the pools,
    the output, the pools' buffers, the DMA semaphores, the rest."""
    refs = iter(refs)

    def take(n):
        return tuple(itertools.islice(refs, n))

    return (take(n_scalars), next(refs), take(n_pools), next(refs),
            take(n_pools), next(refs), tuple(refs))


# ------------------------------------------------------- one query row a slot


def _slots_kernel(*refs, n_pools: int, bounded: bool, seeded: bool,
                  entries: int, page: int, n_rep: int, sm_scale: float):
    scalars, q_ref, pools, o_ref, bufs, sems, (half_ref,) = _refs(
        refs, 3 + bounded + 3 * seeded, n_pools)
    scalars, seed = scalars[:3 + bounded], scalars[3 + bounded:]
    layer_ref, *lo_ref, hi_ref, tables_ref = scalars
    b, n_slots = pl.program_id(0), pl.num_programs(0)
    _, per_block, page_rows, dim = bufs[0].shape
    n_kv = page_rows // page
    rows = per_block * page_rows
    rank = o_ref.shape[-1]
    layer = layer_ref[0]

    def each_page(slot, block, half, live, dead=None):
        """``live(copy)`` on the DMAs (one a pool) of every page of
        ``slot``'s ``block`` that its walk visits into ``half`` of the
        buffers, ``dead(k)`` on the block's other pages."""
        first = lo_ref[0][slot] // page if bounded else 0
        last = hi_ref[slot] // page
        for k in range(per_block):
            p = first + block * per_block + k

            @pl.when(p <= last)
            def _(k=k, p=p):
                at = _entry(tables_ref, p, entries, slot)
                for copy in _page_copies(pools, bufs, sems, layer, at,
                                         half, k):
                    live(copy)

            if dead is not None and k:  # a block's first page is live
                pl.when(p > last)(functools.partial(dead, k))

    def start(slot, block, half):
        each_page(slot, block, half, lambda copy: copy.start())

    @pl.when(b == 0)
    def _():
        half_ref[0] = 0
        start(0, 0, 0)

    lo = lo_ref[0][b] if bounded else 0
    hi = hi_ref[b]
    first = lo // page
    n_blocks = pl.cdiv(hi // page - first + 1, per_block)
    q = q_ref[...]  # [heads, D]
    heads = q.shape[0]
    # Column c of a block's scores is the row of position c // H_kv (from
    # the block's first) and KV head c % H_kv; query row h is of the group
    # h // n_rep (the padding's rows of the last, their outputs dropped).
    # GQA without a repeat and without cutting the block a head: ALL the
    # query heads meet all of a block's rows in one product, and a score
    # whose row is another KV head's than its query's group is masked like
    # a position outside the walk, so its probability is exactly 0 and the
    # one value product over all rows adds nothing of another head.
    col = jax.lax.broadcasted_iota(jnp.int32, (heads, rows), 1)
    own = None
    if n_kv > 1:
        group = jnp.minimum(
            jax.lax.broadcasted_iota(jnp.int32, (heads, rows), 0) // n_rep,
            n_kv - 1)
        own = col % n_kv == group
    walk_pos = first * page if bounded else None  # the walk's first page's
    col_pos = col // n_kv if n_kv > 1 else col
    if bounded:
        col_pos = walk_pos + col_pos

    def body(j, carry):
        m, l, acc = (_Carry(x) for x in carry)
        half = half_ref[0]
        other = 1 - half

        @pl.when(j + 1 < n_blocks)
        def _():
            start(b, j + 1, other)

        # A slot's last block starts the next slot's first.
        @pl.when((j + 1 == n_blocks) & (b + 1 < n_slots))
        def _():
            start(b + 1, 0, other)

        def zero(k):
            bufs[-1][half, k] = jnp.zeros((page_rows, dim), bufs[-1].dtype)

        each_page(b, j, half, lambda copy: copy.wait(), zero)
        half_ref[0] = other
        keys = bufs[0][half].reshape(rows, dim)
        s = jax.lax.dot_general(
            q, keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [heads, rows]
        pos = col_pos + j * (per_block * page)
        seen = _and(own, pos >= lo) if bounded else own
        s = jnp.where(_and(seen, pos <= hi), s, NEG_INF)
        if n_pools == 1:
            values = lambda: keys[:, :rank]
        else:
            values = lambda: bufs[1][half].reshape(rows, dim)
        _online_softmax(s, m, l, acc, values, q.dtype)
        return m.x, l.x, acc.x

    # The softmax goes on from where a shared pass left it (``walk_shared``:
    # the slot's maximum, sum and accumulator over the pages before ``lo``).
    m, l, acc = jax.lax.fori_loop(
        0, n_blocks, body,
        tuple(ref[...] for ref in seed) if seeded else
        (jnp.full((heads, 1), NEG_INF, jnp.float32),
         jnp.zeros((heads, 1), jnp.float32),
         jnp.zeros((heads, rank), jnp.float32)))
    o_ref[...] = (acc / l).astype(o_ref.dtype)


def walk_slots(name: str, q: jax.Array, pools: tuple, layer,
               tables: jax.Array, lo: jax.Array | None, hi: jax.Array, *,
               rank: int, per_block: int, sm_scale: float,
               interpret: bool, seed: tuple = ()) -> jax.Array:
    """One query row a head and slot, q [B, H, D], over the pages of
    ``tables`` [B, T] in ``pools``' ``layer``, ``per_block`` pages at a
    time: slot ``b`` is one grid step and sees the positions ``lo[b] <= p <=
    hi[b]`` (``lo`` None: from 0, and no lower bound is compared), so it
    visits pages ``lo[b] // page .. hi[b] // page``; an empty slot (``lo =
    hi = 0``, an all-scratch table) costs one page.  A slot's last block
    puts the next slot's first in flight.  The pages are unrolled in the
    kernel's text, which is traced a call.  ``seed``: a softmax begun
    elsewhere over positions before ``lo`` (``walk_shared``'s float32
    maximum and sum [B, H, 1] and accumulator [B, H, rank]), which a slot's
    walk goes on from.  Returns [B, H, rank]."""
    B, H, D = q.shape
    page, n_kv = pools[0].shape[2], _kv_heads(pools[0])
    # Whole sublane tiles of query rows; the padding's outputs are dropped.
    tile = sublanes(q.dtype)
    heads = -(-H // tile) * tile
    q, *seed = (jnp.pad(x, ((0, 0), (0, heads - H), (0, 0)))
                for x in (q, *seed))
    bounds = [hi] if lo is None else [lo, hi]
    out = pl.pallas_call(
        functools.partial(_slots_kernel, n_pools=len(pools),
                          bounded=lo is not None, seeded=bool(seed),
                          entries=tables.shape[1], page=page,
                          n_rep=H // n_kv, sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2 + len(bounds),
            grid=(B,),
            in_specs=[pl.BlockSpec((None, heads, x.shape[-1]),
                                   lambda b, *_: (b, 0, 0))
                      for x in (*seed, q)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=pl.BlockSpec((None, heads, rank),
                                   lambda b, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, per_block, page * n_kv, D),
                                       q.dtype)] * len(pools)
            + [pltpu.SemaphoreType.DMA((len(pools), 2)),
               pltpu.SMEM((1,), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, heads, rank), q.dtype),
        # A slot's last block starts the next slot's first: in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      *(x.astype(jnp.int32) for x in bounds),
      tables.astype(jnp.int32).reshape(-1), *seed, q, *map(_flat, pools))
    return out[:, :H]


# ------------------------------------- a run of pages that several slots hold


class Runs(NamedTuple):
    """What ``shared_runs`` finds in a decode step's tables, once a step for
    every layer's call: which slots open with the same pages, and what
    ``walk_shared`` and the slots' own walks each take of them."""
    #: [B] the leading pages of slot ``b``'s table that its group's pass
    #: walks for it (0: it walks its whole table alone).
    run: jax.Array
    #: [B] the slots with a group's members side by side, the groups in
    #: their leaders' order, the slots of no group behind them; and [B]
    #: slot ``b``'s place among them.
    order: jax.Array
    place: jax.Array
    #: [4 x B] by LEADER slot, one after the other: its first member's
    #: place in ``order``, its members (0: the slot leads no pass), the
    #: pages of its pass (the longest run of a member) and the shortest.
    passes: jax.Array
    #: [R, 1] by stacked query row (``heads`` a slot in ``order``, padded to
    #: whole blocks of rows): its slot's leader (-1: none) and the first
    #: position it does not see in the pass (``run x page``).
    group: jax.Array
    limit: jax.Array
    #: The pages the per-slot walk would visit and this one does not, a
    #: layer: every member's run less one visit a pass.
    pages_saved: jax.Array


def shared_runs(tables: jax.Array, seq_lens: jax.Array, active: jax.Array,
                *, page: int, heads: int, rows: int) -> Runs:
    """The runs of a decode step's ``tables`` [B, T]: a slot's LEADER is the
    first active slot whose table opens with the same page, its RUN the
    leading entries equal to the leader's, cut to the whole pages below both
    slots' ``seq_lens // page`` (a shared page is whole and wholly live; the
    page a step writes is never shared).  A leader's own run is the longest
    of its followers'.  An inactive slot never groups (every empty slot's
    table is all scratch), and sharing below a group's own (two followers
    that go on together past their leader's end) is not looked for: those
    pages are walked slot by slot.  ``heads`` query rows a slot are stacked
    in blocks of ``rows`` (``walk_shared``).  A few comparisons on [B, T]
    int32, in the program."""
    B, T = tables.shape
    slot = jnp.arange(B, dtype=jnp.int32)
    whole = jnp.where(active, seq_lens // page, 0).astype(jnp.int32)
    same = (tables[:, :1] == tables[None, :, 0]) \
        & active[:, None] & active[None, :]
    leader = jnp.where(active, jnp.argmax(same, axis=1), slot)
    differs = tables != tables[leader]
    common = jnp.where(jnp.any(differs, axis=1),
                       jnp.argmax(differs, axis=1), T)
    run = jnp.where(leader == slot, 0, jnp.minimum(
        common, jnp.minimum(whole, whole[leader]))).astype(jnp.int32)
    longest = jnp.zeros_like(run).at[leader].max(run)
    run = jnp.where(leader == slot, longest, run)
    member = run > 0
    count = jnp.zeros_like(run).at[leader].add(member.astype(jnp.int32))
    shortest = jnp.full_like(run, T).at[leader].min(
        jnp.where(member, run, T))
    order = jnp.argsort(jnp.where(member, leader, B),
                        stable=True).astype(jnp.int32)
    place = jnp.zeros_like(order).at[order].set(slot)
    padded = -(-B * heads // rows) * rows

    def by_row(x, fill):  # [B] by slot -> [R, 1] by stacked query row
        return jnp.pad(jnp.repeat(x[order], heads), (0, padded - B * heads),
                       constant_values=fill)[:, None]

    return Runs(run, order, place,
                jnp.concatenate([jnp.cumsum(count) - count, count, longest,
                                 shortest]).astype(jnp.int32),
                by_row(jnp.where(member, leader, -1), -1),
                by_row(run * page, 0),
                jnp.sum(run) - jnp.sum(longest))


def _shared_kernel(layer_ref, passes_ref, tables_ref, q_ref, group_ref,
                   limit_ref, pool, m_ref, l_ref, acc_ref, buf, sems, *,
                   slots: int, heads: int, entries: int, page: int,
                   rows: int, sm_scale: float):
    keys, dim = buf.shape[1:]
    rank = acc_ref.shape[-1]
    per_block = keys // page
    layer = layer_ref[0]
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    pos = jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 1)

    def one_pass(leader, _):
        first, count, longest, shortest = (
            passes_ref[i * slots + leader] for i in range(4))
        # The members' stacked rows, and the blocks of rows that hold them.
        r0, r1 = first * heads, (first + count) * heads
        n_blocks = pl.cdiv(longest, per_block)

        def pages(block):
            return jnp.minimum(per_block, longest - block * per_block)

        def part(k):
            return pl.ds(pl.multiple_of(k * page, page), page)

        def each_page(block, half, then):
            def one(k, _):
                at = _entry(tables_ref, block * per_block + k, entries,
                            leader)
                for copy in _page_copies((pool,), (buf,), sems, layer, at,
                                         half, part(k)):
                    then(copy)
                return 0

            jax.lax.fori_loop(0, pages(block), one, 0)

        def body(j, _):
            half = j % 2

            @pl.when(j + 1 < n_blocks)
            def _():
                each_page(j + 1, 1 - half, lambda copy: copy.start())

            each_page(j, half, lambda copy: copy.wait())

            def zero(k, _):  # what the run does not fill of the last block
                buf[half, part(k)] = jnp.zeros((page, dim), buf.dtype)
                return 0

            jax.lax.fori_loop(pages(j), per_block, zero, 0)
            k0 = j * keys  # the block's first position

            def block_of_rows(c, _):
                at = pl.ds(pl.multiple_of(c * rows, rows), rows)

                def attend(masked: bool):
                    block = buf[half]
                    s = jax.lax.dot_general(
                        q_ref[at, :], block, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * sm_scale
                    if masked:
                        # A row of another group, of none, or of the
                        # padding sees nothing; a member, its own run.
                        limit = jnp.where(group_ref[at, :] == leader,
                                          limit_ref[at, :], 0) - k0
                        s = jnp.where(pos < limit, s, NEG_INF)
                    _online_softmax(s, m_ref.at[at], l_ref.at[at],
                                    acc_ref.at[at],
                                    lambda: block[:, :rank], buf.dtype)

                # Every row of the block is a member, and every member's
                # run holds the whole of these keys.
                whole = (c * rows >= r0) & ((c + 1) * rows <= r1) \
                    & (k0 + keys <= shortest * page)
                pl.when(whole)(functools.partial(attend, False))
                pl.when(jnp.logical_not(whole))(
                    functools.partial(attend, True))
                return 0

            jax.lax.fori_loop(r0 // rows, pl.cdiv(r1, rows), block_of_rows,
                              0)
            return 0

        @pl.when(count > 0)
        def _():
            each_page(0, 0, lambda copy: copy.start())
            jax.lax.fori_loop(0, n_blocks, body, 0)

        return 0

    jax.lax.fori_loop(0, slots, one_pass, 0)


def walk_shared(name: str, q: jax.Array, pool: jax.Array, layer,
                tables: jax.Array, runs: Runs, *, rank: int, rows: int,
                per_block: int, vmem_limit_bytes: int, sm_scale: float,
                interpret: bool) -> tuple:
    """The part of a decode step's softmax that lies in pages several slots
    hold, each run FETCHED ONCE for all its holders: for every leader of
    ``runs`` with followers, the run's pages of the leader's table in
    ``pool``'s ``layer`` (a latent pool [L, P+1, page, W]), ``per_block``
    pages at a time through the walk's page DMAs and double buffer,
    multiplied against the stacked query rows of ALL its members (q
    [B, H, D]: ``H`` rows a slot, the members side by side, in blocks of
    ``rows``), a member masked past its own run.  A step with no run
    anywhere walks nothing.  Returns the online softmax where the passes
    left it, float32 by slot: (maximum [B, H, 1], sum [B, H, 1], accumulator
    [B, H, rank]); of a slot in no run ``NEG_INF``, and what a first visible
    score wipes (its rows may have stood in a member's block, masked: a
    probability of exp(0) under a maximum of ``NEG_INF``, times ``exp(NEG_INF
    - m)``, exactly 0, when a score comes).  ``walk_slots`` goes on from
    them over each slot's own tail (``seed``, ``lo = run x page``)."""
    return _shared_call(jnp.asarray(layer, jnp.int32).reshape(1), q, pool,
                        tables.astype(jnp.int32), runs, name=name,
                        rank=rank, rows=rows,
                        per_block=min(per_block, tables.shape[1]),
                        vmem_limit_bytes=vmem_limit_bytes, sm_scale=sm_scale,
                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "name", "rank", "rows", "per_block", "vmem_limit_bytes", "sm_scale",
    "interpret"))
def _shared_call(layer, q, pool, tables, runs, *, name, rank, rows,
                 per_block, vmem_limit_bytes, sm_scale, interpret):
    """``walk_shared``'s kernel call between the stacking of the queries and
    the partials' way back to their slots, jitted on its own as ``_call``
    is: the layers of a program share one trace and one lowering of it."""
    B, H, D = q.shape
    page = pool.shape[2]
    R = runs.group.shape[0]
    stacked = jnp.pad(q[runs.order].reshape(B * H, D),
                      ((0, R - B * H), (0, 0)))

    def whole(width):  # every stacked row at once, in VMEM
        return pl.BlockSpec((R, width), lambda i, *_: (0, 0))

    def kept(width):  # float32, a row a stacked query row
        return jax.ShapeDtypeStruct((R, width), jnp.float32)

    partials = pl.pallas_call(
        functools.partial(_shared_kernel, slots=B, heads=H,
                          entries=tables.shape[1], page=page, rows=rows,
                          sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[whole(D), whole(1), whole(1),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[whole(1), whole(1), whole(rank)],
            scratch_shapes=[pltpu.VMEM((2, per_block * page, D), q.dtype),
                            pltpu.SemaphoreType.DMA((1, 2))],
        ),
        out_shape=[kept(1), kept(1), kept(rank)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        name=name,
    )(layer, runs.passes, tables.reshape(-1), stacked, runs.group,
      runs.limit, pool)
    return tuple(x[:B * H].reshape(B, H, -1)[runs.place] for x in partials)


# -------------------------------------- a block of query rows of one sequence


def _head_rows(buf, g: int, n_kv: int, keys: int):
    """KV head ``g``'s rows [keys, D] of a buffer ref [keys x n_kv, D] that
    holds whole pages as they lie (row ``j x n_kv + g`` is position ``j``'s,
    head ``g``'s).  Two heads' rows share a 32-bit word of a bfloat16 pool,
    so no DMA can part them: they are parted here, in VMEM.  Rows of 32 bits
    part by a strided load.  Two rows of 16 bits share a word (the even row
    its low half): the words of the head's pair are loaded by stride and the
    half shifted into a float32's high bits, which IS the bfloat16's
    value."""
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


def _rows_kernel(*refs, n_pools: int, entries: int, page: int, n_kv: int,
                 window: int, sm_scale: float):
    (layer_ref, span_ref, table_ref), q_ref, pools, o_ref, bufs, sems, \
        (m_ref, l_ref, acc_ref) = _refs(refs, 3, n_pools)
    i = pl.program_id(0)
    heads, rows, dim = q_ref.shape
    rank = o_ref.shape[-1]
    n_rep = heads // n_kv
    page_rows = page * n_kv
    per_block = bufs[0].shape[1] // page_rows
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

    def part(k):
        """Page ``k`` of a half of a buffer."""
        return pl.ds(pl.multiple_of(k * page_rows, page_rows), page_rows)

    def each_page(block, half, then):
        """``then(copy)`` on the DMAs (one a pool) of every page of
        ``block`` that the walk visits, into ``half`` of the buffers.  A
        loop, not unrolled: the kernel's text is traced and lowered once a
        program and kind of layer, and that is set-up time."""
        def one(k, _):
            at = _entry(table_ref, first + block * per_block + k, entries)
            for copy in _page_copies(pools, bufs, sems, layer, at, half,
                                     part(k)):
                then(copy)
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
            to = part(k)
            bufs[-1][half, to] = jnp.zeros((page_rows, dim), bufs[-1].dtype)
            return 0

        jax.lax.fori_loop(pages(j), per_block, zero, 0)
        c0 = (first + j * per_block) * page  # the block's first position

        def attend(masked: bool):
            # GQA without a repeat and without ``walk_slots``' all-heads
            # product (which at 2048 query rows would be ``H_kv`` times the
            # MXU's work): a query group meets its OWN KV head's rows.
            for g in range(n_kv):
                q = q_ref[g * n_rep:(g + 1) * n_rep].reshape(group, dim)
                head = _head_rows(bufs[0].at[half], g, n_kv, keys)
                s = jax.lax.dot_general(
                    q, head, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * sm_scale
                if masked:
                    d = rel + c0
                    seen = (d <= 0) & (d > -window) if window else d <= 0
                    s = jnp.where(seen, s, NEG_INF)
                if n_pools == 1:
                    values = lambda: head[:, :rank]
                else:
                    values = functools.partial(
                        _head_rows, bufs[1].at[half], g, n_kv, keys)
                _online_softmax(s, m_ref.at[g], l_ref.at[g], acc_ref.at[g],
                                values, q.dtype)

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
            n_rep, rows, rank).astype(o_ref.dtype)


def walk_rows(name: str, q: jax.Array, pools: tuple, layer, table: jax.Array,
              first, length, *, rank: int, window: int, rows: int,
              per_block: int, vmem_limit_bytes: int, sm_scale: float,
              interpret: bool) -> jax.Array:
    """The query rows of one prefill call, q [S, H, D], over the pages of
    ``table`` [T] in ``pools``' ``layer``, into which the call's own rows
    are already written (the kernel reads pages only): row ``r`` sits at
    position ``first + r`` and sees ``max(0, p - window + 1) <= j <= p``
    (``window`` 0: from 0, and no window is compared).  A block of ``rows``
    query rows is one grid step.  Its rows that are real (before ``length``)
    see positions ``lo .. hi``, from the window of its first row to its last
    real row: it walks pages ``lo // page .. hi // page`` and no other,
    ``per_block`` pages at a time.  A block of keys that every row of the
    query block sees whole (behind the diagonal, inside the window) skips
    the mask; a block wholly in the bucket's padding walks nothing.  Rows at
    or past ``length`` see what the last real row sees of the fetched pages:
    finite, and dropped by the caller.  Returns [S, H, rank]."""
    return _call(jnp.asarray(layer, jnp.int32).reshape(1),
                 jnp.stack([jnp.asarray(first, jnp.int32),
                            jnp.asarray(length, jnp.int32)]),
                 table.astype(jnp.int32), q, pools, name=name, rank=rank,
                 window=window, rows=rows,
                 per_block=min(per_block, table.shape[0]),
                 vmem_limit_bytes=vmem_limit_bytes, sm_scale=sm_scale,
                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "name", "rank", "window", "rows", "per_block", "vmem_limit_bytes",
    "sm_scale", "interpret"))
def _call(layer, span, table, q, pools, *, name, rank, window, rows,
          per_block, vmem_limit_bytes, sm_scale, interpret):
    """``walk_rows``' kernel call, jitted on its own: the layers of one kind
    in a program (and a bucket's two programs) then share ONE trace of the
    kernel and one lowering of it a program, where each call of its own
    would cost 0.4 s of every start, warm or cold (the layer is data):
    eighty calls of their own cost every start of SmallThinker's cell 39 s
    (PERF.md, PR 44)."""
    S, H, D = q.shape
    page, n_kv = pools[0].shape[2], _kv_heads(pools[0])
    group = H // n_kv * rows
    out = pl.pallas_call(
        functools.partial(_rows_kernel, n_pools=len(pools),
                          entries=table.shape[0], page=page, n_kv=n_kv,
                          window=window, sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S // rows,),
            in_specs=[pl.BlockSpec((H, rows, D), lambda i, *_: (0, i, 0))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=pl.BlockSpec((H, rows, rank), lambda i, *_: (0, i, 0)),
            scratch_shapes=[pltpu.VMEM((2, per_block * page * n_kv, D),
                                       q.dtype)] * len(pools)
            + [pltpu.SemaphoreType.DMA((len(pools), 2)),
               pltpu.VMEM((n_kv, group, 1), jnp.float32),
               pltpu.VMEM((n_kv, group, 1), jnp.float32),
               pltpu.VMEM((n_kv, group, rank), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((H, S, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        name=name,
    )(layer, span, table, q.transpose(1, 0, 2), *map(_flat, pools))
    return out.transpose(1, 0, 2)
