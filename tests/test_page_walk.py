"""``ray_tpu/ops/page_walk.py``: the one walk under the four paged-attention
kernels.  What the merge rests on and no other test states: a LATENT is the
K/V PAIR with one pool and one head, in both forms of the walk; and the
shared half of the geometry check answers for every front in the front's
own words.  The kernels against the gather form, the dead pages and the
walk's ends stay in the four fronts' own test files."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import walk_ref
from ray_tpu.ops import (latent_decode, latent_prefill, page_walk,
                         paged_decode, paged_prefill)

WIDTH, RANK, HEADS = 256, 128, 4
#: dtype -> positions a page (one sublane tile of it).
PAGES = {jnp.bfloat16: 16, jnp.float32: 8}


def _pool(dtype, entries, slots=1):
    """A latent pool of two layers, its last page the scratch page, and
    ``slots`` tables of ``entries`` pages that share none."""
    pages = slots * entries + 3
    kv = walk_ref.seeded((2, pages + 1, PAGES[dtype], WIDTH), dtype, 0)
    tables = np.random.default_rng(0).permutation(pages)[:slots * entries]
    return kv, jnp.asarray(tables.reshape(slots, entries), jnp.int32)


def _as_pair(kv):
    """The latent pool as a K/V pair of one head: K the pool, V the pool
    with the columns past the value zeroed (the pair wants one shape)."""
    k = kv[:, :, :, None, :]
    return k, k.at[..., RANK:].set(0)


def _same(latent, pair, rows):
    latent, pair = (np.asarray(x, np.float32) for x in (latent, pair))
    assert latent.shape == (rows, HEADS, RANK)
    assert np.isfinite(latent).all()
    np.testing.assert_array_equal(latent, pair[..., :RANK])
    assert not pair[..., RANK:].any()


@pytest.mark.parametrize("dtype", list(PAGES), ids=["bfloat16", "float32"])
def test_a_latent_is_the_pair_with_one_pool_and_one_head_a_slot(dtype):
    """One query row a slot: an empty slot (an all-scratch table), one
    inside its first page, a partial block, two blocks and a part."""
    page, entries = PAGES[dtype], 5
    kv, tables = _pool(dtype, entries, slots=4)
    tables = tables.at[0].set(kv.shape[1] - 1)
    lens = jnp.asarray([0, page - 3, 2 * page - 1, 4 * page + 2], jnp.int32)
    q = walk_ref.seeded((4, HEADS, WIDTH), dtype, 1)
    k, v = _as_pair(kv)
    # Two pages a block on both fronts, so the slots' blocks are the same.
    latent = walk_ref.blocked(
        latent_decode, latent_decode.latent_decode_attention,
        (("PAGES_PER_BLOCK", 2),), rank=RANK, sm_scale=0.1, interpret=True)(
        (q, kv), tables, lens)
    pair = walk_ref.blocked(
        paged_decode, paged_decode.paged_decode_attention,
        (("BLOCK_BYTES", 2 * 2 * k[0, 0].nbytes),), sm_scale=0.1,
        interpret=True)((q, k, v), tables, jnp.zeros_like(lens), lens)
    _same(latent, pair, 4)


@pytest.mark.parametrize("first, real", [
    (0, 32), (0, 21), (5 * 16 + 3, 32), (3 * 16, 27)],
    ids=["first-rows", "first-rows-padded", "a-suffix-inside-a-page",
         "a-chunk-behind-cached-pages"])
@pytest.mark.parametrize("dtype", list(PAGES), ids=["bfloat16", "float32"])
def test_a_latent_is_the_pair_with_one_pool_and_one_head_a_block_of_rows(
        dtype, first, real):
    """A block of query rows of one sequence, ``window`` 0: two blocks of 16
    rows against blocks of 32 keys on both fronts, over a table long enough
    for every case (one compile a dtype and front)."""
    kv, tables = _pool(dtype, (5 * 16 + 3 + 32) // PAGES[dtype] + 2)
    q = walk_ref.seeded((32, HEADS, WIDTH), dtype, 1)
    k, v = _as_pair(kv)
    blocks = (("BLOCK_ROWS", 16), ("BLOCK_KEYS", 32))
    at = (tables[0], jnp.int32(first), jnp.int32(first + real))
    latent = walk_ref.blocked(
        latent_prefill, latent_prefill.latent_prefill_attention, blocks,
        rank=RANK, sm_scale=0.1, interpret=True)((q, kv), *at)
    pair = walk_ref.blocked(
        paged_prefill, paged_prefill.paged_prefill_attention, blocks,
        window=0, sm_scale=0.1, interpret=True)((q, k, v), *at)
    _same(latent, pair, 32)


# --------------------------------------------------- the shared geometry check

#: front -> (its words, its check, what it is handed: name -> shape).
FRONTS = {
    "latent_decode": ("latent decode attention", latent_decode.check_geometry,
                      dict(q=(2, 8, 256), kv=(1, 5, 16, 256))),
    "paged_decode": ("paged decode attention", paged_decode.check_geometry,
                     dict(q=(2, 8, 128), k=(1, 5, 16, 2, 128),
                          v=(1, 5, 16, 2, 128), tables=(2, 4))),
    "latent_prefill": ("latent prefill attention",
                       latent_prefill.check_geometry,
                       dict(q=(32, 8, 256), kv=(1, 5, 16, 256), table=(4,))),
    "paged_prefill": ("paged prefill attention", paged_prefill.check_geometry,
                      dict(q=(32, 8, 128), k=(1, 5, 16, 2, 128),
                           v=(1, 5, 16, 2, 128), table=(4,))),
}


def _check(front, change):
    words, check, shapes = FRONTS[front]
    shapes, dtypes = change(dict(shapes)), {}
    if "q_dtype" in shapes:
        dtypes["q"] = shapes.pop("q_dtype")
    args = [jax.ShapeDtypeStruct(shape, dtypes.get(
        name, jnp.int32 if name.startswith("table") else jnp.bfloat16))
        for name, shape in shapes.items()]
    if "kv" in shapes:
        args.append(128)  # rank
    return words, lambda: check(*args)


def _pools(shapes, fn):
    return {k: fn(v) if k in ("kv", "k", "v") else v
            for k, v in shapes.items()}


SHARED = {  # a shared condition broken: (the sentence that says so, how)
    "q-rank": ("takes", lambda s: {**s, "q": (1, *s["q"])}),
    "pool-rank": ("takes", lambda s: _pools(s, lambda p: p[1:])),
    "q-width": ("takes", lambda s: {**s, "q": (*s["q"][:2], 384)}),
    "one-dtype": ("takes", lambda s: {**s, "q_dtype": jnp.float32}),
    "tables-rank": ("takes", lambda s: {
        k: (3, *v) if k.startswith("table") else v for k, v in s.items()}),
    "a-page-of-whole-sublane-tiles": (
        "needs", lambda s: _pools(s, lambda p: (*p[:2], 12, *p[3:]))),
    "a-row-of-whole-lane-tiles": ("needs", lambda s: _pools(
        {**s, "q": (*s["q"][:2], 192)}, lambda p: (*p[:-1], 192))),
}


@pytest.mark.parametrize("front, broken", [
    (f, b) for f in FRONTS for b in SHARED
    # latent_decode's check is handed no tables.
    if (f, b) != ("latent_decode", "tables-rank")])
def test_the_shared_check_refuses_in_the_fronts_own_words(front, broken):
    sentence, change = SHARED[broken]
    words, check = _check(front, change)
    with pytest.raises(ValueError, match=f"^{words} {sentence} "):
        check()
    _check(front, lambda s: s)[1]()  # and takes what the front runs


def test_the_walk_is_written_in_one_module():
    """The DMAs and the online softmax live in ``page_walk`` alone, and no
    front leans on another."""
    import inspect

    fronts = (latent_decode, paged_decode, latent_prefill, paged_prefill)
    for front in fronts:
        text = inspect.getsource(front)
        assert "make_async_copy" not in text and "pallas_call" not in text
        assert not any(f"from .{other.__name__.rsplit('.', 1)[1]} import"
                       in text for other in fronts)
    assert inspect.getsource(page_walk).count("make_async_copy(") == 1
    assert latent_decode.on_tpu is paged_decode.on_tpu is page_walk.on_tpu
