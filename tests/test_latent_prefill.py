"""``ops/latent_prefill.py``: the Pallas kernel in which a prefill call's
absorbed query rows attend the live pages of their table in a LATENT pool,
run here in interpret mode on the CPU against the gather form it replaces on
a TPU (``paged._attend_pages`` on the same pool, table and ``visible``),
parametrised over the calls it has to take; then the prefill programs of the
two latent families through it (``latent_decode.on_tpu`` steered, the one
thing a CPU cannot see) against the gather form they take everywhere else,
and who walks.  What the chip's compiler says of it is in
``tests/benchmark/test_benchmark_latent_prefill.py``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

import walk_ref
from ray_tpu.models import paged
from ray_tpu.ops import latent_decode, latent_prefill, paged_decode
from ray_tpu.ops.latent_prefill import latent_prefill_attention
from walk_ref import LAYER, TINY_PAGE

#: A pool row as both cells keep it: 512 + 64 numbers in five lane tiles
#: (what the DMAs move).  Lengths at a QUARTER of the cells': a page of 32
#: rows for one of 128, a call of 32 rows for one of 128, the table's 24
#: pages as they were: a walk visits the pages it visits there.
PAGE, WIDTH, RANK, ROPE, ROWS = 32, 640, 512, 64, 32
BLOCK = ROWS // 2  # two blocks of query rows a call
ENTRIES = 24
HEAD_DIM = 256  # an expanded head's q and k are 192 + 64 wide: the scale

#: Query heads over the one shared row: GLM-4.7-Flash's and Kimi-Linear's.
GEOMETRIES = {"glm-20-heads": 20, "kimi-linear-32-heads": 32}

#: The calls of ROWS query rows, two blocks of 16: name -> (first position,
#: length).  Rows at or past ``length`` are the bucket's padding.
CALLS = {
    "first-rows": (0, ROWS),
    "length-inside-the-first-block": (0, 10),
    "prefix-on-a-pages-edge": (8 * PAGE, 8 * PAGE + ROWS),
    "prefix-inside-a-page": (250, 250 + ROWS - 5),
    "one-real-row": (129, 130),
    "the-tables-last-pages": (ENTRIES * PAGE - ROWS, ENTRIES * PAGE - 3)}


def _pool(dtype, page=PAGE, entries=ENTRIES, width=WIDTH, used=RANK + ROPE):
    """A seeded pool whose rows are zero past their ``used`` numbers, and a
    table that names its pages out of order."""
    return walk_ref.pool_and_table(dtype, page, entries, (width,), used)


def _queries(heads, dtype, rows=ROWS, width=WIDTH, used=RANK + ROPE, seed=1,
             scale=1.0):
    return walk_ref.seeded((rows, heads, width), dtype, seed, used, scale)


def _visible(first, rows, keys):
    """What ``prefill_prefix_logits`` hands the gather form: [1, rows,
    keys]."""
    return jnp.arange(keys)[None, None, :] \
        <= (first + jnp.arange(rows))[None, :, None]


def _gather_form(q, kv, table, first, rank=RANK):
    visible = _visible(first, q.shape[0], len(table) * kv.shape[2])
    return walk_ref.gather_form(q[None], kv, None, table[None], visible,
                                HEAD_DIM, rank)[0]


def _kernel(q, kv, table, first, length, rank=RANK, rows=BLOCK,
            keys=2 * PAGE, interpret=True):
    """Two blocks of query rows a call, two pages a block of keys; compiled
    once a shape: the first position and the length are data."""
    call = walk_ref.blocked(
        latent_prefill, latent_prefill_attention,
        (("BLOCK_ROWS", rows), ("BLOCK_KEYS", keys)), rank=rank,
        sm_scale=HEAD_DIM ** -0.5, interpret=interpret)
    return np.asarray(call((q, kv), jnp.asarray(table), jnp.int32(first),
                           jnp.int32(length)), np.float32)


def _walk(first, length, total=ROWS, rows=BLOCK, page=PAGE):
    """The last page each block of ``rows`` query rows visits (its walk
    starts at page 0), None of a block wholly in the padding."""
    out = []
    for p0 in range(first, first + total, rows):
        hi = min(p0 + rows, length) - 1
        out.append(hi // page if hi >= p0 else None)
    return out


@pytest.mark.parametrize("call", CALLS)
@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_the_kernel_is_the_gather_form(geometry, call):
    """The real rows of every call on a bfloat16 pool: within the rounding
    of the probabilities (the gather form rounds them after dividing by
    their sum, the kernel before: online softmax) and of the bfloat16
    output, which is what ``tests/test_paged_prefill.py`` holds its kernel
    to.  The padding's rows are finite."""
    heads = GEOMETRIES[geometry]
    first, length = CALLS[call]
    kv, table = _pool(jnp.bfloat16)
    q = _queries(heads, jnp.bfloat16)
    out = _kernel(q, kv, table, first, length)
    ref = _gather_form(q, kv, table, first)
    assert np.isfinite(out).all()
    real = length - first
    assert 0 < real <= ROWS
    np.testing.assert_allclose(out[:real], ref[:real], atol=2e-2, rtol=2e-2)


def test_the_calls_hold_the_walks_they_are_named_for():
    walks = {name: _walk(*at) for name, at in CALLS.items()}
    assert walks["first-rows"] == [0, 0]
    # A call whose length leaves padding rows, one whose last block of rows
    # is all padding, and one with a single real row.
    assert walks["length-inside-the-first-block"] == [0, None]
    assert walks["one-real-row"] == [129 // PAGE, None]
    first, length = CALLS["prefix-inside-a-page"]
    assert first % PAGE and first + BLOCK < length < first + ROWS
    assert CALLS["prefix-on-a-pages-edge"][0] % PAGE == 0
    assert walks["the-tables-last-pages"][1] == ENTRIES - 1
    for name, (first, length) in CALLS.items():
        assert first < length <= min(first + ROWS, ENTRIES * PAGE), name


@pytest.mark.parametrize("heads", [20, 32])
@pytest.mark.parametrize("page", [128, 16])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_either_dtype_and_page_size(dtype, page, heads):
    """Pages of 128 (the cells') and of 16 (the least a bfloat16 pool's
    DMAs move whole), rows of float32 and of bfloat16, a prefix that ends
    inside a page (a prefix hit's copied page): float32 within float32
    rounding of the gather form."""
    entries = 12
    first, length = 9 * page + 5, 9 * page + 5 + ROWS - 9
    kv, table = _pool(jnp.dtype(dtype).type, page, entries)
    q = _queries(heads, jnp.dtype(dtype).type)
    out = _kernel(q, kv, table, first, length, keys=4 * page)
    ref = _gather_form(q, kv, table, first)
    tol = 3e-6 if dtype == "float32" else 2e-2
    real = length - first
    np.testing.assert_allclose(out[:real], ref[:real], atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype, bucket", [("bfloat16", 16), ("float32", 8),
                                           ("float32", 32)])
@pytest.mark.parametrize("first, real", [(0, 5), (77, 8), (300, 3)],
                         ids=["at-0", "mid-page", "behind-300"])
def test_a_bucket_smaller_than_one_block_of_rows(dtype, bucket, first, real):
    """A bucket of one sublane tile is one block of that many rows."""
    page = 16
    kv, table = _pool(jnp.dtype(dtype).type, page, 32, width=256, used=136)
    q = _queries(4, jnp.dtype(dtype).type, rows=bucket, width=256, used=136)
    assert latent_prefill._blocks(bucket, page)[0] == bucket < 64
    out = _kernel(q, kv, table, first, first + real, rank=128, keys=64)
    ref = _gather_form(q, kv, table, first, rank=128)
    tol = 3e-6 if dtype == "float32" else 2e-2
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[:real], ref[:real], atol=tol, rtol=tol)


@pytest.mark.parametrize("rows, keys", [(32, 32), (8, 32), (16, 128),
                                        (8, 256), (32, 2048)])
@pytest.mark.parametrize("call", ["prefix-inside-a-page",
                                  "the-tables-last-pages"])
def test_the_answer_does_not_depend_on_the_blocks(call, rows, keys):
    """One block of query rows or four (of a float32 sublane tile), a page
    a block of keys or more than the table holds: one answer (a float32
    pool, so to rounding)."""
    first, length = CALLS[call]
    kv, table = _pool(jnp.float32)
    q = _queries(8, jnp.float32, seed=rows)
    out = _kernel(q, kv, table, first, length, rows=rows, keys=keys)
    ref = _gather_form(q, kv, table, first)
    real = length - first
    np.testing.assert_allclose(out[:real], ref[:real], atol=3e-6, rtol=3e-6)


def _poisoned(kv, table, walks, keep=lambda block, page: True):
    """``kv`` with NaN in every page of every layer except the pages
    ``keep(block, page)`` of ``LAYER`` among those the blocks' walks visit:
    the table's dead tail, the pool's spare pages and the scratch page."""
    return walk_ref.poisoned(kv, {
        int(table[p]) for b, last in enumerate(walks)
        if last is not None for p in range(last + 1) if keep(b, p)})


@pytest.mark.parametrize("interpret", [True, pltpu.InterpretParams()],
                         ids=["interpret", "tpu-interpreter-nan-scratch"])
@pytest.mark.parametrize("call", ["first-rows", "prefix-inside-a-page",
                                  "length-inside-the-first-block",
                                  "one-real-row"])
def test_a_page_outside_the_walk_is_not_fetched(call, interpret):
    """NaN in every page that no block's walk visits (the table's pages
    after the call's last real row, the scratch page, the pool's spare
    pages) and in the other layer: the gather form multiplies them by zero
    and returns NaN; the kernel never fetches them.  Under the TPU
    interpreter uninitialised VMEM is NaN too: a block of keys that the walk
    does not fill, and the half of the buffer an earlier block of rows left,
    meet zeros, not that."""
    first, length = CALLS[call]
    kv, table = _pool(jnp.float32)
    q = _queries(8, jnp.float32)
    # Three pages a block of keys: the walks fill their last block unevenly.
    sound = _kernel(q, kv, table, first, length, keys=3 * PAGE)
    bad = _poisoned(kv, table, _walk(first, length))
    out = _kernel(q, bad, table, first, length, keys=3 * PAGE,
                  interpret=interpret)
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out, sound)
    assert np.isnan(_gather_form(q, bad, table, first)[:length - first]).all()


@pytest.mark.parametrize("end", ["first", "last"])
@pytest.mark.parametrize("block", [0, 1])
def test_both_ends_of_a_blocks_walk_are_visited(block, end):
    """A NaN in page 0 or in the last page of one block's walk reaches that
    block's rows: the walk is ``0 .. hi // page`` and no shorter; the page
    after the first block's last row does not reach the first block."""
    first = 8 * PAGE + BLOCK  # the two blocks of rows end in different pages
    length = first + ROWS
    kv, table = _pool(jnp.float32)
    q = _queries(8, jnp.float32)
    walks = _walk(first, length)
    assert walks == [8, 9]
    at = 0 if end == "first" else walks[block]
    bad = np.array(kv)
    bad[LAYER, table[at]] = np.nan
    out = _kernel(q, jnp.asarray(bad), table, first, length)
    assert np.isnan(out[BLOCK * block:BLOCK * (block + 1)]).all()
    if end == "last" and block == 1:  # after the first block's last row
        assert np.isfinite(out[:BLOCK]).all()


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_bfloat16_pools_are_accumulated_in_float32(geometry):
    """``walk_ref.accumulates_in_float32``, at scores four times as wide as
    a unit draw's over 576 numbers."""
    heads = GEOMETRIES[geometry]
    first, length = 6 * PAGE, 6 * PAGE + ROWS
    kv, table = _pool(jnp.bfloat16)
    q = _queries(heads, jnp.bfloat16, scale=4.0)
    walk_ref.accumulates_in_float32(
        _kernel(q, kv, table, first, length), q[None], kv, None,
        table[None], _visible(first, ROWS, ENTRIES * PAGE), HEAD_DIM,
        tol=2.5e-2, rank=RANK)


def _shapes(**over):
    q = jax.ShapeDtypeStruct(over.get("q", (256, 8, 256)),
                             over.get("q_dtype", jnp.bfloat16))
    kv = jax.ShapeDtypeStruct(over.get("kv", (1, 5, 16, 256)),
                              over.get("kv_dtype", jnp.bfloat16))
    table = jax.ShapeDtypeStruct(over.get("table", (4,)), jnp.int32)
    return q, kv, table, over.get("rank", 128)


@pytest.mark.parametrize("what, over", [
    ("takes", dict(q_dtype=jnp.float32)),
    ("takes", dict(table=(1, 4))),
    ("takes", dict(q=(256, 8, 128))),
    ("takes", dict(q=(1, 256, 8, 256))),
    ("takes", dict(kv=(5, 16, 256))),
    ("needs", dict(kv=(1, 5, 12, 256))),
    ("needs", dict(q=(256, 8, 192), kv=(1, 5, 16, 192))),
    ("needs", dict(rank=96)),
    ("needs", dict(rank=384)),
    ("needs", dict(q=(8, 8, 256))),
    ("needs", dict(q=(96, 8, 256)))],
    ids=["dtype", "a-batch-of-tables", "q-width", "a-batch-of-queries",
         "pool-rank", "page", "row-width", "value-width",
         "value-wider-than-the-row", "rows-under-a-tile",
         "rows-not-whole-blocks"])
def test_a_geometry_the_kernel_cannot_take_raises_before_it_is_traced(
        what, over):
    with pytest.raises(ValueError, match=f"latent prefill attention {what}"):
        latent_prefill.check_geometry(*_shapes(**over))
    latent_prefill.check_geometry(*_shapes())
    # What the engines run: a page-sized bucket up to the chunk at both
    # cells' heads, and float32 rows of 8.
    for heads in (20, 32):
        for rows in (128, 2048):
            latent_prefill.check_geometry(*_shapes(
                q=(rows, heads, 640), kv=(6, 9, 128, 640), table=(150,),
                rank=512))
    latent_prefill.check_geometry(*_shapes(
        q=(8, 4, 128), q_dtype=jnp.float32, kv=(1, 5, 8, 128),
        kv_dtype=jnp.float32))


# ------------------------------------------------ through the prefill programs

TINY = {"glm4-moe-lite-tiny": "rotary on the shared key",
        "kimi-linear-tiny": "no position; three KDA layers to a latent one"}


def _tiny(name):
    """``walk_ref.tiny`` with a latent of 128 (what the kernel's value
    product takes whole; rows of 256 with the 8 rotary numbers)."""
    return walk_ref.tiny(name, kv_lora_rank=128)


@pytest.mark.parametrize("prompt, chunk, first", [
    (13, 16, 0), (37, 16, 0), (61, 8, 0), (43, 32, 19)],
    ids=["a-cold-prompt-in-one-call", "three-chunks", "eight-chunks-of-a-page",
         "a-prefix-hit-inside-a-page"])
@pytest.mark.parametrize("name", TINY)
def test_the_prefill_programs_through_the_kernel(name, prompt, chunk, first):
    """The prefill calls of the tiny GLM-4.7-Flash (rotary on the 8 shared
    columns) and Kimi-Linear (no position, KDA layers beside the latent one
    whose chunk form carries its state in the slot) as a TPU takes them (the
    suffix program through the kernel, a prompt's first rows too), against
    the cold program (which EXPANDS its rows) and the suffix program in the
    gather form: the same logits after every call, within the tolerance
    ``tests/test_paged_prefill_programs.py`` holds the K/V-pair walk to, and
    the same rows left in the pool (``walk_ref.same_prefills``)."""
    cfg = _tiny(name)
    assert paged.latent_row_width(cfg) == 256 and cfg.kv_lora_rank == 128
    walk_ref.same_prefills(cfg, latent_decode, prompt, chunk, first)


@pytest.mark.parametrize("name", TINY)
def test_the_suffix_program_holds_the_kernel_only_where_it_walks(
        monkeypatch, name):
    """Steered, the suffix program is traced with the kernel under its name
    ONCE (its call is jitted on its own and the layer is data: one function,
    called a latent layer); unsteered with none."""
    from ray_tpu.models import init_and_apply

    cfg = _tiny(name)
    on, i32 = jax.ShapeDtypeStruct, jnp.int32
    state = 2 if paged.state_layers(cfg) else 0
    shapes = jax.eval_shape(lambda: (
        init_and_apply(cfg)[0](cfg, jax.random.PRNGKey(0)),
        paged.init_paged_pools(cfg, 16, TINY_PAGE, state_slots=state),
        paged.init_adapter_pool(cfg, 1, 2)))

    def text(walk):
        monkeypatch.setattr(latent_decode, "on_tpu", lambda: walk)
        jax.clear_caches()
        try:
            return str(jax.make_jaxpr(
                functools.partial(paged.prefill_prefix_logits, cfg))(
                *shapes, on((1, 16), i32), on((), i32), on((), i32),
                on((8,), i32), on((), i32), None,
                on((), i32) if state else None))
        finally:
            jax.clear_caches()

    walked, gathered = text(True), text(False)
    assert "pallas_call" not in gathered
    assert walked.count("pallas_call") == 1
    assert "name=latent_prefill" in walked
    assert walked.count("name=_call") == len(paged.kv_layers(cfg)[0])


@pytest.mark.parametrize("name", TINY)
def test_off_the_tpu_a_latent_models_prefills_gather(name):
    """Nothing steers it here: on this backend both latent families' prefills
    are the gather form; where ``latent_decode.on_tpu`` answers true they
    walk, as their decode step does, whatever the K/V-pair kernels'
    predicate answers."""
    cfg = _tiny(name)
    assert not paged._walks_live_pages(cfg)
    assert paged.prefill_attention_form(cfg) == "gather"
    assert paged.decode_attention_form(cfg) == "gather"
    was = paged_decode.on_tpu, latent_decode.on_tpu
    try:
        paged_decode.on_tpu = lambda: True
        assert paged.prefill_attention_form(cfg) == "gather"
        latent_decode.on_tpu = lambda: True
        assert paged.prefill_attention_form(cfg) == "walk"
        assert paged.decode_attention_form(cfg) == "walk"
        paged_decode.on_tpu = lambda: False
        assert paged.prefill_attention_form(cfg) == "walk"
    finally:
        paged_decode.on_tpu, latent_decode.on_tpu = was


@pytest.mark.parametrize("name, walks", [
    ("rehearsal-tiny", False), ("olmoe-tiny", False),
    ("smallthinker-tiny", True), ("trinity-mini-tiny", True)])
def test_a_configuration_without_a_latent_pool_answers_as_it_did(name, walks):
    """One whole-length kind of K/V pairs never walks; a model with window
    layers walks where ``paged_decode.on_tpu`` says so, whatever the latent
    kernels' predicate answers."""
    cfg = walk_ref.tiny(name)
    assert paged.prefill_attention_form(cfg) == "gather"
    was = paged_decode.on_tpu, latent_decode.on_tpu
    try:
        latent_decode.on_tpu = lambda: True
        assert paged.prefill_attention_form(cfg) == "gather"
        paged_decode.on_tpu = lambda: True
        assert (paged.prefill_attention_form(cfg) == "walk") == walks
        assert (paged.decode_attention_form(cfg) == "walk") == walks
    finally:
        paged_decode.on_tpu, latent_decode.on_tpu = was


@pytest.mark.parametrize("start, end", [(0, 5), (0, 8), (0, 30), (8, 24),
                                        (3, 9), (16, 17), (40, 64)])
@pytest.mark.parametrize("name", TINY)
def test_attn_pairs_of_a_latent_model_counts_its_latent_layers(name, start,
                                                               end):
    """Every latent layer is whole-length: a row at ``p`` sees ``p + 1``
    rows on each (all three of the tiny GLM's layers; one of the tiny
    Kimi-Linear's five, whose KDA layers keep no rows)."""
    cfg = _tiny(name)
    whole, window = paged.kv_layers(cfg)
    assert not window
    assert len(whole) == {"glm4-moe-lite-tiny": 3, "kimi-linear-tiny": 1}[name]
    assert paged.attn_pairs(cfg, start, end) \
        == len(whole) * sum(p + 1 for p in range(start, end))

