"""``ops/paged_decode.py``: the Pallas kernel that walks the live K/V pages of
a whole-length table or of a window layer's ring in the decode step, run
here in interpret mode on the CPU against the gather form it replaces on a
TPU (``paged._attend_pages``), and through the decode program itself
(``paged._walks_live_pages`` steered, the one thing a CPU cannot see).  What
the chip's compiler says of it is in
``tests/benchmark/test_benchmark_chip_compile_paged_decode.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

import walk_ref
from ray_tpu.models import paged
from ray_tpu.ops import paged_decode
from ray_tpu.ops.paged_decode import paged_decode_attention
from walk_ref import LAYER

PAGE, ENTRIES, POOL, DIM = 8, 6, 44, 128
WINDOW = 20  # three or four pages of a ring of six
SCALE = DIM ** -0.5

#: One batch a kind of cache that holds every kind of slot at once: (name,
#: rows already cached).  The step's own row is written at that position, so
#: a slot sees ``lo .. len`` and walks pages ``lo // PAGE .. len // PAGE``.
WHOLE = [("empty", 0), ("one-token", 1), ("hi-on-a-pages-last-row", PAGE - 1),
         ("hi-on-a-pages-first-row", PAGE), ("lo-and-hi-inside-pages", 21),
         ("two-blocks-of-three", 5 * PAGE - 1),
         ("filled-to-its-last-page", ENTRIES * PAGE - 1)]
RING = [("empty", 0), ("shorter-than-the-window", WINDOW - 5),
        ("never-wrapped", 30), ("lo-on-a-pages-first-row", WINDOW - 1 + PAGE),
        ("filled-to-its-last-page", ENTRIES * PAGE - 1),
        ("hi-on-the-first-row-after-the-wrap", ENTRIES * PAGE),
        ("wrapped-once", ENTRIES * PAGE + 9),
        ("wrapped-many-times", 11 * ENTRIES * PAGE + 2 * PAGE + 3)]
KINDS = {"whole": WHOLE, "ring": RING}
#: (query heads, KV heads): Trinity-Mini's 8:1, SmallThinker's 7:1 (14 rows
#: padded to whole sublane tiles), and one query head a KV head.
HEADS = {"gqa-8": (16, 2), "gqa-7": (14, 2), "mha": (4, 4)}


def _walk(kind):
    """(tables [B, ENTRIES], lo [B], hi [B]) of the batch of ``kind``: every
    slot's entries name pages of its own, the empty slot's the scratch page
    (index POOL)."""
    lens = np.array([n for _, n in KINDS[kind]], np.int32)
    tables = np.arange(len(lens) * ENTRIES, dtype=np.int32) \
        .reshape(len(lens), ENTRIES)[:, ::-1] % POOL
    tables[lens == 0] = POOL
    lo = np.maximum(0, lens - WINDOW + 1) if kind == "ring" \
        else np.zeros_like(lens)
    return tables, lo.astype(np.int32), lens


def _visible(kind, lens):
    """What ``decode_logits`` hands the gather form: [B, 1, ENTRIES*PAGE]."""
    lens = jnp.asarray(lens)
    if kind == "whole":
        return jnp.arange(ENTRIES * PAGE)[None, None, :] \
            <= lens[:, None, None]
    held = paged._ring_positions(lens, ENTRIES, PAGE)
    age = lens[:, None] - held
    return ((held >= 0) & (age >= 0) & (age < WINDOW))[:, None]


def _pages_visited(lo, hi):
    return hi // PAGE - lo // PAGE + 1


def _inputs(heads, n_kv, slots, dtype, seed=0):
    shape = (2, POOL + 1, PAGE, n_kv, DIM)
    return (walk_ref.seeded((slots, heads, DIM), dtype, seed),
            walk_ref.seeded(shape, dtype, seed + 100),
            walk_ref.seeded(shape, dtype, seed + 200))


def _gather_form(q, k, v, tables, visible):
    return walk_ref.gather_form(q[:, None], k, v, tables, visible, DIM)[:, 0]


def _block_bytes(pages, k):
    """``BLOCK_BYTES`` of that many pages of K and V a block."""
    return pages * 2 * k[0, 0].nbytes


def _kernel(q, k, v, tables, lo, hi, interpret=True, per_block=3):
    """At three pages a block: a walk of one page, of a block exactly, of a
    block and a part, of two blocks.  Compiled once a batch's shapes."""
    call = walk_ref.blocked(
        paged_decode, paged_decode_attention,
        (("BLOCK_BYTES", _block_bytes(per_block, k)),), sm_scale=SCALE,
        interpret=interpret)
    return np.asarray(call((q, k, v), jnp.asarray(tables), jnp.asarray(lo),
                           jnp.asarray(hi)), np.float32)


@pytest.fixture(scope="module", params=[
    (kind, heads, dtype) for kind in KINDS for heads in HEADS
    for dtype in ("float32", "bfloat16")], ids="-".join)
def both(request):
    """(the kernel's output, the gather form's, the dtype) on the batch of
    one kind of cache."""
    kind, heads, dtype = request.param
    heads, n_kv = HEADS[heads]
    tables, lo, hi = _walk(kind)
    q, k, v = _inputs(heads, n_kv, len(hi), dtype)
    return (_kernel(q, k, v, tables, lo, hi),
            _gather_form(q, k, v, tables, _visible(kind, hi)), dtype)


def test_the_kernel_is_the_gather_form(both):
    """Every slot of the batch: within float32 rounding where the pools are
    float32; where they are bfloat16, within the rounding of the
    probabilities (the gather form rounds them after dividing by their sum,
    the kernel before: online softmax) and of the bfloat16 output."""
    out, ref, dtype = both
    tol = 2e-6 if dtype == "float32" else 2e-2
    assert np.isfinite(out).all()
    for slot in range(len(out)):
        np.testing.assert_allclose(out[slot], ref[slot], atol=tol, rtol=tol,
                                   err_msg=f"slot {slot}")


@pytest.mark.parametrize("kind", KINDS)
def test_the_batches_hold_the_walks_they_are_named_for(kind):
    tables, lo, hi = _walk(kind)
    names = [n for n, _ in KINDS[kind]]
    pages = dict(zip(names, _pages_visited(lo, hi)))
    assert pages["empty"] == 1 and pages["filled-to-its-last-page"] >= 3
    at = dict(zip(names, zip(lo, hi)))
    if kind == "ring":
        assert at["lo-on-a-pages-first-row"][0] % PAGE == 0
        assert at["never-wrapped"][0] % PAGE
        assert at["hi-on-the-first-row-after-the-wrap"][1] % PAGE == 0
        assert at["wrapped-many-times"][1] // (ENTRIES * PAGE) > 10
        assert pages["wrapped-once"] == WINDOW // PAGE + 2  # both ends cut
    else:
        assert at["hi-on-a-pages-last-row"][1] % PAGE == PAGE - 1
        assert at["hi-on-a-pages-first-row"][1] % PAGE == 0
        assert pages["filled-to-its-last-page"] == ENTRIES


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("per_block", [1, 2, 4, ENTRIES, 64])
def test_the_walk_does_not_depend_on_the_blocks_size(monkeypatch, kind,
                                                     per_block):
    """Blocks of one page, blocks that the walks fill unevenly, one that
    holds the whole table and one wider than it: one answer."""
    tables, lo, hi = _walk(kind)
    q, k, v = _inputs(16, 2, len(hi), jnp.float32, seed=per_block)
    monkeypatch.setattr(paged_decode, "BLOCK_BYTES",
                        _block_bytes(per_block, k))
    assert paged_decode._pages_per_block(k, ENTRIES) \
        == min(per_block, ENTRIES)
    np.testing.assert_allclose(
        _kernel(q, k, v, tables, lo, hi, per_block=per_block),
        _gather_form(q, k, v, tables, _visible(kind, hi)),
        atol=2e-6, rtol=2e-6)


def test_a_block_is_sized_by_its_bytes():
    """Four pages of 128 x 4 x 128 bfloat16 K and V (the two cells'), one
    page of an MHA pool four times as wide, never more than the table."""
    def pool(page, n_kv):
        return jax.ShapeDtypeStruct((1, 9, page, n_kv, 128), jnp.bfloat16)

    assert paged_decode._pages_per_block(pool(128, 4), 56) == 4
    assert paged_decode._pages_per_block(pool(128, 16), 56) == 1
    assert paged_decode._pages_per_block(pool(128, 32), 56) == 1
    assert paged_decode._pages_per_block(pool(16, 4), 56) == 32
    assert paged_decode._pages_per_block(pool(16, 4), 12) == 12


def _poisoned(pool, tables, lo, hi, keep=lambda b, p: True):
    """``pool`` with NaN in every page of every layer except the pages
    ``keep(b, p)`` of ``LAYER`` among those the slots' walks visit."""
    return walk_ref.poisoned(pool, {
        int(tables[b, p % ENTRIES]) for b in range(len(hi))
        for p in range(lo[b] // PAGE, hi[b] // PAGE + 1) if keep(b, p)})


@pytest.mark.parametrize("interpret", [True, pltpu.InterpretParams()],
                         ids=["interpret", "tpu-interpreter-nan-scratch"])
@pytest.mark.parametrize("kind", KINDS)
def test_a_poisoned_dead_page_does_not_reach_the_output(kind, interpret):
    """NaN in every page no walk visits (a ring's entries behind the window
    and a table's tail name some), in the scratch page (the empty slot reads
    it as its one page, so it is left out of this batch) and in the other
    layer: the gather form multiplies them by zero and returns NaN; the
    kernel never fetches them.  Under the TPU interpreter uninitialised VMEM
    is NaN too: a block the walk does not fill meets zeros, not that."""
    tables, lo, hi = (x[1:] for x in _walk(kind))
    q, k, v = _inputs(16, 2, len(hi), jnp.float32)
    sound = _kernel(q, k, v, tables, lo, hi)
    bad_k, bad_v = (_poisoned(x, tables, lo, hi) for x in (k, v))
    out = _kernel(q, bad_k, bad_v, tables, lo, hi, interpret=interpret)
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out, sound)
    assert np.isnan(_gather_form(q, bad_k, bad_v, tables,
                                 _visible(kind, hi))).any()


@pytest.mark.parametrize("end", ["first", "last"])
@pytest.mark.parametrize("kind, slot", [
    (kind, slot) for kind, slots in KINDS.items()
    for slot in range(1, len(slots))],
    ids=lambda x: x if isinstance(x, str) else str(x))
def test_both_ends_of_a_walk_are_visited(kind, slot, end):
    """A NaN in the first or the last page of one slot's walk (a value the
    mask inside that page lets through) reaches that slot's output, so the
    walk is ``lo // page .. hi // page`` and no shorter, and no other
    slot's."""
    tables, lo, hi = _walk(kind)
    q, k, v = _inputs(16, 2, len(hi), jnp.float32)
    at = (lo if end == "first" else hi)[slot] // PAGE
    bad_v = _poisoned(v, tables, lo, hi,
                      lambda b, p: (b, p) != (slot, at) or b == 0)
    bad_v = bad_v.at[LAYER, POOL].set(0.0)  # the scratch page: slot 0's
    out = _kernel(q, k, bad_v, tables, lo, hi)
    for b in range(len(hi)):
        assert np.isnan(out[b]).any() == (b == slot), (b, slot)


@pytest.mark.parametrize("kind", KINDS)
def test_bfloat16_pools_are_accumulated_in_float32(kind):
    """``walk_ref.accumulates_in_float32``, at scores eight times as wide as
    a unit draw's (up to ~25): a bfloat16 score is then off by up to 0.06,
    a probability by 6%."""
    tables, lo, hi = _walk(kind)
    q, k, v = _inputs(16, 2, len(hi), jnp.bfloat16)
    q = (8 * q).astype(jnp.bfloat16)
    walk_ref.accumulates_in_float32(
        _kernel(q, k, v, tables, lo, hi), q[:, None], k, v, tables,
        _visible(kind, hi), DIM, tol=1.5e-2)


def _shapes(**over):
    q = jax.ShapeDtypeStruct(over.get("q", (2, 8, 128)),
                             over.get("q_dtype", jnp.bfloat16))
    pool = jax.ShapeDtypeStruct(over.get("pool", (1, 5, 16, 2, 128)),
                                jnp.bfloat16)
    v = jax.ShapeDtypeStruct(over.get("v", pool.shape), jnp.bfloat16)
    tables = jax.ShapeDtypeStruct(over.get("tables", (2, 4)), jnp.int32)
    return q, pool, v, tables


@pytest.mark.parametrize("what, over", [
    ("takes", dict(q_dtype=jnp.float32)),
    ("takes", dict(v=(1, 5, 16, 4, 128))),
    ("takes", dict(tables=(3, 4))),
    ("takes", dict(q=(2, 8, 64))),
    ("takes", dict(pool=(5, 16, 2, 128))),
    ("needs", dict(q=(2, 7, 128))),
    ("needs", dict(q=(2, 8, 64), pool=(1, 5, 16, 2, 64))),
    ("needs", dict(pool=(1, 5, 4, 2, 128)))],
    ids=["dtype", "k-and-v", "slots", "q-width", "pool-rank", "groups",
         "head_dim", "page"])
def test_a_geometry_the_kernel_cannot_take_raises_before_it_is_traced(
        what, over):
    with pytest.raises(ValueError, match=f"paged decode attention {what}"):
        paged_decode.check_geometry(*_shapes(**over))
    paged_decode.check_geometry(*_shapes())


# ------------------------------------------------- through the decode program

SLOT_LENS = {"empty": 0, "inside-the-window": 5, "at-the-window": 7,
             "past-the-window": 11, "at-the-rings-wrap": 16,
             "wrapped-twice": 37}


@pytest.mark.parametrize("name, heads", [("smallthinker-tiny", 14),
                                         ("trinity-mini-tiny", 16)])
def test_the_decode_program_through_the_kernel(name, heads):
    """The decode step of the tiny SmallThinker (7:1, a whole-length layer
    before three rings) and Trinity-Mini (8:1, three rings to a whole-length
    layer, gate and norms around them) as a TPU takes it, against the gather
    form: the same logits in a slot inside the window, at it, past it, at
    the ring's wrap (two pages of 8) and past two of them; ``kv_rows_live``
    as it was; ``kv_rows_read`` the pages the kernel's walks visit x page,
    where the gather form's is every slot's whole table and ring."""
    cfg = walk_ref.tiny_pair(name, 48)
    whole, window = paged.kv_layers(cfg)
    assert whole and window and cfg.window == PAGE and cfg.n_heads == heads
    assert paged.counter_keys(cfg)[-2:] == paged.KV_KEYS
    lens = np.array(list(SLOT_LENS.values()), np.int32)
    b, maxp = len(lens), 48 // PAGE
    ring = paged.ring_entries(cfg, PAGE, PAGE)
    model = walk_ref.model_of(cfg, b * maxp, PAGE, b * ring)
    tables = np.arange(b * maxp, dtype=np.int32).reshape(b, maxp)
    rings = np.arange(b * ring, dtype=np.int32).reshape(b, ring)
    tables[0], rings[0] = b * maxp, b * ring  # the empty slot: scratch
    walked, logits = walk_ref.decode(cfg, paged_decode, True, model, tables,
                                     lens, rings)
    gathered, ref = walk_ref.decode(cfg, paged_decode, False, model, tables,
                                    lens, rings)
    scale = float(np.abs(ref).max())
    np.testing.assert_allclose(logits, ref, atol=2e-5 * scale, rtol=0)
    np.testing.assert_array_equal(walked[:b], gathered[:b])
    lo = np.maximum(0, lens - cfg.window + 1)
    visited = len(whole) * int(_pages_visited(0 * lens, lens).sum()) \
        + len(window) * int(_pages_visited(lo, lens).sum())
    assert walked[-2] == visited * PAGE
    assert gathered[-2] == b * PAGE * (len(whole) * (48 // PAGE)
                                       + len(window) * ring)
    live = sum(len(whole) * (n + 1) + len(window) * min(n + 1, cfg.window)
               for n in lens if n)
    assert walked[-1] == gathered[-1] == live
    assert walked[-2] < gathered[-2] and walked[-1] / walked[-2] > 0.5


def test_off_the_tpu_the_decode_program_is_the_gather_form():
    """Nothing steers it here: on this backend a model with window layers
    lowers without the kernel; one without them everywhere, whatever the
    backend answers."""
    import dataclasses

    cfg = walk_ref.tiny_pair("trinity-mini-tiny", 48)
    assert not paged._walks_live_pages(cfg)
    assert paged.decode_attention_form(cfg) == "gather"
    plain = dataclasses.replace(cfg, window=0, window_layout=(),
                                rope_layout=())
    was = paged_decode.on_tpu
    paged_decode.on_tpu = lambda: True
    try:
        assert paged._walks_live_pages(cfg)
        assert not paged._walks_live_pages(plain)
        assert paged.decode_attention_form(plain) == "gather"
    finally:
        paged_decode.on_tpu = was
