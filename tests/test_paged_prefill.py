"""``ops/paged_prefill.py``: the Pallas kernel in which a prefill call's query
rows attend the live K/V pages of their whole-length table or window ring,
run here in interpret mode on the CPU against the gather form it replaces on
a TPU (``paged._attend_pages`` on the same pools, tables and ``visible``),
parametrised over the calls it has to take.  The prefill programs through
it are in ``tests/test_paged_prefill_programs.py``; what the chip's compiler
says of it in
``tests/benchmark/test_benchmark_chip_compile_paged_prefill.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

import walk_ref
from ray_tpu.models import paged
from ray_tpu.ops import paged_prefill
from ray_tpu.ops.paged_prefill import paged_prefill_attention
from walk_ref import LAYER

#: Lengths at an EIGHTH of the cells' (a page of 16 rows for one of 128, a
#: call of 32 rows for one of 256), the widths whole (heads of 128 over 4
#: KV heads: the tiles the DMAs move): a walk visits the pages it visits
#: there, and the interpreter moves an eighth of the rows.
PAGE, DIM, ROWS = 16, 128, 32
BLOCK = ROWS // 2  # two blocks of query rows a call

#: The geometries: (query heads, KV heads, window; 0 a whole-length table).
#: SmallThinker's 7:1 at 4 KV heads under its window of 4096 (here 512: 32
#: pages), Trinity-Mini's 8:1 under 2048 (256: 16 pages), and each one's
#: whole-length layers (a table of 40 pages).
GEOMETRIES = {"smallthinker-ring-4096": (28, 4, 4096 // 8),
              "trinity-mini-ring-2048": (32, 4, 2048 // 8),
              "smallthinker-whole": (28, 4, 0),
              "trinity-mini-whole": (32, 4, 0)}
WHOLE_ENTRIES = 40


def _entries(window):
    """A ring as ``paged.ring_entries`` sizes it for calls of ROWS rows."""
    return window // PAGE + ROWS // PAGE if window else WHOLE_ENTRIES


def _calls(window):
    """The calls of ROWS query rows a geometry is held to: name -> (first
    position, length).  Rows at or past ``length`` are the bucket's
    padding."""
    if not window:
        return {
            "first-chunk": (0, ROWS),
            "length-inside-the-first-block": (0, 12),
            "prefix-on-a-pages-edge": (8 * PAGE, 8 * PAGE + ROWS),
            "prefix-inside-a-page": (125, 125 + ROWS - 7),
            "the-tables-last-pages": (WHOLE_ENTRIES * PAGE - ROWS,
                                      WHOLE_ENTRIES * PAGE - 3)}
    lap = _entries(window) * PAGE
    return {
        "first-chunk": (0, ROWS),
        "length-inside-the-first-block": (0, 12),
        "prefix-on-a-pages-edge": (5 * PAGE, 5 * PAGE + ROWS),
        # Rows before the window is full beside rows whose window has left
        # position 0 behind, in one query block.
        "a-block-straddles-the-windows-edge": (window - PAGE,
                                               window + PAGE - 3),
        "the-ring-filled-to-its-last-page": (lap - ROWS, lap),
        "lapped-once": (lap + 2 * PAGE, lap + 2 * PAGE + ROWS),
        "lapped-twice-with-padding": (2 * lap + 3 * PAGE,
                                      2 * lap + 3 * PAGE + 25)}


CASES = [(g, c) for g, (_, _, w) in GEOMETRIES.items() for c in _calls(w)]


def _pools(n_kv, entries, dtype):
    """Seeded K and V pools and a table that names its pages out of
    order."""
    return walk_ref.pool_and_table(dtype, PAGE, entries, (n_kv, DIM),
                                   pairs=2)


def _queries(heads, dtype, seed=1, scale=1.0):
    return walk_ref.seeded((ROWS, heads, DIM), dtype, seed, scale=scale)


def _visible(window, entries, first, length):
    """What ``prefill_prefix_logits`` hands the gather form:
    [1, ROWS, entries * PAGE]."""
    positions = first + jnp.arange(ROWS)
    if not window:
        return jnp.arange(entries * PAGE)[None, None, :] \
            <= positions[None, :, None]
    last = jnp.minimum(first + ROWS, length) - 1
    held = paged._ring_positions(last, entries, PAGE)
    age = positions[:, None] - held[None, :]
    return ((held >= 0) & (held <= last) & (age >= 0)
            & (age < window))[None]


def _gather_form(q, k, v, table, visible):
    return walk_ref.gather_form(q[None], k, v, table[None], visible, DIM)[0]


def _kernel(q, k, v, table, first, length, window, rows=BLOCK,
            keys=2 * PAGE, interpret=True):
    """Two blocks of query rows a call, two pages a block of keys; compiled
    once a geometry: the first position and the length are data."""
    call = walk_ref.blocked(
        paged_prefill, paged_prefill_attention,
        (("BLOCK_ROWS", rows), ("BLOCK_KEYS", keys)), window=window,
        sm_scale=DIM ** -0.5, interpret=interpret)
    return np.asarray(call((q, k, v), jnp.asarray(table), jnp.int32(first),
                           jnp.int32(length)), np.float32)


def _walk(first, length, window, rows=BLOCK):
    """The pages each block of ``rows`` query rows visits: a list of
    (first page, last page), None of a block wholly in the padding."""
    out = []
    for p0 in range(first, first + ROWS, rows):
        hi = min(p0 + rows, length) - 1
        lo = max(0, p0 - window + 1) if window else 0
        out.append((lo // PAGE, hi // PAGE) if hi >= p0 else None)
    return out


@pytest.mark.parametrize("geometry, call", CASES)
def test_the_kernel_is_the_gather_form(geometry, call):
    """The real rows of every call on bfloat16 pools: within the rounding
    of the probabilities (the gather form rounds them after dividing by
    their sum, the kernel before: online softmax) and of the bfloat16
    output, which is what ``tests/test_paged_decode.py`` holds its kernel
    to.  The padding's rows are finite."""
    heads, n_kv, window = GEOMETRIES[geometry]
    first, length = _calls(window)[call]
    entries = _entries(window)
    k, v, table = _pools(n_kv, entries, jnp.bfloat16)
    q = _queries(heads, jnp.bfloat16)
    out = _kernel(q, k, v, table, first, length, window)
    ref = _gather_form(q, k, v, table,
                       _visible(window, entries, first, length))
    assert np.isfinite(out).all()
    real = length - first
    assert 0 < real <= ROWS
    np.testing.assert_allclose(out[:real], ref[:real], atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_the_calls_hold_the_walks_they_are_named_for(geometry):
    _, _, window = GEOMETRIES[geometry]
    calls, entries = _calls(window), _entries(window)
    walks = {name: _walk(*at, window) for name, at in calls.items()}
    assert walks["length-inside-the-first-block"] == [(0, 0), None]
    assert calls["prefix-on-a-pages-edge"][0] % PAGE == 0
    for name, (first, length) in calls.items():
        assert first < length <= first + ROWS, name
        assert length <= entries * PAGE or window, name
        for walk in walks[name]:  # no entry of the table is visited twice
            assert walk is None or walk[1] - walk[0] < entries, name
    if not window:
        assert calls["prefix-inside-a-page"][0] % PAGE
        assert walks["the-tables-last-pages"][1][1] == entries - 1
        return
    first, length = calls["a-block-straddles-the-windows-edge"]
    assert first < window - 1 < first + BLOCK  # inside the first block
    assert first + BLOCK - window + 1 > 0  # the second block's rows: all past
    assert walks["the-ring-filled-to-its-last-page"][1][1] == entries - 1
    lap = entries * PAGE
    assert calls["lapped-once"][0] // lap == 1
    assert calls["lapped-twice-with-padding"][0] // lap == 2
    # Its walk crosses the ring's end: the page numbers wrap in between.
    lo, hi = walks["lapped-once"][0]
    assert lo // entries != hi // entries
    # A block of a page's rows under a window of w walks w / PAGE + 1 pages,
    # where the table has w / PAGE + 2 and the whole bucket's scores
    # (w + ROWS) keys.
    assert hi - lo + 1 == window // PAGE + 1


@pytest.mark.parametrize("geometry", ["smallthinker-ring-4096",
                                      "trinity-mini-whole"])
@pytest.mark.parametrize("dtype, heads, n_kv", [
    ("float32", 14, 2), ("float32", 4, 4), ("float32", 8, 1),
    ("bfloat16", 16, 2), ("bfloat16", 8, 1)],
    ids=["f32-gqa-7", "f32-mha", "f32-one-kv-head", "bf16-two-kv-heads",
         "bf16-one-kv-head"])
def test_the_heads_are_parted_in_either_dtype(geometry, dtype, heads, n_kv):
    """Rows of 32 bits part by a strided load, two bfloat16 KV heads by the
    halves of a word, one KV head not at all: float32 within float32
    rounding of the gather form."""
    _, _, window = GEOMETRIES[geometry]
    entries = _entries(window)
    first, length = 3 * PAGE, 3 * PAGE + ROWS - 3
    k, v, table = _pools(n_kv, entries, jnp.dtype(dtype).type)
    q = _queries(heads, jnp.dtype(dtype).type)
    out = _kernel(q, k, v, table, first, length, window)
    ref = _gather_form(q, k, v, table,
                       _visible(window, entries, first, length))
    tol = 3e-6 if dtype == "float32" else 2e-2
    real = length - first
    np.testing.assert_allclose(out[:real], ref[:real], atol=tol, rtol=tol)


@pytest.mark.parametrize("rows, keys", [(32, 16), (8, 16), (16, 64),
                                        (8, 128), (32, 1024)])
@pytest.mark.parametrize("geometry", ["smallthinker-ring-4096",
                                      "smallthinker-whole"])
def test_the_answer_does_not_depend_on_the_blocks(geometry, rows, keys):
    """One block of query rows or four (of a float32 sublane tile), a page
    a block of keys or more than the table holds: one answer (float32
    pools, so to rounding)."""
    heads, n_kv, window = 8, 2, GEOMETRIES[geometry][2]
    entries = _entries(window)
    calls = _calls(window)
    first, length = calls["lapped-twice-with-padding" if window
                          else "prefix-inside-a-page"]
    k, v, table = _pools(n_kv, entries, jnp.float32)
    q = _queries(heads, jnp.float32, seed=rows)
    out = _kernel(q, k, v, table, first, length, window, rows, keys)
    ref = _gather_form(q, k, v, table,
                       _visible(window, entries, first, length))
    real = length - first
    np.testing.assert_allclose(out[:real], ref[:real], atol=3e-6, rtol=3e-6)


def _poisoned(pool, table, walks, entries, keep=lambda block, page: True):
    """``pool`` with NaN in every page of every layer except the pages
    ``keep(block, page)`` of ``LAYER`` among those the blocks' walks
    visit."""
    return walk_ref.poisoned(pool, {
        int(table[p % entries]) for b, walk in enumerate(walks)
        if walk is not None for p in range(walk[0], walk[1] + 1)
        if keep(b, p)})


@pytest.mark.parametrize("interpret", [True, pltpu.InterpretParams()],
                         ids=["interpret", "tpu-interpreter-nan-scratch"])
@pytest.mark.parametrize("geometry, call", [
    ("smallthinker-ring-4096", "lapped-twice-with-padding"),
    ("smallthinker-ring-4096", "a-block-straddles-the-windows-edge"),
    ("smallthinker-whole", "length-inside-the-first-block"),
    ("smallthinker-whole", "prefix-on-a-pages-edge")])
def test_a_page_outside_the_walk_is_not_fetched(geometry, call, interpret):
    """NaN in every page that no block's walk visits (the ring's entries
    behind the window of the call's first row, the table's pages after its
    last real row, the scratch page) and in the other layer: the gather form
    multiplies them by zero and returns NaN; the kernel never fetches them.
    Under the TPU interpreter uninitialised VMEM is NaN too: a block of keys
    that the walk does not fill meets zeros, not that."""
    heads, n_kv, window = 8, 2, GEOMETRIES[geometry][2]
    entries = _entries(window)
    first, length = _calls(window)[call]
    k, v, table = _pools(n_kv, entries, jnp.float32)
    q = _queries(heads, jnp.float32)
    # Three pages a block of keys: the walks fill their last block unevenly.
    sound = _kernel(q, k, v, table, first, length, window, keys=3 * PAGE)
    walks = _walk(first, length, window)
    bad_k, bad_v = (_poisoned(x, table, walks, entries) for x in (k, v))
    out = _kernel(q, bad_k, bad_v, table, first, length, window,
                  keys=3 * PAGE, interpret=interpret)
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out, sound)
    # Where the walks leave an entry of the table out (a ring lapped by a
    # full bucket has none to spare), the gather form brings its NaN in.
    visited = {p % entries for w in walks if w for p in range(w[0], w[1] + 1)}
    assert (len(visited) < entries) == (call != "lapped-twice-with-padding")
    assert np.isnan(_gather_form(
        q, bad_k, bad_v, table, _visible(window, entries, first, length))
        [:length - first]).any() == (len(visited) < entries)


@pytest.mark.parametrize("end", ["first", "last"])
@pytest.mark.parametrize("block", [0, 1])
@pytest.mark.parametrize("geometry", ["smallthinker-ring-4096",
                                      "smallthinker-whole"])
def test_both_ends_of_a_blocks_walk_are_visited(geometry, block, end):
    """A NaN in the first or the last page of one block's walk (a value the
    mask inside that page lets through to some row) reaches that block's
    rows: the walk is ``lo // page .. hi // page`` and no shorter."""
    heads, n_kv, window = 8, 2, GEOMETRIES[geometry][2]
    entries = _entries(window)
    first = (2 * entries + 3) * PAGE if window else 8 * PAGE
    length = first + ROWS
    k, v, table = _pools(n_kv, entries, jnp.float32)
    q = _queries(heads, jnp.float32)
    walks = _walk(first, length, window)
    at = walks[block][0 if end == "first" else 1]
    if end == "first" and window:
        # No later block's walk reaches back to this page (of a whole-length
        # table every block's does: to page 0).
        assert all(w[0] > at for w in walks[block + 1:])
    bad_v = np.array(v)
    bad_v[LAYER, table[at % entries]] = np.nan
    out = _kernel(q, k, jnp.asarray(bad_v), table, first, length, window)
    rows = slice(BLOCK * block, BLOCK * (block + 1))
    assert np.isnan(out[rows]).any()
    if end == "first" and block == 0:  # behind the second block's window
        assert np.isfinite(out[BLOCK:]).all() == bool(window)
    if end == "last" and block == 1:  # after the first block's last row
        assert np.isfinite(out[:BLOCK]).all()


@pytest.mark.parametrize("geometry", ["trinity-mini-ring-2048",
                                      "smallthinker-whole"])
def test_bfloat16_pools_are_accumulated_in_float32(geometry):
    """``walk_ref.accumulates_in_float32``, at scores eight times as wide as
    a unit draw's: a bfloat16 score is then off by up to 0.06, a
    probability by 6%."""
    heads, n_kv, window = 8, 2, GEOMETRIES[geometry][2]
    entries = _entries(window)
    first, length = 6 * PAGE, 6 * PAGE + ROWS
    k, v, table = _pools(n_kv, entries, jnp.bfloat16)
    q = _queries(heads, jnp.bfloat16, scale=8.0)
    walk_ref.accumulates_in_float32(
        _kernel(q, k, v, table, first, length, window), q[None], k, v,
        table[None], _visible(window, entries, first, length), DIM,
        tol=1.5e-2)


def _shapes(**over):
    q = jax.ShapeDtypeStruct(over.get("q", (256, 8, 128)),
                             over.get("q_dtype", jnp.bfloat16))
    pool = jax.ShapeDtypeStruct(over.get("pool", (1, 5, 16, 2, 128)),
                                over.get("pool_dtype", jnp.bfloat16))
    v = jax.ShapeDtypeStruct(over.get("v", pool.shape), pool.dtype)
    table = jax.ShapeDtypeStruct(over.get("table", (4,)), jnp.int32)
    return q, pool, v, table


@pytest.mark.parametrize("what, over", [
    ("takes", dict(q_dtype=jnp.float32)),
    ("takes", dict(v=(1, 5, 16, 4, 128))),
    ("takes", dict(table=(1, 4))),
    ("takes", dict(q=(256, 8, 64))),
    ("takes", dict(q=(1, 256, 8, 128))),
    ("takes", dict(pool=(5, 16, 2, 128))),
    ("needs", dict(q=(256, 7, 128))),
    ("needs", dict(q=(256, 8, 64), pool=(1, 5, 16, 2, 64))),
    ("needs", dict(pool=(1, 5, 4, 2, 128))),
    ("needs", dict(q=(256, 9, 128), pool=(1, 5, 16, 3, 128))),
    ("needs", dict(q=(8, 8, 128))),
    ("needs", dict(q=(192, 8, 128)))],
    ids=["dtype", "k-and-v", "a-batch-of-tables", "q-width",
         "a-batch-of-queries", "pool-rank", "groups", "head_dim", "page",
         "an-odd-number-of-bf16-kv-heads", "rows-under-a-tile",
         "rows-not-whole-blocks"])
def test_a_geometry_the_kernel_cannot_take_raises_before_it_is_traced(
        what, over):
    with pytest.raises(ValueError, match=f"paged prefill attention {what}"):
        paged_prefill.check_geometry(*_shapes(**over))
    paged_prefill.check_geometry(*_shapes())
    # What the engines run: a page-sized bucket up to the chunk, float32
    # rows of 8, one KV head.
    paged_prefill.check_geometry(*_shapes(q=(128, 28, 128),
                                          pool=(2, 9, 128, 4, 128)))
    paged_prefill.check_geometry(*_shapes(
        q=(8, 6, 128), q_dtype=jnp.float32, pool=(1, 5, 8, 3, 128),
        pool_dtype=jnp.float32))
    paged_prefill.check_geometry(*_shapes(q=(2048, 8, 128),
                                          pool=(1, 5, 16, 1, 128)))
