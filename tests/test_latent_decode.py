"""``ops/latent_decode.py``: the Pallas kernel that walks a latent pool's live
pages in the decode step, run here in interpret mode on the CPU against the
gather form it replaces on a TPU (``paged._attend_pages``), and through the
decode program itself (``paged._walks_live_pages`` steered, the one thing a
CPU cannot see).  What the chip's compiler says of it is in
``tests/benchmark/test_benchmark_chip_compile_glm4_moe_lite.py``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

import walk_ref
from ray_tpu.models import MoEConfig, paged
from ray_tpu.ops import latent_decode
from ray_tpu.ops.latent_decode import latent_decode_attention
from walk_ref import LAYER

PAGE, MAXP, POOL, LAYERS = 16, 5, 24, 2
HEADS, RANK, ROPE, WIDTH = 5, 128, 8, 256
HEAD_DIM = 48  # an expanded head's q and k: the scores' scale

#: One batch that holds every kind of slot at once: (name, rows already
#: cached).  The step's own row is written at that position, so a slot sees
#: ``len + 1`` rows and walks ``len // PAGE + 1`` pages.
SLOTS = [("empty", 0), ("one-token", 1), ("ends-on-a-page-edge", PAGE - 1),
         ("one-past-the-edge", PAGE), ("full-table", MAXP * PAGE - 1),
         ("shares-its-first-pages", 2 * PAGE + 5),
         ("shares-its-first-pages-too", 2 * PAGE + 9)]
NAMES = [n for n, _ in SLOTS]
LENS = np.array([n for _, n in SLOTS], np.int32)


def _tables():
    """Each slot's live pages from the pool in turn; the last two slots hold
    the same first two pages (a shared prefix); past the live pages, and the
    whole of the empty slot's table, the scratch page."""
    tables = np.full((len(SLOTS), MAXP), POOL, np.int32)
    free = iter(range(POOL))
    for b, n in enumerate(LENS):
        if NAMES[b] == "empty":
            continue
        tables[b, :n // PAGE + 1] = [next(free) for _ in range(n // PAGE + 1)]
    tables[-1, :2] = tables[-2, :2]
    return tables


def _inputs(dtype, seed=0):
    """(queries, the pool): the rows' padding zero on both sides."""
    return (walk_ref.seeded((len(SLOTS), HEADS, WIDTH), dtype, seed,
                            RANK + ROPE),
            walk_ref.seeded((LAYERS, POOL + 1, PAGE, WIDTH), dtype,
                            seed + 100, RANK + ROPE))


def _gather_form(q, kv, tables, lens):
    visible = jnp.arange(MAXP * PAGE)[None, None, :] \
        <= jnp.asarray(lens)[:, None, None]
    return walk_ref.gather_form(q[:, None], kv, None, tables, visible,
                                HEAD_DIM, RANK)[:, 0]


def _kernel(q, kv, tables, lens, interpret=True, per_block=None):
    """Compiled once a batch's shapes and a block's pages."""
    blocks = (("PAGES_PER_BLOCK", per_block),) if per_block else ()
    call = walk_ref.blocked(latent_decode, latent_decode_attention, blocks,
                            rank=RANK, sm_scale=HEAD_DIM ** -0.5,
                            interpret=interpret)
    return np.asarray(call((q, kv), jnp.asarray(tables), jnp.asarray(lens)),
                      np.float32)


@pytest.fixture(scope="module", params=[jnp.float32, jnp.bfloat16],
                ids=["float32", "bfloat16"])
def both(request):
    """(the kernel's output, the gather form's, the dtype) on the batch."""
    q, kv = _inputs(request.param)
    tables = _tables()
    return (_kernel(q, kv, tables, LENS), _gather_form(q, kv, tables, LENS),
            request.param)


@pytest.mark.parametrize("slot", range(len(SLOTS)), ids=NAMES)
def test_the_kernel_is_the_gather_form(both, slot):
    """Within float32 rounding where the pool is float32; where it is
    bfloat16, within the rounding of the probabilities (the gather form
    rounds them after dividing by their sum, the kernel before: online
    softmax) and of the bfloat16 output."""
    out, ref, dtype = both
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    assert np.isfinite(out[slot]).all()
    np.testing.assert_allclose(out[slot], ref[slot], atol=tol, rtol=tol)


def test_the_shared_pages_are_read_for_each_slot_that_holds_them(both):
    """Two slots over the same first pages get each their own answer."""
    out, ref, _ = both
    assert np.abs(out[-1] - out[-2]).max() > 1e-3
    assert np.abs(ref[-1] - ref[-2]).max() > 1e-3


@pytest.mark.parametrize("per_block", [1, 2, 3, 8])
def test_the_walk_does_not_depend_on_the_blocks_size(per_block):
    """Blocks of one page, blocks that the live pages fill unevenly, and a
    block wider than the table: one answer."""
    q, kv = _inputs(jnp.float32, seed=per_block)
    tables = _tables()
    np.testing.assert_allclose(_kernel(q, kv, tables, LENS,
                                       per_block=per_block),
                               _gather_form(q, kv, tables, LENS),
                               atol=2e-6, rtol=2e-6)


def _poisoned(kv, tables, lens, keep):
    """``kv`` with NaN in every page of every layer except the pages
    ``keep(b, p)`` of ``LAYER`` among the slots' LIVE ones."""
    return walk_ref.poisoned(kv, {
        int(tables[b, p]) for b, n in enumerate(lens)
        for p in range(n // PAGE + 1) if keep(b, p)})


@pytest.mark.parametrize("interpret", [True, pltpu.InterpretParams()],
                         ids=["interpret", "tpu-interpreter-nan-scratch"])
def test_a_poisoned_dead_page_does_not_reach_the_output(interpret):
    """NaN in every page no slot's live length reaches (the tables' tails
    still name some), in the scratch page (the empty slot reads it as its
    one page, so it is left out of this batch) and in the other layer: the
    gather form would multiply them by zero and return NaN; the kernel
    never fetches them.  Under the TPU interpreter uninitialised VMEM is NaN
    too: a block the live pages do not fill meets zeros, not that."""
    q, kv = _inputs(jnp.float32)
    tables = _tables()
    stale = np.arange(POOL, dtype=np.int32)[::-1][:MAXP]
    for b, n in enumerate(LENS):  # dead table entries name real pages
        tables[b, n // PAGE + 1:] = stale[n // PAGE + 1:]
    sound = _kernel(q, kv, tables, LENS)[1:]
    bad = _poisoned(kv, tables[1:], LENS[1:], lambda b, p: True)
    out = _kernel(q[1:], bad, tables[1:], LENS[1:], interpret=interpret)
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out, sound)
    assert np.isnan(_gather_form(q[1:], bad, tables[1:], LENS[1:])).any()


@pytest.mark.parametrize("slot", range(1, len(SLOTS)), ids=NAMES[1:])
def test_every_live_page_is_visited(slot):
    """A NaN in the LAST live page of one slot reaches that slot's output
    (the page is read up to its end: the walk is ``len // page + 1`` pages,
    no fewer) and no other slot's, apart from a slot that shares it."""
    q, kv = _inputs(jnp.float32)
    tables = _tables()
    last = LENS[slot] // PAGE
    bad = _poisoned(kv, tables, LENS,
                    lambda b, p: (b, p) != (slot, last) or NAMES[b] == "empty")
    bad = bad.at[LAYER, POOL].set(0.0)  # the scratch page: the empty slot's
    out = _kernel(q, bad, tables, LENS)
    hit = {b for b in range(len(SLOTS))
           if tables[b, min(last, LENS[b] // PAGE)] == tables[slot, last]
           and LENS[b] // PAGE >= last}
    for b in range(len(SLOTS)):
        assert np.isnan(out[b]).any() == (b in hit), (b, hit)


@pytest.mark.parametrize("what, kw", [
    ("page", dict(page=12)), ("width", dict(width=192)),
    ("rank", dict(rank=96)), ("dtype", dict(q_dtype=jnp.float32))])
def test_a_geometry_the_kernel_cannot_take_raises_before_it_is_traced(
        what, kw):
    page, width = kw.get("page", 16), kw.get("width", 256)
    q = jax.ShapeDtypeStruct((2, 4, width), kw.get("q_dtype", jnp.bfloat16))
    kv = jax.ShapeDtypeStruct((1, 3, page, width), jnp.bfloat16)
    with pytest.raises(ValueError, match="latent decode attention"):
        latent_decode.check_geometry(q, kv, kw.get("rank", 128))


# ---------------------------------- a run of pages that several slots hold


def _scene(*slots):
    """(lens, tables, the runs ``shared_runs`` has to find, the slots'
    activity) of slots given as (rows already cached, the table's live
    pages, the run expected); a slot of no rows is empty and inactive, its
    table all scratch."""
    tables = np.full((len(slots), MAXP), POOL, np.int32)
    for b, (n, pages, _) in enumerate(slots):
        assert len(pages) == (n // PAGE + 1 if n else 0)
        tables[b, :len(pages)] = pages
    lens = np.array([n for n, _, _ in slots], np.int32)
    return lens, tables, [run for _, _, run in slots]


#: A follower's run is what it has in common with its LEADER (the first
#: active slot that opens with the same page), in whole pages below both
#: slots' last; a leader's own the longest of its followers'.
SCENES = {
    "every-slot-one-run": _scene(
        (2 * PAGE + 3, [0, 1, 2], 2), (2 * PAGE + 9, [0, 1, 3], 2),
        (3 * PAGE + 1, [0, 1, 4, 5], 2), (2 * PAGE, [0, 1, 6], 2)),
    "two-groups-of-different-runs-beside-a-loner": _scene(
        (3 * PAGE + 5, [0, 1, 2, 3], 3), (PAGE + 2, [8, 9], 1),
        (4 * PAGE + 15, [0, 1, 2, 4, 5], 3), (2 * PAGE + 7, [10, 11, 12], 0),
        (PAGE, [8, 13], 1)),
    "a-follower-whose-run-is-shorter-than-its-leaders": _scene(
        (4 * PAGE + 1, [0, 1, 2, 3, 4], 4), (4 * PAGE + 9, [0, 1, 2, 3, 5], 4),
        (3 * PAGE + 3, [0, 1, 6, 7], 2), (PAGE + 4, [0, 1], 1)),
    "a-run-of-one-page-and-of-a-whole-table-less-one": _scene(
        (PAGE + 1, [0, 1], 1), (5 * PAGE - 1, [2, 3, 4, 5, 6], 4),
        (PAGE + 8, [0, 7], 1), (4 * PAGE, [2, 3, 4, 5, 8], 4)),
    "inactive-slots-with-all-scratch-tables-are-not-grouped": _scene(
        (0, [], 0), (2 * PAGE + 2, [0, 1, 2], 2), (0, [], 0),
        (2 * PAGE + 6, [0, 1, 3], 2), (0, [], 0)),
    "two-followers-that-go-on-together-past-their-leaders-end": _scene(
        (PAGE + 3, [0, 1], 1), (3 * PAGE + 2, [0, 4, 5, 6], 1),
        (3 * PAGE + 9, [0, 4, 5, 7], 1)),
    "no-run-anywhere": _scene(
        (2 * PAGE + 5, [0, 1, 2], 0), (0, [], 0), (PAGE - 1, [3], 0),
        (5 * PAGE - 1, [4, 5, 6, 7, 8], 0), (1, [9], 0)),
}


def _shared_attention(q, kv, layer, tables, lens, **static):
    """The front in the shared form, the runs found as the program finds
    them: (the heads' outputs, the runs)."""
    runs = latent_decode.shared_runs(tables, lens, lens > 0, page=PAGE,
                                     heads=HEADS)
    return latent_decode_attention(q, kv, layer, tables, lens, runs=runs,
                                   **static), runs


def _shared(q, kv, tables, lens, interpret=True, rows=None, per_block=None):
    """The shared form on the scene, compiled once a batch's shapes and a
    block setting: (the heads' outputs, the runs it found)."""
    blocks = tuple((name, value) for name, value in (
        ("SHARED_ROWS", rows), ("SHARED_PAGES_PER_BLOCK", per_block))
        if value)
    call = walk_ref.blocked(latent_decode, _shared_attention, blocks,
                            rank=RANK, sm_scale=HEAD_DIM ** -0.5,
                            interpret=interpret)
    out, runs = call((q, kv), jnp.asarray(tables), jnp.asarray(lens))
    return np.asarray(out, np.float32), runs


def _scene_inputs(scene, dtype, seed=0):
    lens, tables, _ = SCENES[scene]
    q = walk_ref.seeded((len(lens), HEADS, WIDTH), dtype, seed, RANK + ROPE)
    return q, _inputs(dtype, seed)[1], tables, lens


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("scene", SCENES)
def test_the_shared_form_is_the_gather_form(scene, dtype):
    """A run fetched once for all its holders, and each slot's own tail
    from the run's end: the softmax over the same keys, in float32 to 1e-4
    and in bfloat16 to the per-slot walk's tolerance."""
    q, kv, tables, lens = _scene_inputs(scene, dtype)
    out, _ = _shared(q, kv, tables, lens)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, _gather_form(q, kv, tables, lens),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("scene", SCENES)
def test_the_runs_are_the_pages_a_slot_shares_with_its_leader(scene):
    lens, tables, expected = SCENES[scene]
    runs = latent_decode.shared_runs(
        jnp.asarray(tables), jnp.asarray(lens), jnp.asarray(lens > 0),
        page=PAGE, heads=HEADS)
    assert np.asarray(runs.run).tolist() == expected
    # A group's members stand side by side, and every slot has its place.
    order = np.asarray(runs.order)
    assert sorted(order.tolist()) == list(range(len(lens)))
    np.testing.assert_array_equal(np.asarray(runs.place)[order],
                                  np.arange(len(lens)))
    first, count, longest, shortest = np.asarray(runs.passes).reshape(
        4, -1)
    saved = 0
    for leader in np.flatnonzero(count):
        members = order[first[leader]:first[leader] + count[leader]]
        assert tables[members, 0].tolist() == [tables[leader, 0]] * len(
            members)
        member_runs = [expected[b] for b in members]
        assert min(member_runs) == shortest[leader] > 0
        assert max(member_runs) == longest[leader] == expected[leader]
        saved += sum(member_runs) - longest[leader]
    assert sum(count) == sum(r > 0 for r in expected)
    assert int(runs.pages_saved) == saved


def test_with_no_run_anywhere_the_shared_form_is_the_walk_bit_for_bit():
    q, kv, tables, lens = _scene_inputs("no-run-anywhere", jnp.float32)
    out, runs = _shared(q, kv, tables, lens)
    assert not np.asarray(runs.run).any()
    np.testing.assert_array_equal(out, _kernel(q, kv, tables, lens))


@pytest.mark.parametrize("rows, per_block", [(16, 1), (32, 3), (48, 8)])
def test_the_shared_pass_does_not_depend_on_its_blocks_size(rows, per_block):
    """Blocks of rows that part a slot's heads and a group's members, and
    blocks of pages that a run fills unevenly: one answer."""
    scene = "two-groups-of-different-runs-beside-a-loner"
    q, kv, tables, lens = _scene_inputs(scene, jnp.float32, seed=rows)
    out, _ = _shared(q, kv, tables, lens, rows=rows, per_block=per_block)
    np.testing.assert_allclose(out, _gather_form(q, kv, tables, lens),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("interpret", [True, pltpu.InterpretParams()],
                         ids=["interpret", "tpu-interpreter-nan-scratch"])
def test_the_shared_pass_fetches_its_runs_and_nothing_else(interpret):
    """NaN in every page that is no slot's live one: a pass walks its run's
    pages and no further (the last block's other pages meet zeros), and a
    tail starts where the run ended."""
    scene = "a-follower-whose-run-is-shorter-than-its-leaders"
    q, kv, tables, lens = _scene_inputs(scene, jnp.float32)
    sound, _ = _shared(q, kv, tables, lens)
    bad = _poisoned(kv, tables, lens, lambda b, p: True)
    out, _ = _shared(q, bad, tables, lens, interpret=interpret,
                     per_block=3)
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, sound, atol=1e-6, rtol=1e-6)


# ------------------------------------------------- through the decode program


def _config(dtype):
    return MoEConfig(
        vocab_size=96, d_model=32, n_layers=LAYERS, n_heads=4, n_kv_heads=4,
        d_ff=48, n_experts=4, top_k=2, max_seq=MAXP * PAGE, dtype=dtype,
        q_lora_rank=16, kv_lora_rank=RANK, qk_nope_head_dim=12,
        qk_rope_head_dim=ROPE, v_head_dim=20, ffn_layout=(0, 1),
        dense_d_ff=40, n_shared_experts=1, router_score="sigmoid",
        routed_scaling_factor=1.8, remat=False)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_decode_program_through_the_kernel(dtype):
    """The decode step of a latent model as a TPU takes it, against the
    gather form: the same logits, ``kv_rows_live`` as it was, and
    ``kv_rows_read`` the pages the kernel visits x page x layers where the
    gather form's is every slot's whole table."""
    cfg = _config(dtype)
    assert paged.latent_row_width(cfg) == WIDTH
    assert paged.counter_keys(cfg)[-2:] == paged.KV_KEYS
    model = walk_ref.model_of(cfg, POOL, PAGE)
    walked, logits = walk_ref.decode(cfg, latent_decode, True, model,
                                     _tables(), LENS)
    gathered, ref = walk_ref.decode(cfg, latent_decode, False, model,
                                    _tables(), LENS)
    tol = 1e-4 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(logits, ref, atol=tol, rtol=0)
    b = len(SLOTS)
    visited = int(sum(n // PAGE + 1 for n in LENS))
    assert walked[-2] == visited * PAGE * LAYERS
    assert gathered[-2] == b * MAXP * PAGE * LAYERS
    live = int(sum(n + 1 for n in LENS if n)) * LAYERS
    assert walked[-1] == gathered[-1] == live
    if dtype == jnp.float32:
        np.testing.assert_array_equal(walked[:b], gathered[:b])


@pytest.mark.parametrize("scene", [
    "two-groups-of-different-runs-beside-a-loner",
    "inactive-slots-with-all-scratch-tables-are-not-grouped",
    "no-run-anywhere"])
def test_the_shared_decode_program_counts_a_run_once(scene):
    """The decode step in the shared form against the gather form and the
    per-slot walk: the same logits; ``kv_rows_read`` a run's pages once a
    pass and a tail's once a slot, ``kv_rows_shared`` what that saved, the
    two together what the per-slot walk counts; ``kv_rows_live`` as it
    was; with nothing shared the per-slot walk's tokens and counts."""
    cfg = _config(jnp.float32)
    assert paged.counter_keys(cfg, True)[-3:] \
        == paged.KV_KEYS + paged.SHARED_KEYS
    model = walk_ref.model_of(cfg, POOL, PAGE)
    lens, tables, runs = SCENES[scene]
    shared, logits = walk_ref.decode(cfg, latent_decode, True, model, tables,
                                     lens, shared=True)
    walked, _ = walk_ref.decode(cfg, latent_decode, True, model, tables,
                                lens)
    gathered, ref = walk_ref.decode(cfg, latent_decode, False, model, tables,
                                    lens)
    np.testing.assert_allclose(logits, ref, atol=1e-4, rtol=0)
    b = len(lens)
    np.testing.assert_array_equal(shared[:b], gathered[:b])
    read, live, saved = shared[-3:]
    passes = {}  # by a group's first page: its longest run, the leader's
    for first, r in zip(tables[:, 0], runs):
        passes[first] = max(passes.get(first, 0), r)
    tails = int(sum(n // PAGE + 1 - r for n, r in zip(lens, runs)))
    assert read == (sum(passes.values()) + tails) * PAGE * LAYERS
    assert saved == (sum(runs) - sum(passes.values())) * PAGE * LAYERS
    assert read + saved == walked[-2]
    assert live == walked[-1] == gathered[-1]
    if not any(runs):
        np.testing.assert_array_equal(shared[:-1], walked)


def test_a_program_that_does_not_walk_has_no_shared_form():
    """Off the TPU the latent decode step gathers: asking it for the shared
    form is refused where the program is traced."""
    cfg = _config(jnp.float32)
    assert not paged.shares_walked_pages(cfg)
    model = walk_ref.model_of(cfg, POOL, PAGE)
    lens, tables, _ = SCENES["no-run-anywhere"]
    with pytest.raises(ValueError, match="no shared form"):
        walk_ref.decode(cfg, latent_decode, False, model, tables, lens,
                        shared=True)


def _engine_and_its_decode_text(cfg, steer):
    """(an engine's ``stats()``, its counters' names, its decode program as
    it dispatches it, lowered for the TPU platform with ``steer.on_tpu``
    answering true: nothing is compiled, nothing runs)."""
    from ray_tpu.models import init_and_apply
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    params = jax.jit(init_and_apply(cfg)[0], static_argnums=0)(
        cfg, jax.random.PRNGKey(0))
    was, steer.on_tpu = steer.on_tpu, lambda: True
    try:
        eng = InferenceEngine(cfg, params, EngineConfig(
            batch_slots=4, page_size=8, max_prompt_len=16,
            max_new_tokens_cap=32), seed=0)
        text = paged.paged_decode_step.trace(
            cfg, params, eng.pools, eng.adapter_pool.arrays,
            *map(jnp.asarray, (eng._tokens, eng._page_tables, eng._seq_lens,
                               eng._active, eng._temps, eng._adapter_slots)),
            eng._d_key, eng._d_ring_tables, shared=eng._shared_walk).lower(
            lowering_platforms=("tpu",)).as_text()
        return eng.stats(), eng._counter_keys, text
    finally:
        steer.on_tpu = was
        jax.clear_caches()


@pytest.mark.parametrize("name, off, form", [
    ("kimi-linear-tiny", "recurrent layers", "walk"),
    ("smallthinker-tiny", "window layers", "walk"),
    ("glm4-moe-lite-tiny", None, "walk+shared")])
def test_an_engine_asks_for_the_shared_form_only_where_pages_can_be_shared(
        name, off, form):
    """Only the prefix cache puts one page into two slots' tables.  Where a
    configuration runs without it (recurrent layers, window layers) the
    decode program is compiled as it was, with no shared pass in its text
    and no ``kv_rows_shared`` behind its tokens; a latent model with the
    cache on gets the pass, and its replica says so."""
    from ray_tpu.ops import paged_decode

    if name == "smallthinker-tiny":
        cfg, steer = walk_ref.tiny_pair(name), paged_decode
    else:
        cfg, steer = walk_ref.tiny(name, kv_lora_rank=128), latent_decode
    stats, keys, text = _engine_and_its_decode_text(cfg, steer)
    assert stats["prefix_cache_off"] == off
    assert stats["decode_attention"] == form
    shared = form == "walk+shared"
    assert ("kv_rows_shared" in keys) == shared
    assert keys[-2 - shared:][:2] == paged.KV_KEYS
    assert ("latent_decode_shared" in text) == shared
    assert ("latent_decode" in text) == (steer is latent_decode)


def test_off_the_tpu_the_decode_program_is_the_gather_form():
    """Nothing steers it here: on this backend the latent decode step
    lowers without the kernel, a non-latent model's everywhere."""
    cfg = _config(jnp.float32)
    assert not paged._walks_live_pages(cfg)
    dense = dataclasses.replace(
        cfg, q_lora_rank=0, kv_lora_rank=0, qk_nope_head_dim=0,
        qk_rope_head_dim=0, v_head_dim=0)
    assert not paged._walks_live_pages(dense)
