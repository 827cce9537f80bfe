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


def test_off_the_tpu_the_decode_program_is_the_gather_form():
    """Nothing steers it here: on this backend the latent decode step
    lowers without the kernel, a non-latent model's everywhere."""
    cfg = _config(jnp.float32)
    assert not paged._walks_live_pages(cfg)
    dense = dataclasses.replace(
        cfg, q_lora_rank=0, kv_lora_rank=0, qk_nope_head_dim=0,
        qk_rope_head_dim=0, v_head_dim=0)
    assert not paged._walks_live_pages(dense)
