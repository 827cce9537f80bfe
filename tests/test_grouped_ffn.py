"""``ops/grouped_ffn.py``: the Pallas kernels that read each hit expert's
weights once through the routed FFN's three grouped products (``stream``: a
decode step's rows whole in VMEM; ``rows``: a prompt's rows in blocks), run
here in interpret mode on the CPU against the ``ragged_dot`` form they
replace on a TPU, and through ``moe._moe_ffn`` itself
(``moe._streams_experts`` steered, the one thing a CPU cannot see).  What
the chip's compiler says of them is in ``tests/test_chip_compile.py``."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.models import MoEConfig, moe
from ray_tpu.models.moe import _moe_ffn, moe_init, moe_loss
from ray_tpu.ops import grouped_ffn
from ray_tpu.ops.grouped_ffn import grouped_ffn_rows, grouped_ffn_stream

E = 16
#: Lane-aligned cuts of the routed cells' geometries: (d, f, top_k, the
#: gate's non-linearity, decode slots, what else the family has).
CUTS = {
    "olmoe-top8-silu": dict(d_model=256, d_ff=128, top_k=8,
                            norm_topk_prob=False),
    "smallthinker-top6-relu": dict(d_model=384, d_ff=256, top_k=6,
                                   expert_act="relu"),
    "glm-top4-sigmoid-x1.8": dict(d_model=256, d_ff=384, top_k=4,
                                  router_score="sigmoid",
                                  routed_scaling_factor=1.8),
    "trinity-top8-sigmoid-shared": dict(d_model=256, d_ff=128, top_k=8,
                                        router_score="sigmoid",
                                        n_shared_experts=1),
}
SLOTS = 6


def _config(cut, dtype=jnp.float32):
    return MoEConfig(vocab_size=64, n_layers=1, n_heads=2, n_kv_heads=2,
                     n_experts=E, max_seq=32, dtype=dtype, remat=False,
                     **CUTS[cut])


def _layer(cfg, seed=0):
    return moe_init(cfg, jax.random.PRNGKey(seed))["layers"][0]["moe"]


def _weights(dtype, d=256, f=256, seed=0, experts=E):
    rng = np.random.default_rng(seed)
    w1 = rng.standard_normal((experts, d, f)) * d ** -0.5
    w3 = rng.standard_normal((experts, d, f)) * d ** -0.5
    w2 = rng.standard_normal((experts, f, d)) * f ** -0.5
    return [jnp.asarray(w, dtype) for w in (w1, w3, w2)]


def _rows(dtype, n, d=256, seed=1):
    return jnp.asarray(np.random.default_rng(seed).standard_normal((n, d)),
                       dtype)


@functools.partial(jax.jit, static_argnames=("act", "acc"))
def _three_calls(xs, w1, w3, w2, counts, act, acc):
    def grouped(rows, w):
        return jax.lax.ragged_dot(rows, w, counts,
                                  preferred_element_type=acc)

    h = (grouped_ffn.ACTS[act](grouped(xs, w1).astype(jnp.float32))
         * grouped(xs, w3).astype(jnp.float32)).astype(xs.dtype)
    return grouped(h, w2)


def _ragged_form(xs, w1, w3, w2, counts, act="silu", acc=jnp.float32):
    """The three ``ragged_dot`` calls of ``_moe_ffn``
    (``grouped_ffn.grouped_ffn_ragged``, which accumulates in float32),
    written out so that a test can accumulate them in another dtype (one
    program a shape: eagerly every operation of it is compiled a shape)."""
    return np.asarray(_three_calls(
        xs, w1, w3, w2, jnp.asarray(counts, jnp.int32), act=act, acc=acc),
        np.float32)


def _exact(xs, ws, counts):
    """The SiLU-gated products in float64 on the operands as they are
    rounded (``h`` rounded to bfloat16, as every form rounds it); zeros
    behind the last group."""
    x64 = np.asarray(xs, np.float64)
    w1, w3, w2 = (np.asarray(w, np.float64) for w in ws)
    exact = np.zeros(x64.shape)
    start = 0
    for e, c in enumerate(counts):
        rows = x64[start:start + c]
        a, b = rows @ w1[e], rows @ w3[e]
        h = np.asarray(jnp.asarray(a / (1 + np.exp(-a)) * b, jnp.bfloat16),
                       np.float64)
        exact[start:start + c] = h @ w2[e]
        start += c
    return exact


def _rows_form(xs, w1, w3, w2, counts, act="silu", interpret=True):
    return np.asarray(grouped_ffn_rows(
        xs, w1, w3, w2, jnp.asarray(counts, jnp.int32), act=act,
        interpret=interpret))


def _written(n, counts):
    """The rows of a buffer of ``n`` that the row-block form writes: up to
    the end of the last block that holds a row."""
    _, block = grouped_ffn.rows_blocks(n, len(counts), jnp.float32)
    return min(n, -(-int(np.sum(counts)) // block) * block)


def _stream(xs, w1, w3, w2, counts, act="silu", interpret=True):
    return np.asarray(grouped_ffn_stream(
        xs, w1, w3, w2, jnp.asarray(counts, jnp.int32), act=act,
        interpret=interpret))


def _counts(**load):
    counts = np.zeros(E, np.int32)
    for e, n in load.items():
        counts[int(e[1:])] = n
    return counts


#: (name, rows in the buffer, rows each expert has).
LOADS = [
    ("every-pair-on-one-expert", 48, _counts(e5=48)),
    ("most-experts-empty", 32, _counts(e0=1, e9=2, e15=3)),
    ("groups-across-tile-edges", 64, _counts(e1=15, e2=3, e3=17, e8=29)),
    ("one-or-two-rows-an-expert", 32, np.array([2, 1] * 8, np.int32)),
    ("rows-behind-the-last-group", 40, _counts(e2=7, e3=1, e14=9)),
    ("a-buffer-that-is-not-whole-tiles", 21, _counts(e0=4, e7=11, e15=6)),
    ("no-expert-hit", 16, _counts()),
]


@pytest.mark.parametrize("act", ["silu", "relu"])
@pytest.mark.parametrize("name, n, counts", LOADS,
                         ids=[name for name, _, _ in LOADS])
def test_the_kernel_is_the_ragged_dot_form(name, n, counts, act):
    """In float32 within its rounding, at every load: the rows an expert
    has are all multiplied, by that expert and no other; the rows behind
    the last group are exact zeros."""
    xs, ws = _rows(jnp.float32, n), _weights(jnp.float32)
    out = _stream(xs, *ws, counts, act)
    ref = _ragged_form(xs, *ws, counts, act)
    live = int(counts.sum())
    assert out.shape == (n, 256) and out.dtype == np.float32
    np.testing.assert_allclose(out[:live], ref[:live], atol=2e-5, rtol=2e-5)
    assert (out[live:] == 0).all()
    if live:
        assert np.abs(out[:live]).max() > 0.1


@pytest.mark.parametrize("slab_bytes, slabs", [
    (1 << 30, 1), (3 * 256 * 4 * 128, 2), (1, 2)])
def test_the_answer_does_not_depend_on_the_slabs_width(monkeypatch,
                                                       slab_bytes, slabs):
    """An expert's weights whole, cut in two, and (no width fitting) in
    single lane tiles: the slabs' partial sums are one answer."""
    monkeypatch.setattr(grouped_ffn, "SLAB_BYTES", slab_bytes)
    assert 256 // grouped_ffn.slab_width(256, 256, jnp.float32) == slabs
    counts = LOADS[2][2]
    xs, ws = _rows(jnp.float32, 64), _weights(jnp.float32)
    np.testing.assert_allclose(_stream(xs, *ws, counts),
                               _ragged_form(xs, *ws, counts),
                               atol=2e-5, rtol=2e-5)


def test_the_slab_is_the_widest_that_fits_at_the_cells_widths():
    """Two of them are the double buffer: at the three cells' widths an
    expert of 12.6 or 11.8 MB whole, one of 18.9 MB in halves; whole lane
    tiles that divide the expert's width."""
    for d, f, width in [(2048, 1024, 1024), (2560, 768, 768),
                        (2048, 1536, 768)]:
        assert grouped_ffn.slab_width(d, f, jnp.bfloat16) == width
        assert 3 * d * width * 2 <= grouped_ffn.SLAB_BYTES
    assert grouped_ffn.slab_width(4096, 14336, jnp.bfloat16) == 512


@pytest.mark.parametrize("interpret", [True, pltpu.InterpretParams()],
                         ids=["interpret", "tpu-interpreter-nan-scratch"])
def test_what_no_row_of_an_expert_may_see_reaches_no_output(interpret):
    """NaN in every matrix of every expert with no row (never fetched), and
    in the rows behind the last group (they share a tile with the last
    expert's rows, and are selected out, not multiplied by zero): the
    ``ragged_dot`` form's own rows come out the same, the rows behind are
    exact zeros.  Under the TPU interpreter uninitialised VMEM is NaN too."""
    _, n, counts = LOADS[4]
    xs, ws = _rows(jnp.float32, n), _weights(jnp.float32)
    sound = _stream(xs, *ws, counts)
    live = int(counts.sum())
    unhit = np.flatnonzero(counts == 0)
    bad_ws = [w.at[unhit].set(jnp.nan) for w in ws]
    bad_xs = xs.at[live:].set(jnp.nan)
    out = _stream(bad_xs, *bad_ws, counts, interpret=interpret)
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out, sound)
    assert (out[live:] == 0).all()


def test_a_hit_experts_weights_are_read_to_their_end():
    """A NaN in the last column of w1, of w3 and the last row of w2 of ONE
    hit expert reaches that expert's rows, each of them, and no other's."""
    _, n, counts = LOADS[2]
    xs, ws = _rows(jnp.float32, n), _weights(jnp.float32)
    first = int(counts[:3].sum())
    rows = np.zeros(n, bool)
    rows[first:first + counts[3]] = True
    for which in range(3):
        bad = list(ws)
        bad[which] = ws[which].at[3, 255, 255].set(jnp.nan)
        out = _stream(xs, *bad, counts)
        assert (np.isnan(out).any(axis=1) == rows).all(), which


def test_the_products_accumulate_in_float32():
    """bfloat16 operands: against float64 arithmetic on the same rounded
    operands (``h`` rounded to bfloat16, as both forms round it) the kernel
    is within 2e-3 of the output's scale; the same products accumulated in
    bfloat16, or with float8 weights, are not."""
    _, n, counts = LOADS[2]
    xs, ws = _rows(jnp.bfloat16, n), _weights(jnp.bfloat16)
    exact = _exact(xs, ws, counts)
    scale = np.abs(exact).max()

    def off(out):
        return np.abs(out - exact).max() / scale

    assert off(_stream(xs, *ws, counts)) < 2e-3
    assert off(_ragged_form(xs, *ws, counts)) < 2e-3
    assert off(_ragged_form(xs, *ws, counts, acc=jnp.bfloat16)) > 4e-3
    float8 = [w.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16) for w in ws]
    assert off(_stream(xs, *float8, counts)) > 1e-2


# ------------------------------------------------------ a prompt's rows

#: (name, rows in the buffer, rows each expert has) at prefill loads: with
#: 16 experts a buffer of 2048 rows passes in blocks of 256 rows, each
#: multiplied in tiles of 64; one of 512 in tiles of 32.
ROWS_LOADS = [
    ("none-one-a-block-less-one-a-block-several-blocks", 2048,
     _counts(e1=1, e2=255, e3=256, e5=700, e6=0, e15=836)),
    ("every-row-on-one-expert", 1024, _counts(e9=1024)),
    ("rows-behind-the-last-group-in-blocks-never-met", 2048,
     _counts(e0=130, e4=1, e7=300, e12=90)),
    ("even-groups-that-end-on-block-edges", 512,
     np.full(E, 32, np.int32)),
    ("a-buffer-that-is-not-whole-tiles", 333,
     _counts(e0=40, e3=200, e15=93)),
    ("one-row", 512, _counts(e8=1)),
    ("no-expert-hit", 512, _counts()),
]


def test_the_row_blocks_follow_the_static_row_count():
    """The tile a product multiplies is as tall as an expert's average
    share of the rows, 64 at most and a row tile of the dtype at least;
    the block a DMA moves is 256 rows, or a smaller buffer in whole tiles."""
    blocks = grouped_ffn.rows_blocks
    assert blocks(12288, 64, jnp.bfloat16) == (64, 256)    # SmallThinker
    assert blocks(4096, 64, jnp.bfloat16) == (64, 256)     # GLM, 1024
    assert blocks(512, 64, jnp.bfloat16) == (16, 256)      # GLM, 128
    assert blocks(16384, 128, jnp.bfloat16) == (64, 256)   # Trinity-Mini
    assert blocks(2048, 64, jnp.bfloat16) == (32, 256)     # OLMoE, 256
    assert blocks(333, 16, jnp.float32) == (32, 256)
    assert blocks(24, 16, jnp.float32) == (8, 24)


@pytest.mark.parametrize("act", ["silu", "relu"])
@pytest.mark.parametrize("name, n, counts", ROWS_LOADS,
                         ids=[name for name, _, _ in ROWS_LOADS])
def test_the_row_block_form_is_the_ragged_dot_form(name, n, counts, act):
    """In float32 within its rounding, at every load, under the gated ReLU
    too: an expert's rows are all multiplied, by that expert and no other,
    whether they fill a part of a block, a block or several; the rows
    behind the last group are exact zeros as far as the last block met."""
    xs, ws = _rows(jnp.float32, n), _weights(jnp.float32)
    out = _rows_form(xs, *ws, counts, act)
    ref = _ragged_form(xs, *ws, counts, act)
    live, written = int(counts.sum()), _written(n, counts)
    assert out.shape == (n, 256) and out.dtype == np.float32
    np.testing.assert_allclose(out[:live], ref[:live], atol=2e-5, rtol=2e-5)
    assert (out[live:written] == 0).all()
    if live:
        assert np.abs(out[:live]).max() > 0.1


@pytest.mark.parametrize("interpret", [True, pltpu.InterpretParams()],
                         ids=["interpret", "tpu-interpreter-nan-scratch"])
def test_what_no_row_of_an_expert_may_see_reaches_no_row_block(interpret):
    """NaN in every matrix of every expert with no row (never fetched) and
    in the rows past ``sum(counts)`` (those in the last block met are
    selected out, the blocks behind it are neither fetched nor written):
    the real rows come out as they do without, bit for bit."""
    _, n, counts = ROWS_LOADS[2]
    xs, ws = _rows(jnp.float32, n), _weights(jnp.float32)
    sound = _rows_form(xs, *ws, counts)
    live, written = int(counts.sum()), _written(n, counts)
    assert live < written < n
    unhit = np.flatnonzero(counts == 0)
    bad_ws = [w.at[unhit].set(jnp.nan) for w in ws]
    bad_xs = xs.at[live:].set(jnp.nan)
    out = _rows_form(bad_xs, *bad_ws, counts, interpret=interpret)
    np.testing.assert_array_equal(out[:written], sound[:written])
    assert np.isfinite(out[:written]).all() and (out[live:written] == 0).all()


def test_a_row_blocks_experts_are_read_to_their_end():
    """A NaN in the last column of w1, of w3 and the last row of w2 of ONE
    hit expert reaches that expert's rows, each of them over its three
    blocks, and no other's."""
    _, n, counts = ROWS_LOADS[0]
    xs, ws = _rows(jnp.float32, n), _weights(jnp.float32)
    first = int(counts[:5].sum())
    rows = np.zeros(n, bool)
    rows[first:first + counts[5]] = True
    for which in range(3):
        bad = list(ws)
        bad[which] = ws[which].at[5, 255, 255].set(jnp.nan)
        out = _rows_form(xs, *bad, counts)
        assert (np.isnan(out).any(axis=1) == rows).all(), which


def test_an_expert_the_stream_cuts_in_two_passes_whole(monkeypatch):
    """GLM's experts reach the decode step in two slabs; a prompt's rows
    meet the expert whole (both slabs resident while its blocks pass), so
    the answer is the same whatever a slab may hold."""
    _, n, counts = ROWS_LOADS[0]
    xs, ws = _rows(jnp.float32, n, d=256), _weights(jnp.float32, f=384)
    whole = _rows_form(xs, *ws, counts)
    monkeypatch.setattr(grouped_ffn, "SLAB_BYTES", 3 * 256 * 4 * 128)
    assert 384 // grouped_ffn.slab_width(256, 384, jnp.float32) == 3
    jax.clear_caches()
    np.testing.assert_array_equal(_rows_form(xs, *ws, counts), whole)
    live = int(counts.sum())
    np.testing.assert_allclose(whole[:live],
                               _ragged_form(xs, *ws, counts)[:live],
                               atol=2e-5, rtol=2e-5)
    assert grouped_ffn.holds_an_expert(2048, 1536, jnp.bfloat16)   # GLM's
    assert not grouped_ffn.holds_an_expert(4096, 14336, jnp.bfloat16)


def test_the_row_blocks_products_accumulate_in_float32():
    """bfloat16 operands at a prefill load: against float64 arithmetic on
    the same rounded operands the row-block form is within 2e-3 of the
    output's scale, as the ``ragged_dot`` form is; the same products
    accumulated in bfloat16, or with float8 weights, are not."""
    _, n, counts = ROWS_LOADS[2]
    xs, ws = _rows(jnp.bfloat16, n), _weights(jnp.bfloat16)
    live = int(counts.sum())
    exact = _exact(xs, ws, counts)[:live]
    scale = np.abs(exact).max()

    def off(out):
        return np.abs(out[:live] - exact).max() / scale

    assert off(_rows_form(xs, *ws, counts)) < 2e-3
    assert off(_ragged_form(xs, *ws, counts)) < 2e-3
    assert off(_ragged_form(xs, *ws, counts, acc=jnp.bfloat16)) > 4e-3
    float8 = [w.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16) for w in ws]
    assert off(_rows_form(xs, *float8, counts)) > 1e-2


def test_the_ragged_dot_form_of_the_module_is_the_three_calls():
    """``grouped_ffn_ragged``, the reference the kernels are held to and
    the form ``_moe_ffn`` takes off the TPU, is the three calls written out
    in this file, bit for bit."""
    _, n, counts = LOADS[2]
    xs, ws = _rows(jnp.bfloat16, n), _weights(jnp.bfloat16)
    for act in ("silu", "relu"):
        got = grouped_ffn.grouped_ffn_ragged(
            xs, *ws, jnp.asarray(counts), act=act)
        assert got.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(got),
                                      _ragged_form(xs, *ws, counts, act))


@pytest.mark.parametrize("what, shapes", [
    ("a width that is not whole lane tiles", dict(d=192)),
    ("an expert's width that is not whole lane tiles", dict(f=320)),
    ("rows of another dtype", dict(x_dtype=jnp.float32)),
    ("w2 the other way round", dict(w2=(E, 256, 256 + 128)))])
def test_a_geometry_the_kernel_cannot_take_raises_before_it_is_traced(
        what, shapes):
    d, f = shapes.get("d", 256), shapes.get("f", 256)
    xs = jax.ShapeDtypeStruct((32, d), shapes.get("x_dtype", jnp.bfloat16))
    w13 = jax.ShapeDtypeStruct((E, d, f), jnp.bfloat16)
    w2 = jax.ShapeDtypeStruct(shapes.get("w2", (E, f, d)), jnp.bfloat16)
    with pytest.raises(ValueError, match="streamed routed FFN"):
        grouped_ffn.check_geometry(xs, w13, w13, w2)


# --------------------------------------------------------- through _moe_ffn


def _ffn(cfg, layer, x, valid, stream, monkeypatch):
    """``_moe_ffn`` as this backend takes it, or (``stream``) as a TPU
    takes the rows (a decode step's through the stream, a prefill's in row
    blocks), the kernel interpreted.  Jitted, a function a call: jit keeps a
    trace by its function and arguments, not by what ``on_tpu`` answered."""
    monkeypatch.setattr(grouped_ffn, "on_tpu", lambda: stream)
    with pltpu.force_tpu_interpret_mode():
        out, aux, counts = jax.jit(
            lambda layer, x, valid: _moe_ffn(cfg, layer, x, valid))(
            layer, x, valid)
    return np.asarray(out, np.float32), float(aux), np.asarray(counts)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("cut", CUTS)
def test_the_routed_ffn_through_the_kernel(monkeypatch, cut, dtype):
    """A decode step's rows of each family through ``_moe_ffn``: the same
    output (float32: to rounding; bfloat16: to the rounding of ``h`` and of
    the output), the same load-balancing loss, the same counts.  An empty
    slot (``valid`` false) gets exact zeros and is in no count."""
    cfg = _config(cut, dtype)
    layer = _layer(cfg)
    x = _rows(dtype, SLOTS, cfg.d_model, seed=3)
    valid = jnp.asarray([True, True, False, True, False, True])
    streamed = _ffn(cfg, layer, x, valid, True, monkeypatch)
    ragged = _ffn(cfg, layer, x, valid, False, monkeypatch)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(streamed[0], ragged[0], atol=tol, rtol=tol)
    assert np.abs(ragged[0]).max() > 0.05
    assert streamed[1] == ragged[1]
    np.testing.assert_array_equal(streamed[2], ragged[2])
    assert streamed[2].sum() == 4 * cfg.top_k
    assert (streamed[0][~np.asarray(valid)] == 0).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("cut", CUTS)
def test_a_prefills_rows_through_the_row_block_kernel(monkeypatch, cut, dtype):
    """A bucket of 96 rows of each family, the last 29 of them padding
    (``valid`` false), through ``_moe_ffn`` as a TPU takes a prefill: the
    ``ragged_dot`` form's output, loss and counts; the padded rows, whose
    pairs lie behind every group where the kernel writes zeros or nothing,
    get exact zeros and are in no count."""
    cfg = _config(cut, dtype)
    layer = _layer(cfg)
    tokens = 96
    assert moe.STREAM_ROWS_AN_EXPERT * E < tokens * cfg.top_k
    x = _rows(dtype, tokens, cfg.d_model, seed=5)
    valid = jnp.arange(tokens) < 67
    rows = _ffn(cfg, layer, x, valid, True, monkeypatch)
    ragged = _ffn(cfg, layer, x, valid, False, monkeypatch)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(rows[0], ragged[0], atol=tol, rtol=tol)
    assert np.abs(ragged[0]).max() > 0.05
    assert rows[1] == ragged[1]
    np.testing.assert_array_equal(rows[2], ragged[2])
    assert rows[2].sum() == 67 * cfg.top_k
    assert (rows[0][67:] == 0).all()
    # And with every row real (a chunk: no ``valid``), nothing is selected
    # away behind the kernel: every block is met and written.
    whole = _ffn(cfg, layer, x, None, True, monkeypatch)
    np.testing.assert_allclose(
        whole[0], _ffn(cfg, layer, x, None, False, monkeypatch)[0],
        atol=tol, rtol=tol)


def _form(cfg, tokens, monkeypatch, on_tpu=True):
    """Which form ``_moe_ffn`` traces for ``tokens`` rows."""
    monkeypatch.setattr(grouped_ffn, "on_tpu", lambda: on_tpu)
    x = jax.ShapeDtypeStruct((tokens, cfg.d_model), cfg.dtype)
    layer = jax.eval_shape(lambda: _layer(cfg))
    text = str(jax.make_jaxpr(
        lambda m, rows: _moe_ffn(cfg, m, rows))(layer, x))
    forms = {"stream": "ragged-dot-stream" in text,
             "rows": "ragged-dot-rows" in text,
             "ragged_dot": "ragged_dot" in text}
    form, = [k for k, v in forms.items() if v]
    assert form == moe.grouped_form(cfg, tokens)
    return form


#: (experts, top_k, d, f, the cell's decode slots) of the four routed cells
#: whose programs hold every expert.
CELLS = {"olmoe": (64, 8, 2048, 1024, 16),
         "smallthinker": (64, 6, 2560, 768, 16),
         "glm": (64, 4, 2048, 1536, 32),
         "trinity-mini": (128, 8, 2048, 1024, 32)}


def _cell_config(cell, **more):
    experts, k, d, f, _ = CELLS[cell]
    return MoEConfig(vocab_size=64, d_model=d, n_layers=1, n_heads=2,
                     n_kv_heads=2, d_ff=f, n_experts=experts, top_k=k,
                     max_seq=32, remat=False, **more)


@pytest.mark.parametrize("cell", CELLS)
def test_a_decode_step_streams_and_every_prefill_bucket_passes_in_row_blocks(
        monkeypatch, cell):
    """On a TPU, at the cell's own widths (traced, not run): the decode
    program's rows (one a slot) take the stream, every prefill bucket and
    chunk (128 rows and up) the row-block kernel; off the TPU nothing takes
    a kernel."""
    cfg, slots = _cell_config(cell), CELLS[cell][-1]
    assert _form(cfg, slots, monkeypatch) == "stream"
    for bucket in (128, 256, 512, 1024, 2048):
        assert moe.grouped_form(cfg, bucket) == "rows"
    assert _form(cfg, 128, monkeypatch) == "rows"
    assert _form(cfg, slots, monkeypatch, on_tpu=False) == "ragged_dot"
    assert _form(cfg, 128, monkeypatch, on_tpu=False) == "ragged_dot"


@pytest.mark.parametrize("what, change, tokens, form", [
    ("a width the DMAs cannot cut", dict(d_ff=1024 + 64), 16, "ragged_dot"),
    ("the same width under a prompt", dict(d_ff=1024 + 64), 512,
     "ragged_dot"),
    ("an expert that does not fit VMEM twice (Mixtral's)",
     dict(d_model=4096, d_ff=14336, n_experts=8, top_k=2), 512,
     "ragged_dot"),
    ("that expert under a decode step, in slabs",
     dict(d_model=4096, d_ff=14336, n_experts=8, top_k=2), 8, "stream"),
    ("a share of the router's experts, a decode step: rows over the "
     "ROUTER's width", dict(n_experts=8, router_experts=64), 32, "stream"),
    ("a share of the router's experts, a prompt",
     dict(n_experts=8, router_experts=64), 2048, "ragged_dot"),
    ("the whole router's experts, the same prompt", dict(), 2048, "rows"),
])
def test_the_predicates_three_answers(monkeypatch, what, change, tokens,
                                      form):
    """``moe._streams_experts``, by what it can see: the static pair
    count, the widths, whether an expert fits, and whether the program
    holds every expert its router scores (Kimi-Linear's does not: its
    prefills keep ``ragged_dot``)."""
    cfg = dataclasses.replace(_cell_config("olmoe"), **change)
    monkeypatch.setattr(grouped_ffn, "on_tpu", lambda: True)
    assert moe.grouped_form(cfg, tokens) == form
    assert (moe._streams_experts(cfg, tokens * cfg.top_k)
            or "ragged_dot") == form
    monkeypatch.setattr(grouped_ffn, "on_tpu", lambda: False)
    assert moe.grouped_form(cfg, tokens) == "ragged_dot"


def test_under_a_mesh_the_products_stay_ragged_dot(monkeypatch):
    """XLA does not partition a Mosaic call: a program traced under an
    ambient mesh keeps the form it can lay out."""
    from jax.sharding import Mesh

    monkeypatch.setattr(grouped_ffn, "on_tpu", lambda: True)
    cfg = _cell_config("olmoe")
    assert moe.grouped_form(cfg, 2048) == "rows"
    with jax.set_mesh(Mesh(np.array(jax.devices()[:2]), ("ep",))):
        assert moe.grouped_form(cfg, 2048) == "ragged_dot"
        assert moe.grouped_form(cfg, 16) == "ragged_dot"


def test_the_loss_through_the_kernel_has_the_ragged_dot_forms_gradient(
        monkeypatch):
    """A training batch now meets the predicate on a TPU: the forward is
    the row-block kernel (interpreted here), the backward the ``ragged_dot``
    form's own through the custom VJP, so ``jax.grad(moe_loss)`` returns
    what it returns off the TPU."""
    cfg = MoEConfig.tiny(dtype=jnp.float32, remat=False)
    params = moe_init(cfg, jax.random.PRNGKey(0))
    tokens = jnp.arange(2 * 16, dtype=jnp.int32).reshape(2, 16) % 512

    def loss_and_grads(on_tpu):
        monkeypatch.setattr(grouped_ffn, "on_tpu", lambda: on_tpu)
        jax.clear_caches()
        fn = lambda p: moe_loss(cfg, p, tokens, tokens)  # noqa: E731
        text = str(jax.make_jaxpr(jax.grad(fn))(params))
        with pltpu.force_tpu_interpret_mode():  # a new ``fn``: traced anew
            return (text, *jax.jit(jax.value_and_grad(fn))(params))

    assert moe.grouped_form(cfg, tokens.size) == "ragged_dot"
    ragged_text, ragged_loss, ragged = loss_and_grads(False)
    text, loss, grads = loss_and_grads(True)
    assert moe.grouped_form(cfg, tokens.size) == "rows"
    assert "ragged-dot-rows" in text and "ragged_dot" in text  # fwd, bwd
    assert "ragged-dot-rows" not in ragged_text
    np.testing.assert_allclose(float(loss), float(ragged_loss), rtol=1e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), r in zip(flat, jax.tree.leaves(ragged)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=1e-6,
                                   rtol=1e-4, err_msg=str(path))
    g = np.asarray(grads["layers"][0]["moe"]["w2"])
    assert np.isfinite(g).all() and np.abs(g).max() > 0


def test_off_the_tpu_the_routed_ffn_is_the_ragged_dot_form():
    """Nothing steers it here."""
    cfg = _config("olmoe-top8-silu")
    assert not moe._streams_experts(cfg, SLOTS * cfg.top_k)
    assert moe.grouped_form(cfg, SLOTS) == "ragged_dot"
    assert moe.grouped_form(cfg, 2048) == "ragged_dot"


def test_the_engine_names_the_forms_its_programs_hold(monkeypatch):
    """``stats()["grouped_ffn"]``: by the engine's slots, as ``_moe_ffn``
    chooses for the decode program; ``["grouped_ffn_prefill"]``: by its
    largest bucket, as it chooses for the prefills; None of a dense
    model."""
    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    def forms(cfg, params):
        geometry = EngineConfig(batch_slots=4, page_size=8,
                                max_prompt_len=16, max_new_tokens_cap=32)
        stats = InferenceEngine(cfg, params, geometry, seed=0).stats()
        return stats["grouped_ffn"], stats["grouped_ffn_prefill"]

    routed = _config("olmoe-top8-silu")
    params = moe_init(routed, jax.random.PRNGKey(0))
    assert forms(routed, params) == ("ragged_dot", "ragged_dot")
    monkeypatch.setattr(grouped_ffn, "on_tpu", lambda: True)
    assert forms(routed, params) == ("stream", "rows")
    dense = LlamaConfig.tiny(remat=False, dtype=jnp.float32)
    assert forms(dense, llama_init(dense, jax.random.PRNGKey(0))) \
        == (None, None)
