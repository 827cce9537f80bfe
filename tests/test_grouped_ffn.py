"""``ops/grouped_ffn.py``: the Pallas kernel that streams each hit expert's
weights once through the routed FFN's three grouped products, run here in
interpret mode on the CPU against the ``ragged_dot`` form it replaces in a
decode step on a TPU, and through ``moe._moe_ffn`` itself
(``moe._streams_experts`` steered, the one thing a CPU cannot see).  What
the chip's compiler says of it is in ``tests/test_chip_compile.py``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.models import MoEConfig, moe
from ray_tpu.models.moe import _moe_ffn, moe_init, moe_loss
from ray_tpu.ops import grouped_ffn
from ray_tpu.ops.grouped_ffn import grouped_ffn_stream

E = 16
#: Lane-aligned cuts of the three routed cells' geometries: (d, f, top_k,
#: the gate's non-linearity, decode slots, what else the family has).
CUTS = {
    "olmoe-top8-silu": dict(d_model=256, d_ff=128, top_k=8,
                            norm_topk_prob=False),
    "smallthinker-top6-relu": dict(d_model=384, d_ff=256, top_k=6,
                                   expert_act="relu"),
    "glm-top4-sigmoid-x1.8": dict(d_model=256, d_ff=384, top_k=4,
                                  router_score="sigmoid",
                                  routed_scaling_factor=1.8),
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


def _ragged_form(xs, w1, w3, w2, counts, act="silu", acc=jnp.float32):
    """The three ``ragged_dot`` calls of ``_moe_ffn``."""
    def grouped(rows, w):
        return jax.lax.ragged_dot(rows, w, jnp.asarray(counts, jnp.int32),
                                  preferred_element_type=acc)

    h = (grouped_ffn.ACTS[act](grouped(xs, w1).astype(jnp.float32))
         * grouped(xs, w3).astype(jnp.float32)).astype(xs.dtype)
    return np.asarray(grouped(h, w2), np.float32)


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
    x64 = np.asarray(xs, np.float64)
    w1, w3, w2 = (np.asarray(w, np.float64) for w in ws)
    exact = np.zeros((n, 256))
    start = 0
    for e, c in enumerate(counts):
        rows = x64[start:start + c]
        a, b = rows @ w1[e], rows @ w3[e]
        h = np.asarray(jnp.asarray(a / (1 + np.exp(-a)) * b, jnp.bfloat16),
                       np.float64)
        exact[start:start + c] = h @ w2[e]
        start += c
    scale = np.abs(exact).max()

    def off(out):
        return np.abs(out - exact).max() / scale

    assert off(_stream(xs, *ws, counts)) < 2e-3
    assert off(_ragged_form(xs, *ws, counts)) < 2e-3
    assert off(_ragged_form(xs, *ws, counts, acc=jnp.bfloat16)) > 4e-3
    float8 = [w.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16) for w in ws]
    assert off(_stream(xs, *float8, counts)) > 1e-2


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
    takes a decode step's rows, the kernel interpreted."""
    monkeypatch.setattr(grouped_ffn, "on_tpu", lambda: stream)
    with pltpu.force_tpu_interpret_mode():
        out, aux, counts = _moe_ffn(cfg, layer, x, valid)
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


def _form(cfg, tokens, monkeypatch, on_tpu=True):
    """Which form ``_moe_ffn`` traces for ``tokens`` rows."""
    monkeypatch.setattr(grouped_ffn, "on_tpu", lambda: on_tpu)
    x = jax.ShapeDtypeStruct((tokens, cfg.d_model), cfg.dtype)
    layer = jax.eval_shape(lambda: _layer(cfg))
    text = str(jax.make_jaxpr(
        lambda m, rows: _moe_ffn(cfg, m, rows))(layer, x))
    forms = {"stream": "ragged-dot-stream" in text,
             "ragged_dot": "ragged_dot" in text}
    form, = [k for k, v in forms.items() if v]
    assert form == moe.grouped_form(cfg, tokens)
    return form


#: (experts, top_k, d, f, the cell's decode slots) of the three routed cells.
CELLS = {"olmoe": (64, 8, 2048, 1024, 16),
         "smallthinker": (64, 6, 2560, 768, 16),
         "glm": (64, 4, 2048, 1536, 32)}


@pytest.mark.parametrize("cell", CELLS)
def test_a_decode_step_streams_and_every_prefill_bucket_does_not(
        monkeypatch, cell):
    """On a TPU, at the cell's own widths (traced, not run): the decode
    program's rows (one a slot) take the kernel, every prefill bucket and
    chunk (128 rows and up) the ``ragged_dot`` form; off the TPU nothing
    takes the kernel."""
    experts, k, d, f, slots = CELLS[cell]
    cfg = MoEConfig(vocab_size=64, d_model=d, n_layers=1, n_heads=2,
                    n_kv_heads=2, d_ff=f, n_experts=experts, top_k=k,
                    max_seq=32, remat=False)
    assert _form(cfg, slots, monkeypatch) == "stream"
    for bucket in (128, 256, 512, 1024, 2048):
        assert moe.grouped_form(cfg, bucket) == "ragged_dot"
    assert _form(cfg, 128, monkeypatch) == "ragged_dot"
    assert _form(cfg, slots, monkeypatch, on_tpu=False) == "ragged_dot"
    # A width the kernel's DMAs cannot cut keeps the general form.
    odd = dataclasses.replace(cfg, d_ff=f + 64)
    assert moe.grouped_form(odd, slots) == "ragged_dot"


def test_the_loss_keeps_the_ragged_dot_form_and_its_gradient(monkeypatch):
    """A training batch has eight rows an expert and up: on a TPU too the
    loss is the ``ragged_dot`` form, which has a gradient."""
    monkeypatch.setattr(grouped_ffn, "on_tpu", lambda: True)
    cfg = MoEConfig.tiny(dtype=jnp.float32, remat=False)
    params = moe_init(cfg, jax.random.PRNGKey(0))
    tokens = jnp.arange(2 * 16, dtype=jnp.int32).reshape(2, 16) % 512
    assert moe.grouped_form(cfg, tokens.size) == "ragged_dot"
    text = str(jax.make_jaxpr(
        lambda p: moe_loss(cfg, p, tokens, tokens))(params))
    assert "ragged_dot" in text and "ragged-dot-stream" not in text
    loss, grads = jax.value_and_grad(
        lambda p: moe_loss(cfg, p, tokens, tokens))(params)
    assert np.isfinite(float(loss))
    g = grads["layers"][0]["moe"]["w2"]
    assert np.isfinite(np.asarray(g)).all() and np.abs(np.asarray(g)).max() > 0


def test_off_the_tpu_the_routed_ffn_is_the_ragged_dot_form():
    """Nothing steers it here."""
    cfg = _config("olmoe-top8-silu")
    assert not moe._streams_experts(cfg, SLOTS * cfg.top_k)
    assert moe.grouped_form(cfg, SLOTS) == "ragged_dot"


def test_the_engine_names_the_form_its_decode_program_holds(monkeypatch):
    """``stats()["grouped_ffn"]``: by the engine's slots, as ``_moe_ffn``
    chooses for the decode program; None of a dense model."""
    from ray_tpu.models import LlamaConfig, llama_init
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    def form(cfg, params):
        geometry = EngineConfig(batch_slots=4, page_size=8,
                                max_prompt_len=16, max_new_tokens_cap=32)
        return InferenceEngine(cfg, params, geometry,
                               seed=0).stats()["grouped_ffn"]

    routed = _config("olmoe-top8-silu")
    params = moe_init(routed, jax.random.PRNGKey(0))
    assert form(routed, params) == "ragged_dot"
    monkeypatch.setattr(grouped_ffn, "on_tpu", lambda: True)
    assert form(routed, params) == "stream"
    dense = LlamaConfig.tiny(remat=False, dtype=jnp.float32)
    assert form(dense, llama_init(dense, jax.random.PRNGKey(0))) is None
