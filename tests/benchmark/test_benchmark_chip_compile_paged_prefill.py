"""Compile-only rehearsal, for a *described* TPU v5e, of what a TPU makes of
the prefill programs of the two cells with window layers (SmallThinker's and
Trinity-Mini's) since a prefill call's query rows walk the live K/V pages in
a Pallas kernel (``ray_tpu/ops/paged_prefill.py``), beside
``test_benchmark_chip_compile_paged_decode.py``, which does the same for the
decode step's kernel.  The one thing a CPU cannot see is steered in the test
(``paged_decode.on_tpu``, which ``paged._walks_live_pages`` asks).  Nothing
executes, so nothing here is a measurement.  The topology is described
inside a fixture, never at import."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import test_benchmark_chip_compile_afmoe as afmoe
import test_benchmark_chip_compile_smallthinker as smallthinker
from bench_testlib import ROOT
# The described topology (a fixture of this file too).
from test_benchmark_chip_compile_afmoe import HBM_BYTES, v5e  # noqa: F401

from benchmarks import spec

CELLS = {"smallthinker": smallthinker, "trinity-mini": afmoe}
#: The configurations whose prefills never ask: one whole-length kind of K/V
#: pairs (llama's, OLMoE) or a latent pool (GLM-4.7-Flash, Kimi-Linear).
OTHERS = {"internlm2-1.8b": "serve-saturated",
          "olmoe-1b-7b-0125": "serve-saturated",
          "glm-4.7-flash-L6": "serve-agent-shared-context",
          "kimi-linear-48b-a3b-L13": "serve-long-decode-doc-tail"}


@pytest.fixture(scope="module", params=list(CELLS))
def cell(request, v5e):  # noqa: F811
    return request.param, CELLS[request.param]._cell(v5e)


def _steered(monkeypatch, on_tpu, lower):
    """``lower()`` as a backend that answers ``on_tpu`` traces it.  jit
    keeps a trace by its arguments, not by that answer, so its caches go
    first."""
    from ray_tpu.ops import latent_decode, paged_decode

    monkeypatch.setattr(paged_decode, "on_tpu", lambda: on_tpu)
    monkeypatch.setattr(latent_decode, "on_tpu", lambda: on_tpu)
    jax.clear_caches()
    try:
        return lower()
    finally:
        jax.clear_caches()


def _without_kernel_bodies(text):
    return re.sub(r'"body":"[^"]*"', '"body":""', text)


def _calls(text):
    return re.findall(r"^\s*%?(\S+) = \S+ custom-call\(", text, re.M)


def test_the_suffix_program_walks_and_forms_no_score_matrix(
        cell, capsys, monkeypatch, program="paged_prefill_prefix"):
    """One ``paged_prefill`` custom call a layer, whole-length or ring, in
    the suffix program at its 2048-row bucket (which a prompt's first rows
    take too where it walks: the engine's rule); no result of the scores'
    shape (28 or 32 heads x 2048 rows x the table's or the ring's keys, or
    the bucket's own 2048) in float32 anywhere, grouped or not; the pools
    are read where they lie (the kernel's view a bitcast, no copy of one);
    and the program fits with less set aside than the gather form's 512 MB
    score blocks."""
    name, c = cell
    module = CELLS[name]
    lowered = _steered(monkeypatch, True, lambda: module._lower(c, program))
    # The layers of a kind share ONE trace and lowering of the kernel (its
    # call is jitted on its own, the layer is data): a function a kind in
    # the program's text, called once a layer.  Eighty kernels of their own
    # cost SmallThinker's cell 39 s of every start (PERF.md, PR 44).
    text = lowered.as_text()
    assert len(re.findall(r"func\.func private @_call\w*\(", text)) == 2
    assert len(re.findall(r"call @_call\w*\(", text)) \
        == c["model"]["num_hidden_layers"]
    compiled = lowered.compile()
    total = module._report(capsys, program + ", walking", c, compiled)
    assert 0.6 * 16e9 < total < HBM_BYTES - 1e9
    text = _without_kernel_bodies(compiled.as_text())
    layers = c["model"]["num_hidden_layers"]
    calls = _calls(text)
    assert sum(x.startswith("paged_prefill") for x in calls) == layers, calls
    assert not any(x.startswith(("paged_decode", "latent_decode"))
                   for x in calls)
    ec = c["ec"]
    keys = {ec.pages_per_seq * 128, c["ring"] * 128, 2048}
    assert keys >= {15360, 6144} or keys >= {7168, 4096}
    for k in keys:
        # A head (or group) axis before the rows: [2048, 4096] alone is
        # Trinity-Mini's dense layer at its 2048 rows.
        assert not re.search(r"f32\[(\d+,)+2048," + str(k) + r"\]", text), k
        assert not re.search(r"f32\[(\d+,)+" + str(k) + r",2048\]", text), k
    for name_, pool in c["pools"].items():
        shape = ",".join(map(str, pool.shape))
        made = re.findall(
            r"^\s*(?:ROOT\s+)?\S+ = \w+\[" + re.escape(shape) + r"\]\S* "
            r"([\w-]+)\(", text, re.M)
        assert "parameter" in made  # the pattern still reads this HLO
        assert "copy" not in made, (name_, made)
        paged = ",".join(map(str, (*pool.shape[:2], 128 * 4, 128)))
        viewed = re.findall(
            r"^\s*\S+ = \w+\[" + re.escape(paged) + r"\]\S* ([\w-]+)\(",
            text, re.M)
        assert viewed and set(viewed) == {"bitcast"}, (name_, viewed)
    # No gathered table or ring either.
    for entries in (ec.pages_per_seq, c["ring"]):
        for gathered in (f"[1,{entries},128,4,128]",
                         f"[1,{entries * 128},4,128]"):
            assert gathered not in text, gathered
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
    assert "attn_window" in text and "attn_global" in text


def test_the_cold_program_is_not_this_kernels(cell, monkeypatch):
    """``paged_prefill`` lowers to one text whatever the backend answers,
    with no kernel in it: where the suffix program walks the engine sends a
    prompt's first rows through that (``prefix_len`` 0), so the cold
    program, its dense scores and its set-up seconds are never paid there;
    everywhere else it is what it was."""
    name, c = cell
    here, there = (
        _steered(monkeypatch, on_tpu,
                 lambda: CELLS[name]._lower(c, "paged_prefill")).as_text()
        for on_tpu in (False, True))
    assert here == there
    assert "tpu_custom_call" not in here


def test_off_the_tpu_the_suffix_program_keeps_the_gather(cell, monkeypatch):
    """With the predicate steered false the suffix program is the gather
    form, the kernel's reference: no call of the kernel, and the scores in
    float32 blocks of the whole table's width."""
    name, c = cell
    text = _steered(
        monkeypatch, False,
        lambda: CELLS[name]._lower(c, "paged_prefill_prefix")).as_text()
    assert "tpu_custom_call" not in text  # the kernel's, or any other's
    keys = c["ec"].pages_per_seq * 128
    assert re.search(r"x\d+x" + str(keys) + r"xf32>", text)


def _suffix_text(config, traffic):
    """The StableHLO of ``config``'s suffix program at ``traffic``'s engine
    geometry and largest bucket, lowered by this backend from shapes
    alone."""
    from ray_tpu.models import paged
    from ray_tpu.serve.engine import EngineConfig

    model = spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", config + ".json"))
    tr = spec.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", traffic + ".json"))
    fam, ec = spec.family(model), EngineConfig(**tr["engine"])
    cfg = fam.program_config(model, remat=False,
                             max_seq=ec.pages_per_seq * ec.page_size)
    assert not paged.kv_layers(cfg)[1]
    i32, on = jnp.int32, jax.ShapeDtypeStruct
    state = ec.batch_slots if paged.state_layers(cfg) else 0
    shapes = jax.eval_shape(lambda: (
        fam.init(cfg, jax.random.PRNGKey(0)),
        paged.init_paged_pools(cfg, ec.pool_pages, ec.page_size,
                               state_slots=state),
        paged.init_adapter_pool(cfg, ec.max_adapters, ec.lora_rank),
        jax.random.PRNGKey(0)))
    bucket = ec.prefill_buckets()[-1]
    scalar = on((), i32)
    return paged.paged_prefill_prefix.lower(
        cfg, *shapes[:3], on((1, bucket), i32), scalar, scalar,
        on((ec.pages_per_seq,), i32), scalar, on((), jnp.float32),
        shapes[3], None, scalar if state else None).as_text()


@pytest.mark.parametrize("config", OTHERS)
def test_a_configuration_without_window_layers_never_asks(monkeypatch,
                                                          config):
    """The llama family's, OLMoE's, GLM-4.7-Flash's and Kimi-Linear's
    suffix programs lower to the same text whatever ``on_tpu`` answers for
    either decode kernel, with no call of this one: one whole-length kind
    keeps the gather (and the capped dense cells their numbers: B11), a
    latent model its own ``_latent_attend``."""
    here, there = (
        _steered(monkeypatch, on_tpu,
                 lambda: _suffix_text(config, OTHERS[config]))
        for on_tpu in (False, True))
    assert here == there
    assert "paged_prefill\"" not in here and "tpu_custom_call" not in here


@pytest.mark.parametrize("rows, page, n_kv, heads, window, dtype", [
    (2048, 128, 4, 28, 0, jnp.bfloat16), (2048, 128, 4, 28, 4096, jnp.bfloat16),
    (2048, 128, 4, 32, 2048, jnp.bfloat16), (128, 128, 4, 28, 4096, jnp.bfloat16),
    (512, 64, 4, 32, 0, jnp.bfloat16), (2048, 128, 8, 16, 0, jnp.bfloat16),
    (1024, 128, 16, 16, 0, jnp.bfloat16), (16, 16, 2, 16, 0, jnp.bfloat16),
    (8, 8, 2, 8, 0, jnp.float32)],
    ids=["smallthinker-whole", "smallthinker-ring", "trinity-mini-ring",
         "the-least-bucket", "chip-smoke-64", "internlm2-gqa-2", "olmoe-mha",
         "bf16-least", "f32-least"])
def test_the_kernel_compiles_at_the_geometries_the_engines_use(
        v5e, rows, page, n_kv, heads, window, dtype):  # noqa: F811
    """The two cells' geometries at their chunk and at their least bucket,
    ``chip_smoke.py``'s pages, the two dense families' heads (the one
    predicate that widens the walk to them is ROADMAP S4b's), and the least
    the kernel takes of either dtype; under its name, with nothing of a
    table's size beside it."""
    from ray_tpu.ops import paged_prefill_attention

    one = SingleDeviceSharding(v5e.devices[0])
    entries = (window or 4096) // page + -(-rows // page)

    def on(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    pool = on((2, 8 * entries + 1, page, n_kv, 128), dtype)
    compiled = jax.jit(
        lambda q, k, v, t, first, length: paged_prefill_attention(
            q, k, v, 1, t, first, length, window=window,
            sm_scale=128 ** -0.5)).lower(
        on((rows, heads, 128), dtype), pool, pool, on((entries,), jnp.int32),
        on((), jnp.int32), on((), jnp.int32)).compile()
    text = _without_kernel_bodies(compiled.as_text())
    assert re.search(r"paged_prefill\S* = \S+ custom-call\(", text)
    shape = ",".join(map(str, pool.shape))
    assert f"[{shape}]" in text
    assert not re.search(re.escape(f"[{shape}]") + r"\S* copy\(", text)
    # The queries and the output, head-major for the kernel: nothing wider.
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= 2 * rows * heads * 128 * jnp.dtype(dtype).itemsize + 4096
