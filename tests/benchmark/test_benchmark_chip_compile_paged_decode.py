"""Compile-only rehearsal, for a *described* TPU v5e, of what a TPU makes of
the decode programs of the two cells with window layers (Trinity-Mini's and
SmallThinker's) since their decode step walks its live K/V pages in a Pallas
kernel (``ray_tpu/ops/paged_decode.py``), beside
``test_benchmark_chip_compile_afmoe.py`` and ``..._smallthinker.py``, whose
programs are what THIS backend lowers (the gather form:
``jax.default_backend()`` is the CPU's here).  The one thing a CPU cannot
see is steered in the test (``paged_decode.on_tpu``), as
``test_benchmark_chip_compile_latent_decode.py`` steers the latent kernel's.
Nothing executes, so nothing here is a measurement.  The topology is
described inside a fixture, never at import."""

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

CELLS = {"trinity-mini": afmoe, "smallthinker": smallthinker}
#: A cell's decode program as the gather form holds it (the parent commit's
#: text, b2d4c11: sha256 equal when this was written): the lines of its
#: StableHLO, and the widest gathered table in it.
GATHER_FORM = {
    "trinity-mini": (3391, "tensor<32x56x128x4x128xbf16>"),
    "smallthinker": (3857, "tensor<16x120x128x4x128xbf16>")}
#: The configurations whose decode program never asks: one whole-length kind
#: of K/V pairs (llama's two, OLMoE) or a latent pool (its own kernel).
OTHERS = {"internlm2-1.8b": "serve-saturated",
          "mistral-7b-v0.3-L4": "serve-saturated",
          "olmoe-1b-7b-0125": "serve-saturated",
          "glm-4.7-flash-L6": "serve-agent-shared-context",
          "rehearsal-tiny": "serve-saturated"}


@pytest.fixture(scope="module", params=list(CELLS))
def cell(request, v5e):  # noqa: F811
    return request.param, CELLS[request.param]._cell(v5e)


def _steered(monkeypatch, on_tpu, lower):
    """``lower()`` as a backend that answers ``on_tpu`` traces it: the
    decode step asks whether to walk or to gather.  jit keeps a trace by its
    arguments, not by that answer, so its caches go first."""
    from ray_tpu.ops import paged_decode

    monkeypatch.setattr(paged_decode, "on_tpu", lambda: on_tpu)
    jax.clear_caches()
    try:
        return lower()
    finally:
        jax.clear_caches()


#: The kernel's name in a program's text (the program's own is
#: ``jit_paged_decode_step``).
KERNEL = re.compile(r"paged_decode(?!_step)")


def _without_kernel_bodies(text):
    return re.sub(r'"body":"[^"]*"', '"body":""', text)


def test_the_decode_program_walks_the_pools_where_they_lie(cell, capsys,
                                                           monkeypatch):
    """One ``paged_decode`` custom call a layer, whole-length or ring; the
    donated pools, written by each layer's scatters and then read by that
    call (as ``[page x H_kv, D]`` pages: a bitcast), keep their layout from
    argument to result with no copy of one; nothing of a gathered table's or
    ring's size is left, so the temporaries fall under 0.05 GB (0.28 and
    0.41 GB gathering); and it fits as before."""
    name, c = cell
    module = CELLS[name]
    compiled = _steered(monkeypatch, True,
                        lambda: module._lower(c, "decode")).compile()
    total = module._report(capsys, "decode, walking", c, compiled)
    assert 0.6 * 16e9 < total < HBM_BYTES - 3e9
    text = _without_kernel_bodies(compiled.as_text())
    layers = c["model"]["num_hidden_layers"]
    calls = re.findall(r"^\s*%?(\S+) = \S+ custom-call\(", text, re.M)
    assert sum(x.startswith("paged_decode") for x in calls) == layers, calls
    # The grouped products keep theirs: three a routed layer off the chip.
    assert sum(x.startswith("ragged-dot") for x in calls) >= 3 * (layers - 1)
    slots, ec = c["ec"].batch_slots, c["ec"]
    for name_, pool in c["pools"].items():
        shape = ",".join(map(str, pool.shape))
        assert f"bf16[{shape}]{{4,3,2,1,0" in text, name_
        made = re.findall(
            r"^\s*(?:ROOT\s+)?\S+ = \w+\[" + re.escape(shape) + r"\]\S* "
            r"([\w-]+)\(", text, re.M)
        assert "parameter" in made  # the pattern still reads this HLO
        assert "copy" not in made, (name_, made)
        # The kernel's view of it is the same bytes.
        paged = ",".join(map(str, (*pool.shape[:2], 128 * 4, 128)))
        viewed = re.findall(
            r"^\s*\S+ = \w+\[" + re.escape(paged) + r"\]\S* ([\w-]+)\(",
            text, re.M)
        assert viewed and set(viewed) == {"bitcast"}, (name_, viewed)
    for entries in (ec.pages_per_seq, c["ring"]):
        for gathered in (f"[{slots},{entries},128,4,128]",
                         f"[{slots},{entries * 128},4,128]"):
            assert gathered not in text, gathered
    assert compiled.memory_analysis().temp_size_in_bytes < 0.05e9
    assert "attn_window" in text and "attn_global" in text


def test_off_the_tpu_the_decode_program_lowers_as_it_did(cell, monkeypatch):
    """With the predicate steered false the cell's decode program is the
    gather form, the kernel's reference: the parent commit's text."""
    name, c = cell
    text = _steered(monkeypatch, False,
                    lambda: CELLS[name]._lower(c, "decode")).as_text()
    lines, table = GATHER_FORM[name]
    assert not KERNEL.search(text)
    assert len(text.splitlines()) == lines
    assert table in text


@pytest.mark.parametrize("program", ["paged_prefill", "paged_prefill_prefix"])
def test_the_prefills_lower_as_they_did(cell, monkeypatch, program):
    """Many query rows a slot: the cold prefill and the suffix / chunk
    prefill over cached pages lower to one text whatever the backend
    answers, with no ``paged_decode`` in it (S5(a) is not this kernel's
    yet)."""
    name, c = cell
    here, there = (
        _steered(monkeypatch, on_tpu,
                 lambda: CELLS[name]._lower(c, program)).as_text()
        for on_tpu in (False, True))
    assert here == there
    assert not KERNEL.search(here)


def _decode_text(config, traffic):
    """The StableHLO of ``config``'s decode program at ``traffic``'s engine
    geometry, lowered by this backend from shapes alone."""
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
    b, i32 = ec.batch_slots, jnp.int32
    shapes = jax.eval_shape(lambda: (
        fam.init(cfg, jax.random.PRNGKey(0)),
        paged.init_paged_pools(cfg, ec.pool_pages, ec.page_size),
        paged.init_adapter_pool(cfg, ec.max_adapters, ec.lora_rank),
        jax.random.PRNGKey(0)))
    on = jax.ShapeDtypeStruct
    return paged.paged_decode_step.lower(
        cfg, *shapes[:3], on((b + paged.routing_width(cfg),), i32),
        on((b, ec.pages_per_seq), i32), on((b,), i32), on((b,), bool),
        on((b,), jnp.float32), on((b,), i32), shapes[3]).as_text()


@pytest.mark.parametrize("config", OTHERS)
def test_a_configuration_without_window_layers_never_asks(monkeypatch,
                                                          config):
    """The five other configurations' decode programs lower to the same
    text whatever ``on_tpu`` answers for this kernel, with no call of it:
    the llama family's and OLMoE's keep the gather (and their cells their
    numbers: B11), GLM-4.7-Flash its own kernel's choice."""
    here, there = (
        _steered(monkeypatch, on_tpu,
                 lambda: _decode_text(config, OTHERS[config]))
        for on_tpu in (False, True))
    assert here == there
    assert not KERNEL.search(here)


@pytest.mark.parametrize("page, n_kv, heads, dtype", [
    (128, 4, 32, jnp.bfloat16), (128, 4, 28, jnp.bfloat16),
    (64, 4, 32, jnp.bfloat16), (128, 8, 16, jnp.bfloat16),
    (128, 16, 16, jnp.bfloat16), (16, 2, 16, jnp.bfloat16),
    (8, 2, 8, jnp.float32)],
    ids=["trinity-mini", "smallthinker", "chip-smoke-64", "internlm2-gqa-2",
         "olmoe-mha", "bf16-least", "f32-least"])
def test_the_kernel_compiles_at_the_geometries_the_engines_use(
        v5e, page, n_kv, heads, dtype):  # noqa: F811
    """The two cells' geometries, ``chip_smoke.py``'s pages, the two dense
    families' heads (the one predicate that widens the walk to them is
    ROADMAP S4b's), and the least page the kernel takes of either dtype;
    under its name, with nothing of a table's size beside it."""
    from ray_tpu.ops import paged_decode_attention

    one = SingleDeviceSharding(v5e.devices[0])
    entries = 4096 // page

    def on(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    pool = on((2, 8 * entries + 1, page, n_kv, 128), dtype)
    compiled = jax.jit(
        lambda q, k, v, t, lo, hi: paged_decode_attention(
            q, k, v, 1, t, lo, hi, sm_scale=128 ** -0.5)).lower(
        on((8, heads, 128), dtype), pool, pool, on((8, entries), jnp.int32),
        on((8,), jnp.int32), on((8,), jnp.int32)).compile()
    text = _without_kernel_bodies(compiled.as_text())
    assert re.search(r"paged_decode\S* = \S+ custom-call\(", text)
    shape = ",".join(map(str, pool.shape))
    assert f"[{shape}]" in text
    assert not re.search(re.escape(f"[{shape}]") + r"\S* copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1e6
