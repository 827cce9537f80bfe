"""Compile-only rehearsal, for a *described* TPU v5e, of what a TPU makes of
the GLM-4.7-Flash cell's decode program since the latent decode step walks
its live pages in a Pallas kernel (``ray_tpu/ops/latent_decode.py``), beside
``test_benchmark_chip_compile_glm4_moe_lite.py``, whose programs are what
THIS backend lowers (the gather form: ``jax.default_backend()`` is the CPU's
here).  The one thing a CPU cannot see is steered in the test
(``latent_decode.on_tpu``), as ``tests/test_chip_compile.py`` steers the
flash kernels.  Nothing executes, so nothing here is a measurement.  The
topology is described inside a fixture, never at import."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# Its fixtures too: the described topology and the cell's arguments on it.
from test_benchmark_chip_compile_glm4_moe_lite import (  # noqa: F401
    HBM_BYTES, _lower, _report, cell, v5e)


def _lowered(cell, monkeypatch, program, on_tpu):
    """``program`` lowered as a backend that answers ``on_tpu`` traces it:
    the decode step asks it whether to walk or to gather.  jit keeps a
    trace by its arguments, not by that answer, so its caches go first."""
    from ray_tpu.ops import latent_decode

    monkeypatch.setattr(latent_decode, "on_tpu", lambda: on_tpu)
    jax.clear_caches()
    try:
        return _lower(cell, program)
    finally:
        jax.clear_caches()


def _without_kernel_bodies(text):
    return re.sub(r'"body":"[^"]*"', '"body":""', text)


def test_the_decode_program_walks_the_pool_where_it_lies(cell, capsys,
                                                         monkeypatch):
    """One ``latent_decode`` custom call a layer; the donated pool, written
    by each layer's scatter and then read by that call, keeps its row-minor
    layout from argument to result with no copy of it; nothing of a gathered
    table's size (32 x 150 pages x 128 x 640, 786 MB) is left, so the
    temporaries fall from 0.87 GB to under 0.05; and it fits as before."""
    compiled = _lowered(cell, monkeypatch, "decode", True).compile()
    total = _report(capsys, "decode, walking", cell, compiled)
    assert 0.7 * 16e9 < total < HBM_BYTES - 1e9
    text = _without_kernel_bodies(compiled.as_text())
    layers = cell["model"]["num_hidden_layers"]
    calls = re.findall(r"^\s*%?(\S+) = \S+ custom-call\(", text, re.M)
    assert sum(c.startswith("latent_decode") for c in calls) == layers, calls
    # The grouped products keep their name: ``moe_decode_roofline.moe`` reads
    # them by it, and no kernel of ours answers to it.
    assert len(calls) - layers >= 3 * (layers - 1)
    assert "ragged-dot" not in " ".join(
        c for c in calls if c.startswith("latent_decode"))
    pool = "6,4801,128,640"
    assert f"bf16[{pool}]{{3,2,1,0" in text
    assert f"bf16[{pool}]{{2,3,1,0" not in text
    pool_shaped = re.findall(
        r"^\s*(?:ROOT\s+)?\S+ = \w+\[" + re.escape(pool) + r"\]\S* "
        r"([\w-]+)\(", text, re.M)
    assert "parameter" in pool_shaped  # the pattern still reads this HLO
    assert "copy" not in pool_shaped, pool_shaped
    for gathered in ("[32,150,128,640]", "[32,19200,640]", "[32,19200,512]",
                     "[32,19200,1,640]"):
        assert gathered not in text, gathered
    assert compiled.memory_analysis().temp_size_in_bytes < 0.05e9
    assert "attn_latent" in text and "moe_shared" in text


@pytest.mark.parametrize("program, lines", [
    ("paged_prefill", 2941), ("paged_prefill_prefix", 3346)],
    ids=["bucket-2048", "chunk-2048"])
def test_the_prefills_lower_as_they_did(cell, monkeypatch, program, lines):
    """Many query rows a slot: the cold prefill and the suffix prefill over
    cached pages lower to one text whatever the backend answers, with no
    ``latent_decode`` in it.  That text is the parent commit's (b64ede9:
    sha256 equal when this was written): its lines are counted here."""
    here = _lowered(cell, monkeypatch, program, False).as_text()
    there = _lowered(cell, monkeypatch, program, True).as_text()
    assert here == there
    assert "latent_decode" not in here
    assert len(here.splitlines()) == lines


def test_off_the_tpu_the_decode_program_lowers_as_it_did(cell, monkeypatch):
    """The decode program of a backend that is no TPU is the gather form,
    the kernel's reference: no ``latent_decode`` in it."""
    text = _lowered(cell, monkeypatch, "decode", False).as_text()
    assert "latent_decode" not in text
    assert len(text.splitlines()) == 3084  # the parent commit's text
    assert "tensor<32x150x128x640xbf16>" in text  # a gathered table


@pytest.mark.parametrize("page, dtype", [
    (128, jnp.bfloat16), (64, jnp.bfloat16), (16, jnp.bfloat16),
    (8, jnp.float32)], ids=["cell-128", "chip-smoke-64", "bf16-16", "f32-8"])
def test_the_kernel_compiles_at_the_pages_the_engines_use(v5e, page, dtype):
    """The benchmark's pages (128), ``chip_smoke.py``'s (64), and the least
    the kernel takes of either dtype; under its name."""
    from ray_tpu.ops import latent_decode_attention

    one = SingleDeviceSharding(v5e.devices[0])
    pages = 19200 // page

    def on(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    compiled = jax.jit(
        lambda q, kv, t, n: latent_decode_attention(
            q, kv, 3, t, n, rank=512, sm_scale=256 ** -0.5)).lower(
        on((32, 20, 640), dtype), on((6, 257, page, 640), dtype),
        on((32, pages), jnp.int32), on((32,), jnp.int32)).compile()
    text = _without_kernel_bodies(compiled.as_text())
    assert re.search(r"latent_decode\S* = \S+ custom-call\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 4e6
