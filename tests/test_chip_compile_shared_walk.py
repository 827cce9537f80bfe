"""Compile-only rehearsal, for a *described* TPU v5e, of the GLM-4.7-Flash
cell's decode program in the SHARED form (``paged_decode_step(...,
shared=True)``: a run of pages that several slots hold is fetched once for
all its holders, ``ops/latent_decode.py``), beside
``tests/benchmark/test_benchmark_chip_compile_latent_decode.py``, which holds
the default form to the text it had.  The one thing a CPU cannot see is
steered in the test (``latent_decode.on_tpu``).  Nothing executes, so nothing
here is a measurement.  The topology is described inside a fixture, never at
import."""

import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "benchmark"))
# Its fixtures too: the described topology and the cell's arguments on it.
from test_benchmark_chip_compile_glm4_moe_lite import (  # noqa: E402, F401
    HBM_BYTES, _report, cell, v5e)


def _without_kernel_bodies(text):
    return re.sub(r'"body":"[^"]*"', '"body":""', text)


def _custom_calls(text):
    """The names of the Mosaic kernels' calls (a tuple's type has spaces)."""
    return re.findall(r"^\s*%?(\S+) = [^=]*? custom-call\([^\n]*"
                      r"custom_call_target=\"tpu_custom_call\"", text, re.M)


@pytest.fixture(scope="module")
def lowered(cell):
    """The cell's decode step in the shared form, lowered as a TPU traces
    it.  jit keeps a trace by its arguments, not by what ``on_tpu``
    answered, so its caches go before and after."""
    from ray_tpu.models import paged
    from ray_tpu.ops import latent_decode

    ec, on, i32 = cell["ec"], cell["on"], jnp.int32
    b = ec.batch_slots
    was, latent_decode.on_tpu = latent_decode.on_tpu, lambda: True
    jax.clear_caches()
    try:
        assert paged.shares_walked_pages(cell["cfg"])
        return paged.paged_decode_step.lower(
            cell["cfg"], cell["params"], cell["pools"], cell["adapters"],
            on((b + paged.routing_width(cell["cfg"], True),), i32),
            on((b, ec.pages_per_seq), i32), on((b,), i32), on((b,), bool),
            on((b,), jnp.float32), on((b,), i32), cell["key"], shared=True)
    finally:
        latent_decode.on_tpu = was
        jax.clear_caches()


def test_the_shared_form_compiles_and_walks_the_pool_where_it_lies(
        cell, lowered, capsys):
    """It compiles for the chip and fits as the per-slot walk does; a layer
    is two custom calls, the pass over the shared runs and the slots' own
    tails, both under the name ``latent_decode_roofline.mla`` reads
    (``mosaic:latent_decode*``); the donated pool keeps its row-minor layout
    from argument to result with no copy of it; nothing of a gathered
    table's size appears, and the temporaries stay under 0.05 GB."""
    compiled = lowered.compile()
    total = _report(capsys, "decode, the shared form", cell, compiled)
    assert 0.7 * 16e9 < total < HBM_BYTES - 1e9
    text = _without_kernel_bodies(compiled.as_text())
    layers = cell["model"]["num_hidden_layers"]
    ours = [c for c in _custom_calls(text) if c.startswith("latent_decode")]
    assert sum(c.startswith("latent_decode_shared") for c in ours) == layers
    assert len(ours) == 2 * layers, ours
    # Every custom call under the attention's scope is one of the two.
    for line in text.splitlines():
        if "custom-call(" in line and "attn_latent" in line \
                and "custom_call_target=\"tpu_custom_call\"" in line:
            assert re.search(r"^\s*%?latent_decode", line), line
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


def test_the_shared_form_counts_what_it_saved(cell, lowered):
    """One more counter behind the tokens than the per-slot walk's."""
    from ray_tpu.models import paged

    assert lowered.out_info[0].shape == (cell["ec"].batch_slots
                         + paged.routing_width(cell["cfg"]) + 1,)
    assert paged.counter_keys(cell["cfg"], True)[-1] == "kv_rows_shared"


@pytest.mark.parametrize("page, dtype, slots", [
    (128, jnp.bfloat16, 32), (64, jnp.bfloat16, 32), (16, jnp.bfloat16, 7),
    (8, jnp.float32, 4)], ids=["cell-128", "chip-smoke-64", "bf16-16",
                               "f32-8"])
def test_the_two_kernels_compile_at_the_pages_the_engines_use(
        v5e, page, dtype, slots):
    """The benchmark's pages (128), ``chip_smoke.py``'s (64) and the least
    the kernel takes of either dtype, at slot counts whose stacked rows fill
    whole blocks and do not; each under its name, and what lies between
    them (the partials of every row, float32) under 8 MB."""
    from ray_tpu.ops import latent_decode

    one = SingleDeviceSharding(v5e.devices[0])
    pages = 19200 // page

    def on(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    def attend(q, kv, tables, lens, active):
        runs = latent_decode.shared_runs(tables, lens, active, page=page,
                                         heads=q.shape[1])
        return latent_decode.latent_decode_attention(
            q, kv, 3, tables, lens, rank=512, sm_scale=256 ** -0.5,
            runs=runs)

    compiled = jax.jit(attend).lower(
        on((slots, 20, 640), dtype), on((6, 257, page, 640), dtype),
        on((slots, pages), jnp.int32), on((slots,), jnp.int32),
        on((slots,), bool)).compile()
    calls = _custom_calls(_without_kernel_bodies(compiled.as_text()))
    assert sorted(c.split(".")[0] for c in calls) == [
        "latent_decode", "latent_decode_shared"], calls
    assert compiled.memory_analysis().temp_size_in_bytes < 8e6
