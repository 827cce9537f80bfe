"""Compile-only rehearsal, for a *described* TPU v5e, of what a TPU makes of
the suffix prefill programs of the two cells whose cache is a latent pool
(GLM-4.7-Flash's and Kimi-Linear's) since a prefill call's absorbed query
rows walk the live pages in a Pallas kernel
(``ray_tpu/ops/latent_prefill.py``), beside
``test_benchmark_chip_compile_paged_prefill.py``, which does the same for the
K/V-pair kernel.  The one thing a CPU cannot see is steered in the test
(``latent_decode.on_tpu``, which ``paged._walks_live_pages`` asks of a latent
model).  Nothing executes, so nothing here is a measurement.  The topology is
described inside a fixture, never at import."""

import functools
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench_testlib import ROOT
# The described topology (a fixture of this file too).
from test_benchmark_chip_compile_afmoe import HBM_BYTES, v5e  # noqa: F401

from benchmarks import spec

#: cell -> (configuration, traffic, latent layers, query heads, table pages).
CELLS = {"glm": ("glm-4.7-flash-L6", "serve-agent-shared-context", 6, 20, 150),
         "kimi-linear": ("kimi-linear-48b-a3b-L13",
                         "serve-long-decode-doc-tail", 3, 32, 88)}


@pytest.fixture(scope="module", params=list(CELLS))
def cell(request, v5e):  # noqa: F811
    """The cell's engine arguments as shapes on one described chip."""
    from ray_tpu.models import paged
    from ray_tpu.serve.engine import EngineConfig

    config, traffic = CELLS[request.param][:2]
    model = spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", config + ".json"))
    tr = spec.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", traffic + ".json"))
    fam, ec = spec.family(model), EngineConfig(**tr["engine"])
    cfg = fam.program_config(model, remat=False,
                             max_seq=ec.pages_per_seq * ec.page_size)
    one = SingleDeviceSharding(v5e.devices[0])

    def on(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    place = functools.partial(jax.tree.map, lambda x: on(x.shape, x.dtype))
    state = ec.batch_slots if paged.state_layers(cfg) else 0
    return {
        "name": request.param, "model": model, "ec": ec, "cfg": cfg,
        "on": on, "state": state,
        "params": place(jax.eval_shape(
            lambda: fam.init(cfg, jax.random.PRNGKey(0)))),
        "pools": place(jax.eval_shape(lambda: paged.init_paged_pools(
            cfg, ec.pool_pages, ec.page_size, state_slots=state))),
        "adapters": place(jax.eval_shape(lambda: paged.init_adapter_pool(
            cfg, ec.max_adapters, ec.lora_rank))),
        "key": place(jax.eval_shape(lambda: jax.random.PRNGKey(0))),
    }


def _lower(cell, on_tpu, monkeypatch, bucket=None):
    """The suffix program at ``bucket`` rows (the chunk's 2048 unless
    told), lowered as a backend that answers ``on_tpu`` traces it.  jit
    keeps a trace by its arguments, not by that answer, so its caches go
    first."""
    from ray_tpu.models import paged
    from ray_tpu.ops import latent_decode

    ec, on, i32 = cell["ec"], cell["on"], jnp.int32
    bucket = bucket or ec.prefill_buckets()[-1]
    scalar = on((), i32)
    monkeypatch.setattr(latent_decode, "on_tpu", lambda: on_tpu)
    jax.clear_caches()
    try:
        return paged.paged_prefill_prefix.lower(
            cell["cfg"], cell["params"], cell["pools"], cell["adapters"],
            on((1, bucket), i32), scalar, scalar, on((ec.pages_per_seq,), i32),
            scalar, on((), jnp.float32), cell["key"], None,
            scalar if cell["state"] else None)
    finally:
        jax.clear_caches()


def _without_kernel_bodies(text):
    return re.sub(r'"body":"[^"]*"', '"body":""', text)


def test_the_suffix_program_walks_and_forms_no_score_matrix(cell, capsys,
                                                            monkeypatch):
    """One ``latent_prefill`` custom call a latent layer in the suffix
    program at its 2048-row bucket (which a prompt's first rows take too
    where it walks: the engine's rule); nothing of the gathered table's
    shape (``[1, 19200, 640]`` in GLM's cell, ``[1, 11264, 640]`` in
    Kimi-Linear's) nor of the score matrix's (``[heads, rows, 19200]`` in
    float32, blocked or not) anywhere; the pool read where it lies, in one
    row-minor layout with no copy of it; ``attn_latent_prefill`` still in
    the text (two benchmark tests read it); and the program fits, GLM's with
    0.14 GB of temporaries where the gather form's chunk set aside 0.61."""
    _, _, layers, heads, pages = CELLS[cell["name"]]
    ec = cell["ec"]
    assert ec.pages_per_seq == pages and cell["cfg"].n_heads == heads
    lowered = _lower(cell, True, monkeypatch)
    # The latent layers share ONE trace and lowering of the kernel (its call
    # is jitted on its own, the layer is data): a function in the program's
    # text, called once a latent layer (PERF.md, PR 44: what a kernel a
    # layer costs every start).
    text = lowered.as_text()
    assert len(re.findall(r"func\.func private @_call\w*\(", text)) == 1
    assert len(re.findall(r"call @_call\w*\(", text)) == layers
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    total = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    with capsys.disabled():
        print(f"\n{cell['model']['name']} paged_prefill_prefix, walking: "
              f"arguments {ma.argument_size_in_bytes / 1e9:.2f} GB + "
              f"temporaries {ma.temp_size_in_bytes / 1e9:.2f} GB of "
              f"{HBM_BYTES / 1e9:.2f} (compiled for a described v5e; not a "
              f"measurement)")
    assert 0.6 * 16e9 < total < HBM_BYTES - 1e9
    text = _without_kernel_bodies(compiled.as_text())
    calls = re.findall(r"^\s*%?(\S+) = \S+ custom-call\(", text, re.M)
    assert sum(c.startswith("latent_prefill") for c in calls) == layers, calls
    assert not any(c.startswith(("latent_decode", "paged_prefill",
                                 "paged_decode")) for c in calls)
    keys = pages * 128
    for gathered in (f"[1,{keys},640]", f"[1,{pages},128,640]",
                     f"[1,{keys},512]", f"[1,{keys},1,640]"):
        assert gathered not in text, gathered
    assert not re.search(r"f32\[[\d,]*," + str(keys) + r"\]", text)
    assert not re.search(r"f32\[[\d,]*" + str(keys) + r",2048\]", text)
    pool = ",".join(map(str, cell["pools"]["kv"].shape))
    assert f"bf16[{pool}]{{3,2,1,0" in text
    assert f"bf16[{pool}]{{2,3,1,0" not in text
    made = re.findall(
        r"^\s*(?:ROOT\s+)?\S+ = \w+\[" + re.escape(pool) + r"\]\S* "
        r"([\w-]+)\(", text, re.M)
    assert "parameter" in made  # the pattern still reads this HLO
    assert "copy" not in made, made
    assert "attn_latent_prefill" in text
    if cell["name"] == "glm":
        assert ma.temp_size_in_bytes < 0.2e9
    else:  # the KDA layers' chunk form is as it was
        assert "attn_kda_chunk" in text


def test_off_the_tpu_the_suffix_program_keeps_the_gather(cell, monkeypatch):
    """With the predicate steered false the suffix program is the gather
    form, the kernel's reference: no kernel's call, the table gathered
    whole and the scores in float32 blocks of its width."""
    _, _, _, heads, pages = CELLS[cell["name"]]
    text = _lower(cell, False, monkeypatch).as_text()
    assert "tpu_custom_call" not in text
    assert f"tensor<1x{pages}x128x640xbf16>" in text
    assert re.search(r"x\d+x" + str(pages * 128) + r"xf32>", text)


@pytest.mark.parametrize("bucket", [128, 1024])
def test_every_bucket_holds_the_one_function(cell, monkeypatch, bucket):
    """The least bucket and the median suffix's: the same one function a
    program, whatever the rows (lowered only: Mosaic accepts the kernel's
    text at that block of rows)."""
    text = _lower(cell, True, monkeypatch, bucket).as_text()
    assert len(re.findall(r"func\.func private @_call\w*\(", text)) == 1
    assert len(re.findall(r"call @_call\w*\(", text)) \
        == CELLS[cell["name"]][2]
    assert text.count("tpu_custom_call") >= 1


@pytest.mark.parametrize("rows, heads, pages, page, dtype", [
    (2048, 20, 150, 128, jnp.bfloat16), (2048, 32, 88, 128, jnp.bfloat16),
    (128, 20, 150, 128, jnp.bfloat16), (128, 32, 88, 128, jnp.bfloat16),
    (512, 20, 64, 64, jnp.bfloat16), (16, 4, 32, 16, jnp.bfloat16),
    (8, 4, 32, 8, jnp.float32)],
    ids=["glm-chunk", "kimi-linear-chunk", "glm-least-bucket",
         "kimi-linear-least-bucket", "chip-smoke-64", "bf16-least",
         "f32-least"])
def test_the_kernel_compiles_at_the_geometries_the_engines_use(
        v5e, rows, heads, pages, page, dtype):  # noqa: F811
    """Both cells' geometries at their chunk and at their least bucket,
    ``chip_smoke.py``'s pages, and the least the kernel takes of either
    dtype; under its name, with nothing of a table's size beside it."""
    from ray_tpu.ops import latent_prefill_attention

    one = SingleDeviceSharding(v5e.devices[0])

    def on(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    pool = on((3, 2 * pages + 1, page, 640), dtype)
    compiled = jax.jit(
        lambda q, kv, t, first, length: latent_prefill_attention(
            q, kv, 1, t, first, length, rank=512,
            sm_scale=256 ** -0.5)).lower(
        on((rows, heads, 640), dtype), pool, on((pages,), jnp.int32),
        on((), jnp.int32), on((), jnp.int32)).compile()
    text = _without_kernel_bodies(compiled.as_text())
    assert re.search(r"latent_prefill\S* = \S+ custom-call\(", text)
    shape = ",".join(map(str, pool.shape))
    assert f"[{shape}]" in text
    assert not re.search(re.escape(f"[{shape}]") + r"\S* copy\(", text)
    # The queries and the output, head-major for the kernel: nothing wider.
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= rows * heads * (640 + 512) * jnp.dtype(dtype).itemsize + 4096
