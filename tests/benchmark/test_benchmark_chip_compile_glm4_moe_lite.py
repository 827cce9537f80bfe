"""Compile-only rehearsal of the GLM-4.7-Flash cell for a *described* TPU
v5e, the sibling of ``test_benchmark_chip_compile_smallthinker.py``: the
decode program (absorbed attention over the latent pool), the largest
one-bucket prefill (a cold chunk, expanded within itself) and a 2048-token
chunk of the suffix program over a 150-page table, at the cell's geometry
(32 slots, one latent pool), have to fit one chip's 16 GB beside 7.79 GB of
weights, with the pool in ONE layout from argument to result (rows of 576
got a page-minor layout and a copy of the whole pool around every layer's
write: ``paged.latent_row_width``).  The bytes printed here are what the traffic file's ``engine_why``
quotes.  Nothing executes, so nothing here is a measurement.  The topology
is described inside a fixture, never at import.  Each whole-model program
compiles in well under a minute here, so none is behind ``-m slow``."""

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench_testlib import ROOT

from benchmarks import spec

#: bytes_limit of one v5e chip, as memory_stats() gave it (PR 21).
HBM_BYTES = 16909336064
CONFIG, TRAFFIC = "glm-4.7-flash-L6", "serve-agent-shared-context"


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or it cannot describe
        pytest.skip(f"cannot describe a TPU v5e here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _on(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _cell(v5e):
    """The cell's engine arguments as shapes on one described chip."""
    from ray_tpu.models import paged
    from ray_tpu.serve.engine import EngineConfig

    model = spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", CONFIG + ".json"))
    tr = spec.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", TRAFFIC + ".json"))
    fam = spec.family(model)
    ec = EngineConfig(**tr["engine"])
    cfg = fam.program_config(model, remat=False,
                             max_seq=ec.pages_per_seq * ec.page_size)
    one = SingleDeviceSharding(v5e.devices[0])
    place = functools.partial(
        jax.tree.map, lambda x: _on(one, x.shape, x.dtype))
    return {
        "model": model, "ec": ec, "cfg": cfg,
        "on": functools.partial(_on, one),
        "params": place(jax.eval_shape(
            lambda: fam.init(cfg, jax.random.PRNGKey(0)))),
        "pools": place(jax.eval_shape(lambda: paged.init_paged_pools(
            cfg, ec.pool_pages, ec.page_size))),
        "adapters": place(jax.eval_shape(lambda: paged.init_adapter_pool(
            cfg, ec.max_adapters, ec.lora_rank))),
        "key": place(jax.eval_shape(lambda: jax.random.PRNGKey(0))),
    }


@pytest.fixture(scope="module")
def cell(v5e):
    return _cell(v5e)


def _report(capsys, what, cell, compiled):
    ma = compiled.memory_analysis()
    total = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    with capsys.disabled():
        print(f"\n{CONFIG} ({cell['model']['num_hidden_layers']} layers) "
              f"{what}: arguments {ma.argument_size_in_bytes / 1e9:.2f} GB "
              f"+ temporaries {ma.temp_size_in_bytes / 1e9:.2f} GB = "
              f"{total / 1e9:.2f} GB of {HBM_BYTES / 1e9:.2f} (compiled for "
              f"a described v5e; not a measurement)")
    return total



def _lower(cell, program):
    from ray_tpu.models import paged

    ec, on, i32 = cell["ec"], cell["on"], jnp.int32
    b = ec.batch_slots
    head = (cell["cfg"], cell["params"], cell["pools"], cell["adapters"])
    if program == "decode":
        return paged.paged_decode_step.lower(
            *head, on((b + paged.routing_width(cell["cfg"]),), i32),
            on((b, ec.pages_per_seq), i32), on((b,), i32), on((b,), bool),
            on((b,), jnp.float32), on((b,), i32), cell["key"])
    bucket = ec.prefill_buckets()[-1]
    assert bucket == 2048 == ec.prefill_chunk
    scalar, temp = on((), i32), on((), jnp.float32)
    toks, table = on((1, bucket), i32), on((ec.pages_per_seq,), i32)
    if program == "paged_prefill":
        return paged.paged_prefill.lower(
            *head, toks, scalar, table, scalar, temp, cell["key"])
    return paged.paged_prefill_prefix.lower(
        *head, toks, scalar, scalar, table, scalar, temp, cell["key"])


def test_the_pool_is_the_one_latent_kind_the_issue_reckoned(cell):
    """6 layers x (32 x 150 + 1) pages x 128 rows of 640 (a token's 576
    numbers in five whole lane tiles) in bf16: 4.72 GB, 4.25 of it the
    tokens' own bytes, where expanded K and V of 20 heads would be 75 GB;
    beside 3,895,625,536 parameters."""
    ec, pools, model = cell["ec"], cell["pools"], cell["model"]
    assert ec.pages_per_seq == 150 and ec.pool_pages == 4800
    assert ec.prefill_buckets() == [128, 256, 512, 1024, 2048]
    assert set(pools) == {"kv"}
    assert pools["kv"].shape == (6, 4801, 128, 640)
    held = pools["kv"].size * 2
    assert held == 6 * 4801 * 128 * 640 * 2
    assert 4.71e9 < held < 4.73e9
    row = spec.family(model).latent_row_bytes(model)
    assert row == 1152 and 4.24e9 < held * 576 / 640 < 4.26e9
    expanded = 20 * (192 + 64 + 256) * 2
    assert 6 * 4800 * 128 * expanded == pytest.approx(75.5e9, rel=1e-2)
    leaves = jax.tree.leaves(cell["params"])
    assert sum(x.size for x in leaves) == 3895625536 \
        == spec.family(model).param_count(model) \
        == cell["cfg"].param_count()
    weights = sum(x.size * x.dtype.itemsize for x in leaves)
    assert 7.79e9 < weights < 7.80e9
    # The fullest the device gets is above the contract's floor of 25%.
    assert (weights + held) / 16e9 > 0.7


@pytest.mark.parametrize("program", ["decode", "paged_prefill",
                                     "paged_prefill_prefix"],
                         ids=["decode", "bucket-2048", "chunk-2048"])
def test_glm4_moe_lite_programs_fit_at_the_cells_geometry(cell, capsys,
                                                          program):
    compiled = _lower(cell, program).compile()
    total = _report(capsys, program, cell, compiled)
    # Room to spare: the allocator fragments, and the check's reference
    # holds a 17016-token sequence's float32 activations beside all this.
    assert total < HBM_BYTES - 1e9
    assert total > 0.7 * 16e9  # and it is no toy
    text = compiled.as_text()
    # The donated pool keeps its row-minor layout: no copy of it anywhere.
    assert "bf16[6,4801,128,640]{3,2,1,0" in text
    assert "bf16[6,4801,128,640]{2,3,1,0" not in text
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 1.0e9, ma.temp_size_in_bytes
    if program == "decode":
        assert "attn_latent" in text and "moe_shared" in text
        calls = text.count("custom_call_target=\"tpu_custom_call\"")
        # Three grouped products a ROUTED layer; the dense layer has none.
        assert calls >= 3 * (cell["model"]["num_hidden_layers"] - 1), calls
    else:
        assert "attn_latent_prefill" in text
