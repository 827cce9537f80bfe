"""Compile-only rehearsal of the OLMoE cell for a *described* TPU v5e, the
sibling of ``test_benchmark_chip_compile.py``: the decode program and the
1024-token prefill at the cell's geometry have to fit one chip's 16 GB
beside each other's arguments, and the bytes printed here are what decided
how many of the 16 published layers the configuration keeps.  Nothing
executes, so nothing here is a measurement.  The topology is described
inside a fixture, never at import."""

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
CONFIG, TRAFFIC = "olmoe-1b-7b-0125", "serve-saturated"


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


@pytest.fixture(scope="module")
def cell(v5e):
    """The OLMoE cell's engine arguments as shapes on one described chip."""
    from ray_tpu.models.paged import init_adapter_pool, init_paged_pools
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
        "model": model, "ec": ec, "cfg": cfg, "on": functools.partial(
            _on, one),
        "params": place(jax.eval_shape(
            lambda: fam.init(cfg, jax.random.PRNGKey(0)))),
        "pools": place(jax.eval_shape(
            lambda: init_paged_pools(cfg, ec.pool_pages, ec.page_size))),
        "adapters": place(jax.eval_shape(
            lambda: init_adapter_pool(cfg, ec.max_adapters, ec.lora_rank))),
        "key": place(jax.eval_shape(lambda: jax.random.PRNGKey(0))),
    }


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


def test_olmoe_decode_program_fits_at_the_cells_geometry(cell, capsys):
    from ray_tpu.models.paged import paged_decode_step, routing_width

    ec, on, i32 = cell["ec"], cell["on"], jnp.int32
    b = ec.batch_slots
    compiled = paged_decode_step.lower(
        cell["cfg"], cell["params"], cell["pools"], cell["adapters"],
        on((b + routing_width(cell["cfg"]),), i32),
        on((b, ec.pages_per_seq), i32), on((b,), i32), on((b,), bool),
        on((b,), jnp.float32), on((b,), i32), cell["key"]).compile()
    total = _report(capsys, f"decode, {b} slots, {ec.page_size}-token "
                    f"pages", cell, compiled)
    # ~1 GB to spare (the allocator fragments; the check's reference holds
    # one expert's float32 copy and its activations beside all this).
    assert total < HBM_BYTES - 1e9
    # And a cell this small would not stand for a deployment.
    assert total > 0.5 * 16e9
    # The grouped products are XLA's own kernel, three a layer (and one
    # call that lays out the groups), not a loop over the experts.
    calls = compiled.as_text().count("custom_call_target=\"tpu_custom_call\"")
    assert calls >= 3 * cell["model"]["num_hidden_layers"], calls


@pytest.mark.parametrize("program", ["paged_prefill", "paged_prefill_prefix"],
                         ids=["cold", "suffix"])
def test_olmoe_largest_prefill_fits_at_the_cells_geometry(cell, capsys,
                                                          program):
    from ray_tpu.models import paged

    ec, on, i32 = cell["ec"], cell["on"], jnp.int32
    bucket = ec.prefill_buckets()[-1]
    assert bucket == 1024
    scalar, temp = on((), i32), on((), jnp.float32)
    toks, table = on((1, bucket), i32), on((ec.pages_per_seq,), i32)
    head = (cell["cfg"], cell["params"], cell["pools"], cell["adapters"],
            toks)
    if program == "paged_prefill":
        lowered = paged.paged_prefill.lower(
            *head, scalar, table, scalar, temp, cell["key"])
    else:
        lowered = paged.paged_prefill_prefix.lower(
            *head, scalar, scalar, table, scalar, temp, cell["key"])
    total = _report(capsys, f"{program}, bucket {bucket}", cell,
                    lowered.compile())
    assert total < HBM_BYTES - 1e9
