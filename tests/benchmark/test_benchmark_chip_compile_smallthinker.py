"""Compile-only rehearsal of the SmallThinker cell for a *described* TPU
v5e, the sibling of ``test_benchmark_chip_compile_olmoe.py``: the decode
program, the largest one-bucket prefill and a 2048-token chunk of the suffix
program, at the cell's geometry (two kinds of pool, the window layers' ring
tables), have to fit one chip's 16 GB.  The bytes printed here are what
sized ``paged.SCORE_BLOCK_BYTES`` (a chunk's whole score matrix on a
whole-length layer would be 3.5 GB) and the configuration's depth.  Nothing
executes, so nothing here is a measurement.  The topology is described
inside a fixture, never at import.  Each whole-model program compiles in
about ten seconds here, so none is behind ``-m slow``."""

import dataclasses
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
CONFIG, TRAFFIC = "smallthinker-21b-a3b-L8", "serve-long-mixed"


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


def _cell(v5e, layers=None):
    """The cell's engine arguments as shapes on one described chip
    (``layers``: only the first that many, whole periods of the pattern)."""
    from ray_tpu.models import paged
    from ray_tpu.serve.engine import EngineConfig

    model = spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", CONFIG + ".json"))
    if layers:
        model = {**model, "num_hidden_layers": layers,
                 "rope_layout": model["rope_layout"][:layers],
                 "sliding_window_layout":
                     model["sliding_window_layout"][:layers]}
    tr = spec.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", TRAFFIC + ".json"))
    fam = spec.family(model)
    ec = EngineConfig(**tr["engine"])
    cfg = fam.program_config(model, remat=False,
                             max_seq=ec.pages_per_seq * ec.page_size)
    ring = paged.ring_entries(cfg, ec.page_size, ec.prefill_buckets()[-1])
    one = SingleDeviceSharding(v5e.devices[0])
    place = functools.partial(
        jax.tree.map, lambda x: _on(one, x.shape, x.dtype))
    return {
        "model": model, "ec": ec, "cfg": cfg, "ring": ring,
        "on": functools.partial(_on, one),
        "params": place(jax.eval_shape(
            lambda: fam.init(cfg, jax.random.PRNGKey(0)))),
        "pools": place(jax.eval_shape(lambda: paged.init_paged_pools(
            cfg, ec.pool_pages, ec.page_size, ec.batch_slots * ring))),
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
    b, ring = ec.batch_slots, cell["ring"]
    head = (cell["cfg"], cell["params"], cell["pools"], cell["adapters"])
    if program == "decode":
        return paged.paged_decode_step.lower(
            *head, on((b + paged.routing_width(cell["cfg"]),), i32),
            on((b, ec.pages_per_seq), i32), on((b,), i32), on((b,), bool),
            on((b,), jnp.float32), on((b,), i32), cell["key"],
            on((b, ring), i32))
    bucket = ec.prefill_buckets()[-1]
    assert bucket == 2048 == ec.prefill_chunk
    scalar, temp = on((), i32), on((), jnp.float32)
    toks, table = on((1, bucket), i32), on((ec.pages_per_seq,), i32)
    if program == "paged_prefill":
        return paged.paged_prefill.lower(
            *head, toks, scalar, table, scalar, temp, cell["key"],
            on((ring,), i32))
    return paged.paged_prefill_prefix.lower(
        *head, toks, scalar, scalar, table, scalar, temp, cell["key"],
        on((ring,), i32))


def test_the_pools_are_the_two_kinds_the_issue_reckoned(cell):
    """2 whole-length layers x 16 x 120 pages and 6 window layers x 16 x
    48 ring pages, 262,144 bytes a page: 2.21 GB, where one pool for every
    layer would be 4.03 GB."""
    ec, pools = cell["ec"], cell["pools"]
    assert (ec.pages_per_seq, cell["ring"]) == (120, 48)
    assert ec.prefill_buckets() == [128, 256, 512, 1024, 2048]
    page = 128 * 4 * 128 * 2  # one of K or V
    assert pools["k"].shape == (2, 16 * 120 + 1, 128, 4, 128)
    assert pools["kw"].shape == (6, 16 * 48 + 1, 128, 4, 128)
    held = sum(x.size * 2 for x in pools.values())
    assert held == 2 * page * (2 * (16 * 120 + 1) + 6 * (16 * 48 + 1))
    assert 2.20e9 < held < 2.23e9
    assert 8 * 16 * 120 * 2 * page == pytest.approx(4.03e9, rel=2e-3)
    weights = sum(x.size * 2 for x in jax.tree.leaves(cell["params"])
                  if x.dtype == jnp.bfloat16) \
        + sum(x.size * 4 for x in jax.tree.leaves(cell["params"])
              if x.dtype == jnp.float32)
    assert 7.9e9 < weights < 8.0e9
    # The fullest the device gets is above the contract's floor of 25%.
    assert (weights + held) / 16e9 > 0.6


def test_one_period_of_the_decode_step_gathers_no_wider_than_the_ring(
        v5e, capsys):
    """One period (a whole-length layer and three window layers) of the
    decode program: compiles for the chip, and the only gathers of the
    window layers' pools are ring-wide (48 pages), of the whole-length
    layer's table-wide (120)."""
    one = _cell(v5e, layers=4)
    compiled = _lower(one, "decode").compile()
    _report(capsys, "decode, one period", one, compiled)
    text = compiled.as_text()
    assert "attn_window" in text and "attn_global" in text
    ps, ec = 128, one["ec"]
    assert f"bf16[16,{48 * ps},4,128]" in text \
        or f"bf16[16,48,{ps},4,128]" in text
    assert f"bf16[16,{ec.pages_per_seq * ps},4,128]" in text \
        or f"bf16[16,{ec.pages_per_seq},{ps},4,128]" in text
    # No window layer's pool is gathered table-wide: the wide shape
    # appears once a whole-length layer (K and V), not four times.
    wide = text.count(f"bf16[16,{ec.pages_per_seq},{ps},4,128]") \
        + text.count(f"bf16[16,{ec.pages_per_seq * ps},4,128]")
    narrow = text.count(f"bf16[16,48,{ps},4,128]") \
        + text.count(f"bf16[16,{48 * ps},4,128]")
    assert narrow > wide > 0, (narrow, wide)


@pytest.mark.parametrize("program", ["decode", "paged_prefill",
                                     "paged_prefill_prefix"],
                         ids=["decode", "bucket-2048", "chunk-2048"])
def test_smallthinker_programs_fit_at_the_cells_geometry(cell, capsys,
                                                         program):
    compiled = _lower(cell, program).compile()
    total = _report(capsys, program, cell, compiled)
    # ~1 GB to spare (the allocator fragments; the check's reference holds
    # a 13000-token sequence's float32 activations beside all this).
    assert total < HBM_BYTES - 1e9
    assert total > 0.6 * 16e9  # and it is no toy
    if program == "decode":
        calls = compiled.as_text().count(
            "custom_call_target=\"tpu_custom_call\"")
        assert calls >= 3 * cell["model"]["num_hidden_layers"], calls
