"""Compile-only rehearsal of the Trinity-Mini cell for a *described* TPU
v5e, the sibling of ``test_benchmark_chip_compile_smallthinker.py``: the
decode program, the largest one-bucket prefill and a 2048-token chunk of the
suffix program, at the cell's geometry (one whole-length table beside four
rings, 32 slots of 7168 positions), have to fit one chip's 16 GB with the
gate, the four norms and the shared expert in them.  Nothing executes, so
nothing here is a measurement.  The topology is described inside a fixture,
never at import.  Each whole-model program compiles in about ten seconds
here, so none is behind ``-m slow``."""

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
CONFIG, TRAFFIC = "trinity-mini-L5", "serve-reasoning-long-decode"


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
    (``layers``: only the first that many)."""
    from ray_tpu.models import paged
    from ray_tpu.serve.engine import EngineConfig

    model = spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", CONFIG + ".json"))
    if layers:
        model = {**model, "num_hidden_layers": layers,
                 "layer_types": model["layer_types"][:layers]}
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
    """One whole-length layer x 32 x 56 pages and four window layers x 32 x
    32 ring pages (window 2048 + chunk 2048), 262,144 bytes a page of K or
    V: 1.54 GB, where one pool in which every layer kept every page would
    be 2.35 GB."""
    ec, pools = cell["ec"], cell["pools"]
    assert (ec.pages_per_seq, cell["ring"]) == (56, 32)
    assert ec.prefill_buckets() == [128, 256, 512, 1024, 2048]
    page = 128 * 4 * 128 * 2  # one of K or V
    assert pools["k"].shape == (1, 32 * 56 + 1, 128, 4, 128)
    assert pools["kw"].shape == (4, 32 * 32 + 1, 128, 4, 128)
    held = sum(x.size * 2 for x in pools.values())
    assert held == 2 * page * ((32 * 56 + 1) + 4 * (32 * 32 + 1))
    assert 1.53e9 < held < 1.56e9
    assert 5 * 32 * 56 * 2 * page == pytest.approx(2.35e9, rel=2e-3)
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree.leaves(cell["params"]))
    assert 8.48e9 < weights < 8.49e9
    # The fullest the device gets is above the contract's floor of 25%.
    assert (weights + held) / 16e9 > 0.6


def test_a_period_of_the_decode_step_has_the_gate_and_the_four_norms(
        v5e, capsys):
    """The dense window layer and the first routed one: compiles for the
    chip, with the gate's and the post-norms' scopes in the program and the
    window layers' pools gathered ring-wide (32 pages), never table-wide
    (56)."""
    two = _cell(v5e, layers=2)
    compiled = _lower(two, "decode").compile()
    _report(capsys, "decode, two layers", two, compiled)
    text = compiled.as_text()
    assert "attn_window" in text and "attn_global" not in text
    assert "attn_gate" in text and "post_norm" in text
    assert "moe_shared" in text and "moe_ffn" in text
    ps = 128
    assert f"bf16[32,{32 * ps},4,128]" in text \
        or f"bf16[32,32,{ps},4,128]" in text
    assert f"bf16[32,{56 * ps},4,128]" not in text \
        and f"bf16[32,56,{ps},4,128]" not in text


@pytest.mark.parametrize("program", ["decode", "paged_prefill",
                                     "paged_prefill_prefix"],
                         ids=["decode", "bucket-2048", "chunk-2048"])
def test_trinity_mini_programs_fit_at_the_cells_geometry(cell, capsys,
                                                         program):
    compiled = _lower(cell, program).compile()
    total = _report(capsys, program, cell, compiled)
    # Room to spare (the allocator fragments; the check's reference holds
    # a 4106-token sequence's float32 activations and the float32 head
    # beside all this).
    assert total < HBM_BYTES - 3e9
    assert total > 0.6 * 16e9  # and it is no toy
    if program == "decode":
        # XLA's own grouped-matmul kernel, three a routed layer (off the
        # chip ``moe._streams_experts`` does not choose the stream kernel;
        # test_benchmark_grouped_stream.py compiles that one).
        calls = compiled.as_text().count(
            "custom_call_target=\"tpu_custom_call\"")
        assert calls >= 3 * 4, calls
