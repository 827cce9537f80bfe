"""Compile-only rehearsal of the Kimi-Linear cell for a *described* TPU v5e,
the sibling of ``test_benchmark_chip_compile_afmoe.py``: the decode program
(ten recurrent-form layers over the slots' state, three latent layers
through ``ops/latent_decode.py``, twelve routed layers through
``ops/grouped_ffn.py``'s stream: the two answers a CPU cannot give are
steered here, as ``test_benchmark_chip_compile_latent_decode.py`` steers
them), and behind ``-m slow`` the largest one-bucket prefill and a
2048-token chunk of the suffix program (the chunk form), at the cell's
geometry (64 slots of 11264 positions), have to fit one chip's 16 GB beside
6.9 GB of weights, 1.4 GB of state and a 2.8 GB latent pool.  Nothing
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
# The described topology (a fixture of this file too).
from test_benchmark_chip_compile_afmoe import HBM_BYTES, v5e  # noqa: F401

from benchmarks import spec

CONFIG, TRAFFIC = "kimi-linear-48b-a3b-L13", "serve-long-decode-doc-tail"


@pytest.fixture(scope="module")
def cell(v5e):  # noqa: F811
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

    def on(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    place = functools.partial(jax.tree.map, lambda x: on(x.shape, x.dtype))
    return {
        "model": model, "ec": ec, "cfg": cfg, "on": on,
        "params": place(jax.eval_shape(
            lambda: fam.init(cfg, jax.random.PRNGKey(0)))),
        "pools": place(jax.eval_shape(lambda: paged.init_paged_pools(
            cfg, ec.pool_pages, ec.page_size, 0, ec.batch_slots))),
        "adapters": place(jax.eval_shape(lambda: paged.init_adapter_pool(
            cfg, ec.max_adapters, ec.lora_rank))),
        "key": place(jax.eval_shape(lambda: jax.random.PRNGKey(0))),
    }


@pytest.fixture
def on_the_chip(monkeypatch):
    """The two answers only a TPU gives, as the cell's replica hears them."""
    from ray_tpu.ops import grouped_ffn, latent_decode

    monkeypatch.setattr(grouped_ffn, "on_tpu", lambda: True)
    monkeypatch.setattr(latent_decode, "on_tpu", lambda: True)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _lower(cell, program):
    from ray_tpu.models import paged

    ec, on, i32 = cell["ec"], cell["on"], jnp.int32
    b = ec.batch_slots
    head = (cell["cfg"], cell["params"], cell["pools"], cell["adapters"])
    if program == "decode":
        return paged.paged_decode_step.lower(
            *head, on((b + paged.routing_width(cell["cfg"]),), i32),
            on((b, ec.pages_per_seq), i32), on((b,), i32), on((b,), bool),
            on((b,), jnp.float32), on((b,), i32), cell["key"], None)
    bucket = ec.prefill_buckets()[-1]
    assert bucket == 2048 == ec.prefill_chunk
    scalar, temp = on((), i32), on((), jnp.float32)
    toks, table = on((1, bucket), i32), on((ec.pages_per_seq,), i32)
    if program == "paged_prefill":
        return paged.paged_prefill.lower(
            *head, toks, scalar, table, scalar, temp, cell["key"], None,
            scalar)
    return paged.paged_prefill_prefix.lower(
        *head, toks, scalar, scalar, table, scalar, temp, cell["key"], None,
        scalar)


def _report(capsys, what, compiled):
    ma = compiled.memory_analysis()
    total = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    with capsys.disabled():
        print(f"\n{CONFIG} {what}: arguments "
              f"{ma.argument_size_in_bytes / 1e9:.2f} GB + temporaries "
              f"{ma.temp_size_in_bytes / 1e9:.2f} GB = {total / 1e9:.2f} GB "
              f"of {HBM_BYTES / 1e9:.2f} (compiled for a described v5e; "
              f"not a measurement)")
    return total, ma


def test_the_pools_are_what_the_issue_reckoned(cell):
    """The latent pool of the THREE latent layers (64 x 88 pages of 128
    rows of 640), the ten KDA layers' state a slot, and the weights."""
    ec, pools = cell["ec"], cell["pools"]
    assert ec.pages_per_seq == 88
    assert ec.prefill_buckets() == [128, 256, 512, 1024, 2048]
    assert pools["kv"].shape == (3, 64 * 88 + 1, 128, 640)
    assert pools["S"].shape == (10, 64, 32, 128, 128) \
        and pools["S"].dtype == jnp.float32
    assert pools["conv"].shape == (10, 64, 3, 12288)
    size = {n: x.size * x.dtype.itemsize for n, x in pools.items()}
    assert 2.76e9 < size["kv"] < 2.78e9
    assert size["S"] + size["conv"] == 64 * 21_708_800
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree.leaves(cell["params"]))
    assert 6.90e9 < weights < 6.93e9   # the routers and biases are float32
    # The fullest the device gets is above the contract's floor of 25%.
    assert (weights + sum(size.values())) / 16e9 > 0.65


def test_the_decode_program_fits_and_goes_through_both_kernels(
        cell, capsys, on_the_chip):
    compiled = _lower(cell, "decode").compile()
    total, ma = _report(capsys, "decode", compiled)
    assert total < HBM_BYTES - 3e9 and total > 0.6 * 16e9
    # The state and the pool are updated in place: no copy of either.
    assert ma.temp_size_in_bytes < 0.3e9
    text = compiled.as_text()
    assert text.count("latent_decode") >= 3
    assert text.count("ragged-dot-stream") >= 12
    assert "attn_kda" in text and "kda_conv" in text
    assert "attn_kda_chunk" not in text


@pytest.mark.slow
@pytest.mark.parametrize("program", ["paged_prefill", "paged_prefill_prefix"],
                         ids=["bucket-2048", "chunk-2048"])
def test_the_prefill_programs_fit_at_the_cells_geometry(cell, capsys,
                                                         program):
    compiled = _lower(cell, program).compile()
    total, _ = _report(capsys, program, compiled)
    assert total < HBM_BYTES - 3e9
    text = compiled.as_text()
    assert "attn_kda_chunk" in text and "attn_latent_prefill" in text
