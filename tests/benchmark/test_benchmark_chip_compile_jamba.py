"""Compile-only rehearsal of the Jamba2-3B cell for a *described* TPU v5e,
the sibling of ``test_benchmark_chip_compile_kimi_linear.py``: the decode
program at 128 slots (26 recurrent-form layers over the slots' state, the two
attention layers through ``ops/paged_decode.py``: the answer a CPU cannot
give is steered here, as ``test_benchmark_chip_compile_paged_decode.py``
steers it), and behind ``-m slow`` a 2048-token chunk of the suffix program
(the chunk form; on a TPU the engine sends every prefill call through it),
at the cell's geometry (128 slots of 9216 positions), have to fit one chip's
16 GB beside 6.06 GB of weights, 1.19 GB of state and 1.21 GB of pages.
Nothing executes, so nothing here is a measurement.  The topology is
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

CONFIG, TRAFFIC = "jamba2-3b", "serve-reasoning-wide-batch"


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
    """The answer only a TPU gives, as the cell's replica hears it."""
    from ray_tpu.ops import paged_decode

    monkeypatch.setattr(paged_decode, "on_tpu", lambda: True)
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
    return paged.paged_prefill_prefix.lower(
        *head, on((1, bucket), i32), scalar, scalar,
        on((ec.pages_per_seq,), i32), scalar, temp, cell["key"], None,
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
    """The K/V pool pair of the TWO attention layers (128 x 72 pages of 128
    rows of one head of 128), the 26 Mamba layers' state a slot (transposed:
    whole tiles), and the weights with ONE embedding."""
    ec, pools = cell["ec"], cell["pools"]
    assert ec.pages_per_seq == 72
    assert ec.prefill_buckets() == [128, 256, 512, 1024, 2048]
    assert set(pools) == {"k", "v", "S", "conv"}
    assert pools["k"].shape == pools["v"].shape \
        == (2, 128 * 72 + 1, 128, 1, 128)
    assert pools["S"].shape == (26, 128, 16, 5120) \
        and pools["S"].dtype == jnp.float32
    assert pools["conv"].shape == (26, 128, 3 * 5120)
    size = {n: x.size * x.dtype.itemsize for n, x in pools.items()}
    assert size["S"] + size["conv"] == 128 * 9_318_400
    assert 1.20e9 < size["k"] + size["v"] < 1.22e9
    assert "lm_head" not in cell["params"]
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree.leaves(cell["params"]))
    assert 6.05e9 < weights < 6.07e9  # A_log, D and dt_bias are float32
    # The fullest the device gets is above the contract's floor of 25%.
    assert (weights + sum(size.values())) / 16e9 > 0.5


def test_the_decode_program_fits_and_keeps_the_state_where_it_lies(
        cell, capsys, on_the_chip):
    compiled = _lower(cell, "decode").compile()
    total, ma = _report(capsys, "decode", compiled)
    assert 0.5 * 16e9 < total < HBM_BYTES - 3e9
    # The state and the pools lie unpadded (a [5120, 16] state a slot would
    # be padded to eight times its bytes) and are updated in place.
    assert ma.argument_size_in_bytes < 8.6e9
    assert ma.temp_size_in_bytes < 1.0e9
    text = compiled.as_text()
    calls = re.findall(r"^\s*%?(\S+) = \S+ custom-call\(", text, re.M)
    assert sum(x.startswith("paged_decode") for x in calls) == 2, calls
    assert "attn_ssm" in text and "ssm_conv" in text
    # The tied head: no second copy of the embedding, either way up.
    assert "[2560,65536]" not in text
    for name in ("S", "conv"):
        shape = ",".join(map(str, cell["pools"][name].shape))
        made = re.findall(
            r"^\s*(?:ROOT\s+)?\S+ = \w+\[" + re.escape(shape) + r"\]\S* "
            r"([\w-]+)\(", text, re.M)
        assert "parameter" in made and "copy" not in made, (name, made)


@pytest.mark.slow
def test_a_2048_token_chunk_fits_at_the_cells_geometry(cell, capsys,
                                                       on_the_chip):
    compiled = _lower(cell, "paged_prefill_prefix").compile()
    total, _ = _report(capsys, "chunk-2048", compiled)
    assert total < HBM_BYTES - 3e9
    text = compiled.as_text()
    assert "attn_ssm" in text and "ssm_conv" in text
    assert "paged_prefill" in text
