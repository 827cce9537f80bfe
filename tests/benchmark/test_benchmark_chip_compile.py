"""Compile-only rehearsal of the benchmark's cells for a *described* TPU
v5e (``jax.experimental.topologies``): the TPU compiler is installed here and
raises what the chip's compiler would raise.  Nothing executes, so nothing
here is a measurement.  Same style as ``tests/test_chip_compile.py``; the
topology is described inside a fixture, never at import."""

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from bench_testlib import ROOT

from benchmarks import spec
from benchmarks.modelcfg import llama_config

#: bytes_limit of one v5e chip, as memory_stats() gave it (PR 21).
HBM_BYTES = 16909336064


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


@pytest.fixture
def kernels_as_on_chip(monkeypatch):
    from ray_tpu.ops import attention as att

    monkeypatch.setattr(att, "_on_tpu", lambda: True)


def _on(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _cell(config, traffic):
    return (spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", config + ".json")),
        spec.load_json(os.path.join(
            ROOT, "benchmarks", "traffic", traffic + ".json")))


def test_mistral_flash_backward_compiles_at_the_cells_shape(v5e):
    """GQA 32/8, 4 sequences of 4096: the backward PR 21 repaired by
    compile only."""
    from ray_tpu.ops import attention as att

    model, tr = _cell("mistral-7b-v0.3-L4", "train-1chip")
    one = SingleDeviceSharding(v5e.devices[0])
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    d = model["hidden_size"] // h
    q = _on(one, (tr["batch"], h, tr["seq"], d))
    k = _on(one, (tr["batch"], kv, tr["seq"], d))

    def loss(q, k, v):
        return att.flash_attention(
            q, k, v, force_pallas=True).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, k).compile().as_text()
    assert text.count("tpu_custom_call") == 3  # forward, dq, dk+dv


def test_internlm2_decode_program_fits_at_the_chosen_geometry(v5e, capsys):
    from ray_tpu.models import llama_init
    from ray_tpu.models.paged import (init_adapter_pool, init_paged_pools,
                                      paged_decode_step)
    from ray_tpu.serve.engine import EngineConfig

    model, tr = _cell("internlm2-1.8b", "serve-saturated")
    _, mixed = _cell("internlm2-1.8b", "serve-mixed")
    assert mixed["engine"] == tr["engine"]  # the two cells share programs
    ec = EngineConfig(**tr["engine"])
    assert ec.prefill_buckets() == [128, 256, 512, 1024]
    cfg = llama_config(model, remat=False,
                       max_seq=ec.pages_per_seq * ec.page_size)
    one = SingleDeviceSharding(v5e.devices[0])
    place = functools.partial(
        jax.tree.map, lambda x: _on(one, x.shape, x.dtype))
    params = place(jax.eval_shape(
        lambda: llama_init(cfg, jax.random.PRNGKey(0))))
    pools = place(jax.eval_shape(
        lambda: init_paged_pools(cfg, ec.pool_pages, ec.page_size)))
    adapters = place(jax.eval_shape(
        lambda: init_adapter_pool(cfg, ec.max_adapters, ec.lora_rank)))
    key = place(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    b = ec.batch_slots
    ma = paged_decode_step.lower(
        cfg, params, pools, adapters, _on(one, (b,), jnp.int32),
        _on(one, (b, ec.pages_per_seq), jnp.int32), _on(one, (b,), jnp.int32),
        _on(one, (b,), bool), _on(one, (b,), jnp.float32),
        _on(one, (b,), jnp.int32), key).compile().memory_analysis()
    total = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    with capsys.disabled():
        print(f"\ninternlm2-1.8b decode, {b} slots, {ec.page_size}-token "
              f"pages: arguments {ma.argument_size_in_bytes / 1e9:.2f} GB + "
              f"temporaries {ma.temp_size_in_bytes / 1e9:.2f} GB (compiled "
              f"for a described v5e; not a measurement)")
    # Room for the largest prefill's temporaries (1.2 GB) beside it.
    assert total < HBM_BYTES - 2e9
    # And a cell this small would not stand for a deployment.
    assert total > 0.25 * 16e9


def _train_step(model, tr, mesh=None):
    from ray_tpu.models import (TrainState, llama_init, llama_loss,
                                llama_sharding_rules)
    from ray_tpu.models.train_state import default_optimizer, make_train_step

    cfg = llama_config(model, max_seq=tr["seq"], **tr["model_options"])
    tx = default_optimizer(lr=tr["lr"], grad_clip=tr["grad_clip"])
    state = jax.eval_shape(lambda: TrainState.create(
        llama_init(cfg, jax.random.PRNGKey(0)), tx))
    rules = llama_sharding_rules() if mesh is not None else None
    step = make_train_step(
        lambda p, b: llama_loss(cfg, p, b["tokens"], b["targets"]),
        tx, mesh, rules)
    return step, state, rules


@pytest.mark.slow  # ~25 s
def test_mistral_train_step_fits_one_chip(v5e, kernels_as_on_chip):
    model, tr = _cell("mistral-7b-v0.3-L4", "train-1chip")
    step, state, _ = _train_step(model, tr)
    one = SingleDeviceSharding(v5e.devices[0])
    state = jax.tree.map(lambda x: _on(one, x.shape, x.dtype), state)
    batch = {k: _on(one, (tr["batch"], tr["seq"]), jnp.int32)
             for k in ("tokens", "targets")}
    compiled = step.lower(state, batch).compile()
    layers = model["num_hidden_layers"]
    assert compiled.as_text().count("tpu_custom_call") % layers == 0
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM_BYTES


@pytest.mark.slow  # ~4 min
def test_internlm2_train_step_partitions_over_four_chips(
        v5e, kernels_as_on_chip):
    from ray_tpu.parallel.mesh import MESH_AXES, batch_spec
    from ray_tpu.parallel.sharding import named_sharding

    model, tr = _cell("internlm2-1.8b", "train-fsdp2tp2")
    mesh = Mesh(np.array(v5e.devices).reshape(
        tuple(tr["mesh"].get(a, 1) for a in MESH_AXES)), MESH_AXES)
    step, state, rules = _train_step(model, tr, mesh)
    state = jax.tree.map(
        lambda x, s: _on(s, x.shape, x.dtype), state,
        named_sharding(mesh, rules.tree_specs(state)))
    data = NamedSharding(mesh, batch_spec())
    batch = {k: _on(data, (tr["batch"], tr["seq"]), jnp.int32)
             for k in ("tokens", "targets")}
    with jax.set_mesh(mesh):
        compiled = step.lower(state, batch).compile()
    assert compiled.as_text().count("tpu_custom_call") > 0
    ma = compiled.memory_analysis()  # bytes on EACH device
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM_BYTES
