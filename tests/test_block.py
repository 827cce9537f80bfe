"""The decoder layer is spelled once (``ray_tpu/models/block.py``): every
program of the family, traced on a configuration no other test has used,
calls ``block.decoder_layer`` once for each layer it traces.  A program that
grows a private copy of the layer calls it fewer times and fails here."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (LlamaConfig, MoEConfig, block, init_and_apply,
                            llama_init, llama_loss, moe_loss, paged)

N_LAYERS = 3
#: Widths no other test traces, so that no program here is found in jit's
#: cache, already traced.
WIDTHS = dict(vocab_size=320, d_model=64, n_layers=N_LAYERS, n_heads=4,
              n_kv_heads=2, d_ff=96, max_seq=64, dtype=jnp.float32)
SLOTS, PAGE, MAXP, BUCKET = 2, 8, 4, 16


#: Latent attention, a dense layer before the routed ones, a shared expert,
#: sigmoid routing: what ``MoEConfig`` gained for GLM-4.7-Flash's line.
LATENT = dict(q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
              qk_rope_head_dim=8, v_head_dim=20, ffn_layout=(0, 1, 1),
              dense_d_ff=80, n_shared_experts=1, router_score="sigmoid",
              routed_scaling_factor=1.8)


def _config(routed, **kw):
    if routed == "latent":
        return MoEConfig(n_experts=4, top_k=2, **{**WIDTHS, "n_kv_heads": 4},
                         **LATENT, **kw)
    if routed:
        return MoEConfig(n_experts=4, top_k=2, qk_norm=True, **WIDTHS, **kw)
    return LlamaConfig(**WIDTHS, **kw)


def _params(cfg):
    return jax.eval_shape(
        lambda: init_and_apply(cfg)[0](cfg, jax.random.PRNGKey(0)))


def _train(routed, remat=False):
    cfg = _config(routed, remat=remat)
    loss = moe_loss if routed else llama_loss
    toks = jnp.zeros((2, 32), jnp.int32)
    jax.eval_shape(jax.grad(lambda p: loss(cfg, p, toks, toks)),
                   _params(cfg))
    return N_LAYERS


def _train_remat(routed):
    """``jax.checkpoint`` traces the layer once for layers of one shape
    and kind (the dense layer before the routed ones is a kind of its
    own)."""
    _train(routed, remat=True)
    return 2 if routed == "latent" else 1


def _pipeline_stage(routed):
    """Two stages of two layers, four microbatches: a stage scans its
    layers, so each of the schedule's ``n_micro + pp - 1`` ticks traces the
    body, one layer, once."""
    from ray_tpu.parallel import (MeshConfig, make_mesh, make_pp_loss,
                                  stack_layers)

    cfg = dataclasses.replace(_config(routed, remat=False), n_layers=4)
    mesh = make_mesh(MeshConfig(fsdp=4, pp=2))
    stacked = jax.eval_shape(lambda: stack_layers(
        llama_init(cfg, jax.random.PRNGKey(0))))
    toks = jnp.zeros((8, 32), jnp.int32)
    with jax.set_mesh(mesh):
        jax.eval_shape(make_pp_loss(cfg, mesh, n_micro=4), stacked, toks,
                       toks)
    return 4 + 2 - 1


def _serving_arguments(cfg):
    params = _params(cfg)
    pools = jax.eval_shape(
        lambda: paged.init_paged_pools(cfg, SLOTS * MAXP, PAGE))
    adapters = jax.eval_shape(lambda: paged.init_adapter_pool(cfg, 2, 4))
    return params, pools, adapters, jax.random.PRNGKey(0)


def _decode(routed):
    cfg = _config(routed, remat=False)
    params, pools, adapters, key = _serving_arguments(cfg)
    i32 = jnp.zeros((SLOTS,), jnp.int32)
    jax.eval_shape(
        paged.paged_decode_step, cfg, params, pools, adapters,
        jnp.zeros((SLOTS + paged.routing_width(cfg),), jnp.int32),
        jnp.zeros((SLOTS, MAXP), jnp.int32), i32, jnp.zeros((SLOTS,), bool),
        jnp.zeros((SLOTS,), jnp.float32), i32, key)
    return N_LAYERS


def _prefill(routed, prefix=False):
    cfg = _config(routed, remat=False)
    params, pools, adapters, key = _serving_arguments(cfg)
    zero = jnp.zeros((), jnp.int32)
    head = (cfg, params, pools, adapters, jnp.zeros((1, BUCKET), jnp.int32))
    tail = (zero, jnp.zeros((MAXP,), jnp.int32), zero,
            jnp.zeros((), jnp.float32), key)
    if prefix:
        jax.eval_shape(paged.paged_prefill_prefix, *head, zero, *tail)
    else:
        jax.eval_shape(paged.paged_prefill, *head, *tail)
    return N_LAYERS


def _prefill_prefix(routed):
    return _prefill(routed, prefix=True)


@pytest.mark.parametrize("program,routed", [
    (_train, False), (_train, True), (_train_remat, False),
    (_train_remat, True), (_pipeline_stage, False),
    (_decode, False), (_decode, True), (_prefill, False), (_prefill, True),
    (_prefill_prefix, False), (_prefill_prefix, True),
    (_train, "latent"), (_train_remat, "latent"), (_decode, "latent"),
    (_prefill, "latent"), (_prefill_prefix, "latent"),
], ids=lambda v: v.__name__.strip("_") if callable(v) else (
    v if isinstance(v, str) else "routed" if v else "dense"))
def test_every_program_runs_the_one_decoder_layer(monkeypatch, program,
                                                  routed):
    calls, kinds, real = [], [], block.decoder_layer

    def counted(config, *args, **kwargs):
        calls.append(config)
        kinds.append(kwargs.get("routed"))
        return real(config, *args, **kwargs)

    monkeypatch.setattr(block, "decoder_layer", counted)
    expected = program(routed)
    assert len(calls) == expected
    assert all(block.is_routed(c) == bool(routed) for c in calls)
    # Which FFN a layer has is asked of the layer (``block.is_routed(config,
    # i)``), by every program: a pattern reads dense, routed, routed.
    if routed == "latent" and expected == N_LAYERS:
        assert kinds == [False, True, True]
    elif routed != "latent":
        assert set(kinds) == {bool(routed)}


def test_the_routed_ffn_of_one_expert_is_the_dense_layer():
    """``block.ffn`` is the one place that asks which FFN a configuration
    has, and both answers are the same function of the same weights where
    they can be: one expert, taken with probability 1."""
    dense, routed = _config(False), dataclasses.replace(
        _config(True), n_experts=1, top_k=1, qk_norm=False)
    layer = jax.jit(llama_init, static_argnums=0)(
        dense, jax.random.PRNGKey(3))["layers"][0]
    as_routed = dict(layer, moe_norm=layer["mlp_norm"], moe={
        "router": jnp.zeros((dense.d_model, 1), jnp.float32),
        **{k: w[None] for k, w in layer["mlp"].items()}})
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 8, dense.d_model))
    attend = lambda q, k, v: q.reshape(*q.shape[:-2], -1)  # noqa: E731
    # One program each, where the eager form compiles every operation.
    want, no_aux, no_counts = jax.jit(lambda layer, x: block.decoder_layer(
        dense, layer, x, attend, routed=False))(layer, x)
    got, aux, counts = jax.jit(lambda layer, x: block.decoder_layer(
        routed, layer, x, attend, routed=True))(as_routed, x)
    assert no_aux is None and no_counts is None
    assert counts.tolist() == [16] and float(aux) == pytest.approx(1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
