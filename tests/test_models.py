"""Model tests: tiny-Llama forward/training (replicated and 2D-sharded on the
virtual mesh), LoRA, MLP convergence."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (
    LlamaConfig,
    MLPConfig,
    TrainState,
    llama_apply,
    llama_init,
    llama_loss,
    llama_sharding_rules,
    lora_init,
    lora_merge,
    make_train_step,
    mlp_init,
)
from ray_tpu.models.mlp import mlp_loss
from ray_tpu.models.train_state import default_optimizer, shard_train_state
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel import MeshConfig, make_mesh, set_mesh


#: One program a shape, where the eager form compiles every operation.
_apply = jax.jit(llama_apply, static_argnums=0)


@pytest.fixture(scope="module")
def tiny():
    cfg = LlamaConfig.tiny(remat=False, dtype=jnp.float32)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _tokens(cfg, B=2, S=64, seed=1):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (B, S), 0, cfg.vocab_size
    )


class TestLlama:
    def test_forward_shapes(self, tiny):
        cfg, params = tiny
        toks = _tokens(cfg)
        logits = _apply(cfg, params, toks)
        assert logits.shape == (2, 64, cfg.vocab_size)
        assert logits.dtype == jnp.float32
        assert bool(jnp.isfinite(logits).all())

    def test_causality(self, tiny):
        """Changing a future token must not change past logits."""
        cfg, params = tiny
        toks = _tokens(cfg, B=1)
        logits1 = _apply(cfg, params, toks)
        toks2 = toks.at[0, -1].set((toks[0, -1] + 1) % cfg.vocab_size)
        logits2 = _apply(cfg, params, toks2)
        np.testing.assert_allclose(
            logits1[0, :-1], logits2[0, :-1], atol=1e-5
        )
        assert float(jnp.abs(logits1[0, -1] - logits2[0, -1]).max()) > 1e-4

    def test_loss_decreases(self, tiny):
        cfg, params = tiny
        toks = _tokens(cfg, B=4, S=32)
        targets = jnp.roll(toks, -1, axis=1)
        tx = default_optimizer(lr=1e-3)
        state = TrainState.create(jax.tree.map(jnp.copy, params), tx)
        step = make_train_step(
            lambda p, b: llama_loss(cfg, p, b["tokens"], b["targets"]), tx
        )
        batch = {"tokens": toks, "targets": targets}
        losses = []
        for _ in range(8):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0] - 0.1, losses

    def test_sharded_train_step_2d(self, tiny):
        """fsdp=4 x tp=2 over the 8-device CPU mesh; results must match the
        replicated step."""
        cfg, params = tiny
        mesh = make_mesh(MeshConfig(fsdp=4, tp=2))
        rules = llama_sharding_rules()
        toks = _tokens(cfg, B=4, S=32)
        batch = {"tokens": toks, "targets": jnp.roll(toks, -1, axis=1)}
        tx = default_optimizer(lr=1e-3)
        loss_fn = lambda p, b: llama_loss(cfg, p, b["tokens"], b["targets"])

        state_r = TrainState.create(jax.tree.map(jnp.copy, params), tx)
        step_r = make_train_step(loss_fn, tx)
        state_s = shard_train_state(
            TrainState.create(jax.tree.map(jnp.copy, params), tx), mesh, rules
        )
        step_s = make_train_step(loss_fn, tx, mesh, rules)

        with set_mesh(mesh):
            for _ in range(2):
                state_s, m_s = step_s(state_s, batch)
        for _ in range(2):
            state_r, m_r = step_r(state_r, batch)
        assert abs(float(m_s["loss"]) - float(m_r["loss"])) < 1e-3
        # A sharded param really is distributed.
        wq = state_s.params["layers"][0]["attn"]["wq"]
        assert not wq.sharding.is_fully_replicated

    def test_lora(self, tiny):
        cfg, params = tiny
        lora = lora_init(cfg, jax.random.PRNGKey(1), rank=4)
        toks = _tokens(cfg, B=2, S=32)
        # B zero-initialized: LoRA output == base output initially.
        base = _apply(cfg, params, toks)
        with_lora = _apply(cfg, params, toks, lora)
        np.testing.assert_allclose(base, with_lora, atol=1e-6)

        # Train only the adapters; base stays frozen.
        targets = jnp.roll(toks, -1, axis=1)
        tx = default_optimizer(lr=1e-2)
        state = TrainState.create(jax.tree.map(jnp.copy, lora), tx)
        step = make_train_step(
            lambda lp, b: llama_loss(cfg, params, b["tokens"], b["targets"], lp),
            tx,
        )
        batch = {"tokens": toks, "targets": targets}
        l0 = None
        for _ in range(5):
            state, m = step(state, batch)
            l0 = l0 or float(m["loss"])
        assert float(m["loss"]) < l0

        # Merge: merged model output == adapter-applied output.
        merged = lora_merge(cfg, params, state.params)
        np.testing.assert_allclose(
            _apply(cfg, merged, toks),
            _apply(cfg, params, toks, state.params),
            atol=2e-3, rtol=2e-3,
        )

    def test_gqa_config(self):
        cfg = LlamaConfig.tiny(dtype=jnp.float32, remat=False)
        assert cfg.n_kv_heads < cfg.n_heads  # tiny config exercises GQA
        params = llama_init(cfg, jax.random.PRNGKey(0))
        logits = _apply(cfg, params, _tokens(cfg, B=1, S=16))
        assert bool(jnp.isfinite(logits).all())

    def test_param_count_7b(self):
        assert abs(LlamaConfig.llama2_7b().param_count() / 6.74e9 - 1) < 0.02


class TestMLP:
    def test_converges(self):
        cfg = MLPConfig(in_dim=16, hidden=32, out_dim=4)
        params = mlp_init(cfg, jax.random.PRNGKey(0))
        key = jax.random.PRNGKey(1)
        x = jax.random.normal(key, (256, 16))
        y = (x.sum(axis=1) > 0).astype(jnp.int32) + 2 * (x[:, 0] > 0).astype(jnp.int32)
        tx = default_optimizer(lr=1e-2)
        state = TrainState.create(params, tx)
        step = make_train_step(lambda p, b: mlp_loss(cfg, p, b["x"], b["y"]), tx)
        for _ in range(60):
            state, m = step(state, {"x": x, "y": y})
        assert float(m["loss"]) < 0.5


class TestMoE:
    """Mixture-of-Experts family with expert parallelism (net-new vs the
    reference — SURVEY §2.4 lists EP/MoE as absent there)."""

    @pytest.fixture(scope="class")
    def tiny_moe(self):
        from ray_tpu.models import MoEConfig, moe_init

        cfg = MoEConfig.tiny(dtype=jnp.float32, remat=False)
        params = moe_init(cfg, jax.random.PRNGKey(0))
        return cfg, params

    def test_forward_shapes_and_finite(self, tiny_moe):
        from ray_tpu.models import moe_apply

        cfg, params = tiny_moe
        toks = _tokens(cfg, B=2, S=32)
        logits, aux = moe_apply(cfg, params, toks)
        assert logits.shape == (2, 32, cfg.vocab_size)
        assert jnp.isfinite(logits).all()
        # Balanced-random routing gives aux ~ 1.0; wildly off means the
        # load-balancing stats are broken.
        assert 0.5 < float(aux) < 4.0

    def test_single_expert_matches_dense_mlp(self):
        """n_experts=1, top_k=1: the MoE FFN must reduce to the plain
        SwiGLU MLP with the same weights."""
        from ray_tpu.models import MoEConfig
        from ray_tpu.models.moe import _moe_ffn

        cfg = MoEConfig.tiny(dtype=jnp.float32, remat=False)
        cfg = dataclasses.replace(cfg, n_experts=1, top_k=1)
        d, f = cfg.d_model, cfg.d_ff
        key = jax.random.PRNGKey(3)
        k1, k2, k3, kx = jax.random.split(key, 4)
        moe = {
            "router": jnp.zeros((d, 1), jnp.float32),
            "w1": jax.random.normal(k1, (1, d, f)) * 0.05,
            "w3": jax.random.normal(k2, (1, d, f)) * 0.05,
            "w2": jax.random.normal(k3, (1, f, d)) * 0.05,
        }
        x = jax.random.normal(kx, (2, 16, d))
        out, _, counts = _moe_ffn(cfg, moe, x)
        assert counts.tolist() == [2 * 16]
        dense = (jax.nn.silu(x @ moe["w1"][0]) * (x @ moe["w3"][0])) @ moe["w2"][0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                                   rtol=2e-4, atol=2e-5)

    def test_loss_decreases(self, tiny_moe):
        from ray_tpu.models import moe_loss
        from ray_tpu.models.train_state import (
            TrainState, default_optimizer, make_train_step,
        )

        cfg, params = tiny_moe
        toks = _tokens(cfg, B=4, S=32)
        batch = {"tokens": toks, "targets": jnp.roll(toks, -1, axis=1)}
        tx = default_optimizer(lr=3e-3)
        state = TrainState.create(jax.tree.map(jnp.copy, params), tx)
        step = make_train_step(
            lambda p, b: moe_loss(cfg, p, b["tokens"], b["targets"]), tx
        )
        losses = []
        for _ in range(8):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0] - 0.1, losses

    def test_expert_parallel_matches_replicated(self, tiny_moe):
        """ep=2 x fsdp=2 x tp=2 sharded step == replicated step: the expert
        dim shards over ep and XLA's inserted collectives must not change
        the math."""
        from ray_tpu.models import moe_loss, moe_sharding_rules
        from ray_tpu.models.train_state import (
            TrainState, default_optimizer, make_train_step, shard_train_state,
        )

        cfg, params = tiny_moe
        mesh = make_mesh(MeshConfig(fsdp=2, tp=2, ep=2))
        rules = moe_sharding_rules()
        toks = _tokens(cfg, B=4, S=32)
        batch = {"tokens": toks, "targets": jnp.roll(toks, -1, axis=1)}
        tx = default_optimizer(lr=1e-3)
        loss_fn = lambda p, b: moe_loss(cfg, p, b["tokens"], b["targets"])

        state_r = TrainState.create(jax.tree.map(jnp.copy, params), tx)
        step_r = make_train_step(loss_fn, tx)
        state_s = shard_train_state(
            TrainState.create(jax.tree.map(jnp.copy, params), tx), mesh, rules
        )
        step_s = make_train_step(loss_fn, tx, mesh, rules)

        with set_mesh(mesh):
            for _ in range(2):
                state_s, m_s = step_s(state_s, batch)
        for _ in range(2):
            state_r, m_r = step_r(state_r, batch)
        assert abs(float(m_s["loss"]) - float(m_r["loss"])) < 1e-3
        w1 = state_s.params["layers"][0]["moe"]["w1"]
        assert not w1.sharding.is_fully_replicated
        assert w1.sharding.spec == P("ep", "fsdp", "tp")


class TestActivationLayout:
    """The training programs say where their activations live
    (``parallel/sharding.constrain``): only under an ambient mesh, and
    without changing what the step computes."""

    @pytest.mark.parametrize("routed", [False, True],
                             ids=["dense", "routed"])
    def test_constrained_step_matches_one_device(self, routed):
        """Three steps under fsdp=2 x tp=2 with the mesh ambient, so that
        the constraints engage, against the single-device step."""
        from ray_tpu.models import (MoEConfig, moe_init, moe_loss,
                                    moe_sharding_rules)

        if routed:
            cfg = MoEConfig.tiny(dtype=jnp.float32, remat=False)
            init, loss, rules = moe_init, moe_loss, moe_sharding_rules()
        else:
            cfg = LlamaConfig.tiny(dtype=jnp.float32, remat=True,
                                   loss_chunk=16)
            init, loss, rules = llama_init, llama_loss, llama_sharding_rules()
        params = init(cfg, jax.random.PRNGKey(0))
        mesh = make_mesh(MeshConfig(fsdp=2, tp=2), devices=jax.devices()[:4])
        toks = _tokens(cfg, B=4, S=32)
        batch = {"tokens": toks, "targets": jnp.roll(toks, -1, axis=1)}
        tx = default_optimizer(lr=1e-3)
        loss_fn = lambda p, b: loss(cfg, p, b["tokens"], b["targets"])

        state_r = TrainState.create(jax.tree.map(jnp.copy, params), tx)
        step_r = make_train_step(loss_fn, tx)
        state_s = shard_train_state(
            TrainState.create(jax.tree.map(jnp.copy, params), tx), mesh, rules)
        step_s = make_train_step(loss_fn, tx, mesh, rules)

        def constraints(lowered):
            return lowered.as_text().count("sharding_constraint")

        # With no ambient mesh the step holds only the parameters' and the
        # batch's constraints; under one, the activations' too.
        bare = constraints(step_s.lower(state_s, batch))
        with set_mesh(mesh):
            assert constraints(step_s.lower(state_s, batch)) \
                >= bare + 6 * cfg.n_layers
            sharded = []
            for _ in range(3):
                state_s, m = step_s(state_s, batch)
                sharded.append((float(m["loss"]), float(m["grad_norm"])))
        for loss_s, gnorm_s in sharded:
            state_r, m = step_r(state_r, batch)
            assert abs(loss_s - float(m["loss"])) < 1e-3
            assert gnorm_s == pytest.approx(float(m["grad_norm"]), rel=1e-3)

    def test_no_mesh_no_constraint(self):
        """With no ambient mesh, and inside ``shard_map``, ``constrain``
        returns its argument: such a program traces to what it would
        without the call."""
        from ray_tpu.parallel import constrain
        from ray_tpu.parallel.sharding import RESIDUAL

        x = jnp.zeros((4, 8, 16))
        assert constrain(x, RESIDUAL) is x
        mesh = make_mesh(MeshConfig(fsdp=2, tp=2), devices=jax.devices()[:4])
        seen = []

        def per_shard(y):
            seen.append(constrain(y, RESIDUAL) is y)
            return y

        with set_mesh(mesh):
            assert "sharding_constraint" in jax.jit(
                lambda y: constrain(y, RESIDUAL)).lower(x).as_text()
            jax.eval_shape(jax.shard_map(
                per_shard, in_specs=P("fsdp"), out_specs=P("fsdp")), x)
        assert seen == [True]


class TestPipelineParallel:
    """GPipe-style in-jit pipeline over the pp mesh axis (the in-model
    counterpart of the actor pipelines in ray_tpu.dag; the reference's only
    pipeline story is actor dataflow — compiled_dag_node.py)."""

    def test_pp_loss_matches_reference(self):
        from ray_tpu.models import LlamaConfig, llama_init, llama_loss
        from ray_tpu.parallel import (
            MeshConfig, make_mesh, make_pp_loss, stack_layers,
        )

        cfg = LlamaConfig.tiny(dtype=jnp.float32, remat=False)
        cfg = dataclasses.replace(cfg, n_layers=4)
        params = llama_init(cfg, jax.random.PRNGKey(0))
        toks = _tokens(cfg, B=8, S=32)
        targets = jnp.roll(toks, -1, axis=1)

        ref = float(llama_loss(cfg, params, toks, targets))

        mesh = make_mesh(MeshConfig(fsdp=2, pp=4))
        stacked = stack_layers(params)
        pp_loss = make_pp_loss(cfg, mesh, n_micro=4)
        with set_mesh(mesh):
            got = float(jax.jit(pp_loss)(stacked, toks, targets))
        assert abs(got - ref) < 1e-4, (got, ref)

    @pytest.mark.slow  # pipeline-parallel train: ~15s on a loaded CPU host
    def test_pp_grads_flow_and_train(self):
        """jax.grad through ppermute: a few pipelined steps reduce the loss
        and every stage's layer gradients are nonzero."""
        import optax

        from ray_tpu.models import LlamaConfig, llama_init
        from ray_tpu.parallel import (
            MeshConfig, make_mesh, make_pp_loss, stack_layers,
        )

        cfg = LlamaConfig.tiny(dtype=jnp.float32, remat=False)
        cfg = dataclasses.replace(cfg, n_layers=2)
        params = stack_layers(llama_init(cfg, jax.random.PRNGKey(0)))
        toks = _tokens(cfg, B=8, S=32)
        targets = jnp.roll(toks, -1, axis=1)

        mesh = make_mesh(MeshConfig(fsdp=4, pp=2))
        pp_loss = make_pp_loss(cfg, mesh, n_micro=4)
        tx = optax.adam(3e-3)
        opt_state = tx.init(params)

        @jax.jit
        def step(params, opt_state):
            loss, grads = jax.value_and_grad(pp_loss)(params, toks, targets)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss, grads

        losses = []
        with set_mesh(mesh):
            for _ in range(6):
                params, opt_state, loss, grads = step(params, opt_state)
                losses.append(float(loss))
        assert losses[-1] < losses[0] - 0.05, losses
        # Both stages' attention weights received gradient signal.
        gq = np.asarray(grads["layers"]["attn"]["wq"])
        assert np.abs(gq[0]).max() > 0 and np.abs(gq[1]).max() > 0


class TestGradAccum:
    def test_grad_accum_matches_full_batch(self):
        """grad_accum=2 inside one jitted step: the accumulated mean
        gradient must match the full-batch gradient (equal microbatches:
        mean of per-micro means == full mean), so parameters after one
        update agree within bf16/f32 accumulation tolerance."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models import (
            LlamaConfig, TrainState, llama_init, llama_loss,
        )
        from ray_tpu.models.train_state import (
            default_optimizer, make_train_step,
        )

        cfg = LlamaConfig.tiny(remat=False, dtype=jnp.float32)
        params = llama_init(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks, "targets": jnp.roll(toks, -1, axis=1)}
        tx = default_optimizer(lr=1e-3)
        loss_fn = lambda p, b: llama_loss(cfg, p, b["tokens"], b["targets"])

        s_full = TrainState.create(jax.tree.map(jnp.copy, params), tx)
        s_acc = TrainState.create(jax.tree.map(jnp.copy, params), tx)
        step_full = make_train_step(loss_fn, tx)
        step_acc = make_train_step(loss_fn, tx, grad_accum=2)
        s_full, m_full = step_full(s_full, batch)
        s_acc, m_acc = step_acc(s_acc, batch)
        assert float(m_acc["loss"]) == pytest.approx(
            float(m_full["loss"]), rel=1e-5)
        assert float(m_acc["grad_norm"]) == pytest.approx(
            float(m_full["grad_norm"]), rel=1e-4)
        for a, b in zip(jax.tree.leaves(s_acc.params),
                        jax.tree.leaves(s_full.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5, rtol=2e-4)
