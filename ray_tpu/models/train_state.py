"""Train state + sharded train-step factory.

The factory returns a jitted SPMD step: inputs sharded over dp/fsdp (and sp),
params/optimizer state sharded per the rule table, activations where the
model's layer says (``parallel.sharding.constrain``, under an ambient mesh),
gradient reduction done by XLA from those layouts (no explicit allreduce —
the TPU-native replacement for torch DDP/FSDP wrappers, reference:
train/torch/train_loop_utils.py:162 prepare_model).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh import batch_spec
from ..parallel.sharding import ShardingRules, named_sharding


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array

    @staticmethod
    def create(params: Any, tx: optax.GradientTransformation) -> "TrainState":
        return TrainState(
            params=params,
            opt_state=tx.init(params),
            step=jnp.zeros((), jnp.int32),
        )


def default_optimizer(
    lr: float = 3e-4,
    weight_decay: float = 0.0,
    warmup_steps: int = 0,
    total_steps: int = 0,
    b1: float = 0.9,
    b2: float = 0.95,
    grad_clip: float = 1.0,
) -> optax.GradientTransformation:
    if warmup_steps and total_steps:
        sched = optax.warmup_cosine_decay_schedule(
            0.0, lr, warmup_steps, max(total_steps, warmup_steps + 1)
        )
    else:
        sched = lr
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(sched, b1=b1, b2=b2, weight_decay=weight_decay),
    )


def make_train_step(
    loss_fn: Callable[..., jax.Array],
    tx: optax.GradientTransformation,
    mesh: Optional[Mesh] = None,
    rules: Optional[ShardingRules] = None,
    *,
    sp_shard_seq: bool = False,
    donate_state: bool = True,
    grad_accum: int = 1,
    accum_dtype=None,
):
    """Build `step(state, batch) -> (state, metrics)`.

    loss_fn(params, batch) -> scalar loss.  With a mesh+rules, the returned
    step is pjit-ed with parameter/optimizer shardings from the rules and
    batch sharding over (dp, fsdp)[, sp].

    grad_accum > 1 splits the batch's leading dim into that many
    microbatches inside ONE compiled step (lax.scan accumulating mean
    gradients, one optimizer update) — the standard large-batch recipe
    when a full batch's activations exceed HBM: each microbatch runs in
    the small-batch high-MFU regime and only one grad buffer is live
    (reference: train loops accumulate gradients across micro-steps; here
    the accumulation is in-program so XLA overlaps it).

    Accumulation semantics (match the common torch-trainer recipe):
    - Microbatch means average with EQUAL weight.  When loss_fn masks
      tokens (ignore_index) and microbatches carry unequal valid-token
      counts, this differs from the full-batch mean — pack sequences to
      uniform valid lengths if exact equivalence matters.
    - ``accum_dtype`` sets the gradient-accumulator dtype; None keeps the
      parameter dtype.  bf16 params + a handful of microbatches lose only
      ~log2(accum) low bits before Adam's normalization; pass jnp.float32
      for exact sums at +4 bytes/param of HBM (often the difference
      between fitting and spilling — the measured bench tiers use None).
    """

    def _grads_and_loss(params, batch):
        if grad_accum == 1:
            return jax.value_and_grad(loss_fn)(params, batch)
        micro = jax.tree.map(
            lambda x: x.reshape(grad_accum, x.shape[0] // grad_accum,
                                *x.shape[1:]),
            batch,
        )

        def body(carry, mb):
            loss_acc, grads_acc = carry
            loss, grads = jax.value_and_grad(loss_fn)(params, mb)
            return (
                loss_acc + loss.astype(jnp.float32),
                jax.tree.map(
                    lambda a, g: a + g.astype(a.dtype), grads_acc, grads),
            ), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(
                p.shape, accum_dtype if accum_dtype is not None else p.dtype
            ),
            params,
        )
        (loss_sum, grads_sum), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), zeros), micro)
        scale = 1.0 / grad_accum
        return loss_sum * scale, jax.tree.map(
            lambda g, p: (g * scale).astype(p.dtype), grads_sum, params)

    def step(state: TrainState, batch) -> Tuple[TrainState, dict]:
        loss, grads = _grads_and_loss(state.params, batch)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        gnorm = optax.global_norm(grads)
        return (
            TrainState(params=params, opt_state=opt_state, step=state.step + 1),
            {"loss": loss, "grad_norm": gnorm, "step": state.step + 1},
        )

    if mesh is None:
        return jax.jit(step, donate_argnums=(0,) if donate_state else ())

    data_sh = NamedSharding(mesh, batch_spec(sp_shard_seq))

    def constrain(tree):
        # Pin params/optimizer state to the rule table inside the program so
        # the step is rule-sharded even if the caller passed a differently
        # placed state (paths are available while tracing).
        specs = rules.tree_specs(tree)
        return jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, s)
            ),
            tree, specs,
            is_leaf=lambda x: isinstance(x, P),
        )

    @functools.partial(jax.jit, donate_argnums=(0,) if donate_state else ())
    def sharded_step(state, batch):
        state = TrainState(
            params=constrain(state.params),
            opt_state=constrain(state.opt_state),
            step=state.step,
        )
        batch = jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(x, data_sh), batch
        )
        new_state, metrics = step(state, batch)
        new_state = TrainState(
            params=constrain(new_state.params),
            opt_state=constrain(new_state.opt_state),
            step=new_state.step,
        )
        return new_state, metrics

    return sharded_step


def shard_train_state(
    state: TrainState, mesh: Mesh, rules: ShardingRules
) -> TrainState:
    """Place an (often host-built) train state onto the mesh: params and
    optimizer moments follow the param rules; scalars replicate."""

    def put(tree):
        # Optimizer moments mirror the param tree paths (".../attn/wq"), so
        # the same regex rules shard them identically; scalars clip to P().
        return jax.device_put(tree, named_sharding(mesh, rules.tree_specs(tree)))

    return TrainState(
        params=put(state.params),
        opt_state=put(state.opt_state),
        step=jax.device_put(state.step, NamedSharding(mesh, P())),
    )
