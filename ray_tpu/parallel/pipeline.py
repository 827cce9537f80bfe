"""Pipeline parallelism: GPipe-style microbatch pipelining inside one jit.

The reference's pipeline story is actor dataflow (compiled DAGs with NCCL
p2p channels — reference: python/ray/dag/compiled_dag_node.py:498,
experimental/channel/torch_tensor_nccl_channel.py:191); this framework has
that too (ray_tpu.dag).  This module is the TPU-native *in-model* variant:
layers shard over the `pp` mesh axis, activations hop stage-to-stage with
`lax.ppermute` over ICI, and the whole fill/steady/drain schedule compiles
into ONE XLA program — no per-hop host involvement at all, which is the
part an actor pipeline can never match on TPU.

Design (inside `shard_map` over the pp axis):
- per-layer params are stacked on a leading [L] dim and sharded P('pp'):
  each stage holds L/pp consecutive layers and scans over them
- the batch splits into M microbatches; at step t, stage r runs microbatch
  (t - r): rank 0 injects embedded microbatch t while t < M, every stage
  passes its output to stage r+1 via ppermute, and the last stage's outputs
  along the diagonal t = m + pp - 1 are the completed microbatches
- after the drain, the last stage computes the LM loss; a psum makes the
  scalar replicated.  Autodiff flows through ppermute (its transpose is the
  reverse permutation), so one `jax.grad` of the shard_mapped loss trains
  the pipeline.

The schedule wastes the classic GPipe bubble (pp-1 of M+pp-1 steps);
M >= 4*pp keeps utilization high.  Interleaved/1F1B schedules are a future
optimization, not a semantic change.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from .mesh import AXIS_PP, mesh_axis_size

# NOTE: model imports (llama._block etc.) happen inside make_pp_loss —
# models import parallel.mesh/sharding, so a top-level import here would be
# circular through the package __init__s.

Params = Dict[str, Any]


def stack_layers(params: Params) -> Params:
    """Convert the per-layer list to a stacked pytree ([L, ...] leading dim
    per leaf) so the layer dim can shard over pp."""
    layers = params["layers"]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    return {**{k: v for k, v in params.items() if k != "layers"},
            "layers": stacked}


def unstack_layers(params: Params, n_layers: int) -> Params:
    stacked = params["layers"]
    layers = [
        jax.tree.map(lambda x, i=i: x[i], stacked)
        for i in range(n_layers)
    ]
    return {**{k: v for k, v in params.items() if k != "layers"},
            "layers": layers}


def pp_sharding_spec(stacked: Params) -> Params:
    """P('pp') on the stacked layer dim; everything else replicated (tp/fsdp
    composition within a stage is a future extension — the pp axis itself
    is what this module owns)."""
    return {
        **{k: P() for k in stacked if k != "layers"},
        "layers": jax.tree.map(lambda _: P(AXIS_PP), stacked["layers"]),
    }


def make_pp_loss(config, mesh, n_micro: int = 4, ignore_index: int = -100):
    """Build ``loss(stacked_params, tokens, targets) -> scalar`` running the
    GPipe schedule over the mesh's pp axis.  ``config.n_layers`` must divide
    by the pp size; the batch must divide by ``n_micro``.  ``config`` is a
    models.llama.LlamaConfig."""
    from ..models.llama import _block
    from ..ops.losses import masked_nll
    from ..ops.norms import rms_norm
    from ..ops.rotary import rope_frequencies

    pp = mesh_axis_size(mesh, AXIS_PP)
    if config.n_layers % pp != 0:
        raise ValueError(
            f"n_layers={config.n_layers} not divisible by pp={pp}"
        )

    def stage_apply(stacked_local, x, cos, sin):
        """Scan this stage's layers over the activation."""
        def body(h, layer):
            return _block(config, h, layer, cos, sin), None

        h, _ = lax.scan(body, x, stacked_local)
        return h

    def fn(stacked, tokens, targets):
        rank = lax.axis_index(AXIS_PP)
        B, S = tokens.shape
        mb = B // n_micro
        cos, sin = rope_frequencies(
            config.head_dim, config.max_seq, config.rope_theta
        )
        # Embedding is replicated and cheap at the hidden edge; every rank
        # embeds all microbatches, only rank 0's injection is consumed.
        embed = stacked["embed"]
        inputs = embed[tokens].astype(config.dtype).reshape(
            n_micro, mb, S, config.d_model
        )
        local_layers = stacked["layers"]

        state = jnp.zeros((mb, S, config.d_model), config.dtype)
        fwd = [(i, (i + 1) % pp) for i in range(pp)]
        collected = []
        for t in range(n_micro + pp - 1):
            if t < n_micro:
                x_in = jnp.where(rank == 0, inputs[t], state)
            else:
                x_in = state
            y = stage_apply(local_layers, x_in, cos, sin)
            collected.append(y)
            state = lax.ppermute(y, AXIS_PP, fwd)

        # Completed microbatch m = last stage's output at step m + pp - 1.
        outs = jnp.stack([collected[m + pp - 1] for m in range(n_micro)])
        hidden = rms_norm(outs, stacked["final_norm"], config.norm_eps)
        logits = (
            hidden.reshape(B, S, config.d_model) @ stacked["lm_head"]
        ).astype(jnp.float32)
        total, count = masked_nll(logits, targets, ignore_index)
        nll = total / jnp.maximum(count, 1)
        # Only the last stage saw real outputs; zero the others and psum so
        # the scalar is identical (replicated) on every pp rank.
        nll = jnp.where(rank == pp - 1, nll, 0.0)
        return lax.psum(nll, AXIS_PP)

    def loss(stacked, tokens, targets):
        mapped = shard_map(
            fn,
            mesh=mesh,
            in_specs=(pp_sharding_spec(stacked), P(), P()),
            out_specs=P(),
            check_vma=False,
        )
        return mapped(stacked, tokens, targets)

    return loss
