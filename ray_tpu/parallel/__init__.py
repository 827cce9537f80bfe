"""TPU parallelism: device meshes, sharding rules, collectives, and the
multi-host bootstrap.

This is the TPU-native replacement for the reference's NCCL/Gloo collective
stack (reference: python/ray/util/collective/) and torch process-group
bootstrap (reference: python/ray/train/torch/config.py:66
_setup_torch_process_group): the collective *data plane* is XLA ICI/DCN
collectives inside compiled programs; the host-level rendezvous is
jax.distributed keyed from cluster metadata.

Names resolve on first use (PEP 562): a driver that only declares a
``MeshConfig`` for its workers — and must stay off JAX so that the worker
it starts can own the chip — imports no JAX through this package.
"""

import importlib

_EXPORTS = {
    "AXIS_DP": "mesh", "AXIS_EP": "mesh", "AXIS_FSDP": "mesh",
    "AXIS_PP": "mesh", "AXIS_SP": "mesh", "AXIS_TP": "mesh",
    "MeshConfig": "mesh", "batch_spec": "mesh", "data_sharding": "mesh",
    "make_mesh": "mesh", "set_mesh": "mesh",
    "make_pp_loss": "pipeline", "stack_layers": "pipeline",
    "unstack_layers": "pipeline",
    "ShardingRules": "sharding", "infer_param_specs": "sharding",
    "named_sharding": "sharding", "shard_pytree": "sharding",
    "constrain": "sharding", "count_collectives": "sharding",
    "initialize_process_group": "distributed",
    "process_group_barrier": "distributed",
}

__all__ = list(_EXPORTS)


def __getattr__(name):
    module = _EXPORTS.get(name)
    if module is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    return getattr(importlib.import_module(f"{__name__}.{module}"), name)
