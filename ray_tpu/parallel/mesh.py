"""Device mesh construction for dp/fsdp/tp/sp/ep parallelism.

The mesh is the TPU-native replacement for the reference's process groups:
instead of wiring NCCL communicators per worker pair, a single logical mesh is
declared once and XLA inserts the right ICI/DCN collectives from the layouts
the program states: its parameters' (a rule table) and its activations'
(``sharding.constrain``) (the "How to Scale Your Model" recipe).

Axis convention (outer → inner, matching ICI locality preferences):
- dp:    pure data parallel (gradient psum, rides DCN across slices)
- fsdp:  sharded data parallel (params/optimizer sharded, all-gather on use)
- tp:    tensor parallel (megatron-style, wants the fastest ICI axis)
- sp:    sequence/context parallel (ring attention neighbors on ICI)
- ep:    expert parallel (MoE all_to_all)
"""

from __future__ import annotations

import dataclasses
import math
from typing import TYPE_CHECKING, Dict, Optional, Sequence

# JAX is imported where a mesh is BUILT, not where one is declared: a driver
# hands ``MeshConfig`` to its workers without importing JAX itself (the
# process that touches JAX holds the chip).
if TYPE_CHECKING:
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_DP = "dp"
AXIS_FSDP = "fsdp"
AXIS_TP = "tp"
AXIS_SP = "sp"
AXIS_EP = "ep"
AXIS_PP = "pp"

# Canonical axis order: dp outermost (cheapest to cross DCN), tp/sp/ep/pp
# innermost (highest-bandwidth ICI neighbors — ep's all_to_all and pp's
# stage-to-stage ppermute both want ICI adjacency).
MESH_AXES = (AXIS_DP, AXIS_FSDP, AXIS_TP, AXIS_SP, AXIS_EP, AXIS_PP)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Declarative mesh shape.  -1 for at most one axis means "all remaining
    devices"."""

    dp: int = 1
    fsdp: int = -1
    tp: int = 1
    sp: int = 1
    ep: int = 1
    pp: int = 1

    def resolve(self, n_devices: int) -> Dict[str, int]:
        sizes = {"dp": self.dp, "fsdp": self.fsdp, "tp": self.tp,
                 "sp": self.sp, "ep": self.ep, "pp": self.pp}
        wild = [k for k, v in sizes.items() if v == -1]
        if len(wild) > 1:
            raise ValueError("at most one mesh axis may be -1")
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if wild:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {sizes}"
                )
            sizes[wild[0]] = n_devices // fixed
        if math.prod(sizes.values()) != n_devices:
            raise ValueError(
                f"mesh {sizes} does not cover {n_devices} devices"
            )
        return sizes


def make_mesh(
    config: Optional[MeshConfig] = None,
    *,
    devices: Optional[Sequence] = None,
    axis_sizes: Optional[Dict[str, int]] = None,
) -> Mesh:
    """Build a Mesh over the given (default: all) devices.

    Device order follows jax.devices(), which enumerates TPU chips in
    torus-adjacent order — innermost mesh axes therefore land on ICI
    neighbors.
    """
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devices = list(devices if devices is not None else jax.devices())
    sizes = axis_sizes or (config or MeshConfig()).resolve(len(devices))
    shape = tuple(sizes.get(a, 1) for a in MESH_AXES)
    dev_array = np.array(devices).reshape(shape)
    return Mesh(dev_array, MESH_AXES)


def set_mesh(mesh: Mesh):
    """Install ``mesh`` as the ambient mesh (``jax.set_mesh``): the scope in
    which the model's Pallas kernels run per shard (models/llama.py)."""
    import jax

    return jax.set_mesh(mesh)


def batch_spec(sp_shard_seq: bool = False) -> P:
    """PartitionSpec for a [batch, seq, ...] input batch: batch over dp+fsdp,
    optionally sequence over sp (context parallelism)."""
    from jax.sharding import PartitionSpec as P

    return P((AXIS_DP, AXIS_FSDP), AXIS_SP if sp_shard_seq else None)


def data_sharding(mesh: Mesh, sp_shard_seq: bool = False) -> NamedSharding:
    from jax.sharding import NamedSharding

    return NamedSharding(mesh, batch_spec(sp_shard_seq))


def mesh_axis_size(mesh: Mesh, axis: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis, 1)


def local_batch_size(mesh: Mesh, global_batch: int) -> int:
    n = mesh_axis_size(mesh, AXIS_DP) * mesh_axis_size(mesh, AXIS_FSDP)
    if global_batch % n != 0:
        raise ValueError(f"global batch {global_batch} not divisible by {n}")
    return global_batch // n
