"""Sharding rules: map parameter-tree paths to PartitionSpecs.

The TPU-native replacement for the reference's wrapper-class parallelism
(reference: train/torch/train_loop_utils.py prepare_model DDP/FSDP wrapping;
train/lightning/_lightning_utils.py RayFSDPStrategy): instead of wrapping
modules, parameters are annotated with PartitionSpecs by regex rules over
their tree path, and the training programs say where their activations
live (``constrain``); pjit/XLA derives the collectives from the two.
DP→FSDP→TP are points on the same rule table, not different code paths.
"""

from __future__ import annotations

import math
import re
from typing import Any, Dict, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import AXIS_DP, AXIS_FSDP, AXIS_SP, AXIS_TP


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


class ShardingRules:
    """Ordered (regex → PartitionSpec) table.  First match wins; default is
    full replication."""

    def __init__(self, rules: Sequence[Tuple[str, P]], default: P = P()):
        self.rules = [(re.compile(pat), spec) for pat, spec in rules]
        self.default = default

    def spec_for(self, path: str, ndim: int) -> P:
        for pat, spec in self.rules:
            if pat.search(path):
                return _clip_spec(spec, ndim)
        return _clip_spec(self.default, ndim)

    def tree_specs(self, tree: Any) -> Any:
        """PartitionSpec pytree matching `tree`."""
        return jax.tree_util.tree_map_with_path(
            lambda path, x: self.spec_for(_path_str(path), getattr(x, "ndim", 0)),
            tree,
        )


def _clip_spec(spec: P, ndim: int) -> P:
    if len(spec) <= ndim:
        return spec
    return P(*spec[:ndim])


def infer_param_specs(params: Any, rules: ShardingRules) -> Any:
    return rules.tree_specs(params)


def named_sharding(mesh: Mesh, spec_tree: Any) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_pytree(tree: Any, mesh: Mesh, rules: ShardingRules) -> Any:
    """Place a host pytree onto the mesh according to the rules."""
    shardings = named_sharding(mesh, rules.tree_specs(tree))
    return jax.device_put(tree, shardings)


#: Where a training program's activations live (``constrain``), by the axis
#: convention of ``mesh.py``: the batch over dp and fsdp, the sequence over
#: sp, and over tp whatever a megatron layer splits: heads, the FFN's
#: hidden units, the vocabulary.  The residual stream keeps its hidden
#: dimension whole, so under fsdp x tp a weight is gathered over fsdp, a
#: product is reduced over tp, and no activation moves between the two.
_BATCH = (AXIS_DP, AXIS_FSDP)
RESIDUAL = P(_BATCH, AXIS_SP, None)        # [B, S, d]
HEADS = P(_BATCH, AXIS_SP, AXIS_TP, None)  # [B, S, H, D]
SPLIT = P(_BATCH, AXIS_SP, AXIS_TP)        # [B, S, H*D | d_ff | vocab]
#: The embedding table [V, d] as it is looked up: its rows over tp, gathered
#: over fsdp like any weight (the rule table splits the rows over both).
VOCAB_ROWS = P(AXIS_TP, None)


def auto_mesh():
    """The ambient mesh (``jax.set_mesh``) where XLA's partitioner lays the
    program out; None with no mesh, and inside ``shard_map`` (manual axes),
    where the arrays are one shard's already."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty or mesh.manual_axes else mesh


def fit_spec(spec: P, shape: Sequence[int], sizes: Dict[str, int]) -> P:
    """``spec`` for an array of ``shape`` on a mesh of axis ``sizes``: an
    axis the mesh lacks is dropped, and a dimension its axes do not divide
    stays replicated."""
    def fit(dim, axes):
        if axes is None:
            return None
        axes = tuple(a for a in ((axes,) if isinstance(axes, str) else axes)
                     if a in sizes)
        fits = dim % math.prod(sizes[a] for a in axes) == 0
        return axes if axes and fits else None

    return P(*(fit(d, a) for d, a in zip(shape, spec, strict=True)))


def constrain(x, spec: P):
    """``x`` laid out as ``spec`` (``fit_spec``) under an ambient mesh; ``x``
    itself where there is none (``auto_mesh``): a program with no mesh, or
    traced inside ``shard_map``, traces to what it would without the
    call."""
    mesh = auto_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, fit_spec(spec, x.shape, mesh.shape))


#: The collective operations of XLA's HLO, as ``count_collectives`` counts
#: them.
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def count_collectives(compiled_text: str) -> Dict[str, int]:
    """Collective ops in a compiled program's text (``compiled.as_text()``),
    by kind; an asynchronous pair counts once, at its start.  An all-to-all
    in a dense fsdp x tp step means an activation moved between the two
    axes: the layout above no longer holds."""
    return {op: len(re.findall(rf"\b{op}(?:-start)?\(", compiled_text))
            for op in COLLECTIVES}
