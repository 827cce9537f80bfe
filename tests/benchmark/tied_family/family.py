"""A toy family for the tests, added to a temporary copy as
``benchmarks/families/tied.py``: llama's block with the output head tied to
the embedding, so the weight tree has no ``lm_head`` and the embedding's
gradient is the sum of the lookup's and the head's.  The dense reference
cannot stand in for it: it finds no ``lm_head``, and given one it would
count that gradient twice.  The body and its counts are llama's, so those
are taken from where the llama family takes them."""

from __future__ import annotations

from typing import Any, Dict

# The tied head still multiplies: only the storage is shared.
from ..arith import matmul_params, train_flops_per_token  # noqa: F401
from ..modelcfg import llama_config

REHEARSAL_CONFIG = "tied-tiny"


def check_supported(model: Dict[str, Any]) -> None:
    if model.get("tie_word_embeddings") is not True:
        raise ValueError(f"configuration {model.get('name')!r} is of family "
                         f"tied and does not tie its head")


def param_count(model: Dict[str, Any]) -> int:
    """The embedding is the head and is counted once; then the norms."""
    return matmul_params(model) \
        + (2 * model["num_hidden_layers"] + 1) * model["hidden_size"]


def train_step_kernel_ops_bytes(model, batch, seq, tpu_custom_calls):
    raise NotImplementedError("the toy family never runs on a chip")


def program_config(model: Dict[str, Any], *, max_seq: int, **overrides):
    """The body is llama's, so the program's ``LlamaConfig`` describes it;
    ``init`` and ``loss`` below tie the head around it."""
    check_supported(model)
    return llama_config({**model, "tie_word_embeddings": False},
                        max_seq=max_seq, **overrides)


def init(cfg, key):
    from ray_tpu.models import llama_init

    params = llama_init(cfg, key)
    del params["lm_head"]
    return params


def loss(cfg, params, tokens, targets):
    from ray_tpu.models import llama_loss

    return llama_loss(cfg, {**params, "lm_head": params["embed"].T},
                      tokens, targets)


def sharding_rules(cfg):
    from ray_tpu.models import llama_sharding_rules

    return llama_sharding_rules()


def register(model: Dict[str, Any], *, max_seq: int) -> str:
    """All a family can do today: the engine makes its weights with
    ``llama_init`` whatever is registered, so what it serves has a head of
    its own and ``reference`` below says so."""
    from ray_tpu.serve import engine

    cfg = program_config(model, max_seq=max_seq, remat=False)
    engine._MODEL_BUILDERS[model["name"]] = lambda: cfg
    return model["name"]


def reference(model: Dict[str, Any], params, device=None):
    from ..reference.tied_ref import Reference

    return Reference(model, params, device)
