"""The llama family's way from a configuration file (the published
``config.json`` keys) to the program's ``LlamaConfig``, reached by the
harness through ``families/llama.py``.  ``llama_config`` imports JAX: only
the process that holds the chip (replica, train worker) and the tests call
it."""

from __future__ import annotations

from typing import Any, Dict

#: What the dense decoder in ``ray_tpu/models/llama.py`` computes.  A file
#: that asks for anything else is refused, not approximated.
SUPPORTED = {"hidden_act": "silu", "tie_word_embeddings": False,
             "sliding_window": None, "bias": False, "attention_dropout": 0.0}


def check_supported(model: Dict[str, Any]) -> None:
    for key, want in SUPPORTED.items():
        if key in model and model[key] != want:
            raise ValueError(
                f"configuration {model.get('name')!r} has {key}="
                f"{model[key]!r}; the llama family computes only {want!r}")
    if model["hidden_size"] % model["num_attention_heads"]:
        raise ValueError("hidden_size is not a multiple of the head count")


def param_count(model: Dict[str, Any]) -> int:
    """Parameters of the dense decoder, norms included (no JAX)."""
    d, f, v = (model["hidden_size"], model["intermediate_size"],
               model["vocab_size"])
    hd = d // model["num_attention_heads"]
    kv = model["num_key_value_heads"] * hd
    per_layer = 2 * d * d + 2 * d * kv + 3 * d * f + 2 * d
    return 2 * v * d + model["num_hidden_layers"] * per_layer + d


def llama_config(model: Dict[str, Any], *, max_seq: int, **overrides):
    """The ``LlamaConfig`` for ``model`` (a loaded configuration file).
    ``overrides`` are the cell's own choices among what ``LlamaConfig``
    already offers (remat, remat_policy, loss_chunk, flash blocks)."""
    import jax.numpy as jnp

    from ray_tpu.models import LlamaConfig

    check_supported(model)
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        model["torch_dtype"]]
    return LlamaConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        d_ff=model["intermediate_size"], max_seq=max_seq,
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]), dtype=dtype, **overrides)
