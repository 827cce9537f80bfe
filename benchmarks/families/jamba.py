"""The jamba family (``"family": "jamba"``, ``model_type`` ``jamba``): AI21's
Jamba line at ``num_experts`` 1 (Jamba2-3B, Jamba Reasoning 3B) as
``ray_tpu/models/moe.py``, ``mamba.py`` and ``paged.py`` compute it.  Layer
``i`` (0-indexed) is an ATTENTION layer where ``i % attn_layer_period ==
attn_layer_offset`` (multi-query or grouped K/V rows, whole length, NO
rotary and no other position signal, no QK-norm) and a MAMBA-1 layer
otherwise (``mamba_expand`` x hidden channels, a ``mamba_d_state`` float32
state a channel and sequence behind a causal depthwise convolution of
``mamba_d_conv`` taps with a bias, the step through ``mamba_dt_rank``, three
inner RMSNorms, no cache rows); every layer's FFN is the dense SwiGLU of
``intermediate_size`` (``num_experts`` 1: the ``expert_layer_*`` keys select
nothing); RMSNorm, no bias in any projection, the head TIED to the
embedding.  ``llama.py`` says what a family module is; the equations are at
the top of ``reference/jamba_ref.py``.

The configuration files keep the published ``config.json`` keys;
``torch_dtype`` is in the file beside them.

No JAX is imported here at the top: the parent process reads the counts,
and only the process that holds the chip calls what builds a program or a
reference.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List

from ray_tpu.serve import engine as _engine  # imports no JAX

with open(os.path.join(os.path.dirname(os.path.dirname(_engine.__file__)),
                       "models", "moe.py")) as _f:
    # Read, not imported (that would import JAX), and here, where the
    # harness finds the family (``spec.load_cell``), before it starts a
    # process: a replica that failed in its constructor would be started
    # again and again until the deployment's time ran out.
    _moe = _f.read()
    if not all(field in _moe for field in ("ssm_inner", "tie_embeddings")):
        raise ImportError(
            "the jamba family needs a program with state-space layers "
            "beside K/V layers and a tied head: ray_tpu/models/moe.py has "
            "no MoEConfig.ssm_inner / tie_embeddings; this checkout's "
            "program predates the family")
    del _moe

#: ``configs/<this>.json`` is what a ``--rehearse`` run of any cell of this
#: family runs in place of the cell's configuration.
REHEARSAL_CONFIG = "jamba-tiny"

#: What the program computes for this family.  A file that asks for
#: anything else is refused, not approximated.
SUPPORTED = {"num_experts": 1, "num_experts_per_tok": 1,
             "sliding_window": None, "tie_word_embeddings": True,
             "hidden_act": "silu", "mamba_proj_bias": False,
             "mamba_conv_bias": True}
#: Keys of a position signal: Jamba's attention has none, and a file that
#: states one asks for another model.
ROTARY_KEYS = ("rope_theta", "rope_scaling", "rope_parameters",
               "partial_rotary_factor")


def layer_kinds(model: Dict[str, Any]) -> List[str]:
    """``"kv"`` (attention) | ``"ssm"`` (Mamba) for each layer."""
    period, offset = model["attn_layer_period"], model["attn_layer_offset"]
    return ["kv" if i % period == offset else "ssm"
            for i in range(model["num_hidden_layers"])]


def check_supported(model: Dict[str, Any]) -> None:
    for key, want in SUPPORTED.items():
        if key in model and model[key] != want:
            raise ValueError(
                f"configuration {model.get('name')!r} has {key}="
                f"{model[key]!r}; the jamba family computes only {want!r}")
    for key in ROTARY_KEYS:
        if model.get(key) is not None:
            raise ValueError(
                f"configuration {model.get('name')!r} has {key}: the jamba "
                f"family's attention has no position signal")
    if model["hidden_size"] % model["num_attention_heads"] \
            or model["num_attention_heads"] % model["num_key_value_heads"]:
        raise ValueError("hidden_size is not whole heads, or the heads are "
                         "not whole groups of the K/V heads")
    if model["mamba_d_conv"] < 2:
        raise ValueError("mamba_d_conv under 2: the program's Mamba layer "
                         "has a convolution")
    kinds = layer_kinds(model)
    if "kv" not in kinds or "ssm" not in kinds:
        raise ValueError("the jamba family has layers of both kinds: "
                         "attn_layer_period / attn_layer_offset give one")


# ------------------------------------------------------------------ counts


def _bytes(model: Dict[str, Any]) -> int:
    return {"bfloat16": 2, "float32": 4}[model["torch_dtype"]]


def _inner(model: Dict[str, Any]) -> int:
    return model["mamba_expand"] * model["hidden_size"]


def _mamba_params(model: Dict[str, Any]) -> int:
    """One Mamba mixer: W_in; the convolution and its bias; W_x; the three
    inner norms; W_dt and its bias; A_log; D; W_out."""
    d, i = model["hidden_size"], _inner(model)
    n, r = model["mamba_d_state"], model["mamba_dt_rank"]
    return (d * 2 * i + model["mamba_d_conv"] * i + i + i * (r + 2 * n)
            + r + 2 * n + r * i + i + i * n + i + i * d)


def _attn_params(model: Dict[str, Any]) -> int:
    """One attention mixer: W_q, W_k, W_v, W_o."""
    d = model["hidden_size"]
    hd = d // model["num_attention_heads"]
    return 2 * d * d + 2 * d * model["num_key_value_heads"] * hd


def _ffn_params(model: Dict[str, Any]) -> int:
    return 3 * model["hidden_size"] * model["intermediate_size"]


def _layers(model: Dict[str, Any]):
    """(Mamba layers, attention layers)."""
    kinds = layer_kinds(model)
    return kinds.count("ssm"), kinds.count("kv")


def param_count(model: Dict[str, Any]) -> int:
    """Every parameter: the mixers, a SwiGLU and two norms a layer, the
    final norm, and the embedding ONCE (it is the head) (no JAX)."""
    d = model["hidden_size"]
    ssm, kv = _layers(model)
    return (model["vocab_size"] * d + d
            + ssm * _mamba_params(model) + kv * _attn_params(model)
            + model["num_hidden_layers"] * (_ffn_params(model) + 2 * d))


def matmul_params(model: Dict[str, Any]) -> int:
    """Parameters a token multiplies with: every projection of both kinds
    of mixer, the SwiGLUs, and the tied head (the embedding's lookup is not
    a product; its use as the head is)."""
    d, i = model["hidden_size"], _inner(model)
    n, r = model["mamba_d_state"], model["mamba_dt_rank"]
    ssm, kv = _layers(model)
    return (ssm * (d * 2 * i + i * (r + 2 * n) + r * i + i * d)
            + kv * _attn_params(model)
            + model["num_hidden_layers"] * _ffn_params(model)
            + d * model["vocab_size"])


def train_flops_per_token(model, seq):
    raise NotImplementedError("no cell trains the jamba family")


def train_step_kernel_ops_bytes(model, batch, seq, tpu_custom_calls):
    # No cell trains this family: the chunk form's scan has no backward
    # that fits (ROADMAP M8).
    raise NotImplementedError("no cell trains the jamba family")


def kv_row_bytes(model: Dict[str, Any]) -> int:
    """What the cache keeps of one token on one ATTENTION layer: K and V of
    the K/V heads."""
    hd = model["hidden_size"] // model["num_attention_heads"]
    return 2 * model["num_key_value_heads"] * hd * _bytes(model)


def state_slot_bytes(model: Dict[str, Any]) -> int:
    """What one sequence keeps on the Mamba layers together: a float32
    state of ``mamba_d_state`` a channel, and the last ``mamba_d_conv - 1``
    rows of the convolution's input."""
    i = _inner(model)
    return _layers(model)[0] * (
        i * model["mamba_d_state"] * 4
        + (model["mamba_d_conv"] - 1) * i * _bytes(model))


def decode_floor_bytes(model: Dict[str, Any], kv_rows_distinct: int,
                       occupancy: int) -> float:
    """The bytes ANY program must move to compute one decode step's tokens:
    every weight once (the embedding among them: it is the head, which a
    step multiplies with whole; A_log, D and dt_bias in float32),
    ``kv_rows_distinct`` rows of K and V (summed over the attention layers),
    and the recurrent state of the ``occupancy`` live slots read once and
    written once.  Activations are left out: a floor."""
    i = _inner(model)
    f32 = _layers(model)[0] * (i * model["mamba_d_state"] + 2 * i)
    weights = (param_count(model) - f32) * _bytes(model) + f32 * 4
    return float(weights + kv_rows_distinct * kv_row_bytes(model)
                 + occupancy * 2 * state_slot_bytes(model))


# ----------------------------------------------------------------- program


def program_config(model: Dict[str, Any], *, max_seq: int, **overrides):
    """The ``MoEConfig`` for ``model`` (a loaded configuration file): the
    configuration object that reads a layout of attention kinds, with no
    routed layer in it (``ffn_layout`` all dense)."""
    import jax.numpy as jnp

    from ray_tpu.models import MoEConfig

    check_supported(model)
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        model["torch_dtype"]]
    layers = model["num_hidden_layers"]
    return MoEConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=layers, n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        attn_layout=tuple(layer_kinds(model)),
        ssm_inner=_inner(model), ssm_state=model["mamba_d_state"],
        ssm_dt_rank=model["mamba_dt_rank"], ssm_conv=model["mamba_d_conv"],
        rope_layout=(0,) * layers,  # no layer rotates
        ffn_layout=(0,) * layers,   # num_experts 1: no layer is routed
        dense_d_ff=model["intermediate_size"], d_ff=model["intermediate_size"],
        n_experts=1, top_k=1, tie_embeddings=True,
        max_seq=max_seq, norm_eps=float(model["rms_norm_eps"]), dtype=dtype,
        **overrides)


def init(cfg, key):
    from ray_tpu.models import moe_init

    return moe_init(cfg, key)


def register(model: Dict[str, Any], *, max_seq: int) -> str:
    """Make the engine serve ``model`` under the name this returns.  Called
    inside the replica, before ``LLMServer.__init__``."""
    cfg = program_config(model, max_seq=max_seq, remat=False)
    _engine.register_model(model["name"], lambda: cfg)
    return model["name"]


# --------------------------------------------------------------- reference


def reference(model: Dict[str, Any], params, device=None):
    """The plain reference over the system's own parameter tree: an object
    with ``logits(tokens, positions)``."""
    from ..reference.jamba_ref import Reference

    return Reference(model, params, device)
