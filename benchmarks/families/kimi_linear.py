"""The kimi_linear family (``"family": "kimi_linear"``): moonshotai's
Kimi-Linear line (Kimi-Linear-48B-A3B) as ``ray_tpu/models/moe.py``,
``kda.py`` and ``paged.py`` compute it.  ``linear_attn_config.kda_layers``
and ``full_attn_layers`` (1-indexed) give every layer its KIND of attention:
a gated delta-rule layer (``num_heads`` heads of ``head_dim`` behind a
causal depthwise convolution over ``short_conv_kernel_size`` positions, a
float32 matrix state a head and sequence, no cache rows) or latent attention
with NO q latent (``q_lora_rank`` null) and NO rotation (``mla_use_nope``);
the first ``first_k_dense_replace`` layers with a dense SwiGLU of
``intermediate_size``, the others with routed SwiGLUs of
``moe_intermediate_size`` (sigmoid scores, the ``num_experts_per_token``
largest of score + bias taken, their bare scores renormalised and scaled by
``routed_scaling_factor``) beside ``num_shared_experts`` that every token
visits; RMSNorm, untied head, no bias.  ``llama.py`` says what a family
module is; the equations are at the top of ``reference/kimi_linear_ref.py``.

A file of this family may hold a chip's SHARE of the model: ``num_experts``
is the experts held (``first_expert`` .. + ``num_experts`` of the
``router_experts`` the router scores; absent: all of them), and
``vocab_size`` the rows of embedding and head held.  The counts below are of
what the file holds; nothing stands in for the rest.

The configuration files keep the published ``config.json`` keys, among them
``num_experts`` (``layer_metrics/experts_hit_share_moe.py`` reads it: the
held ones, which are what a step's ``experts_hit`` can reach),
``kv_lora_rank`` and ``qk_rope_head_dim``
(``layer_metrics/latent_decode_roofline_mla.py``); ``torch_dtype`` is in the
file beside them.

No JAX is imported here at the top: the parent process reads the counts,
and only the process that holds the chip calls what builds a program or a
reference.
"""

from __future__ import annotations

import os
from typing import Any, Dict

from ray_tpu.serve import engine as _engine  # imports no JAX

with open(os.path.join(os.path.dirname(os.path.dirname(_engine.__file__)),
                       "models", "moe.py")) as _f:
    # Read, not imported (that would import JAX), and here, where the
    # harness finds the family (``spec.load_cell``), before it starts a
    # process: a replica that failed in its constructor would be started
    # again and again until the deployment's time ran out.
    _moe = _f.read()
    if not all(field in _moe for field in ("attn_layout", "kda_heads",
                                           "router_experts")):
        raise ImportError(
            "the kimi_linear family needs a program with gated delta-rule "
            "layers beside latent ones and a share of the experts: "
            "ray_tpu/models/moe.py has no MoEConfig.attn_layout / kda_heads "
            "/ router_experts; this checkout's program predates the family")
    del _moe

#: ``configs/<this>.json`` is what a ``--rehearse`` run of any cell of this
#: family runs in place of the cell's configuration.
REHEARSAL_CONFIG = "kimi-linear-tiny"

#: What the program computes for this family.  A file that asks for
#: anything else is refused, not approximated.
SUPPORTED = {"rope_scaling": None, "tie_word_embeddings": False,
             "hidden_act": "silu", "q_lora_rank": None, "mla_use_nope": True,
             "moe_router_activation_func": "sigmoid", "num_expert_group": 1,
             "topk_group": 1, "moe_layer_freq": 1,
             "num_nextn_predict_layers": 0}


def layer_kinds(model: Dict[str, Any]):
    """``"kda"`` | ``"latent"`` for each layer, from the two 1-indexed
    lists, which have to name every layer once."""
    lin = model["linear_attn_config"]
    layers = range(1, model["num_hidden_layers"] + 1)
    kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    if kda & full or kda | full != set(layers):
        raise ValueError(
            f"kda_layers and full_attn_layers of {model.get('name')!r} do "
            f"not name each of its {len(layers)} layers once")
    return ["kda" if i in kda else "latent" for i in layers]


def check_supported(model: Dict[str, Any]) -> None:
    for key, want in SUPPORTED.items():
        if key in model and model[key] != want:
            raise ValueError(
                f"configuration {model.get('name')!r} has {key}="
                f"{model[key]!r}; the kimi_linear family computes only "
                f"{want!r}")
    layer_kinds(model)
    if model["num_key_value_heads"] != model["num_attention_heads"]:
        raise ValueError("latent attention has a key for every head: "
                         "num_key_value_heads is not num_attention_heads")
    if model["linear_attn_config"]["short_conv_kernel_size"] < 2:
        raise ValueError("short_conv_kernel_size under 2: the program's "
                         "KDA layer has a convolution")
    if not 0 <= model["first_k_dense_replace"] <= model["num_hidden_layers"]:
        raise ValueError("first_k_dense_replace is not in 0..layers")
    held, routed = model["num_experts"], _router_experts(model)
    if not 0 <= model.get("first_expert", 0) <= routed - held:
        raise ValueError("first_expert .. + num_experts are not among the "
                         "router's experts")
    if not 1 <= model["num_experts_per_token"] <= routed:
        raise ValueError("num_experts_per_token is not in 1..router_experts")


# ------------------------------------------------------------------ counts


def _bytes(model: Dict[str, Any]) -> int:
    return {"bfloat16": 2, "float32": 4}[model["torch_dtype"]]


def _router_experts(model: Dict[str, Any]) -> int:
    return model.get("router_experts", model["num_experts"])


def _layers(model: Dict[str, Any]):
    """(dense layers, routed layers, KDA layers, latent layers)."""
    dense = model["first_k_dense_replace"]
    kinds = layer_kinds(model)
    return (dense, model["num_hidden_layers"] - dense, kinds.count("kda"),
            kinds.count("latent"))


def _kda_params(model: Dict[str, Any]) -> int:
    """One KDA layer's attention: W_q, W_k, W_v, W_o; the two low-rank
    pairs (decay, output gate) through ``head_dim``; W_b; the three
    convolutions; A_log, dt_bias and the head norm."""
    d, lin = model["hidden_size"], model["linear_attn_config"]
    h, hd = lin["num_heads"], lin["head_dim"]
    w = h * hd
    return (4 * d * w + 2 * (d * hd + hd * w) + d * h
            + 3 * lin["short_conv_kernel_size"] * w + h + w + hd)


def _latent_params(model: Dict[str, Any]) -> int:
    """One latent layer's attention: W_q (no latent), the K/V
    down-projection and its norm, the up-projection, the output."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    rkv = model["kv_lora_rank"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    v = model["v_head_dim"]
    return (d * h * (nope + rope) + d * (rkv + rope) + rkv
            + rkv * h * (nope + v) + h * v * d)


def _expert_params(model: Dict[str, Any]) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def param_count(model: Dict[str, Any]) -> int:
    """Every parameter the file HOLDS: the held experts, the shared ones,
    the router and selection bias over all the router's experts, the two
    norms of a layer and the final one, the held rows of embedding and
    head (no JAX)."""
    d, r = model["hidden_size"], _router_experts(model)
    dense, routed, kda, latent = _layers(model)
    per_routed = d * r + r + (model["num_experts"]
                              + model["num_shared_experts"]) \
        * _expert_params(model)
    return (2 * model["vocab_size"] * d + d
            + kda * _kda_params(model) + latent * _latent_params(model)
            + model["num_hidden_layers"] * 2 * d
            + dense * 3 * d * model["intermediate_size"]
            + routed * per_routed)


def train_flops_per_token(model, seq):
    raise NotImplementedError("no cell trains the kimi_linear family")


def train_step_kernel_ops_bytes(model, batch, seq, tpu_custom_calls):
    # No cell trains this family: the chunk form's scan has no backward
    # that fits (ROADMAP M8), and a chip holds a share of the experts.
    raise NotImplementedError("no cell trains the kimi_linear family")


def routed_ffn_ops_bytes(model: Dict[str, Any], pairs: int,
                         experts_hit: int) -> Dict[str, float]:
    """What the grouped products of the HELD routed experts must do for
    ``pairs`` (token, expert) pairs that land on ``experts_hit`` of them
    (both summed over the routed layers, as the program's counters
    ``expert_pairs`` and ``experts_hit`` are: a share's count its own): three
    products of ``hidden x expert width`` a pair, each hit expert's three
    matrices read once, each pair's input row read and output row written
    once.  The shared expert is a plain product and is not in here."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    return {"ops": pairs * 3 * 2.0 * d * f,
            "bytes": (experts_hit * 3 * d * f + pairs * 2 * d)
            * _bytes(model)}


def latent_row_bytes(model: Dict[str, Any]) -> int:
    """What the cache keeps of one token on one LATENT layer:
    ``[norm(c_kv) ; k_r]``."""
    return (model["kv_lora_rank"] + model["qk_rope_head_dim"]) \
        * _bytes(model)


def state_slot_bytes(model: Dict[str, Any]) -> int:
    """What one sequence keeps on the KDA layers together: a float32
    ``head_dim x head_dim`` state a head, and the last
    ``short_conv_kernel_size - 1`` rows of the three convolutions'
    inputs."""
    lin = model["linear_attn_config"]
    h, hd = lin["num_heads"], lin["head_dim"]
    kda = _layers(model)[2]
    return kda * (h * hd * hd * 4 + (lin["short_conv_kernel_size"] - 1)
                  * 3 * h * hd * _bytes(model))


def decode_floor_bytes(model: Dict[str, Any], experts_hit: int,
                       kv_rows_distinct: int, occupancy: int) -> float:
    """The bytes ANY program must move to compute one decode step's
    tokens: every held weight a step multiplies with once (both kinds of
    attention, the dense FFN, routers and biases in float32, shared
    experts, norms, the head; of the embedding only the slots' rows, left
    out), ``experts_hit`` held routed experts (summed over the layers),
    ``kv_rows_distinct`` latent rows (summed over the latent layers), and
    the recurrent state of the ``occupancy`` live slots read once and
    written once.  Activations are left out: a floor."""
    d, r, b = model["hidden_size"], _router_experts(model), _bytes(model)
    dense, routed, kda, latent = _layers(model)
    shared = model["num_shared_experts"] * _expert_params(model)
    weights = (d * model["vocab_size"] + d
               + kda * _kda_params(model) + latent * _latent_params(model)
               + model["num_hidden_layers"] * 2 * d
               + dense * 3 * d * model["intermediate_size"]
               + routed * shared) * b + routed * (d * r + r) * 4
    return float(weights + experts_hit * _expert_params(model) * b
                 + kv_rows_distinct * latent_row_bytes(model)
                 + occupancy * 2 * state_slot_bytes(model))


# ----------------------------------------------------------------- program


def program_config(model: Dict[str, Any], *, max_seq: int, **overrides):
    """The ``MoEConfig`` for ``model`` (a loaded configuration file)."""
    import jax.numpy as jnp

    from ray_tpu.models import MoEConfig

    check_supported(model)
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        model["torch_dtype"]]
    layers, lin = model["num_hidden_layers"], model["linear_attn_config"]
    return MoEConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=layers, n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        attn_layout=tuple(layer_kinds(model)),
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        kda_conv=lin["short_conv_kernel_size"],
        q_lora_rank=0, kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        rope_layout=(0,) * layers,  # ``mla_use_nope``: no layer rotates
        ffn_layout=tuple(int(i >= model["first_k_dense_replace"])
                         for i in range(layers)),
        dense_d_ff=model["intermediate_size"],
        d_ff=model["moe_intermediate_size"], n_experts=model["num_experts"],
        router_experts=model.get("router_experts", 0),
        first_expert=model.get("first_expert", 0),
        top_k=model["num_experts_per_token"],
        n_shared_experts=model["num_shared_experts"],
        router_score="sigmoid", norm_topk_prob=bool(model["moe_renormalize"]),
        routed_scaling_factor=float(model["routed_scaling_factor"]),
        max_seq=max_seq, rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]), dtype=dtype, **overrides)


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
    from ..reference.kimi_linear_ref import Reference

    return Reference(model, params, device)
