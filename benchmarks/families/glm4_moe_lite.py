"""The glm4_moe_lite family (``"family": "glm4_moe_lite"``): zai-org's
GLM-4.7-Flash line as ``ray_tpu/models/moe.py`` computes it.  Latent
attention (q through a latent of ``q_lora_rank``, K and V through one of
``kv_lora_rank`` beside ONE rotary key of ``qk_rope_head_dim`` shared by the
heads; a head's q and k ``qk_nope_head_dim`` + ``qk_rope_head_dim`` wide, its
v ``v_head_dim``), the first ``first_k_dense_replace`` layers with a dense
SwiGLU of ``intermediate_size``, the others with ``n_routed_experts`` routed
SwiGLUs of ``moe_intermediate_size`` (sigmoid scores, the
``num_experts_per_tok`` largest of score + ``e_score_correction_bias`` taken,
their bare scores renormalised and scaled by ``routed_scaling_factor``) beside
``n_shared_experts`` that every token visits; RMSNorm, untied head, no bias.
``llama.py`` says what a family module is; the equations are at the top of
``reference/glm4_moe_lite_ref.py``.

The configuration files keep the published ``config.json`` keys.  What the
harness's own readers ask of a configuration under other names is in the
file beside them: ``num_experts`` (``layer_metrics/experts_hit_share_moe.py``),
``torch_dtype``.

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
    if "kv_lora_rank" not in _f.read():
        raise ImportError(
            "the glm4_moe_lite family needs a program with latent "
            "attention: ray_tpu/models/moe.py has no MoEConfig.kv_lora_rank "
            "(nor ffn_layout / n_shared_experts / router_score); this "
            "checkout's program predates the family")

#: ``configs/<this>.json`` is what a ``--rehearse`` run of any cell of this
#: family runs in place of the cell's configuration.
REHEARSAL_CONFIG = "glm4-moe-lite-tiny"

#: What ``ray_tpu/models/moe.py`` computes for this family.  A file that
#: asks for anything else is refused, not approximated.
SUPPORTED = {"rope_scaling": None, "tie_word_embeddings": False,
             "attention_bias": False, "hidden_act": "silu", "n_group": 1,
             "topk_group": 1, "topk_method": "noaux_tc",
             "partial_rotary_factor": 1}


def check_supported(model: Dict[str, Any]) -> None:
    for key, want in SUPPORTED.items():
        if key in model and model[key] != want:
            raise ValueError(
                f"configuration {model.get('name')!r} has {key}="
                f"{model[key]!r}; the glm4_moe_lite family computes only "
                f"{want!r}")
    if model["num_key_value_heads"] != model["num_attention_heads"]:
        raise ValueError("latent attention has a key for every head: "
                         "num_key_value_heads is not num_attention_heads")
    if not 0 <= model["first_k_dense_replace"] <= model["num_hidden_layers"]:
        raise ValueError("first_k_dense_replace is not in 0..layers")
    experts = model["n_routed_experts"]
    if not 1 <= model["num_experts_per_tok"] <= experts:
        raise ValueError("num_experts_per_tok is not in 1..n_routed_experts")
    if model.get("num_experts", experts) != experts:
        raise ValueError("num_experts (the harness's name) differs from "
                         "n_routed_experts")


# ------------------------------------------------------------------ counts


def _bytes(model: Dict[str, Any]) -> int:
    return {"bfloat16": 2, "float32": 4}[model["torch_dtype"]]


def _layers(model: Dict[str, Any]):
    """(dense layers, routed layers)."""
    dense = model["first_k_dense_replace"]
    return dense, model["num_hidden_layers"] - dense


def _attention_params(model: Dict[str, Any]) -> int:
    """One layer's attention: the two down-projections and their norms,
    the two up-projections, the output projection."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    rq, rkv = model["q_lora_rank"], model["kv_lora_rank"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    v = model["v_head_dim"]
    return (d * rq + rq + rq * h * (nope + rope) + d * (rkv + rope) + rkv
            + rkv * h * (nope + v) + h * v * d)


def _expert_params(model: Dict[str, Any]) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def param_count(model: Dict[str, Any]) -> int:
    """Every parameter: all experts, the shared ones, router and selection
    bias, the two norms of a layer and the final one, embedding and head
    (no JAX)."""
    d, e = model["hidden_size"], model["n_routed_experts"]
    dense, routed = _layers(model)
    per_routed = d * e + e \
        + (e + model["n_shared_experts"]) * _expert_params(model)
    return (2 * model["vocab_size"] * d + d
            + model["num_hidden_layers"] * (_attention_params(model) + 2 * d)
            + dense * 3 * d * model["intermediate_size"]
            + routed * per_routed)


def matmul_params(model: Dict[str, Any]) -> int:
    """The ACTIVE parameters a token multiplies with: attention, the dense
    layers' FFN, the router, the experts a token reaches and the shared
    ones, the head."""
    d = model["hidden_size"]
    dense, routed = _layers(model)
    attn = _attention_params(model) - model["q_lora_rank"] \
        - model["kv_lora_rank"]
    active = model["num_experts_per_tok"] + model["n_shared_experts"]
    return (model["num_hidden_layers"] * attn
            + dense * 3 * d * model["intermediate_size"]
            + routed * (d * model["n_routed_experts"]
                        + active * _expert_params(model))
            + d * model["vocab_size"])


def train_flops_per_token(model: Dict[str, Any], seq: int) -> float:
    """6 per active matmul parameter plus causal attention in the expanded
    form: a head's scores over ``nope + rope`` and its values over
    ``v_head_dim``, ``seq / 2`` keys a query on average."""
    per_head = model["qk_nope_head_dim"] + model["qk_rope_head_dim"] \
        + model["v_head_dim"]
    return 6.0 * matmul_params(model) + 6.0 * model["num_hidden_layers"] \
        * model["num_attention_heads"] * per_head * seq / 2.0


def train_step_kernel_ops_bytes(model, batch, seq, tpu_custom_calls):
    # As SmallThinker's: no cell trains this family (the full forward is
    # plain masked attention: there is no flash kernel for keys and values
    # of two widths, ROADMAP M3).
    raise NotImplementedError("no cell trains the glm4_moe_lite family")


def routed_ffn_ops_bytes(model: Dict[str, Any], pairs: int,
                         experts_hit: int) -> Dict[str, float]:
    """What the grouped products of the ROUTED experts must do for
    ``pairs`` (token, expert) pairs that reach ``experts_hit`` experts
    (both summed over the routed layers, as the program's counters are):
    three products of ``hidden x expert width`` a pair, each hit expert's
    three matrices read once, each pair's input row read and output row
    written once.  The shared expert is a plain product, no
    ``ragged-dot``, and is not in here."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    return {"ops": pairs * 3 * 2.0 * d * f,
            "bytes": (experts_hit * 3 * d * f + pairs * 2 * d)
            * _bytes(model)}


def latent_row_bytes(model: Dict[str, Any]) -> int:
    """What the cache keeps of one token on one layer:
    ``[norm(c_kv) ; RoPE(k_r)]``."""
    return (model["kv_lora_rank"] + model["qk_rope_head_dim"]) \
        * _bytes(model)


def decode_floor_bytes(model: Dict[str, Any], experts_hit: int,
                       kv_rows_distinct: int) -> float:
    """The bytes ANY program must read to compute one decode step's
    tokens: every weight a step multiplies with once (attention, the dense
    FFN, routers and biases in float32, shared experts, norms, the head;
    of the embedding only the slots' rows, left out), ``experts_hit``
    routed experts (summed over the layers), and ``kv_rows_distinct``
    latent rows (summed over the layers; a page that several slots share
    counted once).  Activations are left out: a floor."""
    d, e, b = model["hidden_size"], model["n_routed_experts"], _bytes(model)
    dense, routed = _layers(model)
    shared = model["n_shared_experts"] * _expert_params(model)
    weights = (d * model["vocab_size"] + d
               + model["num_hidden_layers"]
               * (_attention_params(model) + 2 * d)
               + dense * 3 * d * model["intermediate_size"]
               + routed * shared) * b + routed * (d * e + e) * 4
    return float(weights + experts_hit * _expert_params(model) * b
                 + kv_rows_distinct * latent_row_bytes(model))


# ----------------------------------------------------------------- program


def program_config(model: Dict[str, Any], *, max_seq: int, **overrides):
    """The ``MoEConfig`` for ``model`` (a loaded configuration file)."""
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
        q_lora_rank=model["q_lora_rank"], kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        ffn_layout=tuple(int(i >= model["first_k_dense_replace"])
                         for i in range(layers)),
        dense_d_ff=model["intermediate_size"],
        d_ff=model["moe_intermediate_size"],
        n_experts=model["n_routed_experts"],
        top_k=model["num_experts_per_tok"],
        n_shared_experts=model["n_shared_experts"],
        # ``topk_method`` noaux_tc: sigmoid scores, the bias in the choice.
        router_score="sigmoid",
        norm_topk_prob=bool(model["norm_topk_prob"]),
        routed_scaling_factor=float(model["routed_scaling_factor"]),
        max_seq=max_seq, rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]), dtype=dtype, **overrides)


def init(cfg, key):
    from ray_tpu.models import moe_init

    return moe_init(cfg, key)


def loss(cfg, params, tokens, targets):
    from ray_tpu.models import moe_loss

    return moe_loss(cfg, params, tokens, targets)


def sharding_rules(cfg):
    from ray_tpu.models import moe_sharding_rules

    return moe_sharding_rules()


def register(model: Dict[str, Any], *, max_seq: int) -> str:
    """Make the engine serve ``model`` under the name this returns.  Called
    inside the replica, before ``LLMServer.__init__``."""
    cfg = program_config(model, max_seq=max_seq, remat=False)
    _engine.register_model(model["name"], lambda: cfg)
    return model["name"]


# --------------------------------------------------------------- reference


def reference(model: Dict[str, Any], params, device=None):
    """The plain reference over the system's own parameter tree: an object
    with ``logits(tokens, positions)`` and
    ``loss_and_grad_norm(tokens, targets)``."""
    from ..reference.glm4_moe_lite_ref import Reference

    return Reference(model, params, device)
