"""The afmoe family (``"family": "afmoe"``): arcee-ai's Trinity line
(Trinity-Mini 26B-A3B) as ``ray_tpu/models/moe.py`` computes it.  GQA with
heads of ``head_dim``, q and k RMS-normalised A HEAD, attention's heads
multiplied by a sigmoid gate of the layer's input before the output
projection; a sandwich-normed block (four norms a layer); ``layer_types[l]``
``sliding_attention`` gives layer ``l`` a window of ``sliding_window`` and
rotary, ``full_attention`` neither (no positional term at all); the first
``num_dense_layers`` layers with a dense SwiGLU of ``intermediate_size``, the
others with ``num_experts`` routed SwiGLUs of ``moe_intermediate_size``
(sigmoid scores, the ``num_experts_per_tok`` largest of score +
``expert_bias`` taken, their bare scores renormalised and scaled by
``route_scale``) beside ``num_shared_experts`` that every token visits; the
embedding scaled by ``sqrt(hidden_size)`` (``mup_enabled``); RMSNorm, untied
head, no bias.  ``llama.py`` says what a family module is; the equations are
at the top of ``reference/afmoe_ref.py``.

The configuration files keep the published ``config.json`` keys, among them
``num_experts``, which ``layer_metrics/experts_hit_share_moe.py`` reads;
``torch_dtype`` is in the file beside them.

No JAX is imported here at the top: the parent process reads the counts,
and only the process that holds the chip calls what builds a program or a
reference.
"""

from __future__ import annotations

import math
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
    if not all(field in _moe for field in ("attn_gate", "post_norm",
                                           "embed_scale")):
        raise ImportError(
            "the afmoe family needs a program with gated attention under "
            "a sandwich-normed block: ray_tpu/models/moe.py has no "
            "MoEConfig.attn_gate / post_norm / embed_scale (nor qk_norm "
            "\"head\"); this checkout's program predates the family")
    del _moe

#: ``configs/<this>.json`` is what a ``--rehearse`` run of any cell of this
#: family runs in place of the cell's configuration.
REHEARSAL_CONFIG = "trinity-mini-tiny"

#: What ``ray_tpu/models/moe.py`` computes for this family.  A file that
#: asks for anything else is refused, not approximated.
SUPPORTED = {"rope_scaling": None, "tie_word_embeddings": False,
             "hidden_act": "silu", "score_func": "sigmoid", "n_group": 1,
             "topk_group": 1, "num_expert_groups": 1,
             "num_limited_groups": 1}
LAYER_TYPES = ("sliding_attention", "full_attention")


def check_supported(model: Dict[str, Any]) -> None:
    for key, want in SUPPORTED.items():
        if key in model and model[key] != want:
            raise ValueError(
                f"configuration {model.get('name')!r} has {key}="
                f"{model[key]!r}; the afmoe family computes only {want!r}")
    layers, types = model["num_hidden_layers"], model["layer_types"]
    if len(types) != layers or any(t not in LAYER_TYPES for t in types):
        raise ValueError(
            f"layer_types of {model.get('name')!r} is not one of "
            f"{LAYER_TYPES} for each of its {layers} layers")
    every = model.get("global_attn_every_n_layers")
    if every and any((t == "full_attention") != ((i + 1) % every == 0)
                     for i, t in enumerate(types)):
        raise ValueError("layer_types is not global_attn_every_n_layers' "
                         "pattern")
    if "sliding_attention" in types and model["sliding_window"] <= 0:
        raise ValueError("sliding_window is not positive")
    if model["num_attention_heads"] % model["num_key_value_heads"]:
        raise ValueError("num_attention_heads is not a multiple of "
                         "num_key_value_heads")
    if not 0 <= model["num_dense_layers"] <= layers:
        raise ValueError("num_dense_layers is not in 0..layers")
    if not 1 <= model["num_experts_per_tok"] <= model["num_experts"]:
        raise ValueError("num_experts_per_tok is not in 1..num_experts")


# ------------------------------------------------------------------ counts


def _bytes(model: Dict[str, Any]) -> int:
    return {"bfloat16": 2, "float32": 4}[model["torch_dtype"]]


def _layers(model: Dict[str, Any]):
    """(dense layers, routed layers)."""
    dense = model["num_dense_layers"]
    return dense, model["num_hidden_layers"] - dense


def _attention_params(model: Dict[str, Any]) -> int:
    """One layer's attention: Wq, Wg and Wo (hidden x heads x head_dim
    each), Wk and Wv, and the two norms of ``head_dim``."""
    d, hd = model["hidden_size"], model["head_dim"]
    q, kv = model["num_attention_heads"] * hd, \
        model["num_key_value_heads"] * hd
    return 3 * d * q + 2 * d * kv + 2 * hd


def _expert_params(model: Dict[str, Any]) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def param_count(model: Dict[str, Any]) -> int:
    """Every parameter: all experts, the shared ones, router and selection
    bias, the four norms of a layer and the final one, embedding and head
    (no JAX)."""
    d, e = model["hidden_size"], model["num_experts"]
    dense, routed = _layers(model)
    per_routed = d * e + e \
        + (e + model["num_shared_experts"]) * _expert_params(model)
    return (2 * model["vocab_size"] * d + d
            + model["num_hidden_layers"] * (_attention_params(model) + 4 * d)
            + dense * 3 * d * model["intermediate_size"]
            + routed * per_routed)


def matmul_params(model: Dict[str, Any]) -> int:
    """The ACTIVE parameters a token multiplies with: attention with its
    gate, the dense layers' FFN, the router, the experts a token reaches
    and the shared ones, the head."""
    d = model["hidden_size"]
    dense, routed = _layers(model)
    attn = _attention_params(model) - 2 * model["head_dim"]
    active = model["num_experts_per_tok"] + model["num_shared_experts"]
    return (model["num_hidden_layers"] * attn
            + dense * 3 * d * model["intermediate_size"]
            + routed * (d * model["num_experts"]
                        + active * _expert_params(model))
            + d * model["vocab_size"])


def train_flops_per_token(model: Dict[str, Any], seq: int) -> float:
    """6 per active matmul parameter plus causal attention: 6 x the q
    projection's width x the keys a query sees on average, which a
    sliding layer caps (``seq / 2`` on a full one)."""
    q = model["num_attention_heads"] * model["head_dim"]
    w = min(seq, model["sliding_window"])
    seen = sum(w * (1 - w / (2.0 * seq)) if t == "sliding_attention"
               else seq / 2.0 for t in model["layer_types"])
    return 6.0 * matmul_params(model) + 12.0 * q * seen


def train_step_kernel_ops_bytes(model, batch, seq, tpu_custom_calls):
    # As SmallThinker's: no cell trains this family (a window layer's full
    # forward is plain masked attention: there is no window flash kernel,
    # ROADMAP M1; one chip holds a share of the experts, M2).
    raise NotImplementedError("no cell trains the afmoe family")


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


def kv_row_bytes(model: Dict[str, Any]) -> int:
    """What the cache keeps of one token on one layer: K and V of the KV
    heads."""
    return 2 * model["num_key_value_heads"] * model["head_dim"] \
        * _bytes(model)


def decode_floor_bytes(model: Dict[str, Any], experts_hit: int,
                       kv_rows_live: int) -> float:
    """The bytes ANY program must read to compute one decode step's
    tokens: every weight a step multiplies with once (attention with its
    gate, the dense FFN, routers and biases in float32, shared experts,
    norms, the head; of the embedding only the slots' rows, left out),
    ``experts_hit`` routed experts (summed over the layers), and
    ``kv_rows_live`` rows of K and V (summed over layers and slots: the
    positions a query can see, on a sliding layer at most the window).
    Activations are left out: a floor."""
    d, e, b = model["hidden_size"], model["num_experts"], _bytes(model)
    dense, routed = _layers(model)
    shared = model["num_shared_experts"] * _expert_params(model)
    weights = (d * model["vocab_size"] + d
               + model["num_hidden_layers"]
               * (_attention_params(model) + 4 * d)
               + dense * 3 * d * model["intermediate_size"]
               + routed * shared) * b + routed * (d * e + e) * 4
    return float(weights + experts_hit * _expert_params(model) * b
                 + kv_rows_live * kv_row_bytes(model))


# ----------------------------------------------------------------- program


def program_config(model: Dict[str, Any], *, max_seq: int, **overrides):
    """The ``MoEConfig`` for ``model`` (a loaded configuration file)."""
    import jax.numpy as jnp

    from ray_tpu.models import MoEConfig

    check_supported(model)
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        model["torch_dtype"]]
    layers = model["num_hidden_layers"]
    sliding = tuple(int(t == "sliding_attention")
                    for t in model["layer_types"])
    return MoEConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=layers, n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        # The file's ``assumed``: config.json has a key for none of the
        # three (nor for rotary on the sliding layers only).
        qk_norm="head", attn_gate=True, post_norm=True,
        embed_scale=math.sqrt(model["hidden_size"])
        if model["mup_enabled"] else 1.0,
        window=int(model["sliding_window"]), window_layout=sliding,
        rope_layout=sliding,
        ffn_layout=tuple(int(i >= model["num_dense_layers"])
                         for i in range(layers)),
        dense_d_ff=model["intermediate_size"],
        d_ff=model["moe_intermediate_size"], n_experts=model["num_experts"],
        top_k=model["num_experts_per_tok"],
        n_shared_experts=model["num_shared_experts"],
        router_score="sigmoid", norm_topk_prob=bool(model["route_norm"]),
        routed_scaling_factor=float(model["route_scale"]),
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
    from ..reference.afmoe_ref import Reference

    return Reference(model, params, device)
