"""The smallthinker family (``"family": "smallthinker"``): PowerInfer's
SmallThinker-21BA3B line as ``ray_tpu/models/moe.py`` computes it.  GQA with
heads of ``head_dim`` (not ``hidden_size / heads``), a layer pattern in
which ``sliding_window_layout[l]`` gives layer ``l`` a window of
``sliding_window_size`` and ``rope_layout[l]`` rotary (a layer without it
has no positional term), a routed gated-ReLU FFN in every layer with no
shared expert, whose router reads the layer's normalised INPUT (before
attention) and whose top-k probabilities are renormalised, RMSNorm, untied
head, no bias, no QK-norm.  ``llama.py`` says what a family module is; the
equations are at the top of ``reference/smallthinker_ref.py``.

The configuration files keep the published ``config.json`` keys.  What the
harness's own readers ask of a configuration under other names is in the
file beside them: ``num_experts`` (``layer_metrics/experts_hit_share_moe.py``),
``torch_dtype``.

No JAX is imported here at the top: the parent process reads the counts,
and only the process that holds the chip calls what builds a program or a
reference.
"""

from __future__ import annotations

from typing import Any, Dict

from ray_tpu.serve import engine as _engine  # imports no JAX

if "prefill_chunk" not in getattr(_engine.EngineConfig,
                                  "__dataclass_fields__", {}):
    # Here, where the harness finds the family (``spec.load_cell``), before
    # it starts a process: a replica that failed in its constructor would
    # be started again and again until the deployment's time ran out.
    raise ImportError(
        "the smallthinker family needs a program that keeps window layers "
        "in a ring of pages and prefills long prompts in chunks: "
        "ray_tpu/serve/engine.py has no EngineConfig.prefill_chunk (and "
        "ray_tpu/models/moe.py no MoEConfig.window_layout / head_dim); this "
        "checkout's program predates the family")

#: ``configs/<this>.json`` is what a ``--rehearse`` run of any cell of this
#: family runs in place of the cell's configuration.
REHEARSAL_CONFIG = "smallthinker-tiny"

#: What ``ray_tpu/models/moe.py`` computes for this family.  A file that
#: asks for anything else is refused, not approximated.
SUPPORTED = {"rope_scaling": None, "moe_primary_router_apply_softmax": True,
             "tie_word_embeddings": False, "norm_topk_prob": True,
             "attention_bias": False, "hidden_act": "relu"}


def check_supported(model: Dict[str, Any]) -> None:
    for key, want in SUPPORTED.items():
        if key in model and model[key] != want:
            raise ValueError(
                f"configuration {model.get('name')!r} has {key}="
                f"{model[key]!r}; the smallthinker family computes only "
                f"{want!r}")
    layers = model["num_hidden_layers"]
    for key in ("rope_layout", "sliding_window_layout"):
        layout = model[key]
        if len(layout) != layers or any(x not in (0, 1) for x in layout):
            raise ValueError(
                f"{key} of {model.get('name')!r} is not one 0 or 1 for each "
                f"of its {layers} layers")
    if any(model["sliding_window_layout"]) \
            and model["sliding_window_size"] <= 0:
        raise ValueError("sliding_window_size is not positive")
    if model["num_attention_heads"] % model["num_key_value_heads"]:
        raise ValueError("num_attention_heads is not a multiple of "
                         "num_key_value_heads")
    experts = model["moe_num_primary_experts"]
    if not 1 <= model["moe_num_active_primary_experts"] <= experts:
        raise ValueError("moe_num_active_primary_experts is not in "
                         "1..moe_num_primary_experts")
    if model.get("num_experts", experts) != experts:
        raise ValueError("num_experts (the harness's name) differs from "
                         "moe_num_primary_experts")


# ------------------------------------------------------------------ counts


def _widths(model: Dict[str, Any]):
    """(hidden, q projection, KV projection, one expert, experts)."""
    hd = model["head_dim"]
    return (model["hidden_size"], model["num_attention_heads"] * hd,
            model["num_key_value_heads"] * hd, model["moe_ffn_hidden_size"],
            model["moe_num_primary_experts"])


def param_count(model: Dict[str, Any]) -> int:
    """Every parameter: all experts, the router, the two norms of a layer
    and the final one, embedding and head (no JAX)."""
    d, q, kv, f, e = _widths(model)
    per_layer = 2 * d * q + 2 * d * kv + d * e + e * 3 * d * f + 2 * d
    return 2 * model["vocab_size"] * d \
        + model["num_hidden_layers"] * per_layer + d


def matmul_params(model: Dict[str, Any]) -> int:
    """The ACTIVE parameters a token multiplies with: the attention
    projections, the router, the experts a token reaches, the head."""
    d, q, kv, f, e = _widths(model)
    per_layer = 2 * d * q + 2 * d * kv + d * e \
        + model["moe_num_active_primary_experts"] * 3 * d * f
    return model["num_hidden_layers"] * per_layer + d * model["vocab_size"]


def train_flops_per_token(model: Dict[str, Any], seq: int) -> float:
    """6 per active matmul parameter plus causal attention: 6 x the q
    projection's width x the keys a query sees on average, which a window
    layer caps (``seq / 2`` without one)."""
    _, q, _, _, _ = _widths(model)
    w = model["sliding_window_size"]
    seen = sum(min(seq, w) * (1 - min(seq, w) / (2.0 * seq)) if windowed
               else seq / 2.0 for windowed in model["sliding_window_layout"])
    return 6.0 * matmul_params(model) + 12.0 * q * seen


def train_step_kernel_ops_bytes(model, batch, seq, tpu_custom_calls):
    # As OLMoE's: the grouped products are ``tpu_custom_call`` too, and no
    # cell trains this family (a window layer's full forward is plain
    # masked attention: there is no window flash kernel, ROADMAP M1).
    raise NotImplementedError("no cell trains the smallthinker family")


def routed_ffn_ops_bytes(model: Dict[str, Any], pairs: int,
                         experts_hit: int) -> Dict[str, float]:
    """What the grouped products of the routed FFN MUST do for ``pairs``
    (token, expert) pairs that reach ``experts_hit`` experts (both summed
    over the layers, as the program's counters are): three products of
    ``hidden x expert width`` a pair, each hit expert's three matrices read
    once, each pair's input row read and output row written once.  What
    lies between the products never has to leave the chip's fast memory."""
    d, _, _, f, _ = _widths(model)
    b = {"bfloat16": 2, "float32": 4}[model["torch_dtype"]]
    return {"ops": pairs * 3 * 2.0 * d * f,
            "bytes": (experts_hit * 3 * d * f + pairs * 2 * d) * b}


# ----------------------------------------------------------------- program


def program_config(model: Dict[str, Any], *, max_seq: int, **overrides):
    """The ``MoEConfig`` for ``model`` (a loaded configuration file)."""
    import jax.numpy as jnp

    from ray_tpu.models import MoEConfig

    check_supported(model)
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        model["torch_dtype"]]
    return MoEConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        d_ff=model["moe_ffn_hidden_size"],
        n_experts=model["moe_num_primary_experts"],
        top_k=model["moe_num_active_primary_experts"],
        norm_topk_prob=bool(model["norm_topk_prob"]), qk_norm=False,
        # The file's ``assumed``: config.json has a key for neither.
        expert_act="relu", router_before_attn=True,
        window=int(model["sliding_window_size"]),
        window_layout=tuple(model["sliding_window_layout"]),
        rope_layout=tuple(model["rope_layout"]), max_seq=max_seq,
        rope_theta=float(model["rope_theta"]),
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
    from ..reference.smallthinker_ref import Reference

    return Reference(model, params, device)
