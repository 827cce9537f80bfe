"""The olmoe-1b-7b family (``"family": "olmoe-1b-7b"``; the bare name
``olmoe`` is the family the harness's own tests keep absent): the
sparse-expert decoder of ``ray_tpu/models/moe.py`` as allenai's OLMoE-1B-7B
line has it (MHA or GQA with QK-norm over the projection
width, a routed SwiGLU FFN in every layer with no shared expert, top-k
probabilities kept as the softmax gave them, RoPE, RMSNorm, untied head, no
bias).  ``llama.py`` says what a family module is.

No JAX is imported here at the top: the parent process reads the counts,
and only the process that holds the chip calls what builds a program or a
reference.
"""

from __future__ import annotations

from typing import Any, Dict

from ray_tpu.serve import engine as _engine  # imports no JAX

if not hasattr(_engine, "register_model"):
    # Here, where the harness finds the family (``spec.load_cell``), before
    # it starts a process: a replica that failed in its constructor would
    # be started again and again until the deployment's time ran out.
    raise ImportError(
        "the olmoe-1b-7b family needs a program that serves a routed FFN: "
        "ray_tpu/serve/engine.py has no public register_model (and "
        "ray_tpu/models/moe.py no MoEConfig.norm_topk_prob / qk_norm); "
        "this checkout's program predates the family")

#: ``configs/<this>.json`` is what a ``--rehearse`` run of any cell of this
#: family runs in place of the cell's configuration.
REHEARSAL_CONFIG = "olmoe-tiny"

#: What ``ray_tpu/models/moe.py`` computes.  A file that asks for anything
#: else is refused, not approximated.
SUPPORTED = {"hidden_act": "silu", "tie_word_embeddings": False,
             "attention_bias": False, "clip_qkv": None, "rope_scaling": None}


def check_supported(model: Dict[str, Any]) -> None:
    for key, want in SUPPORTED.items():
        if key in model and model[key] != want:
            raise ValueError(
                f"configuration {model.get('name')!r} has {key}="
                f"{model[key]!r}; the olmoe-1b-7b family computes only {want!r}")
    if model["hidden_size"] % model["num_attention_heads"]:
        raise ValueError("hidden_size is not a multiple of the head count")
    if not 1 <= model["num_experts_per_tok"] <= model["num_experts"]:
        raise ValueError("num_experts_per_tok is not in 1..num_experts")


# ------------------------------------------------------------------ counts


def _widths(model: Dict[str, Any]):
    d = model["hidden_size"]
    kv = model["num_key_value_heads"] * (d // model["num_attention_heads"])
    return d, kv, model["intermediate_size"], model["num_experts"]


def param_count(model: Dict[str, Any]) -> int:
    """Every parameter: all experts, the router, the four norms of a layer
    (two of them the QK-norm's) and the final one (no JAX)."""
    d, kv, f, e = _widths(model)
    per_layer = 2 * d * d + 2 * d * kv + d * e + e * 3 * d * f \
        + 2 * d + d + kv
    return 2 * model["vocab_size"] * d \
        + model["num_hidden_layers"] * per_layer + d


def matmul_params(model: Dict[str, Any]) -> int:
    """The ACTIVE parameters a token multiplies with: the attention
    projections, the router, ``num_experts_per_tok`` experts, the head."""
    d, kv, f, e = _widths(model)
    per_layer = 2 * d * d + 2 * d * kv + d * e \
        + model["num_experts_per_tok"] * 3 * d * f
    return model["num_hidden_layers"] * per_layer + d * model["vocab_size"]


def train_flops_per_token(model: Dict[str, Any], seq: int) -> float:
    """6 per active matmul parameter plus causal attention (6*L*S*d), as
    ``arith.train_flops_per_token`` counts the dense decoder."""
    return 6.0 * matmul_params(model) \
        + 6.0 * model["num_hidden_layers"] * seq * model["hidden_size"]


def train_step_kernel_ops_bytes(model, batch, seq, tpu_custom_calls):
    # The grouped products of the routed FFN are ``tpu_custom_call`` too
    # (XLA's own lowering of ragged_dot), so the count of them says nothing
    # about how often flash ran; and no cell trains this family on a chip
    # yet (one layer with AdamW state is 6.7 GB: ROADMAP R1).
    raise NotImplementedError("no cell trains the olmoe-1b-7b family on a chip")


def routed_ffn_ops_bytes(model: Dict[str, Any], pairs: int,
                         experts_hit: int) -> Dict[str, float]:
    """What the grouped products of the routed FFN MUST do for ``pairs``
    (token, expert) pairs that reach ``experts_hit`` experts (both summed
    over the layers, as the program's counters are): three products of
    ``hidden x intermediate`` a pair, each hit expert's three matrices read
    once, each pair's input row read and output row written once.  What
    lies between the products never has to leave the chip's fast memory."""
    d, _, f, _ = _widths(model)
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
        n_kv_heads=model["num_key_value_heads"],
        d_ff=model["intermediate_size"], n_experts=model["num_experts"],
        top_k=model["num_experts_per_tok"],
        norm_topk_prob=bool(model["norm_topk_prob"]),
        # config.json has no key for it: the published modelling code
        # always has it (the file's ``assumed``).
        qk_norm=bool(model.get("qk_norm", True)), max_seq=max_seq,
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
    from ..reference.olmoe_ref import Reference

    return Reference(model, params, device)
