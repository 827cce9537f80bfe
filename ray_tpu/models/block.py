"""The decoder layer and the stack around it, spelled once.

Every program of the family composes these at trace time: the train step
(``llama.hidden_and_aux``), the pipeline stage, and the engine's
decode, prefill and prefix-prefill programs (``paged.py``).  What differs
between them is the attention: each caller hands ``decoder_layer`` an
``attend(q, k, v)`` closure that owns rotary, any cache write and the
attention arithmetic (flash or ring in training, the paged pool's gather
in decode), and, where it serves adapters, a ``lora(name, h)`` closure
for the deltas on the q and v projections.

``llama`` and ``moe`` are reached through their modules, at call time: the
helpers a test swaps there (``llama._qk_norm``, ``moe._moe_ffn``) are the
ones every program runs.

The layer also says where its activations live under a mesh
(``sharding.constrain``: the residual stream whole in its hidden
dimension, heads and the FFN's hidden units over tp).  Only a training
program is traced under an ambient mesh: in a serving program and inside
the pipeline stage's ``shard_map`` the calls return their argument.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from ..ops.norms import rms_norm
from ..parallel.sharding import (HEADS, RESIDUAL, SPLIT, VOCAB_ROWS,
                                 constrain)
from . import llama, moe

Params = Dict[str, Any]
#: ``attend(q [..., H, D], k, v [..., H_kv, D]) -> [..., H*D]``
Attend = Callable[[jax.Array, jax.Array, jax.Array], jax.Array]
#: ``lora("wq" | "wv", h [..., d]) -> the projection's low-rank delta``
Lora = Callable[[str, jax.Array], jax.Array]


def project_qkv(config, a: Params, h: jax.Array,
                lora: Optional[Lora] = None):
    """The three projections of normalised ``h`` [..., d] by the layer's
    ``attn`` weights ``a``, LoRA on q and v (the standard recipe), QK-norm
    where the configuration has it, split into heads:
    q [..., H, D], k and v [..., H_kv, D].  Nothing is transposed."""
    q, k, v = h @ a["wq"], h @ a["wk"], h @ a["wv"]
    if lora is not None:
        q = q + lora("wq", h)
        v = v + lora("wv", h)
    q, k = llama._qk_norm(config, a, q, k)
    lead, hd = h.shape[:-1], config.head_dim
    return (constrain(q.reshape(*lead, config.n_heads, hd), HEADS),
            constrain(k.reshape(*lead, config.n_kv_heads, hd), HEADS),
            constrain(v.reshape(*lead, config.n_kv_heads, hd), HEADS))


def attention(config, a: Params, h: jax.Array, attend: Attend,
              lora: Optional[Lora] = None) -> jax.Array:
    """Attention of normalised ``h`` through the output projection."""
    out = constrain(attend(*project_qkv(config, a, h, lora)), SPLIT)
    return out @ a["wo"]


def layer_window(config, i: int) -> int:
    """The window of layer ``i``: it attends positions ``j`` with
    ``0 <= i_pos - j < window``; 0 where the layer attends its sequence's
    whole length (every layer of a configuration without a pattern)."""
    layout = getattr(config, "window_layout", ())
    return config.window if layout and layout[i] else 0


def layer_rotary(config, i: int) -> bool:
    """Whether layer ``i`` rotates q and k by position (every layer of a
    configuration without a pattern does)."""
    layout = getattr(config, "rope_layout", ())
    return bool(layout[i]) if layout else True


def layer_kind(config, i: int) -> Tuple[int, bool]:
    """(``layer_window``, ``layer_rotary``) of layer ``i``: what a program
    that traces a layer once a kind (``jax.checkpoint`` in the train step)
    keys the trace on."""
    return layer_window(config, i), layer_rotary(config, i)


def is_routed(config) -> bool:
    """Whether the FFN of ``config``'s layers is routed (``moe.py``) or
    dense (``llama.py``): the one place under ``ray_tpu/`` that asks what
    a configuration object is."""
    return isinstance(config, moe.MoEConfig)


def init_and_apply(config):
    """``(init(config, key) -> params, apply(config, params, tokens) ->
    logits [B, S, V] float32)`` of ``config``'s architecture: how its
    weights are made, and its full forward pass."""
    if is_routed(config):
        return moe.moe_init, lambda c, p, t: moe.moe_apply(c, p, t)[0]
    return llama.llama_init, llama.llama_apply


def ffn(config, layer: Params, x: jax.Array,
        valid: Optional[jax.Array] = None,
        logits: Optional[jax.Array] = None):
    """The second half of the block, x + FFN(norm(x)), dense or routed (a
    trace-time branch, so a dense model compiles to the program it always
    did).  ``valid`` marks the rows that hold a real token; only a routed
    FFN looks at it.  ``logits`` are the router's where ``decoder_layer``
    took them before attention.  Returns (x, the routed layer's
    load-balancing loss, its per-expert token counts [E]); the last two are
    None where the FFN is dense."""
    if is_routed(config):
        h = rms_norm(x, layer["moe_norm"], config.norm_eps)
        # Only a layer that took them early passes ``logits``: the tests'
        # stand-ins for ``_moe_ffn`` keep its older signature.
        early = {} if logits is None else {"logits": logits}
        out, aux, counts = moe._moe_ffn(config, layer["moe"], h, valid,
                                        **early)
        return constrain(x + out, RESIDUAL), aux, counts
    h = rms_norm(x, layer["mlp_norm"], config.norm_eps)
    return constrain(x + llama._mlp(layer, h), RESIDUAL), None, None


def decoder_layer(config, layer: Params, x: jax.Array, attend: Attend, *,
                  lora: Optional[Lora] = None,
                  valid: Optional[jax.Array] = None):
    """One pre-norm decoder layer on x [..., d]; returns what ``ffn``
    returns."""
    h = rms_norm(x, layer["attn_norm"], config.norm_eps)
    logits = None
    if getattr(config, "router_before_attn", False):
        logits = moe.router_logits(layer["moe"], h)
    x = constrain(x + attention(config, layer["attn"], h, attend, lora),
                  RESIDUAL)
    return ffn(config, layer, x, valid, logits)


def decoder_stack(config, params: Params, tokens: jax.Array,
                  layer_fn: Callable[[int, Params, jax.Array], Tuple]
                  ) -> Tuple[jax.Array, List, List]:
    """Embedding, ``layer_fn(i, layer, x) -> (x, aux, counts)`` over the
    layers (a Python list: the program is unrolled), final norm.  Returns
    (hidden [..., d], the layers' aux losses, their expert counts); the
    head stays with the caller, which takes its own rows of the hidden
    state."""
    table = constrain(params["embed"], VOCAB_ROWS)
    x = constrain(table[tokens].astype(config.dtype), RESIDUAL)
    auxes, counts = [], []
    for i, layer in enumerate(params["layers"]):
        x, aux, c = layer_fn(i, layer, x)
        auxes.append(aux)
        counts.append(c)
    return rms_norm(x, params["final_norm"], config.norm_eps), auxes, counts
