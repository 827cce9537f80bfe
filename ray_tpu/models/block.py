"""The decoder layer and the stack around it, spelled once.

Every program of the family composes these at trace time: the train step
(``llama.hidden_and_aux``), the pipeline stage, and the engine's
decode, prefill and prefix-prefill programs (``paged.py``).  What differs
between them is the attention: each caller hands ``decoder_layer`` an
``attend(q, k, v)`` closure that owns rotary, any cache write and the
attention arithmetic (flash or ring in training, the paged pool's gather
in decode), and, where it serves adapters, a ``lora(name, h)`` closure
for the deltas on the q and v projections.  Where attention goes through a
compressed latent (``is_latent``) the closure is handed the latent and
chooses the form: ``attend(q, c, k_r, wkv_b)`` expands K and V out of it
(the full forward, a cold prefill) or absorbs ``wkv_b`` into q and the
output (the paged cache's programs).  A gated delta-rule layer (``is_kda``,
``models/kda.py``) keeps a recurrent state and no cache rows:
``attend(pre, g, beta, a)`` is handed the q/k/v projections before their
convolution, the log-decay and the write strength, owns the state, and
chooses the recurrent or the chunk form.  A selective state-space layer
(``is_ssm``, ``models/mamba.py``) likewise: ``attend(pre, a)`` is handed the
in-projection's xs half before its convolution and returns the recurrence's
output beside the convolved xs.  A configuration with an ``attn_layout`` has
layers of two kinds, one recurrent and one that keeps cache rows: a program
asks each layer.

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
import jax.numpy as jnp

from ..ops.norms import rms_norm
from ..parallel.sharding import (HEADS, RESIDUAL, SPLIT, VOCAB_ROWS,
                                 constrain)
from . import kda, llama, mamba, moe

Params = Dict[str, Any]
#: ``attend(q [..., H, D], k, v [..., H_kv, D]) -> [..., H*D]``; of a
#: latent model ``attend(q, c [..., rank], k_r [..., rope], wkv_b)``
Attend = Callable[..., jax.Array]
#: ``lora("wq" | "wv", h [..., d]) -> the projection's low-rank delta``
Lora = Callable[[str, jax.Array], jax.Array]


def is_latent(config, i: Optional[int] = None) -> bool:
    """Whether the attention of layer ``i`` of ``config`` goes through a
    compressed latent (``MoEConfig.kv_lora_rank``): the cache then keeps one
    latent row a token and layer, not K and V.  With no layer named, whether
    any layer's does (then the pool behind the page tables is the latent
    one)."""
    if getattr(config, "kv_lora_rank", 0) <= 0:
        return False
    return i is None or not is_kda(config, i)


def is_kda(config, i: Optional[int] = None) -> bool:
    """Whether layer ``i`` of ``config`` is a gated delta-rule layer
    (``MoEConfig.attn_layout``, ``models/kda.py``), which keeps a recurrent
    state a sequence and no cache rows; with no layer named, whether any
    layer is."""
    if i is None:
        return "kda" in getattr(config, "attn_layout", ())
    return layer_attn(config, i) == "kda"


def is_ssm(config, i: Optional[int] = None) -> bool:
    """As ``is_kda``, of a selective state-space layer
    (``models/mamba.py``)."""
    if i is None:
        return "ssm" in getattr(config, "attn_layout", ())
    return layer_attn(config, i) == "ssm"


def recurrent(config, i: Optional[int] = None):
    """The module of layer ``i``'s recurrence (``kda`` or ``mamba``: what
    keeps a state a sequence and no cache rows), None of a layer that keeps
    rows; with no layer named, of the configuration's recurrent layers,
    which are of one kind."""
    return kda if is_kda(config, i) else mamba if is_ssm(config, i) else None


def rotary_dim(config) -> int:
    """How many of a head's dimensions rotate with position: all of
    ``head_dim``, or a latent model's ``qk_rope_head_dim``."""
    return getattr(config, "qk_rope_head_dim", 0) or config.head_dim


def project_latent(config, a: Params, h: jax.Array):
    """Latent attention's projections of normalised ``h`` [..., d]:
    q [..., H, nope + rope] through its latent and that latent's norm (not
    rotated yet), the normalised K/V latent c [..., kv_lora_rank], and the
    one rotary key k_r [..., rope] every head shares (not rotated yet).
    ``[c ; RoPE(k_r)]`` is what a cache keeps of the token."""
    if config.q_lora_rank:
        q = rms_norm(h @ a["wq_a"], a["q_norm"], config.norm_eps) \
            @ a["wq_b"]
    else:  # no q latent: one projection, no norm
        q = h @ a["wq"]
    kv = h @ a["wkv_a"]
    rank = config.kv_lora_rank
    c = rms_norm(kv[..., :rank], a["kv_norm"], config.norm_eps)
    q = q.reshape(*h.shape[:-1], config.n_heads, config.head_dim)
    return constrain(q, HEADS), c, kv[..., rank:]


def latent_up(config, wkv_b: jax.Array):
    """``wkv_b`` [kv_lora_rank, H * (nope + v)] as its two halves:
    (W_uk [rank, H, nope], W_uv [rank, H, v]), the maps from the latent to
    a head's unrotated key and to its value."""
    w = wkv_b.reshape(config.kv_lora_rank, config.n_heads, -1)
    return (w[..., :config.qk_nope_head_dim],
            w[..., config.qk_nope_head_dim:])


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
              lora: Optional[Lora] = None, attn: str = "") -> jax.Array:
    """Attention of normalised ``h`` through the output projection.  A
    latent layer takes no adapter: the deltas on ``wq`` and ``wv`` have no
    counterpart among its projections (the engine refuses to load one);
    nor does a KDA or a state-space layer (``attn`` ``"kda"`` / ``"ssm"``:
    the layer's entry of ``attn_layout``, empty for a configuration of one
    kind)."""
    if attn == "kda":
        return kda.output(config, a, h,
                          attend(*kda.project(config, a, h), a))
    if attn == "ssm":
        pre, z = mamba.project(config, a, h)
        return mamba.output(config, a, *attend(pre, a), z)
    if is_latent(config):
        out = attend(*project_latent(config, a, h), a["wkv_b"])
    else:
        out = attend(*project_qkv(config, a, h, lora))
    if getattr(config, "attn_gate", False):
        with jax.named_scope("attn_gate"):
            gate = jax.nn.sigmoid((h @ a["wg"]).astype(jnp.float32))
            out = (out * gate).astype(out.dtype)
    return constrain(out, SPLIT) @ a["wo"]


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


def layer_attn(config, i: int) -> str:
    """Layer ``i``'s entry of ``attn_layout`` (``"kda"`` | ``"ssm"``, the
    recurrent kinds; ``"latent"`` | ``"kv"``, the kinds that keep rows);
    empty for a configuration whose layers are of one kind."""
    layout = getattr(config, "attn_layout", ())
    return layout[i] if layout else ""


def layer_kind(config, i: int) -> Tuple:
    """(``layer_window``, ``layer_rotary``) of layer ``i``, and behind them
    its ``layer_attn`` where the configuration has an ``attn_layout``: what
    a program that traces a layer once a kind (``jax.checkpoint`` in the
    train step) keys the trace on."""
    kind, attn = (layer_window(config, i), layer_rotary(config, i)), \
        layer_attn(config, i)
    return kind + (attn,) if attn else kind


def is_routed(config, i: Optional[int] = None) -> bool:
    """Whether the FFN of layer ``i`` of ``config`` is routed (``moe.py``)
    or dense (``llama.py``); with no layer named, whether any layer's is
    (then the programs carry routing counters).  The one place under
    ``ray_tpu/`` that asks what a configuration object is."""
    if not isinstance(config, moe.MoEConfig):
        return False
    layout = config.ffn_layout
    if not layout:
        return True
    return any(layout) if i is None else bool(layout[i])


def init_and_apply(config):
    """``(init(config, key) -> params, apply(config, params, tokens) ->
    logits [B, S, V] float32)`` of ``config``'s architecture: how its
    weights are made, and its full forward pass.  A layout of attention
    kinds is ``moe.py``'s to read, routed layers beside it or none."""
    if is_routed(config) or getattr(config, "attn_layout", ()):
        return moe.moe_init, lambda c, p, t: moe.moe_apply(c, p, t)[0]
    return llama.llama_init, llama.llama_apply


def head(config, params: Params, x: jax.Array) -> jax.Array:
    """The logits [..., V] float32 of final-normed hidden states x [..., d]:
    through ``lm_head``, or, where the head is tied
    (``MoEConfig.tie_embeddings``: the tree has no ``lm_head``), by the
    embedding where it lies, contracted over its rows' width, so that the
    tree and the device hold the one matrix."""
    if getattr(config, "tie_embeddings", False):
        return jnp.einsum("...d,vd->...v", x, params["embed"]).astype(
            jnp.float32)
    return (x @ params["lm_head"]).astype(jnp.float32)


def post_norm(config, layer: Params, name: str, out: jax.Array) -> jax.Array:
    """A half-block's output through its own norm before it joins the
    residual stream, where the block is sandwich-normed
    (``MoEConfig.post_norm``: ``layer[name]``); as it is elsewhere."""
    if not getattr(config, "post_norm", False):
        return out
    with jax.named_scope("post_norm"):
        return rms_norm(out, layer[name], config.norm_eps)


def ffn(config, layer: Params, x: jax.Array,
        valid: Optional[jax.Array] = None,
        logits: Optional[jax.Array] = None, *, routed: bool):
    """The second half of the block, x + FFN(norm(x)), dense or routed (a
    trace-time branch, so a dense model compiles to the program it always
    did).  ``routed`` is ``is_routed`` of this layer, asked by the program
    that walks the layers.  ``valid`` marks the rows that hold a real
    token; only a routed FFN looks at it.  ``logits`` are the router's where
    ``decoder_layer`` took them before attention.  Returns (x, the routed
    layer's load-balancing loss, its per-expert token counts [E]); the last
    two are None where the FFN is dense."""
    if routed:
        h = rms_norm(x, layer["moe_norm"], config.norm_eps)
        # Only a layer that took them early passes ``logits``: the tests'
        # stand-ins for ``_moe_ffn`` keep its older signature.
        early = {} if logits is None else {"logits": logits}
        out, aux, counts = moe._moe_ffn(config, layer["moe"], h, valid,
                                        **early)
        if config.n_shared_experts:
            out = out + moe._shared_expert(config, layer["moe"]["shared"],
                                           h, valid)
        out = post_norm(config, layer, "ffn_post_norm", out)
        return constrain(x + out, RESIDUAL), aux, counts
    h = rms_norm(x, layer["mlp_norm"], config.norm_eps)
    out = post_norm(config, layer, "ffn_post_norm", llama._mlp(layer, h))
    return constrain(x + out, RESIDUAL), None, None


def decoder_layer(config, layer: Params, x: jax.Array, attend: Attend, *,
                  routed: bool, lora: Optional[Lora] = None,
                  valid: Optional[jax.Array] = None, attn: str = ""):
    """One pre-norm decoder layer on x [..., d] (``routed``: see ``ffn``;
    sandwich-normed where ``post_norm`` says; ``attn``: see ``attention``);
    returns what ``ffn`` returns."""
    h = rms_norm(x, layer["attn_norm"], config.norm_eps)
    logits = None
    if getattr(config, "router_before_attn", False):
        logits = moe.router_logits(layer["moe"], h)
    out = post_norm(config, layer, "attn_post_norm",
                    attention(config, layer["attn"], h, attend, lora, attn))
    x = constrain(x + out, RESIDUAL)
    return ffn(config, layer, x, valid, logits, routed=routed)


def decoder_stack(config, params: Params, tokens: jax.Array,
                  layer_fn: Callable[[int, Params, jax.Array], Tuple]
                  ) -> Tuple[jax.Array, List, List]:
    """Embedding (scaled where ``embed_scale`` says),
    ``layer_fn(i, layer, x) -> (x, aux, counts)`` over the
    layers (a Python list: the program is unrolled), final norm.  Returns
    (hidden [..., d], the layers' aux losses, their expert counts); the
    head stays with the caller, which takes its own rows of the hidden
    state."""
    table = constrain(params["embed"], VOCAB_ROWS)
    x = table[tokens].astype(config.dtype)
    if getattr(config, "embed_scale", 1.0) != 1.0:
        x = x * config.embed_scale
    x = constrain(x, RESIDUAL)
    auxes, counts = [], []
    for i, layer in enumerate(params["layers"]):
        x, aux, c = layer_fn(i, layer, x)
        auxes.append(aux)
        counts.append(c)
    return rms_norm(x, params["final_norm"], config.norm_eps), auxes, counts
