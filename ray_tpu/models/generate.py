"""Autoregressive decoding with a KV cache for the Llama family.

Role-equivalent to the reference's LLM inference path (reference: the Ray
Serve LLM stack serves autoregressive decode; rllib/offline & serve docs
assume models can generate).  TPU-first shape: the cache is a pair of
static-shape [B, n_kv_heads, max_seq, head_dim] buffers per layer updated
with lax.dynamic_update_slice, and one decode step is a single jitted
program (static shapes, no data-dependent control flow) — the serving loop
calls it once per token, so handles/ingresses can stream tokens as they
decode (serve's streaming path).

Prefill reuses the training forward's math (same params, same helpers) but
captures each layer's rotated K and V into the cache; decode attends over
the cache with a length mask.  GQA repeats KV heads query-side.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.norms import rms_norm
from ..ops.rotary import apply_rotary, rope_frequencies
from .llama import LlamaConfig, _mlp, _qk_norm

Params = Any
KVCache = Dict[str, jax.Array]  # {"k": [L,B,H_kv,S,D], "v": ...}


def init_kv_cache(config: LlamaConfig, batch: int,
                  max_seq: Optional[int] = None) -> KVCache:
    s = max_seq or config.max_seq
    shape = (config.n_layers, batch, config.n_kv_heads, s,
             config.head_dim)
    return {"k": jnp.zeros(shape, config.dtype),
            "v": jnp.zeros(shape, config.dtype)}


def _qkv(config: LlamaConfig, layer, x):
    B, S, _ = x.shape
    a = layer["attn"]
    q, k = _qk_norm(config, a, x @ a["wq"], x @ a["wk"])
    q = q.reshape(B, S, config.n_heads, config.head_dim
                  ).transpose(0, 2, 1, 3)
    k = k.reshape(B, S, config.n_kv_heads, config.head_dim
                  ).transpose(0, 2, 1, 3)
    v = (x @ a["wv"]).reshape(B, S, config.n_kv_heads, config.head_dim
                              ).transpose(0, 2, 1, 3)
    return q, k, v


def _cached_attention(config: LlamaConfig, q, k_cache, v_cache, length):
    """Attend q [B, H, S_q, D] over the first ``length`` cached positions.

    Static shapes: the score matrix covers the whole cache and a mask
    removes unwritten (and future) positions — the standard TPU decode
    recipe (no dynamic slicing by length inside the program)."""
    B, H, Sq, D = q.shape
    n_rep = config.n_heads // config.n_kv_heads
    if n_rep > 1:  # GQA: repeat kv heads query-side
        k_cache = jnp.repeat(k_cache, n_rep, axis=1)
        v_cache = jnp.repeat(v_cache, n_rep, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * (D ** -0.5)
    S_total = k_cache.shape[2]
    pos = jnp.arange(S_total)[None, None, None, :]
    # Row i of a prefill chunk may only see positions <= (length - Sq + i).
    row = jnp.arange(Sq)[None, None, :, None]
    limit = length - Sq + row
    scores = jnp.where(pos <= limit, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v_cache)


def _forward_cached(config: LlamaConfig, params: Params, tokens,
                    cache: KVCache, start: int | jax.Array):
    """Run ``tokens`` (at absolute positions start..start+S) through every
    layer, writing rotated K/V into the cache; returns (logits of the LAST
    position, updated cache)."""
    B, S = tokens.shape
    x = params["embed"][tokens].astype(config.dtype)
    cos, sin = rope_frequencies(config.head_dim, cache["k"].shape[3],
                                config.rope_theta)
    new_k, new_v = cache["k"], cache["v"]
    length = start + S
    for i, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"], config.norm_eps)
        q, k, v = _qkv(config, layer, h)
        q = apply_rotary(q, cos, sin, position_offset=start)
        k = apply_rotary(k, cos, sin, position_offset=start)
        new_k = jax.lax.dynamic_update_slice(
            new_k, k[None].astype(new_k.dtype), (i, 0, 0, start, 0))
        new_v = jax.lax.dynamic_update_slice(
            new_v, v[None].astype(new_v.dtype), (i, 0, 0, start, 0))
        out = _cached_attention(config, q, new_k[i], new_v[i], length)
        out = out.transpose(0, 2, 1, 3).reshape(B, S, -1)
        x = x + out @ layer["attn"]["wo"]
        h = rms_norm(x, layer["mlp_norm"], config.norm_eps)
        x = x + _mlp(layer, h)
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    logits = (x[:, -1] @ params["lm_head"]).astype(jnp.float32)
    return logits, {"k": new_k, "v": new_v}


@functools.partial(jax.jit, static_argnums=(0,))
def llama_prefill(config: LlamaConfig, params: Params, tokens,
                  cache: KVCache):
    """Process the whole prompt in one program; cache filled for
    positions [0, S)."""
    return _forward_cached(config, params, tokens, cache, 0)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(3,))
def llama_decode_step(config: LlamaConfig, params: Params, token,
                      cache: KVCache, pos):
    """One token ([B, 1]) at dynamic position ``pos``; the cache buffer is
    donated, so steady-state decode never copies it."""
    return _forward_cached(config, params, token, cache, pos)


def _sample(logits, temperature: float, key):
    if temperature <= 0.0 or key is None:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, logits / temperature, axis=-1).astype(jnp.int32)


def generate(
    config: LlamaConfig,
    params: Params,
    prompt_tokens,                      # [B, S_prompt] int32
    *,
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    seed: int = 0,
    stop_token: Optional[int] = None,
    stream=None,                        # callable(token_array [B]) per step
) -> jax.Array:
    """Greedy/temperature decoding; returns [B, S_prompt + new] tokens.
    ``stream`` receives each new token batch as it decodes — the hook the
    serve streaming path yields from."""
    prompt_tokens = jnp.asarray(prompt_tokens, jnp.int32)
    B, s_prompt = prompt_tokens.shape
    max_seq = s_prompt + max_new_tokens
    cache = init_kv_cache(config, B, max_seq)
    logits, cache = llama_prefill(config, params, prompt_tokens, cache)
    key = jax.random.PRNGKey(seed) if temperature > 0 else None
    out = [prompt_tokens]
    done = jnp.zeros(B, bool)
    token = None
    for step in range(max_new_tokens):
        if key is not None:
            key, sub = jax.random.split(key)
        else:
            sub = None
        token = _sample(logits, temperature, sub)  # [B]
        if stop_token is not None:
            done = done | (token == stop_token)
        out.append(token[:, None])
        if stream is not None:
            stream(jax.device_get(token))
        if stop_token is not None and bool(done.all()):
            break
        if step + 1 < max_new_tokens:
            # The final sampled token needs no forward pass — skipping it
            # saves one whole decode step per call.
            logits, cache = llama_decode_step(
                config, params, token[:, None], cache,
                jnp.asarray(s_prompt + step))
    return jnp.concatenate(out, axis=1)
