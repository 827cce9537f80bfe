"""The plain reference of the Jamba decoder at ``num_experts`` 1
(``model_type`` ``jamba``: AI21-Jamba2-3B): the forward pass in
straightforward ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, with no kernel, no cache, no
page, no chunk and no batching.  A Mamba layer is the position-by-position
recurrence and nothing else (a ``lax.scan`` over the positions with the
``[channels, state]`` matrix as its carry); attention is plain causal
softmax attention a block of queries at a time.  Independent of
``ray_tpu/models``: it shares only the layout of the weight tree, because it
is given the cell's own weights (``layers[i].attn`` of a Mamba layer:
``w_in`` [d, 2 I], ``conv_w`` [taps, I] with the LAST tap on the current
position, ``conv_b`` [I], ``w_x`` [I, R + 2 N], ``dt_norm`` [R], ``b_norm``
[N], ``c_norm`` [N], ``w_dt`` [R, I], ``dt_bias`` [I], ``A_log`` **[N, I]**
(the program keeps it and the state transposed, for the TPU's tiles; here
both are turned back), ``D`` [I], ``w_out`` [I, d]; of an attention layer:
``wq`` [d, H D], ``wk`` / ``wv`` [d, H_kv D], ``wo`` [H D, d];
``layers[i].mlp.w1/w3/w2``; the norms; ``embed``, which is also the head).

With I = ``mamba_expand`` x hidden channels, N = ``mamba_d_state``, R =
``mamba_dt_rank``; layer ``i`` (0-indexed) on one sequence x [S, d], with
u = RMSNorm(x; attn_norm):

  ``i % attn_layer_period != attn_layer_offset``, a Mamba-1 layer:
    [xs ; z] = u W_in                                  no bias
    xs = SiLU(conv(xs) + conv_b)
        conv: out_t = sum_j w_j xs_{t - (taps-1) + j}, zeros before t = 0
    [d ; B ; C] = xs W_x                               no bias
    d = RMSNorm(d; dt_norm), B = RMSNorm(B; b_norm), C = RMSNorm(C; c_norm)
    Delta = softplus(d W_dt + dt_bias)                 [I]
    A = -exp(A_log)                                    [I, N]
    H_t = exp(Delta_t (x) A) . H_{t-1} + (Delta_t . xs_t) (x) B_t   [I, N]
    y_t = H_t C_t + D . xs_t
    x' = x + (y . SiLU(z)) W_out                       no bias
  ``i % attn_layer_period == attn_layer_offset``, an attention layer:
    q_h = (u W_q)_h, k_g = (u W_k)_g, v_g = (u W_v)_g   g = h // (H / H_kv)
    s_ij = q_i . k_j / sqrt(D), causal softmax, NO rotation and no other
        position signal, no QK-norm
    x' = x + concat_h(sum_j p_ij v_j) W_o
  then n = RMSNorm(x'; mlp_norm):
    x'' = x' + [SiLU(n W_gate) * (n W_up)] W_down    every layer: num_experts 1
  after the last layer: logits = RMSNorm(x; final_norm) E^T, E the embedding
  (``tie_word_embeddings``).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..families.jamba import layer_kinds  # no JAX, no program

Q_BLOCK = 256


def _f32(t):
    return t.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(w)


def _swiglu(g, m):
    return (jax.nn.silu(g @ _f32(m["w1"])) * (g @ _f32(m["w3"]))) \
        @ _f32(m["w2"])


def _mamba(x, layer, n, *, inner, state, rank, eps):
    """A Mamba layer's mixer half on x [S, d]: the recurrence, one position
    at a time.  Returns (x', the state [I, N] after the first ``n``
    positions: the rows behind them are padding and leave it as it is)."""
    s = x.shape[0]
    a = layer["attn"]
    u = _rms(x, layer["attn_norm"], eps)
    both = u @ _f32(a["w_in"])
    pre, z = both[:, :inner], both[:, inner:]
    w = _f32(a["conv_w"])
    taps = w.shape[0]
    rows = jnp.concatenate([jnp.zeros((taps - 1, inner)), pre])
    xs = jax.nn.silu(sum(rows[j:j + s] * w[j] for j in range(taps))
                     + _f32(a["conv_b"]))
    dbc = xs @ _f32(a["w_x"])
    d = _rms(dbc[:, :rank], a["dt_norm"], eps)
    bm = _rms(dbc[:, rank:rank + state], a["b_norm"], eps)
    cm = _rms(dbc[:, rank + state:], a["c_norm"], eps)
    delta = jax.nn.softplus(d @ _f32(a["w_dt"]) + _f32(a["dt_bias"]))
    delta = jnp.where((jnp.arange(s) < n)[:, None], delta, 0.0)
    A = -jnp.exp(_f32(a["A_log"]).T)                          # [I, N]

    def step(H, t):
        d_t, x_t, b_t, c_t = t
        H = jnp.exp(d_t[:, None] * A) * H \
            + (d_t * x_t)[:, None] * b_t[None, :]
        return H, H @ c_t

    H, y = jax.lax.scan(step, jnp.zeros((inner, state), jnp.float32),
                        (delta, xs, bm, cm))
    y = (y + _f32(a["D"]) * xs) * jax.nn.silu(z)
    return x + y @ _f32(a["w_out"]), H


def _project(x, layer, *, n_heads, n_kv, eps):
    """An attention layer's q, k and v, [H, S, D] each (the K/V heads
    repeated for the query heads of their group)."""
    s = x.shape[0]
    a = layer["attn"]
    u = _rms(x, layer["attn_norm"], eps)

    def heads(t, n):
        return t.reshape(s, n, -1).transpose(1, 0, 2)

    q = heads(u @ _f32(a["wq"]), n_heads)
    k = jnp.repeat(heads(u @ _f32(a["wk"]), n_kv), n_heads // n_kv, axis=0)
    v = jnp.repeat(heads(u @ _f32(a["wv"]), n_kv), n_heads // n_kv, axis=0)
    return q, k, v


def _attend_block(q, k, v, lo):
    """Queries q [H, B, D] at positions lo.. against all keys, causal."""
    _, b, hd = q.shape
    scores = jnp.einsum("hqd,hkd->hqk", q, k) / math.sqrt(hd)
    i, j = lo + jnp.arange(b)[:, None], jnp.arange(k.shape[1])[None, :]
    probs = jax.nn.softmax(jnp.where((j <= i)[None], scores, -jnp.inf), -1)
    return jnp.einsum("hqk,hkd->hqd", probs, v)


def _attn_out(x, o, layer):
    return x + o.transpose(1, 0, 2).reshape(x.shape[0], -1) \
        @ _f32(layer["attn"]["wo"])


def _ffn(x, layer, *, eps):
    return x + _swiglu(_rms(x, layer["mlp_norm"], eps), layer["mlp"])


def _logits(x, final_norm, embed, eps):
    return _rms(x, final_norm, eps) @ _f32(embed).T


class Reference:
    """The reference over one weight tree.  ``model`` is a loaded
    configuration file; ``params`` the program's weight tree (any dtype);
    everything is computed on ``device``, a layer at a time, so that
    thousands of positions fit beside the weights."""

    def __init__(self, model: Dict[str, Any], params, device=None):
        self.device = device or jax.devices()[0]
        self.params = jax.device_put(params, self.device)
        self.eps = float(model["rms_norm_eps"])
        self.kinds = layer_kinds(model)
        self._jit = {
            "mamba": jax.jit(functools.partial(
                _mamba, inner=model["mamba_expand"] * model["hidden_size"],
                state=model["mamba_d_state"], rank=model["mamba_dt_rank"],
                eps=self.eps)),
            "project": jax.jit(functools.partial(
                _project, n_heads=model["num_attention_heads"],
                n_kv=model["num_key_value_heads"], eps=self.eps)),
            "attend": jax.jit(_attend_block),
            "out": jax.jit(_attn_out),
            "ffn": jax.jit(functools.partial(_ffn, eps=self.eps)),
            "logits": jax.jit(functools.partial(_logits, eps=self.eps))}
        self._last = (None, None)

    def _forward(self, tokens):
        """One sequence through every layer: (hidden [S, d], the Mamba
        layers' states after the last position [Mamba layers, I, N], on the
        device).  Padded behind its end to whole query blocks (every layer
        is causal, and a padded row leaves the state as it is)."""
        tokens = np.asarray(tokens, np.int32)
        n, key, fns = len(tokens), tokens.tobytes(), self._jit
        if self._last[0] != key:
            padded = np.concatenate(
                [tokens, np.zeros((-n % Q_BLOCK,), np.int32)])
            states = []
            with jax.default_matmul_precision("highest"):
                x = _f32(self.params["embed"][jnp.asarray(padded)])
                for kind, lw in zip(self.kinds, self.params["layers"]):
                    if kind == "ssm":
                        x, state = fns["mamba"](x, lw, n)
                        states.append(state)
                    else:
                        q, k, v = fns["project"](x, lw)
                        o = jnp.concatenate([
                            fns["attend"](q[:, lo:lo + Q_BLOCK], k, v, lo)
                            for lo in range(0, x.shape[0], Q_BLOCK)], axis=1)
                        x = fns["out"](x, o, lw)
                    x = fns["ffn"](x, lw)
            self._last = (key, (x[:n], jnp.stack(states)))
        return self._last[1]

    def logits(self, tokens: np.ndarray, positions: Sequence[int]):
        """Next-token logits [len(positions), V] after each of
        ``positions`` of one sequence (full forward pass, no cache, no
        state carried)."""
        x = self._forward(tokens)[0]
        with jax.default_matmul_precision("highest"):
            out = self._jit["logits"](
                x[jnp.asarray(list(positions))], self.params["final_norm"],
                self.params["embed"])
        return np.asarray(out)

    def states(self, tokens: np.ndarray):
        """The Mamba layers' states after the sequence's last position,
        [Mamba layers, I, N] float32 on the device: what a slot has to hold
        of the sequence (the program holds each transposed)."""
        return self._forward(tokens)[1]
