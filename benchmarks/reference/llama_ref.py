"""The plain reference of the dense decoder the configurations describe:
forward pass, loss and gradients in straightforward ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, with no kernel, no cache, no
batching and no sharding.  Independent of ``ray_tpu/models``: it shares only
the layout of the weight tree (``embed``, ``layers[i].attn.wq`` ...), because
it is given the cell's own weights.

Block (Llama / Mistral / InternLM2 as published): pre-norm RMSNorm, grouped-
query attention with rotary embeddings in the half-split (``rotate_half``)
convention, SwiGLU MLP, untied output head.  InternLM2's fused ``wqkv`` is a
storage layout and is not reproduced.

To fit beside the weights it works one sequence and one layer at a time,
upcasting that layer's weights only, and attention walks the queries in
blocks; the gradient is a hand-rolled reverse pass over the layers
(``jax.vjp`` of one layer at a time), so only one layer's float32 gradient
is alive at once.  None of that changes the mathematics.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import teacher_forced_gaps  # noqa: F401 (tests import it from here)

Q_BLOCK = 1024


def _f32(tree):
    return jax.tree.map(lambda t: t.astype(jnp.float32), tree)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [heads, S, D]; position p rotates pair (i, i + D/2) by
    p / theta**(2i / D)."""
    _, s, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)
    c, sn = jnp.cos(ang)[None], jnp.sin(ang)[None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-1)


def _layer(x, layer, *, n_heads: int, n_kv: int, eps: float, theta: float):
    """One decoder block on one sequence: x [S, d] float32, ``layer`` the
    block's weights already in float32."""
    s, d = x.shape
    hd = d // n_heads
    a, m = layer["attn"], layer["mlp"]
    h = _rms(x, layer["attn_norm"], eps)
    q = (h @ a["wq"]).reshape(s, n_heads, hd).transpose(1, 0, 2)
    k = (h @ a["wk"]).reshape(s, n_kv, hd).transpose(1, 0, 2)
    v = (h @ a["wv"]).reshape(s, n_kv, hd).transpose(1, 0, 2)
    q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, n_heads // n_kv, axis=0)
    v = jnp.repeat(v, n_heads // n_kv, axis=0)
    outs = []
    for lo in range(0, s, Q_BLOCK):  # queries in blocks; keys whole
        hi = min(lo + Q_BLOCK, s)
        scores = jnp.einsum("hqd,hkd->hqk", q[:, lo:hi], k) / math.sqrt(hd)
        causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        outs.append(jnp.einsum("hqk,hkd->hqd", probs, v))
    o = jnp.concatenate(outs, axis=1).transpose(1, 0, 2).reshape(s, d)
    x = x + o @ a["wo"]
    h = _rms(x, layer["mlp_norm"], eps)
    return x + (jax.nn.silu(h @ m["w1"]) * (h @ m["w3"])) @ m["w2"]


def _logits(x, final_norm, lm_head, eps):
    return _rms(x, final_norm, eps) @ lm_head


def _nll_sum(x, final_norm, lm_head, targets, eps):
    logits = _logits(x, final_norm, lm_head, eps)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return (logz - gold).sum()


class Reference:
    """The reference over one weight tree.  ``model`` is a loaded
    configuration file; ``params`` the program's weight tree (any dtype, on
    any devices); everything is computed on ``device``."""

    def __init__(self, model: Dict[str, Any], params, device=None):
        self.device = device or jax.devices()[0]
        self.params = params
        self.eps = float(model["rms_norm_eps"])
        kw = dict(n_heads=model["num_attention_heads"],
                  n_kv=model["num_key_value_heads"], eps=self.eps,
                  theta=float(model["rope_theta"]))
        layer = functools.partial(_layer, **kw)
        self._layer = jax.jit(layer)
        self._layer_vjp = jax.jit(
            lambda x, lw, ct: jax.vjp(layer, x, lw)[1](ct))
        self._logits = jax.jit(functools.partial(_logits, eps=self.eps))
        self._head_vg = jax.jit(jax.value_and_grad(
            functools.partial(_nll_sum, eps=self.eps), argnums=(0, 1, 2)))

    def _here(self, tree):
        """``tree`` gathered onto the reference's device, in float32."""
        return _f32(jax.device_put(tree, self.device))

    def hidden(self, tokens: np.ndarray) -> List[jax.Array]:
        """The residual stream of one sequence entering each layer, and
        last the one leaving the stack: L + 1 arrays [S, d]."""
        with jax.default_matmul_precision("highest"):
            embed = jax.device_put(self.params["embed"], self.device)
            xs = [embed[jnp.asarray(tokens)].astype(jnp.float32)]
            for lw in self.params["layers"]:
                xs.append(self._layer(xs[-1], self._here(lw)))
        return xs

    def logits(self, tokens: np.ndarray, positions: Sequence[int]):
        """Next-token logits [len(positions), V] after each of
        ``positions`` of one sequence (full forward pass, no cache)."""
        x = self.hidden(tokens)[-1][jnp.asarray(list(positions))]
        with jax.default_matmul_precision("highest"):
            out = self._logits(x, self._here(self.params["final_norm"]),
                               self._here(self.params["lm_head"]))
        return np.asarray(out)

    def loss_and_grad_norm(self, tokens: np.ndarray,
                           targets: np.ndarray) -> Tuple[float, float]:
        """Mean next-token loss over the batch [B, S], and the global
        2-norm of its gradient with respect to every weight."""
        b, s = tokens.shape
        n = float(b * s)
        sq = 0.0
        with jax.default_matmul_precision("highest"):
            xs = [self.hidden(tokens[i]) for i in range(b)]
            fn = self._here(self.params["final_norm"])
            lm = self._here(self.params["lm_head"])
            total, cts, g_fn, g_lm = 0.0, [], 0.0, 0.0
            for i in range(b):
                nll, (dx, dfn, dlm) = self._head_vg(
                    xs[i][-1], fn, lm, jnp.asarray(targets[i]))
                total += float(nll)
                cts.append(dx / n)
                g_fn, g_lm = g_fn + dfn / n, g_lm + dlm / n
            sq += float(jnp.sum(g_fn * g_fn) + jnp.sum(g_lm * g_lm))
            del g_lm, lm
            for li in reversed(range(len(self.params["layers"]))):
                lw = self._here(self.params["layers"][li])
                acc = None
                for i in range(b):
                    cts[i], g = self._layer_vjp(xs[i][li], lw, cts[i])
                    acc = g if acc is None else jax.tree.map(
                        jnp.add, acc, g)
                sq += float(sum(jnp.sum(t * t)
                                for t in jax.tree.leaves(acc)))
                del acc, lw
            v, d = self.params["embed"].shape
            g_emb = jnp.zeros((v, d), jnp.float32, device=self.device)
            for i in range(b):
                g_emb = g_emb.at[jnp.asarray(tokens[i])].add(cts[i])
            sq += float(jnp.sum(g_emb * g_emb))
        return total / n, math.sqrt(sq)
