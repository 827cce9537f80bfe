"""The plain reference of the OLMoE decoder (allenai/OLMoE-1B-7B): forward
pass, loss and gradient norm in straightforward ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, with no kernel, no cache, no
sort and no grouped product: the routed FFN is a plain loop over the experts
with a mask.  Independent of ``ray_tpu/models``: it shares only the layout of
the weight tree (``layers[i].attn.wq``, ``.q_norm``, ``layers[i].moe.router``,
``.w1`` [E, d, f] ...), because it is given the cell's own weights.

The block, as the published modelling code has it (``transformers``
``models/olmoe/modeling_olmoe.py``):

    h = x + Attn(RMSNorm(x));   y = h + MoE(RMSNorm(h))
    Attn: q = q_norm(x Wq), k = k_norm(x Wk), v = x Wv; q_norm and k_norm are
          RMSNorm with a learned weight over the WHOLE projection width,
          before the split into heads and before RoPE (half-split
          ``rotate_half`` convention); causal softmax attention; Wo.
    MoE:  p = softmax(x Wg) over all experts in float32; the top-k
          probabilities kept as they are (``norm_topk_prob`` false) or
          renormalised to sum to 1 (true);
          out = sum over the k of p_e * W2_e(silu(W1_e x) * W3_e x).
    A final RMSNorm and an untied head.

Departures from the published code, none of which changes the mathematics:
- weights are stored input-major ([d, out], ``x @ W``), as the system's
  tree has them, where the published ``nn.Linear`` stores [out, d];
- the published loop visits only the tokens routed to an expert
  (``index_add_``); here every expert multiplies every token and a mask of
  the router's weights (zero where the expert is not among the token's
  top-k) selects: the same sum, with no gather;
- ``clip_qkv`` is null in the published config and is not implemented;
- the loss adds the auxiliary load-balancing term the SYSTEM's loss adds
  (``aux_loss_coeff`` x E x sum_e f_e P_e, f_e the share of tokens whose
  FIRST choice is e, P_e the mean router probability of e, averaged over
  the layers), not the published ``load_balancing_loss_func`` (which counts
  all top-k choices): it is the system's training objective that is held
  to the reference;
- to fit beside the weights the forward pass works one sequence and one
  layer at a time and upcasts one expert's weights at a time, and attention
  walks the queries in blocks.  The gradient is one ``value_and_grad`` over
  the whole float32 tree: no cell trains this family on a chip yet, so it
  has only the tests' tiny sizes to hold.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 1024
#: The weight of the auxiliary term in the system's loss
#: (``MoEConfig.aux_loss_coeff``'s default, which the family does not change).
AUX_LOSS_COEFF = 0.01


def _f32(t):
    return t.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(w)


def _rope(x, theta):
    """x [heads, S, D]; position p rotates pair (i, i + D/2) by
    p / theta**(2i / D)."""
    _, s, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)
    c, sn = jnp.cos(ang)[None], jnp.sin(ang)[None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-1)


def _route(h, router, top_k: int, renormalise: bool):
    """Router probabilities [S, E], and the weight of every expert for
    every token [S, E]: its probability where it is among the token's
    top-k, else 0."""
    probs = jax.nn.softmax(h @ _f32(router), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    if renormalise:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    weight = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], top_e].set(top_p)
    return probs, weight, top_e


def _layer(x, layer, *, n_heads: int, n_kv: int, eps: float, theta: float,
           top_k: int, renormalise: bool, qk_norm: bool):
    """One decoder block on one sequence: x [S, d] float32, ``layer`` the
    block's weights as stored.  Returns (y, the layer's auxiliary term, the
    top-k experts of every token [S, k])."""
    s, d = x.shape
    hd = d // n_heads
    a, m = layer["attn"], layer["moe"]
    h = _rms(x, layer["attn_norm"], eps)
    q, k, v = h @ _f32(a["wq"]), h @ _f32(a["wk"]), h @ _f32(a["wv"])
    if qk_norm:  # over the whole projection width, before heads and RoPE
        q, k = _rms(q, a["q_norm"], eps), _rms(k, a["k_norm"], eps)
    q = _rope(q.reshape(s, n_heads, hd).transpose(1, 0, 2), theta)
    k = _rope(k.reshape(s, n_kv, hd).transpose(1, 0, 2), theta)
    v = v.reshape(s, n_kv, hd).transpose(1, 0, 2)
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
    x = x + o @ _f32(a["wo"])

    h = _rms(x, layer["moe_norm"], eps)
    probs, weight, top_e = _route(h, m["router"], top_k, renormalise)
    n_experts = probs.shape[-1]
    y = jnp.zeros_like(x)
    for e in range(n_experts):  # every expert on every token, then a mask
        out = (jax.nn.silu(h @ _f32(m["w1"][e])) * (h @ _f32(m["w3"][e]))
               ) @ _f32(m["w2"][e])
        y = y + weight[:, e:e + 1] * out
    first = jax.nn.one_hot(top_e[:, 0], n_experts, dtype=jnp.float32)
    aux_parts = (first.sum(0), probs.sum(0))  # summed over this sequence
    return x + y, aux_parts, top_e


def _logits(x, final_norm, lm_head, eps):
    return _rms(x, final_norm, eps) @ _f32(lm_head)


def _nll_sum(x, final_norm, lm_head, targets, eps):
    logits = _logits(x, final_norm, lm_head, eps)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return (logz - gold).sum()


class Reference:
    """The reference over one weight tree.  ``model`` is a loaded
    configuration file; ``params`` the program's weight tree (any dtype);
    everything is computed on ``device``."""

    def __init__(self, model: Dict[str, Any], params, device=None):
        self.device = device or jax.devices()[0]
        self.params = jax.device_put(params, self.device)
        self.eps = float(model["rms_norm_eps"])
        self.n_experts = model["num_experts"]
        self.layer = functools.partial(
            _layer, n_heads=model["num_attention_heads"],
            n_kv=model["num_key_value_heads"], eps=self.eps,
            theta=float(model["rope_theta"]),
            top_k=model["num_experts_per_tok"],
            renormalise=bool(model["norm_topk_prob"]),
            # No key in config.json: the modelling code always has it; a
            # configuration file may say otherwise under this key.
            qk_norm=bool(model.get("qk_norm", True)))
        self._layer_jit = jax.jit(self.layer)
        self._logits = jax.jit(functools.partial(_logits, eps=self.eps))

    def _embed(self, tokens):
        return _f32(self.params["embed"][jnp.asarray(tokens)])

    def _stack(self, x):
        """x through every layer: (y, per-layer auxiliary parts, per-layer
        top-k experts)."""
        aux, tops = [], []
        for lw in self.params["layers"]:
            x, a, t = self._layer_jit(x, lw)
            aux.append(a)
            tops.append(t)
        return x, aux, tops

    def logits(self, tokens: np.ndarray, positions: Sequence[int]):
        """Next-token logits [len(positions), V] after each of
        ``positions`` of one sequence (full forward pass, no cache)."""
        with jax.default_matmul_precision("highest"):
            x = self._stack(self._embed(tokens))[0]
            out = self._logits(x[jnp.asarray(list(positions))],
                               self.params["final_norm"],
                               self.params["lm_head"])
        return np.asarray(out)

    def top_experts(self, tokens: np.ndarray) -> np.ndarray:
        """The experts every token of one sequence is routed to, in every
        layer: [L, S, k], each row sorted."""
        with jax.default_matmul_precision("highest"):
            tops = self._stack(self._embed(tokens))[2]
        return np.sort(np.stack([np.asarray(t) for t in tops]), axis=-1)

    def _loss(self, params, tokens, targets):
        """Mean next-token loss of the batch plus the auxiliary term; the
        batch's sequences one at a time (a plain Python loop)."""
        b, s = tokens.shape
        n_layers = len(params["layers"])
        nll = 0.0
        first = [0.0] * n_layers
        prob = [0.0] * n_layers
        for i in range(b):
            x = _f32(params["embed"][tokens[i]])
            for li, lw in enumerate(params["layers"]):
                x, (f, p), _ = self.layer(x, lw)
                first[li], prob[li] = first[li] + f, prob[li] + p
            nll = nll + _nll_sum(x, params["final_norm"], params["lm_head"],
                                 targets[i], self.eps)
        n = float(b * s)
        aux = sum(self.n_experts * jnp.sum((f / n) * (p / n))
                  for f, p in zip(first, prob)) / n_layers
        return nll / n + AUX_LOSS_COEFF * aux

    def loss_and_grad_norm(self, tokens: np.ndarray,
                           targets: np.ndarray) -> Tuple[float, float]:
        """The system's training loss over the batch [B, S] (mean
        next-token loss plus the auxiliary load-balancing term), and the
        global 2-norm of its gradient with respect to every weight."""
        with jax.default_matmul_precision("highest"):
            params = jax.tree.map(_f32, self.params)
            loss, grads = jax.jit(jax.value_and_grad(self._loss))(
                params, jnp.asarray(tokens), jnp.asarray(targets))
            sq = sum(float(jnp.sum(g * g)) for g in jax.tree.leaves(grads))
        return float(loss), math.sqrt(sq)
