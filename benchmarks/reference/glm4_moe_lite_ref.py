"""The plain reference of the GLM-4.7-Flash decoder (``model_type``
``glm4_moe_lite``): forward pass, loss and gradient norm in straightforward
``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``, with no
kernel, no cache, no page, no sort and no grouped product.  Attention is the
EXPANDED form only (every position's keys and values are made out of its
latent; the absorbed form the serving programs use is never written here),
the routed FFN is a plain loop over the experts with a mask.  Independent of
``ray_tpu/models``: it shares only the layout of the weight tree
(``layers[i].attn.wq_a`` [d, q_lora], ``.q_norm``, ``.wq_b`` [q_lora,
H*(nope+rope)], ``.wkv_a`` [d, kv_lora+rope], ``.kv_norm``, ``.wkv_b``
[kv_lora, H*(nope+v)], ``.wo`` [H*v, d]; ``layers[0].mlp.w1/w3/w2``;
``layers[i].moe.router`` [d, E], ``.router_bias`` [E], ``.w1`` / ``.w3``
[E, d, f], ``.w2`` [E, f, d], ``.shared.w1/w3/w2``; the norms, ``embed``,
``lm_head``), because it is given the cell's own weights.

Layer ``l`` on one sequence x [S, d] (config keys in brackets):

    h   = RMSNorm(x; attn_norm)                       rms_norm_eps
    c_q = RMSNorm(h W_dq; q_norm)                     q_lora_rank
    [q_n ; q_r]_i = (c_q W_uq)_i                      H heads of qk_nope_head_dim + qk_rope_head_dim
    [c ; k_r] = h W_dkv                               kv_lora_rank + qk_rope_head_dim
    c'  = RMSNorm(c; kv_norm);  k_r' = RoPE(k_r)      ONE k_r' for all heads; rope_theta
    [k_n ; v]_i = (c' W_ukv)_i                        qk_nope_head_dim + v_head_dim
    s_ij = (q_n,i . k_n,j + RoPE(q_r,i) . k_r',j) / sqrt(nope + rope),  causal softmax
    x'  = x + concat_i(sum_j p_ij v_j) W_o
    g   = RMSNorm(x'; mlp_norm or moe_norm)
    layer < first_k_dense_replace:   x'' = x' + W_down(silu(W_gate g) * (W_up g))   intermediate_size
    else:  l = g W_r (float32);  s = sigmoid(l);  the num_experts_per_tok largest of
           s + b chosen (b: e_score_correction_bias, in the choice only);
           w_e = routed_scaling_factor * s_e / (sum of the chosen s + 1e-20)
           x'' = x' + sum_e w_e E_e(g) + E_shared(g)   every E a SwiGLU of moe_intermediate_size
    after the last layer: RMSNorm, untied head.

Departures from what a published implementation would do, none of which
changes the mathematics:
- weights are stored input-major ([d, out], ``x @ W``), as the system's tree
  has them;
- RoPE pairs dimension i with i + rope/2 (half-split), as the system does and
  the configuration file's ``assumed`` states; a checkpoint that stores the
  pairs interleaved is this with the rotary columns of W_uq and W_dkv permuted;
- ``n_group`` and ``topk_group`` are 1, so the group step of ``noaux_tc`` is
  the identity and is not written;
- every expert multiplies every token and a mask of the router's weights
  (zero where the expert is not chosen) selects: the same sum, no gather;
- ``n_shared_experts`` experts are one SwiGLU of their widths together;
- the multi-token-prediction block (``num_nextn_predict_layers``) is not
  computed: it does not enter the layers' logits (nor does the system);
- the loss adds the auxiliary load-balancing term the SYSTEM's loss adds
  (over the routed layers; a token's first expert, and each expert's share of
  the sigmoid scores), as ``olmoe_ref.py`` says of its own;
- to fit 17,016 tokens beside the weights the forward pass works one layer
  at a time in three steps (projections; attention, ``Q_BLOCK`` queries at a
  time against all keys; output projection and the FFN, one expert's float32
  copy at a time under ``lax.scan``), on the sequence padded behind its end
  to whole query blocks.  The gradient is one ``value_and_grad`` over the
  whole float32 tree: no cell trains this family, so it has only the tests'
  tiny sizes to hold.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256
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


def _swiglu(g, m):
    return (jax.nn.silu(g @ _f32(m["w1"])) * (g @ _f32(m["w3"]))) \
        @ _f32(m["w2"])


def _project(x, layer, *, n_heads, nope, rope, rank, eps, theta):
    """The layer's first step, the expanded form: q and k [H, S, nope +
    rope] (the one rotated key repeated for every head), v [H, S, v]; and
    what a cache would keep of each position, ``[c' ; k_r']`` [S, rank +
    rope] (nothing here reads it back: ``Reference.latent_rows``)."""
    s = x.shape[0]
    a = layer["attn"]
    h = _rms(x, layer["attn_norm"], eps)
    c_q = _rms(h @ _f32(a["wq_a"]), a["q_norm"], eps)
    q = (c_q @ _f32(a["wq_b"])).reshape(s, n_heads, nope + rope)
    q = q.transpose(1, 0, 2)
    kv = h @ _f32(a["wkv_a"])
    c = _rms(kv[:, :rank], a["kv_norm"], eps)
    k_r = _rope(kv[None, :, rank:], theta)                   # [1, S, rope]
    up = (c @ _f32(a["wkv_b"])).reshape(s, n_heads, -1).transpose(1, 0, 2)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    k = jnp.concatenate(
        [up[..., :nope], jnp.broadcast_to(k_r, (n_heads, s, rope))], -1)
    return q, k, up[..., nope:], jnp.concatenate([c, k_r[0]], -1)


def _attend_block(q, k, v, lo):
    """Queries q [H, B, D] at positions lo.. against all keys k [H, S, D]
    and values v [H, S, Dv], causal (``lo`` is data, so one compiled block
    serves every block of every layer)."""
    _, b, hd = q.shape
    scores = jnp.einsum("hqd,hkd->hqk", q, k) / math.sqrt(hd)
    i, j = lo + jnp.arange(b)[:, None], jnp.arange(k.shape[1])[None, :]
    probs = jax.nn.softmax(jnp.where((j <= i)[None], scores, -jnp.inf), -1)
    return jnp.einsum("hqk,hkd->hqd", probs, v)


def _route(logits, bias, given, top_k: int, renormalise: bool,
           scaling: float):
    """Each expert's share of the sigmoid scores [S, E] (what the system's
    auxiliary term reads); the weight of every expert for every token
    [S, E] (``scaling`` x its score where it is among the token's chosen,
    renormalised over them, else 0); the chosen experts [S, k]; the MARGIN
    of that choice [S] (the k-th largest of score + bias less the next
    one: how far the token is from being routed otherwise); and, where
    ``given`` [S, k] names a token's experts (not -1), those are used in
    place of the router's choice and ``reach`` [S] says how far from it
    they are, in score + bias: the largest among the experts of its choice
    that were left out, less the smallest among those taken in their place
    (0 where the sets are the same).  The bias enters the choice, the
    margin and the reach, and no weight."""
    scores = jax.nn.sigmoid(logits)
    ranked, order = jax.lax.top_k(scores + bias, top_k + 1)
    margin = ranked[:, top_k - 1] - ranked[:, top_k]
    top_e = jnp.where(given[:, :1] >= 0, given, order[:, :top_k])
    rows = jnp.arange(logits.shape[0])[:, None]
    taken = jnp.zeros(logits.shape, bool).at[rows, top_e].set(True)
    own = jnp.zeros(logits.shape, bool).at[rows, order[:, :top_k]].set(True)
    left_out = jnp.where(own & ~taken, scores + bias, -jnp.inf).max(-1)
    in_place = jnp.where(taken & ~own, scores + bias, jnp.inf).min(-1)
    reach = jnp.where((own == taken).all(-1), 0.0, left_out - in_place)
    top_p = jnp.take_along_axis(scores, top_e, axis=-1)
    if renormalise:
        top_p = top_p / (top_p.sum(-1, keepdims=True) + 1e-20)
    weight = jnp.zeros_like(scores).at[rows, top_e].set(scaling * top_p)
    share = scores / scores.sum(-1, keepdims=True)
    return share, weight, top_e, margin, reach


def _finish(x, o, given, layer, *, eps, top_k, renormalise, scaling):
    """The layer's last step: attention's heads o [H, S, Dv] through W_o,
    the residual, and the FFN of the normalised result: the dense SwiGLU of
    a layer that has ``mlp`` weights, else the sigmoid-routed experts
    (``given``: see ``_route``) beside the shared one.  Returns (y, the
    layer's auxiliary parts, the experts used [S, k], the routing margin
    [S], the reach of ``given`` [S]); a dense layer's last four are None."""
    s = x.shape[0]
    x = x + o.transpose(1, 0, 2).reshape(s, -1) @ _f32(layer["attn"]["wo"])
    if "mlp" in layer:
        g = _rms(x, layer["mlp_norm"], eps)
        return x + _swiglu(g, layer["mlp"]), None, None, None, None
    m = layer["moe"]
    g = _rms(x, layer["moe_norm"], eps)
    logits = g @ _f32(m["router"])
    share, weight, top_e, margin, reach = _route(
        logits, _f32(m["router_bias"]), given, top_k, renormalise, scaling)
    n_experts = share.shape[-1]

    def expert(y, e):  # every expert on every token, then a mask
        w = {k: jax.lax.dynamic_index_in_dim(m[k], e, 0, False)
             for k in ("w1", "w3", "w2")}
        return y + jax.lax.dynamic_slice_in_dim(weight, e, 1, 1) \
            * _swiglu(g, w), None

    # A loop over the experts, one at a time (``lax.scan`` and not 64
    # unrolled copies: at 17,000 tokens those take minutes to compile).
    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(n_experts))
    y = y + _swiglu(g, m["shared"])
    first = jax.nn.one_hot(top_e[:, 0], n_experts, dtype=jnp.float32)
    return x + y, (first.sum(0), share.sum(0)), top_e, margin, reach


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
        self.n_experts = model["n_routed_experts"]
        self.top_k = model["num_experts_per_tok"]
        self._project = functools.partial(
            _project, n_heads=model["num_attention_heads"],
            nope=model["qk_nope_head_dim"], rope=model["qk_rope_head_dim"],
            rank=model["kv_lora_rank"], eps=self.eps,
            theta=float(model["rope_theta"]))
        self._finish = functools.partial(
            _finish, eps=self.eps, top_k=self.top_k,
            renormalise=bool(model["norm_topk_prob"]),
            scaling=float(model["routed_scaling_factor"]))
        self._jit = {
            "project": jax.jit(self._project),
            "attend": jax.jit(_attend_block),
            "finish": jax.jit(self._finish),
            "logits": jax.jit(functools.partial(_logits, eps=self.eps))}
        #: The last sequence's forward pass (the serving check asks for the
        #: same sequence's logits twice, cold and cached).
        self._last = (None, None)

    def _layer(self, x, lw, given=None, fns=None):
        """One layer on x [S, d], a query block at a time; returns what
        ``_finish`` returns.  ``given`` [S, k]: experts to use in place of
        the router's choice, -1 for the tokens left to it (None: all)."""
        fns = fns or self._jit
        if given is None:
            given = jnp.full((x.shape[0], self.top_k), -1, jnp.int32)
        q, k, v, _ = fns["project"](x, lw)
        o = jnp.concatenate([
            fns["attend"](q[:, lo:lo + Q_BLOCK], k, v, lo)
            for lo in range(0, x.shape[0], Q_BLOCK)], axis=1)
        return fns["finish"](x, o, given, lw)

    def _forward(self, tokens, given=None):
        """One sequence through every layer: (hidden [S, d], per-layer
        experts used [L, S, k], per-layer routing margins [L, S],
        per-layer reach of ``given`` [L, S]); a dense layer's experts are
        -1, its margin infinite and its reach 0.  ``given`` [L, S, k]:
        experts to use in place of the router's choice, -1 where it is
        left to choose.  The sequence is padded behind its end to whole
        query blocks (causal attention: no real position sees the
        padding), so that every block is one compiled shape."""
        tokens = np.asarray(tokens, np.int32)
        n, layers = len(tokens), self.params["layers"]
        if given is None:
            given = np.full((len(layers), n, self.top_k), -1, np.int32)
        key = tokens.tobytes() + np.asarray(given, np.int32).tobytes()
        if self._last[0] != key:
            pad = -n % Q_BLOCK
            padded = np.concatenate([tokens, np.zeros((pad,), np.int32)])
            given = np.concatenate(
                [given, np.full((len(layers), pad, self.top_k), -1)], 1)
            with jax.default_matmul_precision("highest"):
                x = _f32(self.params["embed"][jnp.asarray(padded)])
                used, margins, reaches = [], [], []
                for li, lw in enumerate(layers):
                    x, _, e, m, r = self._layer(
                        x, lw, jnp.asarray(given[li], jnp.int32))
                    routed = e is not None
                    used.append(np.asarray(e)[:n] if routed else np.full(
                        (n, self.top_k), -1, np.int32))
                    margins.append(np.asarray(m)[:n] if routed
                                   else np.full((n,), np.inf, np.float32))
                    reaches.append(np.asarray(r)[:n] if routed
                                   else np.zeros((n,), np.float32))
            self._last = (key, (x[:n], np.stack(used), np.stack(margins),
                                np.stack(reaches)))
        return self._last[1]

    def logits(self, tokens: np.ndarray, positions: Sequence[int],
               given=None):
        """Next-token logits [len(positions), V] after each of
        ``positions`` of one sequence (full forward pass, no cache).
        ``given``: see ``routing``."""
        x = self._forward(tokens, given)[0]
        with jax.default_matmul_precision("highest"):
            out = self._jit["logits"](
                x[jnp.asarray(list(positions))], self.params["final_norm"],
                self.params["lm_head"])
        return np.asarray(out)

    def latent_rows(self, tokens: np.ndarray, given=None):
        """``[norm(c_kv) ; RoPE(k_r)]`` of every position in every layer,
        [L, S, rank + rope] float32 on the device: what a latent cache has
        to hold of the sequence, for a comparison that reads the system's
        pool (a full forward pass of its own, nothing kept)."""
        tokens = np.asarray(tokens, np.int32)
        n, pad = len(tokens), -len(tokens) % Q_BLOCK
        padded = np.concatenate([tokens, np.zeros((pad,), np.int32)])
        rows = []
        with jax.default_matmul_precision("highest"):
            x = _f32(self.params["embed"][jnp.asarray(padded)])
            for li, lw in enumerate(self.params["layers"]):
                rows.append(self._jit["project"](x, lw)[3][:n])
                g = None if given is None else jnp.asarray(np.concatenate(
                    [given[li], np.full((pad, self.top_k), -1)]), jnp.int32)
                x = self._layer(x, lw, g)[0]
        return jnp.stack(rows)

    def top_experts(self, tokens: np.ndarray) -> np.ndarray:
        """The experts every token of one sequence is routed to, in every
        layer: [L, S, k], each row sorted (-1 in a dense layer)."""
        return np.sort(self._forward(tokens)[1], axis=-1)

    def routing(self, tokens: np.ndarray, given=None):
        """(margins [L, S], reach [L, S]) of one sequence.  A margin is how
        far a token is, in a layer, from being routed otherwise: the k-th
        largest of its scores plus bias less the next one.  Top-k routing
        is discontinuous there: a system whose hidden state differs by
        rounding takes the next expert where the margin is under that
        rounding, and its logits for that token then differ by a share of
        an FFN's output, not by rounding.  So a comparison may hand the
        reference the experts the system took (``given`` [L, S, k], -1
        where it took none or is not asked): the reference computes with
        those, and ``reach`` says how far each set is from its own
        router's choice (``_route``): 0 the same experts, a few
        thousandths a tie that rounding decided, more another model's
        routing."""
        return self._forward(tokens, given)[2:]

    def _loss(self, params, tokens, targets):
        """Mean next-token loss of the batch plus the auxiliary term; the
        batch's sequences one at a time (a plain Python loop)."""
        plain = {"project": self._project, "attend": _attend_block,
                 "finish": self._finish}
        b, s = tokens.shape
        nll, parts = 0.0, {}
        for i in range(b):
            x = _f32(params["embed"][tokens[i]])
            for li, lw in enumerate(params["layers"]):
                x, aux, _, _, _ = self._layer(x, lw, None, plain)
                if aux is not None:
                    f, p = parts.get(li, (0.0, 0.0))
                    parts[li] = (f + aux[0], p + aux[1])
            nll = nll + _nll_sum(x, params["final_norm"], params["lm_head"],
                                 targets[i], self.eps)
        n = float(b * s)
        aux = sum(self.n_experts * jnp.sum((f / n) * (p / n))
                  for f, p in parts.values()) / max(len(parts), 1)
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
