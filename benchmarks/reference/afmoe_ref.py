"""The plain reference of the Trinity-Mini decoder (arcee-ai/Trinity-Mini,
``model_type`` ``afmoe``): forward pass, loss and gradient norm in
straightforward ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, with no kernel, no cache, no
ring, no page, no sort and no grouped product: the routed FFN is a plain
loop over the experts with a mask, and a window layer is a mask on the whole
score matrix.  Independent of ``ray_tpu/models``: it shares only the layout
of the weight tree (``layers[i].attn.wq`` / ``.wg`` [d, H*D], ``.wk`` /
``.wv`` [d, H_kv*D], ``.wo`` [H*D, d], ``.q_norm`` / ``.k_norm`` [D];
``layers[i].attn_norm``, ``.attn_post_norm``, ``.mlp_norm`` or
``.moe_norm``, ``.ffn_post_norm``; ``layers[i].mlp.w1/w3/w2`` in a dense
layer; ``layers[i].moe.router`` [d, E], ``.router_bias`` [E], ``.w1`` /
``.w3`` [E, d, f], ``.w2`` [E, f, d], ``.shared.w1/w3/w2``; ``embed``,
``final_norm``, ``lm_head``), because it is given the cell's own weights.

The equations (config keys in brackets; what the config has no key for is in
the configuration file's ``assumed``):

    x   = E[t] * sqrt(hidden_size)                    mup_enabled
    layer l on one sequence x [S, d], four norms (sandwich):
    h   = RMSNorm(x; attn_norm)                       rms_norm_eps
    q, k, v = h Wq, h Wk, h Wv                        H heads, H_kv KV heads, all head_dim wide
    g   = h Wg                                        the gate, H * head_dim wide
    q_i = RMSNorm(q_i; q_norm), k_j = RMSNorm(k_j; k_norm)   A HEAD: one weight of head_dim each
    layer_types[l] sliding_attention: q, k = RoPE(q, k)      rope_theta, half-split pairs; and the
                                            mask 0 <= i - j < sliding_window
    layer_types[l] full_attention:  no positional term at all, causal
    a   = softmax(q k^T / sqrt(head_dim) + mask) v
    x'  = x + RMSNorm((concat(a) * sigmoid(g)) Wo; attn_post_norm)
    u   = RMSNorm(x'; mlp_norm or moe_norm)
    l < num_dense_layers:  F = W_down(silu(W_gate u) * (W_up u))      intermediate_size
    else: s = sigmoid(u W_r) in float32; the num_experts_per_tok largest of s + b
          chosen (b: expert_bias, in the choice only; n_group 1, topk_group 1);
          w_e = route_scale * s_e / (sum of the chosen s + 1e-20)     route_norm
          F = sum_e w_e E_e(u) + E_shared(u)          every E a SwiGLU of moe_intermediate_size
    x'' = x' + RMSNorm(F; ffn_post_norm)
    after the last layer: RMSNorm, untied head.

Departures from what a published implementation would do, none of which
changes the mathematics:
- weights are stored input-major ([d, out], ``x @ W``), as the system's tree
  has them;
- RoPE pairs dimension i with i + D/2 (half-split), as the system does;
- every expert multiplies every token and a mask of the router's weights
  (zero where the expert is not chosen) selects: the same sum, no gather;
- ``num_shared_experts`` experts are one SwiGLU of their widths together;
- the loss adds the auxiliary load-balancing term the SYSTEM's loss adds
  (over the routed layers; a token's first expert, and each expert's share of
  the sigmoid scores), as ``olmoe_ref.py`` says of its own;
- to fit 4,106 tokens beside the engine's weights and pools the forward pass
  works one layer at a time in three steps (projections; attention,
  ``Q_BLOCK`` queries at a time against all keys; gate, output projection
  and the FFN, one expert's float32 copy at a time under ``lax.scan``), on
  the sequence padded behind its end to whole query blocks.  The gradient is
  one ``value_and_grad`` over the whole float32 tree: no cell trains this
  family, so it has only the tests' tiny sizes to hold.
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


def _swiglu(u, m):
    return (jax.nn.silu(u @ _f32(m["w1"])) * (u @ _f32(m["w3"]))) \
        @ _f32(m["w2"])


def _project(x, layer, *, n_heads, n_kv, hd, eps, theta, rotary):
    """The layer's first step: q [H, S, D], k and v [H, S, D] with the KV
    heads repeated, q and k normalised a head and (``rotary``) rotated; and
    the gate's logits [S, H * D]."""
    s = x.shape[0]
    a = layer["attn"]
    h = _rms(x, layer["attn_norm"], eps)
    q = (h @ _f32(a["wq"])).reshape(s, n_heads, hd)
    k = (h @ _f32(a["wk"])).reshape(s, n_kv, hd)
    v = (h @ _f32(a["wv"])).reshape(s, n_kv, hd).transpose(1, 0, 2)
    q = _rms(q, a["q_norm"], eps).transpose(1, 0, 2)
    k = _rms(k, a["k_norm"], eps).transpose(1, 0, 2)
    if rotary:
        q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, n_heads // n_kv, axis=0)
    v = jnp.repeat(v, n_heads // n_kv, axis=0)
    return q, k, v, h @ _f32(a["wg"])


def _attend_block(q, k, v, lo, window):
    """Queries q [H, B, D] at positions lo.. against all keys k, v
    [H, S, D]: causal, and ``i - j < window`` (a layer without a window is
    given one longer than the sequence; ``lo`` and ``window`` are data, so
    one compiled block serves every block of every layer)."""
    _, b, hd = q.shape
    s = k.shape[1]
    scores = jnp.einsum("hqd,hkd->hqk", q, k) / math.sqrt(hd)
    i, j = lo + jnp.arange(b)[:, None], jnp.arange(s)[None, :]
    seen = (j <= i) & (i - j < window)
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
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


def _finish(x, o, gate, given, layer, *, eps, top_k, renormalise, scaling):
    """The layer's last step: attention's heads o [H, S, D] times the
    sigmoid of ``gate`` [S, H * D], through Wo and its post-norm, the
    residual; then the FFN of the normalised result (the dense SwiGLU of a
    layer that has ``mlp`` weights, else the sigmoid-routed experts,
    ``given``: see ``_route``, beside the shared one) through ITS post-norm,
    and the residual.  Returns (y, the layer's auxiliary parts, the experts
    used [S, k], the routing margin [S], the reach of ``given`` [S]); a
    dense layer's last four are None."""
    s = x.shape[0]
    a = o.transpose(1, 0, 2).reshape(s, -1) * jax.nn.sigmoid(gate)
    x = x + _rms(a @ _f32(layer["attn"]["wo"]), layer["attn_post_norm"], eps)
    if "mlp" in layer:
        u = _rms(x, layer["mlp_norm"], eps)
        return x + _rms(_swiglu(u, layer["mlp"]), layer["ffn_post_norm"],
                        eps), None, None, None, None
    m = layer["moe"]
    u = _rms(x, layer["moe_norm"], eps)
    logits = u @ _f32(m["router"])
    share, weight, top_e, margin, reach = _route(
        logits, _f32(m["router_bias"]), given, top_k, renormalise, scaling)
    n_experts = share.shape[-1]

    def expert(y, e):  # every expert on every token, then a mask
        w = {k: jax.lax.dynamic_index_in_dim(m[k], e, 0, False)
             for k in ("w1", "w3", "w2")}
        return y + jax.lax.dynamic_slice_in_dim(weight, e, 1, 1) \
            * _swiglu(u, w), None

    # A loop over the experts, one at a time (``lax.scan`` and not 128
    # unrolled copies).
    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(n_experts))
    y = y + _swiglu(u, m["shared"])
    first = jax.nn.one_hot(top_e[:, 0], n_experts, dtype=jnp.float32)
    return (x + _rms(y, layer["ffn_post_norm"], eps),
            (first.sum(0), share.sum(0)), top_e, margin, reach)


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
        self.top_k = model["num_experts_per_tok"]
        self.window = int(model["sliding_window"])
        self.sliding = [t == "sliding_attention"
                        for t in model["layer_types"]]
        self.embed_scale = math.sqrt(model["hidden_size"]) \
            if model["mup_enabled"] else 1.0
        self._project = functools.partial(
            _project, n_heads=model["num_attention_heads"],
            n_kv=model["num_key_value_heads"], hd=model["head_dim"],
            eps=self.eps, theta=float(model["rope_theta"]))
        self._finish = functools.partial(
            _finish, eps=self.eps, top_k=self.top_k,
            renormalise=bool(model["route_norm"]),
            scaling=float(model["route_scale"]))
        self._jit = {
            "project": jax.jit(self._project, static_argnames="rotary"),
            "attend": jax.jit(_attend_block),
            "finish": jax.jit(self._finish),
            "logits": jax.jit(functools.partial(_logits, eps=self.eps))}
        #: The last sequence's forward pass (the serving check asks for the
        #: same sequence's logits twice, cold and cached).
        self._last = (None, None)

    def _embed(self, params, tokens):
        return _f32(params["embed"][tokens]) * self.embed_scale

    def _layer(self, li: int, x, lw, given=None, fns=None):
        """Layer ``li`` on x [S, d], a query block at a time; returns what
        ``_finish`` returns.  ``given`` [S, k]: experts to use in place of
        the router's choice, -1 for the tokens left to it (None: all)."""
        fns = fns or self._jit
        if given is None:
            given = jnp.full((x.shape[0], self.top_k), -1, jnp.int32)
        # A sliding layer rotates and masks; a full layer does neither.
        q, k, v, gate = fns["project"](x, lw, rotary=self.sliding[li])
        window = self.window if self.sliding[li] else x.shape[0] + 1
        o = jnp.concatenate([
            fns["attend"](q[:, lo:lo + Q_BLOCK], k, v, lo, window)
            for lo in range(0, x.shape[0], Q_BLOCK)], axis=1)
        return fns["finish"](x, o, gate, given, lw)

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
                x = self._embed(self.params, jnp.asarray(padded))
                used, margins, reaches = [], [], []
                for li, lw in enumerate(layers):
                    x, _, e, m, r = self._layer(
                        li, x, lw, jnp.asarray(given[li], jnp.int32))
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
            x = self._embed(params, tokens[i])
            for li, lw in enumerate(params["layers"]):
                x, aux, _, _, _ = self._layer(li, x, lw, None, plain)
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
