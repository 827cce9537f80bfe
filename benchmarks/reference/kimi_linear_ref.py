"""The plain reference of the Kimi-Linear decoder (``model_type``
``kimi_linear``): the forward pass in straightforward ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, with no kernel, no cache, no
page, no chunk, no sort and no grouped product.  A gated delta-rule layer is
the token-by-token recurrence and nothing else (a ``lax.scan`` over the
positions with the matrix state as its carry); latent attention is the
EXPANDED form; the routed FFN is a plain loop over the held experts with a
mask.  Independent of ``ray_tpu/models``: it shares only the layout of the
weight tree, because it is given the cell's own weights
(``layers[i].attn`` of a KDA layer: ``wq`` / ``wk`` / ``wv`` [d, H*D],
``conv_q`` / ``conv_k`` / ``conv_v`` [taps, H*D] with the LAST tap on the
current position, ``wf_a`` [d, D], ``wf_b`` [D, H*D], ``A_log`` [H],
``dt_bias`` [H*D], ``wb`` [d, H], ``wg_a`` [d, D], ``wg_b`` [D, H*D],
``o_norm`` [D], ``wo`` [H*D, d]; of a latent layer: ``wq`` [d, H*(nope+rope)],
``wkv_a`` [d, rank+rope], ``kv_norm``, ``wkv_b`` [rank, H*(nope+v)], ``wo``;
``layers[0].mlp.w1/w3/w2``; ``layers[i].moe.router`` [d, 256],
``.router_bias`` [256], ``.w1`` / ``.w3`` [held, d, f], ``.w2`` [held, f, d],
``.shared``; the norms, ``embed``, ``lm_head``).

Layer ``l`` (1-indexed in the config's lists) on one sequence x [S, d], with
h = RMSNorm(x; attn_norm):

  in ``linear_attn_config.kda_layers`` (H heads of D = ``head_dim`` 128):
    q, k, v = SiLU(conv(h W_q)), SiLU(conv(h W_k)), SiLU(conv(h W_v))
        conv: out_t = sum_j w_j pre_{t - (taps-1) + j}, zeros before t = 0
    q_i, k_i L2-normalised a head (x rsqrt(sum x^2 + 1e-6)); q_i x D^-0.5
    g_t = -exp(A_log_i) softplus(h W_f1 W_f2 + dt_bias)   [H, D], a = exp(g)
    beta_t = sigmoid(h W_b)                                [H]
    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t
    x' = x + [RMSNorm_head(o_t; o_norm) * sigmoid(h W_g1 W_g2)] W_o
  in ``linear_attn_config.full_attn_layers``:
    [q_n ; q_r]_i = (h W_q)_i        nope 128 + rope 64; no latent, no norm
    [c ; k_r] = h W_dkv;  c' = RMSNorm(c; kv_norm)
    [k_n ; v]_i = (c' W_ukv)_i
    s_ij = (q_n,i . k_n,j + q_r,i . k_r,j) / sqrt(192), causal softmax
        NO rotation anywhere (``mla_use_nope``)
    x' = x + concat_i(sum_j p_ij v_j) W_o
  then g = RMSNorm(x'; mlp_norm or moe_norm) and
    layer <= first_k_dense_replace:  x'' = x' + SwiGLU(g)     intermediate_size
    else: s = sigmoid(g W_r) over the ROUTER's 256; the
          num_experts_per_token largest of s + b chosen (b in the choice
          only); w_e = routed_scaling_factor s_e / (sum of the chosen s +
          1e-20); x'' = x' + sum over the chosen e HELD HERE of w_e E_e(g)
          + E_shared(g): the partial sum of this chip's experts
          (``first_expert`` .. + ``num_experts``), the shared expert once.
  after the last layer: RMSNorm, the held rows of the untied head.

What a deployment's other chips would add (the other experts' terms, the
other layers, the rest of the vocabulary) is in neither the program nor
here: both compute the same share.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..families.kimi_linear import layer_kinds  # no JAX, no program

Q_BLOCK = 256


def _f32(t):
    return t.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(w)


def _swiglu(g, m):
    return (jax.nn.silu(g @ _f32(m["w1"])) * (g @ _f32(m["w3"]))) \
        @ _f32(m["w2"])


def _conv_silu(pre, w):
    """pre [S, C] through the causal depthwise convolution w [taps, C] (the
    last tap on the current position), then SiLU."""
    taps, s = w.shape[0], pre.shape[0]
    rows = jnp.concatenate([jnp.zeros((taps - 1, pre.shape[1])), pre])
    return jax.nn.silu(sum(rows[j:j + s] * _f32(w[j]) for j in range(taps)))


def _kda(x, layer, n, *, heads, hd, eps):
    """A gated delta-rule layer's attention half on x [S, d]: the
    recurrence, one position at a time.  Returns (x', the state after the
    first ``n`` positions: the rows behind them are padding and leave it as
    it is)."""
    s = x.shape[0]
    a = layer["attn"]
    h = _rms(x, layer["attn_norm"], eps)

    def head(t):
        return t.reshape(s, heads, hd)

    q = head(_conv_silu(h @ _f32(a["wq"]), a["conv_q"]))
    k = head(_conv_silu(h @ _f32(a["wk"]), a["conv_k"]))
    v = head(_conv_silu(h @ _f32(a["wv"]), a["conv_v"]))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
        / math.sqrt(hd)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    f = (h @ _f32(a["wf_a"])) @ _f32(a["wf_b"]) + _f32(a["dt_bias"])
    g = -jnp.exp(_f32(a["A_log"]))[None, :, None] * head(jax.nn.softplus(f))
    beta = jax.nn.sigmoid(h @ _f32(a["wb"]))                 # [S, H]
    real = jnp.arange(s) < n
    g = jnp.where(real[:, None, None], g, 0.0)
    beta = jnp.where(real[:, None], beta, 0.0)

    def step(S, t):  # S [H, D keys, D values]
        q_t, k_t, v_t, g_t, b_t = t
        S = jnp.exp(g_t)[:, :, None] * S                     # Diag(a) S
        S = S - b_t[:, None, None] * k_t[:, :, None] \
            * jnp.einsum("hk,hkv->hv", k_t, S)[:, None, :]
        S = S + b_t[:, None, None] * k_t[:, :, None] * v_t[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    S, o = jax.lax.scan(step, jnp.zeros((heads, hd, hd), jnp.float32),
                        (q, k, v, g, beta))
    gate = jax.nn.sigmoid((h @ _f32(a["wg_a"])) @ _f32(a["wg_b"]))
    o = _rms(o, a["o_norm"], eps).reshape(s, heads * hd) * gate
    return x + o @ _f32(a["wo"]), S


def _latent_project(x, layer, *, n_heads, nope, rope, rank, eps):
    """The expanded form's q and k [H, S, nope + rope] (the one unrotated
    shared key repeated for every head) and v [H, S, v]."""
    s = x.shape[0]
    a = layer["attn"]
    h = _rms(x, layer["attn_norm"], eps)
    q = (h @ _f32(a["wq"])).reshape(s, n_heads, nope + rope)
    kv = h @ _f32(a["wkv_a"])
    c = _rms(kv[:, :rank], a["kv_norm"], eps)
    up = (c @ _f32(a["wkv_b"])).reshape(s, n_heads, -1).transpose(1, 0, 2)
    k = jnp.concatenate(
        [up[..., :nope],
         jnp.broadcast_to(kv[None, :, rank:], (n_heads, s, rope))], -1)
    return q.transpose(1, 0, 2), k, up[..., nope:]


def _attend_block(q, k, v, lo):
    """Queries q [H, B, D] at positions lo.. against all keys, causal."""
    _, b, hd = q.shape
    scores = jnp.einsum("hqd,hkd->hqk", q, k) / math.sqrt(hd)
    i, j = lo + jnp.arange(b)[:, None], jnp.arange(k.shape[1])[None, :]
    probs = jax.nn.softmax(jnp.where((j <= i)[None], scores, -jnp.inf), -1)
    return jnp.einsum("hqk,hkd->hqd", probs, v)


def _latent_out(x, o, layer):
    return x + o.transpose(1, 0, 2).reshape(x.shape[0], -1) \
        @ _f32(layer["attn"]["wo"])


def _route(logits, bias, given, top_k: int, renormalise: bool,
           scaling: float):
    """As ``glm4_moe_lite_ref._route``: the weight of every one of the
    router's experts for every token [S, E] (0 where not chosen), the
    chosen experts [S, k], the margin of the choice [S], and the reach of
    ``given`` [S] (0 where the experts are the router's own)."""
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
    return weight, top_e, margin, reach


def _ffn(x, given, layer, *, eps, top_k, renormalise, scaling, first):
    """The FFN half on x [S, d]: (y, experts used [S, k], margin [S],
    reach [S]); a dense layer's last three are None."""
    if "mlp" in layer:
        g = _rms(x, layer["mlp_norm"], eps)
        return x + _swiglu(g, layer["mlp"]), None, None, None
    m = layer["moe"]
    g = _rms(x, layer["moe_norm"], eps)
    weight, top_e, margin, reach = _route(
        g @ _f32(m["router"]), _f32(m["router_bias"]), given, top_k,
        renormalise, scaling)
    held = m["w1"].shape[0]

    def expert(y, e):  # every held expert on every token, then a mask
        w = {k: jax.lax.dynamic_index_in_dim(m[k], e, 0, False)
             for k in ("w1", "w3", "w2")}
        return y + jax.lax.dynamic_slice_in_dim(weight, first + e, 1, 1) \
            * _swiglu(g, w), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(held))
    return x + y + _swiglu(g, m["shared"]), top_e, margin, reach


def _logits(x, final_norm, lm_head, eps):
    return _rms(x, final_norm, eps) @ _f32(lm_head)


class Reference:
    """The reference over one weight tree.  ``model`` is a loaded
    configuration file; ``params`` the program's weight tree (any dtype);
    everything is computed on ``device``."""

    def __init__(self, model: Dict[str, Any], params, device=None):
        self.device = device or jax.devices()[0]
        self.params = jax.device_put(params, self.device)
        self.eps = float(model["rms_norm_eps"])
        self.top_k = model["num_experts_per_token"]
        self.kinds = layer_kinds(model)
        lin = model["linear_attn_config"]
        self._jit = {
            "kda": jax.jit(functools.partial(
                _kda, heads=lin["num_heads"], hd=lin["head_dim"],
                eps=self.eps)),
            "project": jax.jit(functools.partial(
                _latent_project, n_heads=model["num_attention_heads"],
                nope=model["qk_nope_head_dim"],
                rope=model["qk_rope_head_dim"], rank=model["kv_lora_rank"],
                eps=self.eps)),
            "attend": jax.jit(_attend_block),
            "out": jax.jit(_latent_out),
            "ffn": jax.jit(functools.partial(
                _ffn, eps=self.eps, top_k=self.top_k,
                renormalise=bool(model["moe_renormalize"]),
                scaling=float(model["routed_scaling_factor"]),
                first=int(model.get("first_expert", 0)))),
            "logits": jax.jit(functools.partial(_logits, eps=self.eps))}
        self._last = (None, None)

    def _layer(self, kind, x, lw, given, n):
        """One layer on x [S, d], of which the first ``n`` rows are real;
        returns what ``_ffn`` returns and, of a KDA layer, the state after
        them (else None)."""
        fns, state = self._jit, None
        if kind == "kda":
            x, state = fns["kda"](x, lw, n)
        else:
            q, k, v = fns["project"](x, lw)
            o = jnp.concatenate([
                fns["attend"](q[:, lo:lo + Q_BLOCK], k, v, lo)
                for lo in range(0, x.shape[0], Q_BLOCK)], axis=1)
            x = fns["out"](x, o, lw)
        return (*fns["ffn"](x, given, lw), state)

    def _forward(self, tokens, given=None):
        """One sequence through every layer: (hidden [S, d], per-layer
        experts used [L, S, k] of the ROUTER's numbering, margins [L, S],
        reach of ``given`` [L, S], the KDA layers' states after the last
        position [KDA layers, H, D, D], on the device); a dense layer's
        experts are -1, its margin infinite, its reach 0.  ``given``
        [L, S, k]: experts to use in place of the router's choice, -1
        where it is left to choose.
        Padded behind its end to whole query blocks (every layer is
        causal: no real position sees the padding)."""
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
                used, margins, reaches, states = [], [], [], []
                for li, lw in enumerate(layers):
                    x, e, m, r, state = self._layer(
                        self.kinds[li], x, lw,
                        jnp.asarray(given[li], jnp.int32), n)
                    if state is not None:
                        states.append(state)
                    routed = e is not None
                    used.append(np.asarray(e)[:n] if routed else np.full(
                        (n, self.top_k), -1, np.int32))
                    margins.append(np.asarray(m)[:n] if routed
                                   else np.full((n,), np.inf, np.float32))
                    reaches.append(np.asarray(r)[:n] if routed
                                   else np.zeros((n,), np.float32))
            self._last = (key, (x[:n], np.stack(used), np.stack(margins),
                                np.stack(reaches), jnp.stack(states)))
        return self._last[1]

    def logits(self, tokens: np.ndarray, positions: Sequence[int],
               given=None):
        """Next-token logits [len(positions), V] after each of
        ``positions`` of one sequence (full forward pass, no cache, no
        state carried).  ``given``: see ``routing``."""
        x = self._forward(tokens, given)[0]
        with jax.default_matmul_precision("highest"):
            out = self._jit["logits"](
                x[jnp.asarray(list(positions))], self.params["final_norm"],
                self.params["lm_head"])
        return np.asarray(out)

    def top_experts(self, tokens: np.ndarray) -> np.ndarray:
        """The router's experts of every token in every layer: [L, S, k],
        each row sorted (-1 in a dense layer), in the router's numbering:
        held here or not."""
        return np.sort(self._forward(tokens)[1], axis=-1)

    def routing(self, tokens: np.ndarray, given=None):
        """(margins [L, S], reach [L, S]) of one sequence, as
        ``glm4_moe_lite_ref.Reference.routing``: top-k routing is
        discontinuous at a tie, so a comparison may hand the reference the
        experts the system took (``given``) and read how far they are
        from its own choice."""
        return self._forward(tokens, given)[2:4]

    def states(self, tokens: np.ndarray, given=None):
        """The KDA layers' matrix states after the sequence's last
        position, [KDA layers, H, D keys, D values] float32 on the device:
        what a slot has to hold of the sequence, for a comparison that
        reads the system's state pool."""
        return self._forward(tokens, given)[4]
