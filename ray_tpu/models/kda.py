"""The gated delta-rule layer (Kimi Delta Attention, arXiv 2510.26692): a
linear-attention layer whose memory of a sequence is a matrix state a head,
not cache rows.

On the layer's normalised input x_t, a head at a time (``kda_heads`` heads,
keys and values ``kda_head_dim`` = D wide):

- q, k, v = SiLU(conv(x W_q)), SiLU(conv(x W_k)), SiLU(conv(x W_v)): the
  convolution depthwise and causal over the last ``kda_conv`` positions;
  q and k L2-normalised a head, q times D^-0.5;
- a channel-wise decay g_t = -exp(A_log_h) softplus(x W_f1 W_f2 + dt_bias)
  [H, D] (a_t = exp(g_t) in (0, 1]) and a write strength
  beta_t = sigmoid(x W_b) [H];
- the state S [D keys, D values] in float32:
  ``S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T``,
  ``o_t = S_t^T q_t``;
- out = W_o [RMSNorm_head(o_t) * sigmoid(x W_g1 W_g2)].

``block.attention`` makes the projections (``project``) and the output
(``output``); between them it hands the caller's ``attend(pre, g, beta, a)``
closure what the recurrence needs, and the closure owns the state, as an
attention layer's owns its cache: it calls ``conv`` with the rows before its
own, then one of two forms of the one recurrence:

- ``recurrent``: one token a sequence (the decode step).  The state is read
  twice and written once: ``S' = a S``, both ``S'^T k`` and ``S'^T q`` from one
  pass (``o = S'^T q + (k . q) u``), the rank-one write in the other;
- ``chunked``: a whole prompt, or one chunk of it, in blocks of ``BLOCK``
  tokens with the state carried between blocks (every prefill program and
  the full forward).  Inside a block the recurrence is a triangular system:
  with G the running sum of g from the block's start,
  ``A_ij = sum_d k_i[d] k_j[d] exp(G_i[d] - G_j[d])`` (j < i),
  ``(I + Diag(beta) A) U = Diag(beta) (V - (exp(G) K) S_0)``,
  ``O = (exp(G) Q) S_0 + A^q U`` (A^q as A with q_i for k_i, j <= i),
  ``S_C = Diag(exp(G_C)) S_0 + (exp(G_C - G) K)^T U``.
  Differences of G are taken BEFORE they are exponentiated and only for
  j <= i, where they are <= 0: a decay of 30 a token is within the
  published initialisation, and a factored ``exp(G_i) exp(-G_j)`` overflows
  float32 after three such tokens.  The blocks are walked by a scan, so one
  block's ``[H, BLOCK, BLOCK, D]`` differences live at a time.

Everything between the projections and the output norm is float32, the
products at ``Precision.HIGHEST``: the state integrates thousands of
rank-one writes, and a bfloat16 pass through the MXU in any of them is what
the comparison with the reference reads as a fault."""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.norms import rms_norm

Params = Dict[str, Any]

#: Tokens in one block of the chunk form.
BLOCK = 64
#: The named scope of the recurrent form (``paged.py`` writes the state pool
#: under it too).
SCOPE = "attn_kda"
_HI = jax.lax.Precision.HIGHEST


def widths(config) -> Tuple[int, int, int]:
    """(heads, a head's width, heads x width) of a KDA layer."""
    return (config.kda_heads, config.kda_head_dim,
            config.kda_heads * config.kda_head_dim)


def param_count(config) -> int:
    """One KDA layer's attention parameters."""
    d = config.d_model
    h, hd, w = widths(config)
    return (4 * d * w + 2 * (d * hd + hd * w) + d * h
            + 3 * config.kda_conv * w + h + w + hd)


def state_shapes(config, layers: int, slots: int) -> Dict[str, Any]:
    """The per-sequence state of ``layers`` KDA layers for ``slots``
    sequences, by pool name: the matrix state ``S`` in float32, and ``conv``,
    the last ``kda_conv - 1`` pre-activation rows of the q, k and v
    projections side by side."""
    h, hd, w = widths(config)
    return {
        "S": jax.ShapeDtypeStruct((layers, slots, h, hd, hd), jnp.float32),
        "conv": jax.ShapeDtypeStruct(
            (layers, slots, config.kda_conv - 1, 3 * w), config.dtype)}


def init(config, key: jax.Array) -> Params:
    """A KDA layer's ``attn`` weights.  ``A_log`` and ``dt_bias`` as the
    family's published modelling code draws them (A uniform in 1..16, the
    softplus of dt_bias log-uniform in 0.001..0.1): a decay a token between
    a thousandth and tens, which is what makes the chunk form's care
    necessary; the convolutions at the scale of a width-``kda_conv``
    uniform initialisation."""
    d = config.d_model
    h, hd, w = widths(config)
    ks = jax.random.split(jax.random.fold_in(key, 2), 12)
    std = d ** -0.5

    def dense(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(
            config.dtype)

    dt = jnp.exp(jax.random.uniform(ks[10], (w,), jnp.float32,
                                    jnp.log(0.001), jnp.log(0.1)))
    return {
        "wq": dense(ks[0], (d, w), std), "wk": dense(ks[1], (d, w), std),
        "wv": dense(ks[2], (d, w), std),
        "conv_q": dense(ks[3], (config.kda_conv, w), config.kda_conv ** -0.5),
        "conv_k": dense(ks[4], (config.kda_conv, w), config.kda_conv ** -0.5),
        "conv_v": dense(ks[5], (config.kda_conv, w), config.kda_conv ** -0.5),
        "wf_a": dense(ks[6], (d, hd), std),
        "wf_b": dense(ks[7], (hd, w), hd ** -0.5),
        "A_log": jnp.log(jax.random.uniform(ks[9], (h,), jnp.float32,
                                            1.0, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "wb": dense(ks[8], (d, h), std),
        "wg_a": dense(ks[11], (d, hd), std),
        "wg_b": dense(jax.random.fold_in(ks[11], 1), (hd, w), hd ** -0.5),
        "o_norm": jnp.ones((hd,), config.dtype),
        "wo": dense(jax.random.fold_in(ks[0], 1), (w, d), w ** -0.5),
    }


def project(config, a: Params, x: jax.Array):
    """What the recurrence needs of normalised x [..., d]: the q, k and v
    projections before their convolution, side by side [..., 3 * H * D]; the
    log-decay g [..., H, D] (<= 0) and beta [..., H], both float32."""
    h, hd, _ = widths(config)
    pre = jnp.concatenate([x @ a["wq"], x @ a["wk"], x @ a["wv"]], axis=-1)
    f = ((x @ a["wf_a"]) @ a["wf_b"]).astype(jnp.float32) + a["dt_bias"]
    g = -jnp.exp(a["A_log"])[:, None] \
        * jax.nn.softplus(f).reshape(*x.shape[:-1], h, hd)
    beta = jax.nn.sigmoid((x @ a["wb"]).astype(jnp.float32))
    return pre, g, beta


def output(config, a: Params, x: jax.Array, o: jax.Array) -> jax.Array:
    """The heads' outputs o [..., H, D] float32 through their norm, the
    gate of normalised x [..., d] and the output projection: [..., d]."""
    h, hd, w = widths(config)
    gate = jax.nn.sigmoid(((x @ a["wg_a"]) @ a["wg_b"]).astype(jnp.float32))
    o = rms_norm(o, a["o_norm"].astype(jnp.float32), config.norm_eps)
    o = o.reshape(*x.shape[:-1], w) * gate
    return o.astype(x.dtype) @ a["wo"]


def _l2norm(x: jax.Array) -> jax.Array:
    """x [..., D] over its norm, a head at a time."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def conv(config, a: Params, pre: jax.Array, prev: jax.Array,
         length: Optional[jax.Array] = None):
    """The causal depthwise convolution and SiLU of ``pre`` [B, S, 3 * H * D]
    behind ``prev`` [B, kda_conv - 1, 3 * H * D], the pre-activation rows of
    the positions before it (zeros at a sequence's start).  Returns q and k
    (L2-normalised a head, q scaled) and v, each [B, S, H, D] float32, and
    the rows the next call is behind: the last ``kda_conv - 1`` of the
    ``length`` [B] real rows (all S where None)."""
    h, hd, w = widths(config)
    B, S, _ = pre.shape
    taps = config.kda_conv
    with jax.named_scope("kda_conv"):
        rows = jnp.concatenate([prev.astype(pre.dtype), pre], axis=1)
        weight = jnp.concatenate([a["conv_q"], a["conv_k"], a["conv_v"]],
                                 axis=-1).astype(jnp.float32)
        out = sum(rows[:, j:j + S].astype(jnp.float32) * weight[j]
                  for j in range(taps))
        out = jax.nn.silu(out).reshape(B, S, 3, h, hd)
        q, k, v = out[:, :, 0], out[:, :, 1], out[:, :, 2]
        q, k = _l2norm(q) * hd ** -0.5, _l2norm(k)
        if length is None:
            nxt = rows[:, S:]
        else:  # rows[length : length + taps - 1]: the last real ones
            nxt = jax.vmap(lambda r, n: jax.lax.dynamic_slice_in_dim(
                r, n, taps - 1, axis=0))(rows, length)
    return q, k, v, nxt


def recurrent(S: jax.Array, q: jax.Array, k: jax.Array, v: jax.Array,
              g: jax.Array, beta: jax.Array):
    """One token a sequence: S [B, H, D, D] float32, q, k, v, g [B, H, D],
    beta [B, H].  Returns (o [B, H, D], the new state)."""
    with jax.named_scope(SCOPE):
        S = S * jnp.exp(g)[..., None]
        ks = jnp.sum(S * k[..., None], axis=-2)            # S'^T k
        qs = jnp.sum(S * q[..., None], axis=-2)            # S'^T q
        u = beta[..., None] * (v - ks)
        o = qs + jnp.sum(k * q, -1, keepdims=True) * u
        return o, S + k[..., None] * u[..., None, :]


def _block(S, xs):
    """One block of the chunk form: S [B, H, D, D]; q, k, v, g
    [B, C, H, D], beta [B, C, H] (a padded row has g 0 and beta 0: it
    leaves the state as it is)."""
    q, k, v, g, beta = xs
    C = q.shape[1]
    G = jnp.cumsum(g, axis=1)                              # [B, C, H, D]
    Gh = G.transpose(0, 2, 1, 3)                           # [B, H, C, D]
    qh, kh, vh = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    bh = beta.transpose(0, 2, 1)[..., None]                # [B, H, C, 1]
    i, j = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    # exp of the differences, j <= i only (there they are <= 0).
    diff = Gh[:, :, :, None, :] - Gh[:, :, None, :, :]     # [B, H, C, C, D]
    decay = jnp.exp(jnp.where((j <= i)[..., None], diff, -jnp.inf))
    kd = kh[:, :, None, :, :] * decay
    a_kk = jnp.sum(kh[:, :, :, None, :] * kd, -1)          # [B, H, C, C]
    a_qk = jnp.sum(qh[:, :, :, None, :] * kd, -1)
    lower = jnp.where(j < i, bh * a_kk, 0.0) + jnp.eye(C, dtype=S.dtype)
    eg = jnp.exp(Gh)
    rhs = bh * (vh - jnp.einsum("bhck,bhkv->bhcv", eg * kh, S,
                                precision=_HI))
    u = jax.lax.linalg.triangular_solve(
        lower, rhs, left_side=True, lower=True, unit_diagonal=True)
    o = jnp.einsum("bhck,bhkv->bhcv", eg * qh, S, precision=_HI) \
        + jnp.einsum("bhij,bhjv->bhiv", a_qk, u, precision=_HI)
    last = Gh[:, :, -1:, :]                                # [B, H, 1, D]
    S = jnp.exp(last).transpose(0, 1, 3, 2) * S + jnp.einsum(
        "bhck,bhcv->bhkv", jnp.exp(last - Gh) * kh, u, precision=_HI)
    return S, o.transpose(0, 2, 1, 3)


def chunked(S: jax.Array, q: jax.Array, k: jax.Array, v: jax.Array,
            g: jax.Array, beta: jax.Array,
            valid: Optional[jax.Array] = None):
    """A run of tokens a sequence: S [B, H, D, D] float32 the state before
    them, q, k, v, g [B, T, H, D], beta [B, T, H]; ``valid`` [B, T] false:
    the row holds no token and leaves the state as it is (its output is
    garbage).  Returns (o [B, T, H, D], the state after the last real
    row)."""
    B, T = q.shape[:2]
    if valid is not None:
        g = jnp.where(valid[..., None, None], g, 0.0)
        beta = jnp.where(valid[..., None], beta, 0.0)
    C = min(BLOCK, T)
    pad = -T % C
    if pad:
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad))
                                    + ((0, 0),) * (t.ndim - 2))
                            for t in (q, k, v, g, beta))

    def blocks(t):  # [B, T, ...] -> [T / C, B, C, ...]
        return jnp.moveaxis(t.reshape(B, -1, C, *t.shape[2:]), 1, 0)

    with jax.named_scope("attn_kda_chunk"):
        S, o = jax.lax.scan(_block, S, tuple(map(blocks, (q, k, v, g, beta))))
        o = jnp.moveaxis(o, 0, 1).reshape(B, T + pad, *o.shape[3:])
    return o[:, :T], S


def decode_rows(config, a: Params, S: jax.Array, rows: jax.Array,
                pre: jax.Array, g: jax.Array, beta: jax.Array):
    """A decode step's work in one KDA layer: the new rows' projections
    (``project``: pre [B, .], one row a slot) behind each slot's convolution
    rows, through the recurrent form on each slot's state S.  Returns (the
    heads' outputs [B, H, D] float32, the new states, the next convolution
    rows).  ``mamba.decode_rows`` is its sibling: the two calls ``paged.py``
    makes of a recurrent layer."""
    q, k, v, nxt = conv(config, a, pre[:, None], rows)
    o, new = recurrent(S, q[:, 0], k[:, 0], v[:, 0], g, beta)
    return o, new, nxt


def prefill_rows(config, a: Params, S: jax.Array, rows: jax.Array,
                 valid: jax.Array, pre: jax.Array, g: jax.Array,
                 beta: jax.Array):
    """A prefill call's work in one KDA layer on ONE sequence's rows (pre
    [S_pad, .], ``valid`` [S_pad] the real ones) behind the state S [1, .]
    and the convolution rows [1, .] they follow: the chunk form.  Returns
    (the heads' outputs [1, S_pad, H, D] float32, the state and the
    convolution rows behind the last real row, each [1, .])."""
    n = jnp.sum(valid, dtype=jnp.int32)
    q, k, v, nxt = conv(config, a, pre[None], rows, n[None])
    o, new = chunked(S, q, k, v, g[None], beta[None], valid[None])
    return o, new, nxt


def full_attend(config):
    """The full forward's ``attend`` of a KDA layer (see ``block``): every
    sequence of pre [B, S, .] from a zero state, nothing kept."""
    def attend(pre, g, beta, a):
        B = pre.shape[0]
        st = state_shapes(config, 1, B)
        q, k, v, _ = conv(config, a, pre,
                          jnp.zeros(st["conv"].shape[1:], pre.dtype))
        return chunked(jnp.zeros(st["S"].shape[1:], jnp.float32),
                       q, k, v, g, beta)[0]
    return attend
