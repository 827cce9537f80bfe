"""The selective state-space layer (Mamba-1, arXiv 2312.00752, as the Jamba
family's mixer has it, arXiv 2403.19887): a layer whose memory of a sequence
is a ``[ssm_inner, ssm_state]`` float32 state, not cache rows, and whose
decay differs in every entry of that state and is a function of the token.

On the layer's normalised input u_t (``ssm_inner`` = I channels, ``ssm_state``
= N, ``ssm_dt_rank`` = R, ``ssm_conv`` taps):

- ``[xs ; z] = u W_in`` (W_in [d, 2 I], no bias);
- ``xs = SiLU(conv(xs))``: depthwise and causal over the last ``ssm_conv``
  positions, with a bias a channel, zeros before a sequence's start;
- ``[d ; B ; C] = xs W_x`` (W_x [I, R + 2 N]), each through an RMSNorm of its
  own (the family's three inner norms);
- ``Delta = softplus(d W_dt + b_dt)`` [I], ``A = -exp(A_log)`` [I, N];
- the state H [I, N] in float32:
  ``H_t = exp(Delta_t (x) A) . H_{t-1} + (Delta_t . xs_t) (x) B_t``,
  ``y_t = H_t C_t + D . xs_t``;
- out = ``(y . SiLU(z)) W_out``.

There are no heads, no q and no k: nothing of ``kda.py``'s closure carries
over but its place.  ``block.attention`` makes the in-projection
(``project``) and the output (``output``); between them it hands the
caller's ``attend(pre, a)`` closure the xs half before its convolution, and
the closure owns the state, as an attention layer's owns its cache
(``decode_rows`` / ``prefill_rows``, the two calls ``paged.py`` makes of a
recurrent layer of either kind):

- ``recurrent``: one token a sequence (the decode step), in plain
  ``jax.numpy``: XLA reads every slot's state, writes it, and reads it again
  for ``y``.  On a TPU the decode step hands the recurrence to the Pallas
  kernel ``ops/ssm_decode.py`` instead, which passes over the state pool
  once, where it lies (``_steps_in_place`` chooses; ``recurrent`` is that
  kernel's reference and every other backend's form);
- ``chunked``: a prompt, or one chunk of it, with the state carried from
  position to position by a ``lax.scan`` (a whole chunk's ``[2048, I, N]``
  float32 states are 671 MB a layer at the published widths and are never
  formed); a row that holds no token (``valid`` false) has Delta 0, which
  leaves the state exactly as it is, and costs a trip of the loop all the
  same.  XLA writes the state to HBM and reads it back every trip, so on a
  TPU a PREFILL call (``prefill_rows``: one sequence's bucket, chunk or
  suffix) hands the recurrence to the Pallas kernel ``ops/ssm_scan.py``
  instead, one call a layer: the state stays on the chip over the chunk's
  positions and only the real rows are walked (``_scans_on_chip`` chooses,
  by the backend, the state's tiles and the chunk's length; ``rows_walked``
  says what the form in use went over).  ``chunked`` is that kernel's
  reference, every other backend's form, and the full forward's on every
  backend (``full_attend``: the trainer differentiates through it, and the
  kernel has no gradient).

THE STATE LIES TRANSPOSED, ``[N, I]`` (and ``A_log`` with it): the TPU
tiles an array's last two dimensions in (8, 128), so ``[I, 16]`` float32
would be padded to eight times its bytes in HBM and every pass over it
would move them; ``[16, I]`` is whole tiles.  For the same reason the
convolution's last ``ssm_conv - 1`` pre-activation rows of a slot lie side
by side in one row of ``(ssm_conv - 1) * I`` numbers (oldest first).

The state is updated in float32, ``exp(Delta A)`` and the softplus are
float32; the projections are products in the configuration's dtype with
float32 accumulation, as the block's others."""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import ssm_decode, ssm_scan
from ..ops.norms import rms_norm

Params = Dict[str, Any]

#: The named scope of the recurrence, in both forms (a device trace's name
#: for it; ``paged.py`` writes the state pool under it too).
SCOPE = "attn_ssm"
#: Positions the chunk form's scan takes a trip of its loop.
UNROLL = 8


def widths(config) -> Tuple[int, int, int]:
    """(channels I, state N a channel, the rank R of Delta's projection)."""
    return config.ssm_inner, config.ssm_state, config.ssm_dt_rank


def param_count(config) -> int:
    """One Mamba layer's mixer parameters."""
    d = config.d_model
    i, n, r = widths(config)
    return (d * 2 * i + config.ssm_conv * i + i + i * (r + 2 * n)
            + r + 2 * n + r * i + i + i * n + i + i * d)


def state_shapes(config, layers: int, slots: int) -> Dict[str, Any]:
    """The per-sequence state of ``layers`` Mamba layers for ``slots``
    sequences, by pool name: the state ``S`` in float32, transposed
    (``[N, I]``: see the module's text), and ``conv``, the last
    ``ssm_conv - 1`` pre-activation rows of xs side by side, oldest first."""
    i, n, _ = widths(config)
    return {
        "S": jax.ShapeDtypeStruct((layers, slots, n, i), jnp.float32),
        "conv": jax.ShapeDtypeStruct(
            (layers, slots, (config.ssm_conv - 1) * i), config.dtype)}


def init(config, key: jax.Array) -> Params:
    """A Mamba layer's ``attn`` weights, as Mamba-1 initialises them:
    ``A_log = log(1..N)`` a channel (here [N, I]), ``D`` ones, ``dt_bias``
    the inverse softplus of a log-uniform step in 0.001..0.1, the
    convolution at ``taps^-0.5``."""
    d = config.d_model
    i, n, r = widths(config)
    ks = jax.random.split(jax.random.fold_in(key, 3), 8)
    std = d ** -0.5

    def dense(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(
            config.dtype)

    dt = jnp.exp(jax.random.uniform(ks[5], (i,), jnp.float32,
                                    jnp.log(0.001), jnp.log(0.1)))
    return {
        "w_in": dense(ks[0], (d, 2 * i), std),
        "conv_w": dense(ks[1], (config.ssm_conv, i), config.ssm_conv ** -0.5),
        "conv_b": dense(ks[2], (i,), config.ssm_conv ** -0.5),
        "w_x": dense(ks[3], (i, r + 2 * n), i ** -0.5),
        "dt_norm": jnp.ones((r,), config.dtype),
        "b_norm": jnp.ones((n,), config.dtype),
        "c_norm": jnp.ones((n,), config.dtype),
        "w_dt": dense(ks[4], (r, i), r ** -0.5),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None], (n, i)),
        "D": jnp.ones((i,), jnp.float32),
        "w_out": dense(ks[6], (i, d), i ** -0.5),
    }


def project(config, a: Params, x: jax.Array):
    """The in-projection of normalised x [..., d], split: xs before its
    convolution and the gate z, each [..., I]."""
    both = x @ a["w_in"]
    i = config.ssm_inner
    return both[..., :i], both[..., i:]


def output(config, a: Params, y: jax.Array, xs: jax.Array,
           z: jax.Array) -> jax.Array:
    """The recurrence's y [..., I] float32 and the convolved xs it was
    driven by, through the ``D`` skip, the gate of z and the output
    projection: [..., d]."""
    y = (y + a["D"] * xs) * jax.nn.silu(z.astype(jnp.float32))
    return y.astype(z.dtype) @ a["w_out"]


def conv(config, a: Params, pre: jax.Array, prev: jax.Array,
         length: Optional[jax.Array] = None):
    """The causal depthwise convolution (with its bias) and SiLU of ``pre``
    [B, S, I] behind ``prev`` [B, (ssm_conv - 1) * I], the pre-activation
    rows of the positions before it side by side, oldest first (zeros at a
    sequence's start).  Returns xs [B, S, I] float32 and the rows the next
    call is behind: the last ``ssm_conv - 1`` of the ``length`` [B] real rows
    (all S where None)."""
    B, S, i = pre.shape
    taps = config.ssm_conv
    with jax.named_scope("ssm_conv"):
        rows = jnp.concatenate(
            [prev.astype(pre.dtype).reshape(B, taps - 1, i), pre], axis=1)
        weight = a["conv_w"].astype(jnp.float32)
        out = sum(rows[:, j:j + S].astype(jnp.float32) * weight[j]
                  for j in range(taps)) + a["conv_b"].astype(jnp.float32)
        if length is None:
            nxt = rows[:, S:]
        else:  # rows[length : length + taps - 1]: the last real ones
            nxt = jax.vmap(lambda r, n: jax.lax.dynamic_slice_in_dim(
                r, n, taps - 1, axis=0))(rows, length)
        return jax.nn.silu(out), nxt.reshape(B, (taps - 1) * i)


def _conv_row(config, a: Params, pre: jax.Array, prev: jax.Array):
    """``conv`` of ONE new row a sequence, pre [B, I], on the rows as they
    lie (``prev`` [B, (ssm_conv - 1) * I]: every slice is of whole lane
    tiles, so nothing is laid out again).  Returns (xs [B, I] float32, the
    next rows)."""
    i, taps = config.ssm_inner, config.ssm_conv
    with jax.named_scope("ssm_conv"):
        weight = a["conv_w"].astype(jnp.float32)
        out = pre.astype(jnp.float32) * weight[-1] \
            + a["conv_b"].astype(jnp.float32)
        for j in range(taps - 1):
            out = out + prev[:, j * i:(j + 1) * i].astype(jnp.float32) \
                * weight[j]
        nxt = jnp.concatenate([prev[:, i:], pre.astype(prev.dtype)], axis=-1)
        return jax.nn.silu(out), nxt


def drive(config, a: Params, xs: jax.Array):
    """What drives the recurrence, of convolved xs [..., I] float32: the
    step Delta [..., I] and the token's B and C [..., N], all float32."""
    _, n, r = widths(config)
    dbc = xs.astype(a["w_x"].dtype) @ a["w_x"]
    d = rms_norm(dbc[..., :r], a["dt_norm"], config.norm_eps)
    bm = rms_norm(dbc[..., r:r + n], a["b_norm"], config.norm_eps)
    cm = rms_norm(dbc[..., r + n:], a["c_norm"], config.norm_eps)
    delta = jax.nn.softplus((d @ a["w_dt"]).astype(jnp.float32)
                            + a["dt_bias"])
    return delta, bm.astype(jnp.float32), cm.astype(jnp.float32)


def recurrent(a: Params, H: jax.Array, xs: jax.Array, delta: jax.Array,
              bm: jax.Array, cm: jax.Array):
    """One token a sequence: H [B, N, I] float32, xs and delta [B, I],
    bm and cm [B, N].  Returns (y [B, I] without the ``D`` skip, the new
    state)."""
    with jax.named_scope(SCOPE):
        A = -jnp.exp(a["A_log"])                           # [N, I]
        H = jnp.exp(delta[:, None, :] * A) * H \
            + (delta * xs)[:, None, :] * bm[:, :, None]
        return jnp.sum(H * cm[:, :, None], axis=1), H


def chunked(a: Params, H: jax.Array, xs: jax.Array, delta: jax.Array,
            bm: jax.Array, cm: jax.Array,
            valid: Optional[jax.Array] = None):
    """A run of tokens a sequence: H [B, N, I] float32 the state before
    them, xs and delta [B, T, I], bm and cm [B, T, N]; ``valid`` [B, T]
    false: the row holds no token and leaves the state as it is (its output
    is garbage).  Returns (y [B, T, I] without the ``D`` skip, the state
    after the last real row)."""
    if valid is not None:  # exp(0 A) = 1 and (0 xs) B = 0: H stays
        delta = jnp.where(valid[..., None], delta, 0.0)
    with jax.named_scope(SCOPE):
        A = -jnp.exp(a["A_log"])

        def step(H, t):
            d_t, u_t, b_t, c_t = t
            H = jnp.exp(d_t[:, None, :] * A) * H \
                + u_t[:, None, :] * b_t[:, :, None]
            return H, jnp.sum(H * c_t[:, :, None], axis=1)

        rows = (delta, delta * xs, bm, cm)
        H, y = jax.lax.scan(step, H, tuple(jnp.moveaxis(t, 1, 0)
                                           for t in rows),
                            unroll=min(UNROLL, xs.shape[1]))
        return jnp.moveaxis(y, 0, 1), H


def _steps_in_place(config) -> bool:
    """Whether the decode step of ``config``'s Mamba layers updates the
    state POOL where it lies, through ``ops.ssm_decode_step`` (one pass over
    each slot's state, ``y`` from the same pass), in place of ``recurrent``
    on a layer's slice of it.  The one place that chooses, by what it can
    see: the kernel needs a TPU and a state it can cut (channels of whole
    lane tiles, N of whole sublane tiles, float32: ``state_shapes``')."""
    i, n, _ = widths(config)
    return ssm_decode.on_tpu() and ssm_decode.takes(n, i, jnp.float32)


def _scans_on_chip(config, rows: Optional[int] = None) -> bool:
    """Whether a prefill call of ``rows`` rows (with none named: of some
    length) takes the recurrence of ``config``'s Mamba layers through
    ``ops.ssm_scan_chunk`` (one call a layer, the state on the chip over the
    chunk, the real rows only), in place of ``chunked``'s loop.  The one
    place that chooses, by what it can see: the kernel needs a TPU, a state
    it can cut (as ``_steps_in_place``) and a chunk of whole position
    blocks (the engine's buckets all are)."""
    i, n, _ = widths(config)
    return ssm_scan.on_tpu() and ssm_scan.takes(n, i, jnp.float32) \
        and (rows is None or ssm_scan.takes_rows(rows))


def rows_walked(config, rows: int, real: int) -> int:
    """The positions the chunk form steps the state over in a prefill call
    of ``rows`` rows of which ``real`` hold a token: the kernel walks the
    real ones, ``chunked``'s loop all of them."""
    return real if _scans_on_chip(config, rows) else rows


def decode_rows(config, a: Params, H: jax.Array, rows: jax.Array,
                pre: jax.Array, layer=None,
                active: Optional[jax.Array] = None):
    """A decode step's work in one Mamba layer: the new rows' xs halves pre
    [B, I] (one a slot) behind each slot's convolution rows, through the
    recurrent form on each slot's state H [B, N, I].  Returns ((y, xs) for
    ``output``, the new states, the next convolution rows).

    Where ``_steps_in_place(config)``, H is the WHOLE state pool
    [layers, B, N, I] and ``layer`` this layer's place in it: the kernel
    updates that layer of the ``active`` [B] slots where it lies, and the
    pool comes back (an inactive slot's state as it was, its y zero)."""
    xs, nxt = _conv_row(config, a, pre, rows)
    delta, bm, cm = drive(config, a, xs)
    if _steps_in_place(config):
        with jax.named_scope(SCOPE):
            y, H = ssm_decode.ssm_decode_step(
                H, layer, a["A_log"], delta, xs, bm, cm, active)
    else:
        y, H = recurrent(a, H, xs, delta, bm, cm)
    return (y, xs), H, nxt


def prefill_rows(config, a: Params, H: jax.Array, rows: jax.Array,
                 valid: jax.Array, pre: jax.Array):
    """A prefill call's work in one Mamba layer on ONE sequence's rows, pre
    [S_pad, I] (``valid`` [S_pad] the real ones), behind the state H
    [1, N, I] and convolution rows [1, .] they follow: the chunk form.
    Returns ((y, xs) for ``output``, each [1, S_pad, I], the state and the
    convolution rows behind the last real row, each [1, .]).

    Where ``_scans_on_chip(config, S_pad)``, the recurrence is the kernel's
    (the real rows lead: ``valid`` is a count), and y of a row that holds
    no token is zero."""
    n = jnp.sum(valid, dtype=jnp.int32)
    xs, nxt = conv(config, a, pre[None], rows, n[None])
    if _scans_on_chip(config, pre.shape[0]):
        delta, bm, cm = drive(config, a, xs)
        with jax.named_scope(SCOPE):
            y, H = ssm_scan.ssm_scan_chunk(a["A_log"], H[0], delta[0],
                                           xs[0], bm[0], cm[0], n)
        return (y[None], xs), H[None], nxt
    y, H = chunked(a, H, xs, *drive(config, a, xs), valid[None])
    return (y, xs), H, nxt


def full_attend(config):
    """The full forward's ``attend`` of a Mamba layer (see ``block``): every
    sequence of pre [B, S, I] from a zero state, nothing kept."""
    def attend(pre, a):
        st = state_shapes(config, 1, pre.shape[0])
        xs, _ = conv(config, a, pre,
                     jnp.zeros(st["conv"].shape[1:], pre.dtype))
        y, _ = chunked(a, jnp.zeros(st["S"].shape[1:], jnp.float32), xs,
                       *drive(config, a, xs))
        return y, xs
    return attend
