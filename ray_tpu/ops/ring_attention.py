"""Ring attention: causal attention over a sequence sharded on the `sp` mesh
axis (context parallelism).

Net-new vs the reference (SURVEY.md §5.7: no sequence/context parallelism
exists in Ray).  Mechanics: each sp-shard holds a contiguous sequence chunk of
Q/K/V; K/V chunks rotate around the ring via ppermute while each shard
accumulates its Q-rows' attention with an online-softmax combiner, so the
full S×S score matrix never materializes and per-chip memory is
O(S_local²).  XLA overlaps the ppermute with the chunk compute (ICI
collective-permute).

Call inside shard_map with sequence dim sharded over `axis_name`; with an
axis of size 1 it is plain flash attention.

On TPU the per-chunk math runs the Pallas flash kernels under one JOINT
custom VJP over the whole ring: the forward combines per-chunk (out, lse)
with the online-softmax rule; the backward re-rotates K/V and feeds the
flash backward kernels the GLOBAL lse/delta (the standard flash
decomposition is exact across chunks), with dK/dV accumulators riding the
ring home to their owner shard.  Causal masking across chunks uses the
kernels' q_offset (a prefetch scalar, so it may be rank-dependent): future
chunks mask fully, past chunks fully visible, the diagonal chunk is causal.
Off the TPU the blockwise jnp form is the differentiable reference; on it a
per-shard length the kernels cannot take raises.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .attention import (
    LSE_LANES,
    NEG_INF,
    _flash_bwd,
    _flash_fwd,
    _on_tpu,
    flash_attention,
    kernel_blocks,
)


def _chunk_attn(q, k, v, scale, mode):
    """Blockwise attention for one (Q-chunk, K-chunk) pair.

    mode: 0 = skip (K chunk is entirely in the future), 1 = diagonal
    (causal within chunk), 2 = full (K chunk entirely in the past).
    Returns (unnormalized accumulator [B,H,S,D] f32, lse [B,H,S] f32).
    """
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)

    def compute(causal_mask):
        s = jnp.einsum(
            "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
        ) * scale
        if causal_mask:
            qi = jnp.arange(S)[:, None]
            ki = jnp.arange(S)[None, :]
            s = jnp.where(qi >= ki, s, NEG_INF)
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        acc = jnp.einsum(
            "bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return acc / jnp.maximum(l, 1e-30)[..., None], lse

    def skip(_):
        return (
            jnp.zeros((B, H, S, D), jnp.float32),
            jnp.full((B, H, S), NEG_INF, jnp.float32),
        )

    return lax.switch(
        mode,
        [
            skip,
            lambda _: compute(True),
            lambda _: compute(False),
        ],
        None,
    )


# ------------------------------------------------- fused ring+flash (TPU)


RING_BLOCK = 256  # q and k block cap of the per-chunk kernels


def _ring_flash_fwd_impl(q, k, v, scale, axis_name, n, interpret):
    rank = lax.axis_index(axis_name)
    B, H, S, D = q.shape
    bq, bk = kernel_blocks(q, k, RING_BLOCK, RING_BLOCK)
    perm = [(i, (i + 1) % n) for i in range(n)]
    acc = jnp.zeros((B, H, S, D), jnp.float32)
    m_run = jnp.full((B, H, S), NEG_INF, jnp.float32)
    l_run = jnp.zeros((B, H, S), jnp.float32)
    k_cur, v_cur = k, v
    for s in range(n):  # unrolled: n is a small static mesh-axis size
        src = (rank - s) % n
        # Global offset of this shard's Q rows relative to the K chunk it
        # currently holds: negative (future chunk) masks everything, >= S
        # (past chunk) masks nothing, 0 is the causal diagonal.
        offset = (rank - src) * S
        out_c, lse_c = _flash_fwd(
            q, k_cur, v_cur, scale, True, offset, bq, bk, interpret
        )
        lse_c = lse_c[..., 0]
        m_new = jnp.maximum(m_run, lse_c)
        alpha = jnp.exp(m_run - m_new)
        beta = jnp.exp(lse_c - m_new)
        acc = acc * alpha[..., None] + out_c.astype(jnp.float32) * beta[..., None]
        l_run = l_run * alpha + beta
        m_run = m_new
        if s < n - 1:
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
    out = (acc / jnp.maximum(l_run, 1e-30)[..., None]).astype(q.dtype)
    lse_total = m_run + jnp.log(jnp.maximum(l_run, 1e-30))
    return out, lse_total


def _ring_flash_bwd_impl(q, k, v, out, lse_total, do, scale, axis_name, n,
                         interpret):
    rank = lax.axis_index(axis_name)
    S = q.shape[2]
    bq, bk = kernel_blocks(q, k, RING_BLOCK, RING_BLOCK)
    perm = [(i, (i + 1) % n) for i in range(n)]
    lse4 = jnp.broadcast_to(
        lse_total[..., None], lse_total.shape + (LSE_LANES,)
    )
    dq = jnp.zeros(q.shape, jnp.float32)
    dk_acc = jnp.zeros(k.shape, jnp.float32)
    dv_acc = jnp.zeros(v.shape, jnp.float32)
    k_cur, v_cur = k, v
    for s in range(n):
        src = (rank - s) % n
        offset = (rank - src) * S
        dq_c, dk_c, dv_c = _flash_bwd(
            (q, k_cur, v_cur, out, lse4), do,
            sm_scale=scale, causal=True, q_offset=offset,
            block_q=bq, block_k=bk, interpret=interpret,
        )
        dq = dq + dq_c.astype(jnp.float32)
        dk_acc = dk_acc + dk_c.astype(jnp.float32)
        dv_acc = dv_acc + dv_c.astype(jnp.float32)
        # dK/dV accumulators travel WITH their K/V chunk; after n rotations
        # every chunk's gradient is home.  K/V themselves aren't read after
        # the last step, so only the accumulators take the final hop.
        if s < n - 1:
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
        dk_acc = lax.ppermute(dk_acc, axis_name, perm)
        dv_acc = lax.ppermute(dv_acc, axis_name, perm)
    return dq.astype(q.dtype), dk_acc.astype(k.dtype), dv_acc.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_flash(q, k, v, scale, axis_name, n, interpret):
    out, _ = _ring_flash_fwd_impl(q, k, v, scale, axis_name, n, interpret)
    return out


def _ring_flash_vjp_fwd(q, k, v, scale, axis_name, n, interpret):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _ring_flash_fwd_impl(q, k, v, scale, axis_name, n, interpret)
    # Tagged like the single-shard flash residuals so remat policies can
    # keep them (skipping the whole ring-forward recompute in backward).
    res = checkpoint_name((q, k, v, out, lse), "flash_res")
    return out, res


def _ring_flash_vjp_bwd(scale, axis_name, n, interpret, res, g):
    q, k, v, out, lse = res
    return _ring_flash_bwd_impl(
        q, k, v, out, lse, g, scale, axis_name, n, interpret
    )


_ring_flash.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = "sp",
    causal: bool = True,
    sm_scale: Optional[float] = None,
    force_kernel: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """[B, H, S_local, D] in, same out.  Must run inside shard_map when the
    sp axis is >1."""
    D = q.shape[-1]
    scale = sm_scale if sm_scale is not None else D ** -0.5
    # Lazy import: the collective package pulls in core modules, which must
    # not load as a side effect of importing this kernel module.
    from ..collective.xla_ops import axis_size

    try:
        n = axis_size(axis_name)
    except NameError:
        n = 1
    if n == 1:
        return flash_attention(q, k, v, causal=causal, sm_scale=scale)
    if not causal:
        # Non-causal: all-gather K/V is simpler and bandwidth-equivalent.
        kg = lax.all_gather(k, axis_name, axis=2, tiled=True)
        vg = lax.all_gather(v, axis_name, axis=2, tiled=True)
        return flash_attention(q, kg, vg, causal=False, sm_scale=scale)

    if force_kernel or _on_tpu():
        # Fused ring+flash: Pallas kernels inside one joint custom VJP.  A
        # per-shard length they cannot take raises (kernel_blocks).
        return _ring_flash(q, k, v, scale, axis_name, n, interpret)

    rank = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    B, H, S, _ = q.shape

    acc0 = jnp.zeros((B, H, S, D), jnp.float32)
    m0 = jnp.full((B, H, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)

    chunk = jax.checkpoint(functools.partial(_chunk_attn, scale=scale))

    def step(s, carry):
        k_cur, v_cur, acc, m_run, l_run = carry
        src = (rank - s) % n  # whose K/V chunk we currently hold
        # mode: future chunk -> skip; own chunk -> diagonal; past -> full.
        mode = jnp.where(src > rank, 0, jnp.where(src == rank, 1, 2))
        out_c, lse_c = chunk(q, k_cur, v_cur, mode=mode)
        m_new = jnp.maximum(m_run, lse_c)
        alpha = jnp.exp(m_run - m_new)
        beta = jnp.exp(lse_c - m_new)
        acc = acc * alpha[..., None] + out_c * beta[..., None]
        l_run = l_run * alpha + beta
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return k_nxt, v_nxt, acc, m_new, l_run

    carry = (k, v, acc0, m0, l0)
    for s in range(n):  # unrolled: n is a small static mesh-axis size
        carry = step(s, carry)
    _, _, acc, _, l_run = carry
    out = acc / jnp.maximum(l_run, 1e-30)[..., None]
    return out.astype(q.dtype)
