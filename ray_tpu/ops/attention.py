"""Flash attention: Pallas TPU kernels (forward + backward); off the TPU the
jnp reference, chosen in one place (`flash_attention`).

Design notes (TPU-first):
- Online-softmax forward keeps the S matrix out of HBM entirely; K/V for one
  (batch, head) live in VMEM (up to 12k tokens at head_dim 128 bf16, see
  ``KV_RESIDENT_BYTES`` — longer sequences shard over the `sp` mesh axis via
  ring_attention).
- Backward is the standard two-kernel split (dq; dk+dv) driven by the saved
  logsumexp and delta = rowsum(dO * O), so nothing quadratic is
  rematerialized in HBM.  dK/dV walk Q in chunks, so their VMEM use does
  not grow with the sequence or the GQA group.
- On a TPU nothing here picks interpret mode or the reference by itself: a
  shape the kernels cannot take raises before anything is traced.
- GQA is handled in the BlockSpec index maps (kv head = q head // group), no
  KV broadcast copies.
- `q_offset` supports sequence-parallel callers whose Q block sits at a
  global offset relative to K/V (ring attention steps).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 512
LSE_LANES = 128  # trailing pad so lse blocks meet TPU tiling
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


#: Most bytes of K (and of V) one head may hold for the forward and dq
#: kernels, which keep both whole in VMEM.  Measured by compiling for a
#: described v5e (tests/test_chip_compile.py): 3 MiB per array (12288 x 128
#: bf16, 6144 x 128 f32) compiles forward and backward, 4 MiB is refused
#: with "Ran out of memory in memory space vmem".
KV_RESIDENT_BYTES = 3 << 20


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kernel_blocks(q, k, block_q: int, block_k: int) -> Tuple[int, int]:
    """(bq, bk) the kernels run ``q``/``k`` ([B, H, S, D]) with, or a
    ValueError naming what they cannot take.  The blocks are caps: they
    shrink by powers of two until they divide the sequence (768 -> 256)."""
    Sq, Sk, D = q.shape[2], k.shape[2], k.shape[3]
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    while bq > 16 and Sq % bq:
        bq //= 2
    while bk > 16 and Sk % bk:
        bk //= 2
    if Sq % bq or Sk % bk:
        raise ValueError(
            f"flash attention needs sequence lengths its blocks divide: "
            f"Sq={Sq}, Sk={Sk} against blocks of {bq} and {bk}; pad the "
            f"sequence to a multiple of 16")
    kv_bytes = Sk * D * k.dtype.itemsize
    if kv_bytes > KV_RESIDENT_BYTES:
        raise ValueError(
            f"flash attention keeps K and V of one head in VMEM: {Sk} x {D} "
            f"{k.dtype} is {kv_bytes / 2**20:.1f} MiB each, over the "
            f"{KV_RESIDENT_BYTES >> 20} MiB that compiles; shard the "
            f"sequence over the sp axis (ring_attention) or shorten it")
    return bq, bk


# --------------------------------------------------------------- reference


def mha_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    q_offset: int = 0,
) -> jax.Array:
    """Plain-XLA attention, [B, H, S, D] layout, GQA-aware.  Used as the
    numerical reference and the non-TPU fallback."""
    out, _ = _mha_reference_lse(
        q, k, v, causal=causal, sm_scale=sm_scale, q_offset=q_offset
    )
    return out


def _mha_reference_lse(q, k, v, *, causal, sm_scale, q_offset=0):
    B, H, Sq, D = q.shape
    Hkv = k.shape[1]
    scale = sm_scale if sm_scale is not None else D ** -0.5
    if Hkv != H:
        group = H // Hkv
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    if causal:
        Sk = k.shape[2]
        qi = jnp.arange(Sq)[:, None] + q_offset
        ki = jnp.arange(Sk)[None, :]
        s = jnp.where(qi >= ki, s, NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
    return out.astype(q.dtype), lse


# ------------------------------------------------------------ pallas forward


def _fwd_kernel(q_off_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                *, sm_scale, causal, block_k):
    qb = q_ref[0, 0].astype(jnp.float32) * sm_scale      # [bq, D]
    bq = qb.shape[0]
    Sk = k_ref.shape[2]
    n_kb = Sk // block_k
    q_idx = pl.program_id(2)
    q_global = q_idx * bq + q_off_ref[0]                 # global row offset

    def body(kb, carry):
        acc, m_prev, l_prev = carry
        kblk = k_ref[0, 0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        vblk = v_ref[0, 0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(qb, kblk.T, preferred_element_type=jnp.float32)  # [bq, bk]
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            mask = (rows + q_global) >= (cols + kb * block_k)
            s = jnp.where(mask, s, NEG_INF)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_cur = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + jnp.dot(
            p, vblk, preferred_element_type=jnp.float32
        )
        return acc, m_cur, l_cur

    acc0 = jnp.zeros((bq, q_ref.shape[3]), jnp.float32)
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    if causal and n_kb >= 2:
        # Skip K blocks entirely past the causal diagonal: the last q row
        # of this block is q_global+bq-1, so only k blocks starting at or
        # below it contribute — half the work at long sequence (fully
        # masked q blocks, e.g. ring future chunks, run zero iterations;
        # the merge zeroes them via lse ~ NEG_INF).  Static bound when
        # there is a single K block: a dynamic while_loop only costs there.
        hi = jnp.clip(
            jax.lax.div(q_global + bq + block_k - 1, block_k), 0, n_kb
        )
    else:
        hi = n_kb
    acc, m, l = jax.lax.fori_loop(0, hi, body, (acc0, m0, l0))
    l = jnp.maximum(l, 1e-30)  # fully-masked rows stay finite
    o_ref[0, 0] = (acc / l[:, None]).astype(o_ref.dtype)
    lse = (m + jnp.log(l)).astype(jnp.float32)
    # lse rides a 128-lane pad: TPU blocks need aligned trailing dims.
    lse_ref[0, 0] = jnp.broadcast_to(lse[:, None], lse_ref.shape[2:])


def _flash_fwd(q, k, v, sm_scale, causal, q_offset, block_q, block_k, interpret):
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    group = H // Hkv
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    grid = (B, H, Sq // bq)
    q_off = jnp.asarray([q_offset], jnp.int32)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block_k=bk
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bq, D), lambda b, h, i, *_: (b, h, i, 0)),
                pl.BlockSpec((1, 1, Sk, D), lambda b, h, i, *_: (b, h // group, 0, 0)),
                pl.BlockSpec((1, 1, Sk, D), lambda b, h, i, *_: (b, h // group, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, bq, D), lambda b, h, i, *_: (b, h, i, 0)),
                pl.BlockSpec((1, 1, bq, LSE_LANES),
                             lambda b, h, i, *_: (b, h, i, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, LSE_LANES), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q_off, q, k, v)
    return out, lse


# ----------------------------------------------------------- pallas backward


def _bwd_dq_kernel(q_off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, *, sm_scale, causal, block_k):
    qb = q_ref[0, 0].astype(jnp.float32)
    dob = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0, :, 0]
    delta = delta_ref[0, 0, :, 0]
    bq, D = qb.shape
    Sk = k_ref.shape[2]
    q_idx = pl.program_id(2)
    q_global = q_idx * bq + q_off_ref[0]

    def body(kb, dq):
        kblk = k_ref[0, 0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        vblk = v_ref[0, 0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(qb * sm_scale, kblk.T, preferred_element_type=jnp.float32)
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            mask = (rows + q_global) >= (cols + kb * block_k)
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                       # [bq, bk]
        dp = jnp.dot(dob, vblk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        return dq + jnp.dot(ds, kblk, preferred_element_type=jnp.float32)

    n_kb = Sk // block_k
    if causal and n_kb >= 2:
        # Same diagonal cut as the forward: k blocks past the last q row
        # contribute nothing to dq.
        hi = jnp.clip(
            jax.lax.div(q_global + bq + block_k - 1, block_k), 0, n_kb
        )
    else:
        hi = n_kb
    dq = jax.lax.fori_loop(
        0, hi, body, jnp.zeros((bq, D), jnp.float32)
    )
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, sm_scale, causal, block_q):
    """One (k block, q head of the GQA group, q chunk) grid step: the q
    chunk's blocks are looped here and dK/dV accumulate in f32 VMEM scratch
    across the two innermost (sequential) grid dims, so VMEM holds one q
    chunk at a time — independent of the sequence length and group size."""
    kb_mat = k_ref[0, 0].astype(jnp.float32)                # [bk, D]
    vb_mat = v_ref[0, 0].astype(jnp.float32)
    bk = kb_mat.shape[0]
    chunk = q_ref.shape[2]
    k_idx = pl.program_id(2)
    g, c = pl.program_id(3), pl.program_id(4)
    # Global row of this chunk's first q row, relative to the K columns.
    row0 = c * chunk + q_off_ref[0]

    @pl.when((g == 0) & (c == 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(qb_i, carry):
        dk, dv = carry
        qb = q_ref[0, 0, pl.ds(qb_i * block_q, block_q), :].astype(jnp.float32)
        dob = do_ref[0, 0, pl.ds(qb_i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(qb_i * block_q, block_q), 0]
        delta = delta_ref[0, 0, pl.ds(qb_i * block_q, block_q), 0]
        s = jnp.dot(qb * sm_scale, kb_mat.T,
                    preferred_element_type=jnp.float32)      # [bq, bk]
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)
            mask = (rows + qb_i * block_q + row0) >= (cols + k_idx * bk)
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dv = dv + jnp.dot(p.T, dob, preferred_element_type=jnp.float32)
        dp = jnp.dot(dob, vb_mat.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        dk = dk + jnp.dot(ds.T, qb, preferred_element_type=jnp.float32)
        return dk, dv

    n_qb = chunk // block_q
    if causal:
        # dK/dV for this k block only sees q blocks whose last row reaches
        # the block's first column: start at the diagonal (a chunk wholly
        # above it runs zero iterations).
        lo = jnp.clip(jax.lax.div(k_idx * bk - row0, block_q), 0, n_qb)
    else:
        lo = 0
    dk, dv = jax.lax.fori_loop(lo, n_qb, body, (dk_acc[...], dv_acc[...]))
    dk_acc[...] = dk
    dv_acc[...] = dv

    @pl.when((g == pl.num_programs(3) - 1) & (c == pl.num_programs(4) - 1))
    def _():
        dk_ref[0, 0] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0] = dv.astype(dv_ref.dtype)


#: Rows of Q/dO/lse/delta one dK/dV grid step keeps in VMEM.  2048 rows at
#: head_dim 128 are ~6 MB double-buffered (the lse/delta lane pad is half of
#: it) — inside the 16 MB scoped default of every TPU generation, and the
#: whole sequence for b1-shaped training (S=2048), where the q blocks are
#: then fetched once per head, not once per k block.
DKV_CHUNK_ROWS = 2048


def _dkv_chunk(Sq: int, bq: int) -> int:
    """Largest multiple of ``bq`` dividing ``Sq`` within DKV_CHUNK_ROWS."""
    n = Sq // bq
    per = max(1, min(n, DKV_CHUNK_ROWS // bq))
    while n % per:
        per -= 1
    return per * bq


def _flash_bwd(res, g, *, sm_scale, causal, q_offset, block_q, block_k,
               interpret):
    q, k, v, out, lse = res
    do = g
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    group = H // Hkv
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], delta.shape + (LSE_LANES,))
    q_off = jnp.asarray([q_offset], jnp.int32)

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, sm_scale=sm_scale, causal=causal, block_k=bk
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, Sq // bq),
            in_specs=[
                pl.BlockSpec((1, 1, bq, D), lambda b, h, i, *_: (b, h, i, 0)),
                pl.BlockSpec((1, 1, Sk, D), lambda b, h, i, *_: (b, h // group, 0, 0)),
                pl.BlockSpec((1, 1, Sk, D), lambda b, h, i, *_: (b, h // group, 0, 0)),
                pl.BlockSpec((1, 1, bq, D), lambda b, h, i, *_: (b, h, i, 0)),
                pl.BlockSpec((1, 1, bq, LSE_LANES),
                             lambda b, h, i, *_: (b, h, i, 0)),
                pl.BlockSpec((1, 1, bq, LSE_LANES),
                             lambda b, h, i, *_: (b, h, i, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, *_: (b, h, i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q_off, q, k, v, do, lse, delta)

    # dk/dv: grid over (kv head, k block) with the GQA group's q heads and
    # the q chunks as the two innermost, accumulating dims.
    cq = _dkv_chunk(Sq, bq)

    def q_map(b, h, i, g, c, *_):
        return (b, h * group + g, c, 0)

    def kv_map(b, h, i, g, c, *_):
        return (b, h, i, 0)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, sm_scale=sm_scale, causal=causal, block_q=bq,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, Sk // bk, group, Sq // cq),
            in_specs=[
                pl.BlockSpec((1, 1, cq, D), q_map),
                pl.BlockSpec((1, 1, bk, D), kv_map),
                pl.BlockSpec((1, 1, bk, D), kv_map),
                pl.BlockSpec((1, 1, cq, D), q_map),
                pl.BlockSpec((1, 1, cq, LSE_LANES), q_map),
                pl.BlockSpec((1, 1, cq, LSE_LANES), q_map),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, bk, D), kv_map),
                pl.BlockSpec((1, 1, bk, D), kv_map),
            ],
            scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                            pltpu.VMEM((bk, D), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q_off, q, k, v, do, lse, delta)
    return dq, dk, dv


# ------------------------------------------------------------------ public


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8)
)
def _flash(q, k, v, sm_scale, causal, q_offset, block_q, block_k, interpret):
    out, _ = _flash_fwd(
        q, k, v, sm_scale, causal, q_offset, block_q, block_k, interpret
    )
    return out


def _flash_vjp_fwd(q, k, v, sm_scale, causal, q_offset, block_q, block_k,
                   interpret):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _flash_fwd(
        q, k, v, sm_scale, causal, q_offset, block_q, block_k, interpret
    )
    # Residuals are stored with the lse squeezed to [B, H, S] (the padded
    # lane dim only exists for TPU tiling) and tagged so remat policies can
    # choose to SAVE them — skipping the full attention-forward recompute
    # in the backward pass (see llama.py remat_policy="save_attn").
    res = checkpoint_name((q, k, v, out, lse[..., 0]), "flash_res")
    return out, res


def _flash_vjp_bwd(sm_scale, causal, q_offset, block_q, block_k, interpret,
                   res, g):
    q, k, v, out, lse_slim = res
    lse = jnp.broadcast_to(
        lse_slim[..., None], lse_slim.shape + (LSE_LANES,)
    )
    return _flash_bwd(
        (q, k, v, out, lse), g, sm_scale=sm_scale, causal=causal,
        q_offset=q_offset, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    q_offset: int = 0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    force_pallas: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention over [batch, heads, seq, head_dim] (GQA: k/v may have
    fewer heads).  Pallas on TPU, where a shape the kernels cannot take
    raises; the jnp reference elsewhere (``force_pallas`` with ``interpret``
    runs the kernels off the chip, for tests)."""
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    if not (force_pallas or _on_tpu()):
        return mha_reference(
            q, k, v, causal=causal, sm_scale=scale, q_offset=q_offset
        )
    bq, bk = kernel_blocks(q, k, block_q, block_k)
    return _flash(q, k, v, scale, causal, q_offset, bq, bk, interpret)
