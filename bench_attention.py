"""On-chip evidence for the flash kernels and the ring-attention chunk math.

1. Flash fwd / fwd+bwd kernel throughput on model-representative shapes.
2. Ring chunk parity ON THE REAL DEVICE: simulate an n-rank ring on one
   chip by slicing the sequence into chunks and running the exact per-chunk
   kernel calls + streaming-softmax merges the ring impl uses
   (_flash_fwd/_flash_bwd with q_offset), then compare against the
   full-sequence flash kernel and the XLA reference.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.accelerators import enable_compile_cache, peak_flops
from ray_tpu.ops import attention as att
from ray_tpu.ops.attention import flash_attention, mha_reference

# One process, and it holds the chip: off the TPU there is nothing to time.
assert jax.default_backend() == "tpu", jax.default_backend()
enable_compile_cache()
print(f"device: {jax.devices()[0].platform} {jax.devices()[0].device_kind} "
      f"x{len(jax.devices())}")

# ---- 1. kernel throughput ------------------------------------------------
B, H, S, D = 4, 16, 2048, 128
key = jax.random.PRNGKey(0)
q = jax.random.normal(key, (B, H, S, D), jnp.bfloat16)
k = jax.random.normal(jax.random.PRNGKey(1), (B, H, S, D), jnp.bfloat16)
v = jax.random.normal(jax.random.PRNGKey(2), (B, H, S, D), jnp.bfloat16)

CHAIN = 10  # amortize per-call dispatch latency


@jax.jit
def fwd_chain(q, k, v):
    for _ in range(CHAIN):
        q = flash_attention(q, k, v, causal=True)
    return q


def loss(q, k, v):
    return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()


grad_fn = jax.grad(loss, argnums=(0, 1, 2))


@jax.jit
def bwd_chain(q, k, v):
    for _ in range(CHAIN):
        dq, dk, dv = grad_fn(q, k, v)
        q = q + 0 * dq.astype(q.dtype)  # serialize iterations
        k = k + 0 * dk.astype(k.dtype)
        v = v + 0 * dv.astype(v.dtype)
    return q, k, v

float(fwd_chain(q, k, v).astype(jnp.float32).sum())  # compile+warm
float(bwd_chain(q, k, v)[0].astype(jnp.float32).sum())

N_IT = 3
t0 = time.perf_counter()
out = None
for _ in range(N_IT):
    out = fwd_chain(q, k, v)
float(out.astype(jnp.float32).sum())
fwd_dt = (time.perf_counter() - t0) / (N_IT * CHAIN)

t0 = time.perf_counter()
for _ in range(N_IT):
    g = bwd_chain(q, k, v)
float(g[0].astype(jnp.float32).sum())
bwd_dt = (time.perf_counter() - t0) / (N_IT * CHAIN)

# Causal attention FLOPs: fwd = 2 matmuls * 2*S^2*D/2 rows; bwd ~ 2.5x fwd.
fwd_flops = 2 * 2 * B * H * S * S * D / 2
fwdbwd_flops = fwd_flops * 3.5
peak = peak_flops(jax.devices()[0].device_kind)
print(f"flash fwd:      {fwd_dt*1e3:7.3f} ms  "
      f"{fwd_flops/fwd_dt/1e12:6.1f} TFLOP/s ({fwd_flops/fwd_dt/peak*100:4.1f}% peak)")
print(f"flash fwd+bwd:  {bwd_dt*1e3:7.3f} ms  "
      f"{fwdbwd_flops/bwd_dt/1e12:6.1f} TFLOP/s ({fwdbwd_flops/bwd_dt/peak*100:4.1f}% peak)")

# XLA reference comparison at the same shape.
@jax.jit
def ref_chain(q, k, v):
    for _ in range(CHAIN):
        q = mha_reference(q, k, v, causal=True,
                          sm_scale=D ** -0.5).astype(q.dtype)
    return q


float(ref_chain(q, k, v).astype(jnp.float32).sum())
t0 = time.perf_counter()
for _ in range(N_IT):
    r = ref_chain(q, k, v)
float(r.astype(jnp.float32).sum())
ref_dt = (time.perf_counter() - t0) / (N_IT * CHAIN)
print(f"xla reference:  {ref_dt*1e3:7.3f} ms  (pallas fwd speedup "
      f"{ref_dt/fwd_dt:.2f}x)")

# ---- 2. ring chunk math parity on device ---------------------------------
NEG_INF = float("-inf")


def simulated_ring_fwd(q, k, v, scale, n):
    """The exact per-rank computation from _ring_flash_fwd_impl, with the
    ppermute replaced by local chunk indexing (one chip stands in for all
    ranks)."""
    Sc = q.shape[2] // n
    qs = jnp.split(q, n, axis=2)
    ks = jnp.split(k, n, axis=2)
    vs = jnp.split(v, n, axis=2)
    outs, lses = [], []
    Bq, Hh = q.shape[0], q.shape[1]
    for rank in range(n):
        acc = jnp.zeros((Bq, Hh, Sc, q.shape[3]), jnp.float32)
        m_run = jnp.full((Bq, Hh, Sc), NEG_INF, jnp.float32)
        l_run = jnp.zeros((Bq, Hh, Sc), jnp.float32)
        for s in range(n):
            src = (rank - s) % n
            offset = (rank - src) * Sc
            out_c, lse_c = att._flash_fwd(
                qs[rank], ks[src], vs[src], scale, True, offset,
                min(256, Sc), min(256, Sc), False,
            )
            lse_c = lse_c[..., 0]
            m_new = jnp.maximum(m_run, lse_c)
            alpha = jnp.exp(m_run - m_new)
            beta = jnp.exp(lse_c - m_new)
            acc = acc * alpha[..., None] + \
                out_c.astype(jnp.float32) * beta[..., None]
            l_run = l_run * alpha + beta
            m_run = m_new
        outs.append((acc / jnp.maximum(l_run, 1e-30)[..., None])
                    .astype(q.dtype))
        lses.append(m_run + jnp.log(jnp.maximum(l_run, 1e-30)))
    return jnp.concatenate(outs, axis=2), lses


B2, H2, S2, D2, NRING = 2, 4, 1024, 64, 4
q2 = jax.random.normal(jax.random.PRNGKey(3), (B2, H2, S2, D2), jnp.float32)
k2 = jax.random.normal(jax.random.PRNGKey(4), (B2, H2, S2, D2), jnp.float32)
v2 = jax.random.normal(jax.random.PRNGKey(5), (B2, H2, S2, D2), jnp.float32)
scale = D2 ** -0.5

ring_out, ring_lses = simulated_ring_fwd(q2, k2, v2, scale, NRING)
full_out = flash_attention(q2, k2, v2, causal=True, sm_scale=scale)
ref_out = mha_reference(q2, k2, v2, causal=True, sm_scale=scale)
err_full = float(jnp.abs(ring_out - full_out).max())
err_ref = float(jnp.abs(ring_out - ref_out).max())
print(f"ring fwd parity (n={NRING}, S={S2}): "
      f"max|ring-full_flash|={err_full:.2e} max|ring-xla_ref|={err_ref:.2e}")
assert err_full < 2e-3, err_full  # ring == kernel, tight
assert err_ref < 2e-2, err_ref  # kernel-vs-f32-reference numerics

# Backward chunk math: per-rank _flash_bwd accumulation vs XLA grads.
def ref_loss(q, k, v):
    o = mha_reference(q, k, v, causal=True, sm_scale=scale)
    return (o * jnp.arange(D2, dtype=o.dtype)).sum()


dq_ref, dk_ref, dv_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q2, k2, v2)

Sc = S2 // NRING
do_full = jax.grad(lambda o: (o * jnp.arange(D2, dtype=o.dtype)).sum())(
    ring_out)
qs = jnp.split(q2, NRING, axis=2)
ks = jnp.split(k2, NRING, axis=2)
vs = jnp.split(v2, NRING, axis=2)
outs = jnp.split(ring_out, NRING, axis=2)
dos = jnp.split(do_full, NRING, axis=2)
dq_chunks = [jnp.zeros_like(qs[0]) for _ in range(NRING)]
dk_chunks = [jnp.zeros_like(ks[0]) for _ in range(NRING)]
dv_chunks = [jnp.zeros_like(vs[0]) for _ in range(NRING)]
for rank in range(NRING):
    lse4 = jnp.broadcast_to(
        ring_lses[rank][..., None], ring_lses[rank].shape + (att.LSE_LANES,))
    for s in range(NRING):
        src = (rank - s) % NRING
        offset = (rank - src) * Sc
        dq_c, dk_c, dv_c = att._flash_bwd(
            (qs[rank], ks[src], vs[src], outs[rank], lse4), dos[rank],
            sm_scale=scale, causal=True, q_offset=offset,
            block_q=min(256, Sc), block_k=min(256, Sc), interpret=False,
        )
        dq_chunks[rank] = dq_chunks[rank] + dq_c
        dk_chunks[src] = dk_chunks[src] + dk_c
        dv_chunks[src] = dv_chunks[src] + dv_c
dq_ring = jnp.concatenate(dq_chunks, axis=2)
dk_ring = jnp.concatenate(dk_chunks, axis=2)
dv_ring = jnp.concatenate(dv_chunks, axis=2)
for name, a, b in (("dq", dq_ring, dq_ref), ("dk", dk_ring, dk_ref),
                   ("dv", dv_ring, dv_ref)):
    err = float(jnp.abs(a - b).max())
    rel = err / (float(jnp.abs(b).max()) + 1e-9)
    print(f"ring bwd parity {name}: max_abs_err={err:.2e} rel={rel:.2e}")
    assert rel < 2e-2, (name, rel)

print("RING CHUNK MATH PARITY OK ON TPU")
