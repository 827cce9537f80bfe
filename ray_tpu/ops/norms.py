"""Fused RMSNorm: Pallas kernel + jnp fallback.

RMSNorm is bandwidth-bound; the win is one HBM round-trip for
read→normalize→scale.  Backward goes through the jnp definition (XLA fuses
the elementwise chain well); the forward kernel exists for inference paths
and as the canonical simple-kernel example.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rms_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * w_ref[...]).astype(o_ref.dtype)


def rms_norm_pallas(x: jax.Array, w: jax.Array, eps: float = 1e-6,
                    block_rows: int = 256, interpret: bool = False):
    orig_shape = x.shape
    d = orig_shape[-1]
    x2 = x.reshape(-1, d)
    rows = x2.shape[0]
    br = min(block_rows, rows)
    if rows % br != 0:
        return _rms_ref(x, w, eps)
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((d,), lambda i: (0,), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=interpret,
        name="rms_norm",
    )(x2, w)
    return out.reshape(orig_shape)


def _rms_ref(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w).astype(x.dtype)


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Differentiable RMSNorm.  The jnp form is used under autodiff; XLA
    fuses it into neighbors, which on TPU is within noise of the kernel."""
    return _rms_ref(x, w, eps)
