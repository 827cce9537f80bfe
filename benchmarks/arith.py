"""The benchmark's arithmetic: percentiles, spreads, the operations a token
of training needs, the operations and bytes of the flash-attention calls,
and roofline shares.  Pure Python (no JAX, no numpy), so that the parent
process and the tests can use it, and so that a hand-worked case checks it.

Copied where the program already had it right: ``bench.py``'s ``6N + 6LSd``
count and ``bench_attention.py``'s causal ``2*2*B*H*S*S*D/2`` (the originals
are listed in PERF.md for a later PR to delete).
"""

from __future__ import annotations

import json
import math
import os
import statistics
from typing import Any, Dict, Iterable, Sequence

_HERE = os.path.dirname(os.path.abspath(__file__))


def percentile(values: Iterable[float], p: float) -> float:
    """The ``p``-th percentile (0..100) by linear interpolation between the
    two nearest order statistics (numpy's default).  Raises on no values:
    a tail of nothing is not 0."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    rank = (len(xs) - 1) * p / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def median(values: Iterable[float]) -> float:
    return percentile(values, 50.0)


def spread(values: Sequence[float]) -> float:
    """Inter-quartile distance as a share of the median, with the quartiles
    of ``statistics.quantiles(values, n=4)``: the rule the bounds in
    BENCHMARK.json are set by."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


# ------------------------------------------------------------ model counts


def matmul_params(model: Dict[str, Any]) -> int:
    """Parameters that a token multiplies with: every projection and the
    output head.  The embedding is a lookup and the norms are elementwise."""
    d, f, v = (model["hidden_size"], model["intermediate_size"],
               model["vocab_size"])
    kv = model["num_key_value_heads"] * (d // model["num_attention_heads"])
    return model["num_hidden_layers"] * (2 * d * d + 2 * d * kv + 3 * d * f) \
        + d * v


def train_flops_per_token(model: Dict[str, Any], seq: int) -> float:
    """Operations the forward and backward pass of one token REQUIRE at
    sequence length ``seq``: 6 per matmul parameter (2 forward, 4 backward)
    plus causal attention, 2*2*S*d/2 forward per layer and twice that
    backward, which is 6*L*S*d.  Recomputation (remat, the flash backward's
    second pass over the scores) is not counted."""
    d_attn = model["hidden_size"]  # heads x head size
    return 6.0 * matmul_params(model) \
        + 6.0 * model["num_hidden_layers"] * seq * d_attn


def mfu(tokens_per_s: float, flops_per_token: float, chips: int,
        peak_flops: float) -> float:
    return tokens_per_s * flops_per_token / (chips * peak_flops)


# ---------------------------------------------------------- flash attention


def flash_forward_ops_bytes(batch: int, heads: int, kv_heads: int, seq: int,
                            head_dim: int, itemsize: int = 2,
                            causal: bool = True) -> Dict[str, float]:
    """One forward call: Q.K^T and P.V, half of each under the causal mask;
    reads q, k, v once, writes the output and a float32 log-sum-exp row."""
    ops = 2.0 * 2.0 * batch * heads * seq * seq * head_dim
    if causal:
        ops /= 2.0
    q_o = 2.0 * batch * heads * seq * head_dim * itemsize
    k_v = 2.0 * batch * kv_heads * seq * head_dim * itemsize
    lse = 4.0 * batch * heads * seq
    return {"ops": ops, "bytes": q_o + k_v + lse}


def flash_backward_ops_bytes(batch: int, heads: int, kv_heads: int, seq: int,
                             head_dim: int, itemsize: int = 2,
                             causal: bool = True) -> Dict[str, float]:
    """The backward pass of one call, both kernels (dQ, and dK with dV)
    together: five matmuls are needed (scores again, dP, dQ, dK, dV), 2.5
    times the forward.  The two kernels each rebuild the scores and dP, so
    they execute seven; the two extra are recomputation and not counted.
    Reads q, k, v, o, dO and the log-sum-exp; writes dq, dk, dv."""
    fwd = flash_forward_ops_bytes(batch, heads, kv_heads, seq, head_dim,
                                  itemsize, causal)
    q_like = batch * heads * seq * head_dim * itemsize
    kv_like = batch * kv_heads * seq * head_dim * itemsize
    lse = 4.0 * batch * heads * seq
    return {"ops": 2.5 * fwd["ops"],
            "bytes": 4.0 * q_like + 4.0 * kv_like + 2.0 * lse}


def flash_train_step_ops_bytes(model: Dict[str, Any], batch: int, seq: int,
                               forward_calls_per_layer: int = 1
                               ) -> Dict[str, float]:
    """All flash calls of one train step: per layer one backward and
    ``forward_calls_per_layer`` forwards (2 where remat runs the forward
    again in the backward pass; the roofline share counts what ran)."""
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model["hidden_size"] // heads
    f = flash_forward_ops_bytes(batch, heads, kv, seq, hd)
    b = flash_backward_ops_bytes(batch, heads, kv, seq, hd)
    n = model["num_hidden_layers"]
    return {"ops": n * (forward_calls_per_layer * f["ops"] + b["ops"]),
            "bytes": n * (forward_calls_per_layer * f["bytes"] + b["bytes"])}


def roofline(ops: float, nbytes: float, seconds: float, peak_flops: float,
             peak_bytes_per_s: float) -> Dict[str, Any]:
    """The least time the chip could take (the larger of operations over
    peak FLOP/s and bytes over peak bytes/s) over the time it took, and
    which of the two bounds it."""
    t_ops, t_bytes = ops / peak_flops, nbytes / peak_bytes_per_s
    return {"share": max(t_ops, t_bytes) / seconds,
            "bound": "compute" if t_ops >= t_bytes else "memory",
            "least_s": max(t_ops, t_bytes)}


# -------------------------------------------------------------------- peaks


def load_peaks(device_kind: str) -> Dict[str, float]:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        known = sorted(k for k in table if not k.startswith("_"))
        raise ValueError(
            f"no peaks on record for device_kind {device_kind!r}; add it to "
            f"benchmarks/peaks.json with its source (known: {known})")
    return table[device_kind]
