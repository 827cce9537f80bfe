"""The one general traffic generator.  A traffic mix is a data file of
parameters (``benchmarks/traffic/<mix>.json``); this module turns it and a
seed into requests and arrival times.  numpy only, no JAX.

Every seed gets the SAME schedule: sizes are the stratified quantiles of the
file's distribution (not random draws), their order and the order of the
gaps between arrivals come from the file's ``schedule_seed``, and ``--seed``
only fills in the token ids (and, in the program, the weights).  Runs with
different seeds then do the same work at the same times.  The first proof
runs permuted the order by ``--seed`` as well: the 90th percentile of the
time to first token then moved by 26-29% between seeds and by 3-11% between
two runs of one seed (PERF.md, PR 23), so the seed was changing the work.

The open-loop arrival process and the percentile arithmetic follow
``bench_serve.py`` (Poisson arrivals, each request timed from when it was
due); what differs is that the gaps here are the exponential's quantiles.
"""

from __future__ import annotations

import math
import statistics
from typing import Any, Dict, List

import numpy as np

_NORMAL = statistics.NormalDist()


def seed32(seed: int) -> int:
    """Seeds arrive as large as a little over 2**31; JAX keys and numpy's
    legacy generators take 32 bits."""
    return int(seed) % (2 ** 31 - 1)


def quantile_lengths(dist: Dict[str, Any], n: int) -> List[int]:
    """``n`` lengths: the (i + 0.5) / n quantiles of the distribution in
    ``dist``, clipped to its ``min`` and ``max``.  ``lognormal`` takes
    ``median`` and ``sigma``; ``fixed`` takes ``value``."""
    kind = dist.get("dist", "lognormal")
    if kind == "fixed":
        return [int(dist["value"])] * n
    if kind != "lognormal":
        raise ValueError(f"unknown length distribution {kind!r}")
    mu, sigma = math.log(dist["median"]), float(dist["sigma"])
    out = []
    for i in range(n):
        x = math.exp(mu + sigma * _NORMAL.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(x), dist["min"]), dist["max"])))
    return out


def quantile_gaps(rate_rps: float, n: int) -> List[float]:
    """``n`` gaps between arrivals of a Poisson process of ``rate_rps``:
    the exponential's (i + 0.5) / n quantiles.  Their sum is the same for
    every seed, so every seed offers the same load."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate_rps for i in range(n)]


def requests(traffic: Dict[str, Any], n: int, seed: int,
             vocab_size: int) -> List[Dict[str, Any]]:
    """``n`` requests: prompts of random token ids below the vocabulary
    (fresh ids for every request, so no two share a prefix unless the file
    asks for ``shared_prefix`` tokens), each with the tokens to generate.
    Lengths cycle through a fixed pool of ``traffic['pool']`` pairs; each
    pass over the pool is a new order, the same for every ``seed``."""
    order = np.random.default_rng([int(traffic.get("schedule_seed", 0)), 1])
    rng = np.random.default_rng([seed32(seed), 1])
    pool = int(traffic.get("pool", 64))
    prompts = quantile_lengths(traffic["prompt_len"], pool)
    outputs = quantile_lengths(traffic["output_len"], pool)
    # Pair prompt and output lengths independently of each other.
    outputs = [outputs[i] for i in order.permutation(pool)]
    shared = int(traffic.get("shared_prefix", 0))
    prefix = rng.integers(1, vocab_size, size=shared).tolist()
    out: List[Dict[str, Any]] = []
    while len(out) < n:
        for i in order.permutation(pool):
            body = max(1, prompts[i] - shared)
            toks = prefix + rng.integers(1, vocab_size, size=body).tolist()
            out.append({"prompt": toks, "max_new": outputs[i]})
            if len(out) == n:
                break
    return out


def arrivals(rate_rps: float, n: int, schedule_seed: int = 0) -> List[float]:
    """Seconds after the start at which each of ``n`` requests is due."""
    rng = np.random.default_rng([int(schedule_seed), 2])
    gaps = np.asarray(quantile_gaps(rate_rps, n))[rng.permutation(n)]
    return np.cumsum(gaps).tolist()


def train_batch(seed: int, step: int, batch: int, seq: int,
                vocab_size: int) -> Dict[str, np.ndarray]:
    """The host-made batch of one train step: seeded token ids, and the
    same shifted by one as targets."""
    rng = np.random.default_rng([seed32(seed), 3, step])
    tokens = rng.integers(0, vocab_size, size=(batch, seq), dtype=np.int32)
    return {"tokens": tokens, "targets": np.roll(tokens, -1, axis=1)}
