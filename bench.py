"""Benchmark: Llama train-step MFU on one TPU chip.

Prints ONE JSON line:
  {"metric": ..., "value": ..., "unit": ..., "vs_baseline": ...}

value = model FLOPs utilization (%) of a full forward+backward+optimizer
train step of the ~1.3B-param Llama config (bf16, remat, Pallas flash
attention).  vs_baseline = MFU / 50% — the north-star target from
BASELINE.json ("≥50% MFU ... zero GPUs"); the reference has no TPU numbers
(BASELINE.json.published == {}).

MFU convention: required model FLOPs only (6N per token + causal attention
6·L·S·d), rematerialization excluded — the standard PaLM-style accounting.
The peak is the ``ray_tpu.accelerators`` table's entry for the chip's
``device_kind``; an unknown chip is an error.

Runs on a TPU only, in this one process (it holds the chip): off the chip it
exits non-zero, and a tier that fails is a failure of the run.
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp

from ray_tpu.accelerators import enable_compile_cache, peak_flops


def _run(batch: int, seq: int, steps: int, cfg, grad_accum: int = 1) -> dict:
    from ray_tpu.models import TrainState, llama_init, llama_loss
    from ray_tpu.models.train_state import default_optimizer, make_train_step

    params = llama_init(cfg, jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    tx = default_optimizer(lr=1e-4, grad_clip=1.0)
    state = TrainState.create(params, tx)
    step = make_train_step(
        lambda p, b: llama_loss(cfg, p, b["tokens"], b["targets"]), tx,
        grad_accum=grad_accum,
    )
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq), 0, cfg.vocab_size
    )
    batch_d = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}

    # Compile + warmup.
    for _ in range(2):
        state, m = step(state, batch_d)
        jax.block_until_ready(m)

    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = step(state, batch_d)
    jax.block_until_ready((state, m))  # the whole dependent chain
    dt = time.perf_counter() - t0
    final_loss = float(m["loss"])

    tokens_per_step = batch * seq
    tokens_per_sec = tokens_per_step * steps / dt
    flops_per_token = 6 * n_params + 6 * cfg.n_layers * seq * cfg.d_model
    mfu = tokens_per_sec * flops_per_token / peak_flops(
        jax.devices()[0].device_kind)
    return {
        "n_params": n_params,
        "tokens_per_sec": round(tokens_per_sec, 1),
        "step_time_s": round(dt / steps, 4),
        "mfu": mfu,
        "loss": final_loss,
    }


def main():
    from ray_tpu.models import LlamaConfig

    if jax.default_backend() != "tpu":
        print(f"bench.py measures a TPU; JAX runs on "
              f"{jax.default_backend()!r} here", file=sys.stderr)
        return 2
    enable_compile_cache()
    base = LlamaConfig.b1(remat=True, dtype=jnp.bfloat16, max_seq=2048)
    # (batch, seq, steps, remat_policy, grad_accum, block_q,
    # loss_chunk).  The choices below come from a pre-round record on a
    # v5e that is removed and not reproducible; re-measure before relying
    # on any of them:
    # - policy: xla_cse (XLA-chosen activation keeping) at short seq;
    #   cse_save_attn (+ kept flash residuals, no attention recompute)
    #   wins the attention-dominated tiers.
    # - grad_accum > 1: the tier runs as accum microbatches inside ONE
    #   jitted step (one optimizer update) — 8x2048/16x2048 ride the
    #   4x2048-sized activation regime instead of spilling
    #   (54.0 -> 64.6 / 65.9).
    # - loss_chunk == seq (unchunked vocab projection, ~1 GiB fp32
    #   logits at 8192 tokens): +2.5-5pp on the single-shot tiers; the
    #   grad-accum tiers are tighter on HBM inside the scan and prefer
    #   chunk=256.
    # - block_q: 512 wins warm (1024 only led cold 6-step sweeps).
    # Every tier runs and is reported; the best MFU is the headline.
    plan = [
        (32, 256, 10, "xla_cse", 1, 512, 256),
        (16, 512, 10, "xla_cse", 1, 512, 512),
        (8, 1024, 10, "xla_cse", 1, 512, 1024),
        (4, 2048, 10, "cse_save_attn", 1, 512, 2048),
        (8, 2048, 10, "cse_save_attn", 2, 512, 256),
        (16, 2048, 10, "cse_save_attn", 4, 512, 256),
    ]

    import dataclasses

    result = None
    tiers = {}
    for batch, seq, steps, policy, accum, bq, chunk in plan:
        cfg = dataclasses.replace(
            base, remat_policy=policy, max_seq=max(seq, 256),
            flash_block_q=bq, loss_chunk=chunk,
        )
        r = _run(batch, seq, steps, cfg, grad_accum=accum)
        r.update(batch=batch, seq=seq, remat_policy=policy, grad_accum=accum)
        tiers[f"{batch}x{seq}"] = round(r["mfu"] * 100, 2)
        if result is None or r["mfu"] > result["mfu"]:
            result = r

    dev = jax.devices()[0]
    print(json.dumps({
        "metric": "llama_1b3_train_mfu_single_chip",
        "value": round(result["mfu"] * 100, 2),
        "unit": "%MFU",
        "vs_baseline": round(result["mfu"] / 0.50, 4),
        "platform": dev.platform,
        "device": dev.device_kind,
        "device_count": len(jax.devices()),
        "tokens_per_sec": result["tokens_per_sec"],
        "step_time_s": result["step_time_s"],
        "n_params": result["n_params"],
        "batch": result["batch"],
        "seq": result["seq"],
        "remat_policy": result["remat_policy"],
        # Long-sequence tiers alongside the headline (%MFU per shape):
        # the north-star workload resembles seq>=1024, not the headline's.
        "tiers": tiers,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
