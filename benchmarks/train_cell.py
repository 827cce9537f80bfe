"""The parent's side of a training run: ``JaxTrainer(...).fit()`` with one
worker holding the cell's chips, then the comparison of what the worker
reported.  Never imports JAX."""

from __future__ import annotations

import time
from typing import Any, Dict


def run_train(cell: Dict[str, Any], *, seed: int, seconds: float,
              trace: bool, platform: str, chips: int, fail_phase: str,
              storage: str, log, phase) -> Dict[str, Any]:
    from ray_tpu.parallel import MeshConfig
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    from .train_loop import train_loop

    tr = cell["traffic"]
    phase("worker_grant")
    t_run = time.time()
    on_chip = platform == "tpu"
    result = JaxTrainer(
        train_loop,
        train_loop_config=dict(
            model=cell["model"], traffic=tr, seed=seed, seconds=seconds,
            trace=trace, platform=platform, fail_phase=fail_phase),
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=on_chip,
            resources_per_worker={"TPU": chips} if on_chip else None,
            mesh=MeshConfig(**tr["mesh"]) if tr["mesh"] else None),
        run_config=RunConfig(name="bench_train", storage_path=storage),
    ).fit()
    if result.error is not None:
        raise result.error
    r = result.metrics
    log({"phase": "ready", "worker_start_s": r["devices_ready_wall"] - t_run,
         "weights_s": r["weights_s"], "reference_s": r["reference_s"],
         "warmup_compile_s": r["compile_s"],
         "compile_cache": r["compile_cache"], "device": r["device"],
         "worker_pid": r["pid"], "mesh": r["mesh"],
         "tpu_custom_calls": r["tpu_custom_calls"]})

    phase("compare")
    check, reasons = tr["check"], []
    loss_err = abs(r["first_loss"] - r["ref_loss"]) / abs(r["ref_loss"])
    gn_err = abs(r["first_grad_norm"] - r["ref_grad_norm"]) \
        / abs(r["ref_grad_norm"])
    if not loss_err <= check["loss_rtol"]:
        reasons.append(
            f"first loss {r['first_loss']} against the reference's "
            f"{r['ref_loss']}: off by {loss_err:.2e} (tolerance "
            f"{check['loss_rtol']})")
    if not gn_err <= check["grad_norm_rtol"]:
        reasons.append(
            f"first gradient norm {r['first_grad_norm']} against the "
            f"reference's {r['ref_grad_norm']}: off by {gn_err:.2e} "
            f"(tolerance {check['grad_norm_rtol']})")
    if not r["all_finite"]:
        reasons.append("a loss of the window is not finite")
    if on_chip and not r["tpu_custom_calls"]:
        reasons.append("the compiled step holds no tpu_custom_call: the "
                       "flash kernel did not run")
    log({"phase": "samples", "steps": r["steps"],
         "elapsed_s": r["elapsed_s"], "first_loss": r["first_loss"],
         "ref_loss": r["ref_loss"], "loss_rel_err": loss_err,
         "first_grad_norm": r["first_grad_norm"],
         "ref_grad_norm": r["ref_grad_norm"], "grad_norm_rel_err": gn_err,
         "losses": [round(x, 4) for x in r["losses"]]})
    return {
        "kind": "train", "seconds": seconds, "attempted": r["steps"],
        "failed": 0 if r["all_finite"] else 1, "reasons": reasons,
        "steps": r["steps"], "elapsed_s": r["elapsed_s"],
        "step_s": r["step_s"], "tokens_per_step": r["tokens_per_step"],
        "batch": tr["batch"], "seq": tr["seq"], "trace": r["trace"],
        "window_wall": r["window_wall"],
        "worker_start_s": r["devices_ready_wall"] - t_run,
        "warmup_compile_s": r["compile_s"], "device": r["device"],
        "holder_pid": r["pid"], "chips": chips,
        "tpu_custom_calls": r["tpu_custom_calls"],
    }
