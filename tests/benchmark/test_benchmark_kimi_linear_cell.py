"""The Kimi-Linear cell's rehearsals, apart from
``test_benchmark_kimi_linear.py`` so that the two files run side by side:
the cell through ``benchmarks/run.py`` at the tiny configuration, the parent
of the PR that added the family failing before any process, and the chip
comparison's script on the CPU."""

import json
import os
import subprocess
import sys
import time

from bench_testlib import ROOT, run_bench

CELL = "kimi-linear-48b-a3b-L13.serve-long-decode-doc-tail"
READERS = ("decode_bytes_floor_share.kda", "expert_pairs_held_share.moe")
LOGIT_TOL = 2e-4
#: The script's arguments for three of its faults: one of the recurrence,
#: the state's precision, and the serving cell's control.
FAULTS = ["--fault", "no-decay", "--fault", "bf16-state", "--fault",
          "float8"]


def test_the_cell_rehearses_and_prints_its_metrics():
    rc, lines, err = run_bench(
        "--workload", CELL, "--seed", str(2 ** 31 + 43), "--seconds", "3",
        "--trace", "1", "--rehearse")
    assert rc == 0, err[-3000:]
    out = lines[-1]
    assert out["correct"] is True, lines
    assert out["attempted"] > 0 and out["failed"] == 0
    got = out["metrics"]
    held = got["expert_pairs_held_share.moe"]
    # Four of the router's sixteen experts: a quarter under an even router.
    assert held["unit"] == "%" and 10 < held["value"] < 45
    assert 0 < got["kv_gather_live_share.swa"]["value"] <= 100
    assert 0 < got["experts_hit_share.moe"]["value"] <= 80
    assert {"prefill_chunk_ms.swa", "decode_step_ms.sat",
            "batch_occupancy.sat", "prefill_stall_share.sat",
            "decode_period_ms.sat", "ahead_share.sat",
            "device_starved_share.sat"} <= set(got)
    # No peak for a CPU: no share of one is printed.
    assert READERS[0] not in got and "moe_stream_roofline.moe" not in got \
        and "latent_decode_roofline.mla" not in got
    samples = next(l for l in lines if l.get("phase") == "samples")
    assert samples["reference_gap_max"] <= 1e-3 and samples["shed"] == 0


def test_a_program_without_the_fields_fails_before_any_process(tmp_path):
    """The parent of this PR under this PR's benchmark files: the family
    says why where the harness finds it (``spec.load_cell``), exit 1 in
    about a second, no replica started and restarted until the
    deployment's time runs out."""
    import shutil

    root = tmp_path / "old"
    root.mkdir()
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for pkg in ("ray_tpu", "ray_tpu/serve", "ray_tpu/models"):
        os.makedirs(root / pkg)
        (root / pkg / "__init__.py").write_text("")
    (root / "ray_tpu" / "models" / "moe.py").write_text(
        "import dataclasses\n\n@dataclasses.dataclass\n"
        "class MoEConfig:\n    n_experts: int = 8\n"
        "    kv_lora_rank: int = 0\n    attn_gate: bool = False\n"
        "    post_norm: bool = False\n    embed_scale: float = 1.0\n")
    (root / "ray_tpu" / "serve" / "engine.py").write_text(
        "import dataclasses\n\n@dataclasses.dataclass\n"
        "class EngineConfig:\n    batch_slots: int = 8\n"
        "    prefill_chunk: int = 0\n\n"
        "def register_model(name, builder):\n    pass\n")
    t0 = time.time()
    rc, lines, err = run_bench(
        "--workload", CELL, "--seed", "1", "--seconds", "2", "--trace", "0",
        "--rehearse", root=str(root), timeout=60)
    assert rc == 1 and not lines and time.time() - t0 < 30
    assert "ray_tpu/models/moe.py has no MoEConfig.attn_layout" in err


def test_the_chip_comparison_rehearses_and_refuses_each_fault():
    """``benchmarks/reference/kimi_linear_compare.py`` at the tiny
    configuration: the decode rows' experts are the reference's own (in
    float32 nothing rounds a choice the other way), logits through state
    and pool within the float32 tolerance at the check's three lengths
    (one bucket, two chunks, four), slots used again, and each of the
    faults asked for read as incorrect (three of the twelve here, the
    script's whole control flow; ``test_benchmark_kimi_linear.py`` holds
    every fault to the reference through the same programs)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "benchmarks/reference/kimi_linear_compare.py",
         "--rehearse", "--seed", str(2 ** 31 + 3), *FAULTS],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    assert out["ok"] is True and out["logit_tol"] == LOGIT_TOL
    assert out["prompt_lens"] == [6, 14, 30] and out["swap_margin"] == 0.0
    base, *faults = out["results"]
    assert base["correct"] and base["rows"] == 3 * (1 + 4)
    assert base["rows_judged"] == base["argmax_agree"] == base["rows"]
    assert base["tie_swaps"] == base["routing_violations"] == 0
    # STATE: what the slots hold at the end against the reference's.
    assert base["state_err_mean"] <= base["state_err_max"] < 1e-5
    assert out["state_tol"] == 1e-4
    by_name = {f["fault"]: f for f in faults}
    assert by_name["bf16-state"]["state_err_mean"] > 10 * out["state_tol"]
    assert [f["fault"] for f in faults] == FAULTS[1::2]
    assert not any(f["correct"] for f in faults)
    assert all(f["max_abs_logit_diff"] > 25 * LOGIT_TOL for f in faults)
    assert base["cell_check_passes"] and out["cell_logit_tol"] == 1e-3
