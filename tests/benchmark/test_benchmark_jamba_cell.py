"""The Jamba2-3B cell's rehearsals, apart from ``test_benchmark_jamba.py`` so
that the two files run side by side: the cell through ``benchmarks/run.py``
at the tiny configuration (its line, the two new readers, and nothing left
running or lying behind it), the parent of the PR that added the family
failing before any process, and the chip comparison's script on the CPU."""

import json
import os
import subprocess
import sys
import time

from bench_testlib import (RESULT_KEYS, ROOT, assert_nothing_left, run_bench,
                           state_of)

CELL = "jamba2-3b.serve-reasoning-wide-batch"
READERS = ("decode_bytes_floor_share.ssm", "scan_padding_share.ssm")
LOGIT_TOL = 2e-4
#: The script's arguments for three of its faults: one of the mixer's
#: mathematics, the state's precision, and the serving cell's control.
FAULTS = ["--fault", "no-dt-norm", "--fault", "bf16-state", "--fault",
          "float8"]


def _no_descendant_is_left(lines):
    """``assert_nothing_left`` (the pids the run itself listed at teardown,
    its shm segments and its session directories), and no ``rtpu-*`` process
    whose parent is gone to init that this run started."""
    assert_nothing_left(lines)
    ready = next(l for l in lines if l.get("phase") == "ready")
    assert state_of(ready["replica_pid"]) in (None, "Z")


def test_the_cell_rehearses_and_prints_its_end_to_end_line():
    rc, lines, err = run_bench(
        "--workload", CELL, "--seed", str(2 ** 31 + 48), "--seconds", "2",
        "--trace", "0", "--rehearse")
    assert rc == 0, err[-3000:]
    out = lines[-1]
    assert set(out) >= RESULT_KEYS and out["correct"] is True, lines
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["serve_tok_s"]["unit"] == "tokens/s"
    assert out["metrics"]["serve_tok_s"]["value"] > 0
    assert out["metrics"]["setup_s"]["value"] > 0
    assert out["device"]["platform"] == "cpu"  # never a measurement
    _no_descendant_is_left(lines)


def test_the_traced_rehearsal_reads_what_a_cpu_can_and_leaves_nothing():
    rc, lines, err = run_bench(
        "--workload", CELL, "--seed", str(2 ** 31 + 49), "--seconds", "3",
        "--trace", "1", "--rehearse")
    assert rc == 0, err[-3000:]
    out = lines[-1]
    assert out["correct"] is True, lines
    got = out["metrics"]
    # Counted on the host from lengths: a CPU's records read it.  Pages of
    # 4 and buckets of 4 and 8 under prompts of 4..48: some padding, never
    # half.
    padding = got["scan_padding_share.ssm"]
    assert padding["unit"] == "%" and 0 < padding["value"] < 50
    # No peak for a CPU: no share of one is printed (the reader returns
    # None; on the chip it reads a number, CHANGES.md).
    assert READERS[0] not in got
    assert {"prefill_chunk_ms.swa", "decode_step_ms.sat",
            "batch_occupancy.sat", "prefill_stall_share.sat",
            "decode_period_ms.sat", "ahead_share.sat",
            "device_starved_share.sat", "loop_accounted_share.sat",
            "admission_drain_ms.sat"} <= set(got)
    # Nothing of another architecture's is read here.
    assert not [m for m in got if m.endswith((".moe", ".mla", ".kda"))]
    samples = next(l for l in lines if l.get("phase") == "samples")
    assert samples["reference_gap_max"] <= 1e-3 and samples["shed"] == 0
    _no_descendant_is_left(lines)


def test_a_program_without_the_fields_fails_before_any_process(tmp_path):
    """The parent of this PR under this PR's benchmark files: the family
    says why where the harness finds it (``spec.load_cell``), exit 1 in
    about a second, no replica started and restarted until the
    deployment's time runs out, nothing left running."""
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
        "    attn_layout: tuple = ()\n    kda_heads: int = 0\n"
        "    router_experts: int = 0\n")
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
    assert "ray_tpu/models/moe.py has no MoEConfig.ssm_inner" in err


def test_the_chip_comparison_rehearses_and_refuses_each_fault():
    """``benchmarks/reference/jamba_compare.py`` at the tiny configuration:
    logits through state and cache within the float32 tolerance at the
    check's three lengths (a padded bucket, two chunks, four), slots used
    again, every slot's final state the reference's, and each of the faults
    asked for read as incorrect (three of the six here, the script's whole
    control flow; ``test_benchmark_jamba.py`` holds every fault to the
    reference through the same programs)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "benchmarks/reference/jamba_compare.py",
         "--rehearse", "--seed", str(2 ** 31 + 3), *FAULTS],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    assert out["ok"] is True and out["logit_tol"] == LOGIT_TOL
    assert out["prompt_lens"] == [6, 14, 30] and out["state_tol"] == 1e-4
    assert out["decode_attention"] == "gather"  # no TPU here
    base, *faults = out["results"]
    assert base["correct"] and base["rows"] == 3 * (1 + 4)
    assert base["argmax_agree"] == base["rows"] and not base["rows_over"]
    # STATE: what the slots hold at the end against the reference's.
    assert base["state_err_mean"] <= base["state_err_max"] < 1e-5
    by_name = {f["fault"]: f for f in faults}
    assert by_name["bf16-state"]["state_err_mean"] > 10 * out["state_tol"]
    assert [f["fault"] for f in faults] == FAULTS[1::2]
    assert not any(f["correct"] for f in faults)
    assert all(f["max_abs_logit_diff"] > 25 * LOGIT_TOL for f in faults)
    assert base["cell_check_passes"] and out["cell_logit_tol"] == 1e-3
    assert not by_name["float8"]["cell_check_passes"]
