"""A run that fails says where, still tears down, prints no result and
exits non-zero; a machine without a TPU is never measured."""

import os

import pytest

from bench_testlib import (ROOT, assert_nothing_left, repo_copy,  # noqa: F401
                           run_bench)

T1 = "mistral-7b-v0.3-L4.train-1chip"
MIX = "internlm2-1.8b.serve-mixed"
PHASES = ("cluster_start", "worker_grant", "libtpu_start", "compile",
          "warmup", "measure", "compare", "teardown")


def _has_result(lines):
    return any("correct" in l for l in lines)


@pytest.mark.parametrize("phase", PHASES)
def test_a_forced_failure_names_its_phase(phase):
    rc, lines, err = run_bench(
        "--workload", T1, "--seed", "1", "--seconds", "1", "--trace", "0",
        "--rehearse", "--fail-phase", phase)
    assert rc == 1
    assert f"BENCH-FAILED phase={phase} " in err, err[-2000:]
    assert "forced failure" in err
    assert not _has_result(lines)
    if phase != "cluster_start":
        assert_nothing_left(lines)


@pytest.mark.parametrize("phase", ["libtpu_start", "compile", "warmup"])
def test_a_failure_inside_the_replica_names_its_phase(phase):
    rc, lines, err = run_bench(
        "--workload", MIX, "--seed", "1", "--seconds", "1", "--trace", "0",
        "--rehearse", "--fail-phase", phase)
    assert rc == 1
    assert f"BENCH-FAILED phase={phase} " in err, err[-2000:]
    assert not _has_result(lines)
    assert_nothing_left(lines)


def test_without_a_tpu_there_is_no_result():
    """The measuring path (no --rehearse) on this CPU-only machine."""
    rc, lines, err = run_bench("--workload", T1, "--seed", "1", "--seconds",
                               "1", "--trace", "0",
                               env={"BENCH_RUN": "whatever"})
    assert rc == 2 and not lines
    assert "needs 1 TPU chip" in err and "not measured" in err


def test_without_the_program_there_is_no_result(repo_copy):  # noqa: F811
    """A directory that holds only BENCHMARK.json and the benchmark."""
    os.unlink(repo_copy / "ray_tpu")
    rc, lines, err = run_bench("--workload", T1, "--seed", "1", "--seconds",
                               "1", "--trace", "0", root=str(repo_copy))
    assert rc != 0 and not lines


def test_an_unknown_workload_is_refused():
    rc, lines, err = run_bench("--workload", "no-such.cell", "--rehearse")
    assert rc != 0 and not lines and "no workload" in err
