"""Shared by the benchmark's tests: where the repo is, and how a rehearsal
run of ``benchmarks/run.py`` is started and read.  No JAX, no topology."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_bench(*args, root=ROOT, timeout=300, env=None):
    """Run ``benchmarks/run.py`` from ``root``; returns (returncode,
    [parsed JSON lines of stdout], stderr)."""
    full = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    full.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"), *args],
        cwd=root, env=full, capture_output=True, text=True, timeout=timeout)
    lines = []
    for line in proc.stdout.splitlines():
        if line.startswith("{"):
            lines.append(json.loads(line))
    return proc.returncode, lines, proc.stderr


def state_of(pid):
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0]
    except (FileNotFoundError, ProcessLookupError):
        return None


def assert_nothing_left(lines):
    """No process, shm segment or session directory of the run remains."""
    session = next(l["session"] for l in lines
                   if l.get("phase") == "cluster_start")
    pids = next(l["pids"] for l in lines if l.get("phase") == "teardown")
    assert pids, "the run started no process?"
    assert [p for p in pids if state_of(p) not in (None, "Z")] == []
    left = [p for p in (f"/dev/shm/rtpu-pool-{session}",
                        f"/tmp/ray_tpu_logs/{session}",
                        f"/tmp/ray_tpu_fncache/{session}")
            if os.path.exists(p)]
    left += [n for n in os.listdir("/dev/shm")
             if n.startswith(f"rtpu-{session}")]
    assert left == []


@pytest.fixture
def repo_copy(tmp_path):
    """A temporary copy of what the benchmark owns, with the program
    linked in: files can be added there, and none that exist is edited."""
    root = tmp_path / "copy"
    root.mkdir()
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(ROOT, "ray_tpu"), root / "ray_tpu")
    return root
