"""Test fixtures.

JAX runs on a virtual 8-device CPU mesh in tests (the multi-chip sharding
path is validated without TPU hardware, mirroring the reference's
single-machine multi-node test strategy — reference:
python/ray/tests/conftest.py ray_start_regular / cluster_utils.Cluster).
"""

import os

# Before jax is imported anywhere in the test process, and inherited by every
# worker the tests spawn: the CPU backend with 8 virtual devices.  Forced
# (not setdefault): the machine's own environment may point JAX at a chip.
os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()
# Deterministic TPU autodetect: the machine under test may expose real
# /dev/accel* chips; tests that want chips mock them via RT_TPU_CHIPS.
os.environ.setdefault("RT_TPU_CHIPS", "0")
# Headless suicide deadline, shortened for tests: workers orphaned by
# head-kill tests (test_head_crash, test_head_kill9, workflow restarts)
# redial the dead address until this deadline — at the 45 s production
# default they'd linger across later tests and eat the tier-1 budget on
# small CI boxes.  Tests that assert specific deadlines override it.
os.environ.setdefault("RT_HEAD_RECONNECT_DEADLINE_S", "8")

import pytest  # noqa: E402


@pytest.fixture
def rt_start():
    """A fresh single-node cluster per test."""
    import ray_tpu

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def rt_shared():
    """A shared cluster for cheap tests within one module."""
    import ray_tpu

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    yield ray_tpu
    ray_tpu.shutdown()
