"""TPU accelerator support: autodetect, pod resources, chip-ID isolation.

Mirrors the reference's accelerator-manager tests
(reference: python/ray/tests/accelerators/test_tpu.py) with the /dev scan
mocked via RT_TPU_CHIPS.
"""

import errno
import os

import pytest

from ray_tpu import accelerators
from ray_tpu.core.ids import NodeID
from ray_tpu.core.scheduler import ClusterScheduler


@pytest.fixture
def tpu_host(monkeypatch):
    """Pretend this host has a 4-chip v5e slice, worker 0."""
    monkeypatch.setenv("RT_TPU_CHIPS", "4")
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5e-8")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    monkeypatch.setenv("TPU_NAME", "my-tpu")
    yield


class TestDetection:
    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("RT_TPU_CHIPS", "8")
        assert accelerators.num_chips() == 8

    def test_no_chips(self, monkeypatch):
        monkeypatch.setenv("RT_TPU_CHIPS", "0")
        assert accelerators.num_chips() == 0
        assert accelerators.node_resources() == {}

    def test_pod_type_validation(self):
        assert accelerators.is_valid_pod_type("v5e-8")
        assert accelerators.is_valid_pod_type("v4-16")
        assert accelerators.is_valid_pod_type("v5litepod-16")
        assert not accelerators.is_valid_pod_type("tpu-v4")
        assert not accelerators.is_valid_pod_type("v4")

    def test_node_resources_with_pod(self, tpu_host):
        res = accelerators.node_resources()
        assert res["TPU"] == 4.0
        assert res["TPU-V5E"] == 4.0
        assert res["TPU-v5e-8-head"] == 1.0

    def test_non_head_worker_has_no_head_marker(self, tpu_host, monkeypatch):
        monkeypatch.setenv("TPU_WORKER_ID", "1")
        res = accelerators.node_resources()
        assert "TPU-v5e-8-head" not in res

    def test_labels(self, tpu_host):
        labels = accelerators.node_labels()
        assert labels == {
            "tpu-pod-type": "v5e-8",
            "tpu-name": "my-tpu",
            "tpu-worker-id": "0",
        }

    def test_pod_worker_count(self):
        assert accelerators.pod_worker_count("v4-16") == 2   # cores, 8/host
        assert accelerators.pod_worker_count("v5e-8") == 2   # chips, 4/host
        assert accelerators.pod_worker_count("v5e-4") == 1

    def test_validate_request(self):
        assert accelerators.validate_request(1) is None
        assert accelerators.validate_request(8) is None
        assert accelerators.validate_request(0.5) is None
        assert accelerators.validate_request(3) is not None


class TestVisibilityEnv:
    def test_single_chip(self, tpu_host):
        env = accelerators.visibility_env([2], host_chips=4)
        assert env["TPU_VISIBLE_CHIPS"] == "2"
        assert env["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,1,1"
        assert env["TPU_HOST_BOUNDS"] == "1,1,1"

    def test_two_chips(self, tpu_host):
        env = accelerators.visibility_env([1, 3], host_chips=4)
        assert env["TPU_VISIBLE_CHIPS"] == "1,3"
        assert env["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,2,1"

    def test_all_chips_keeps_host_bounds(self, tpu_host):
        # A whole-host grant clears only the per-process view: unsetting the
        # host's bounds sends libtpu to the metadata server.
        env = accelerators.visibility_env([0, 1, 2, 3], host_chips=4)
        assert env == {"TPU_VISIBLE_CHIPS": ""}

    def test_apply_pins_tpu_and_refuses_a_cpu_jax(self, tpu_host, monkeypatch):
        # Register every var apply_visibility mutates so monkeypatch
        # restores them — a leaked JAX_PLATFORMS=tpu would poison every
        # worker spawned by later tests in this process.
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        monkeypatch.setenv("TPU_VISIBLE_CHIPS", "stale")
        monkeypatch.setenv("TPU_HOST_BOUNDS", "1,1,1")
        monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
        # This process's jax already runs on the CPU (conftest): a grant
        # cannot re-point it, so it raises instead of computing there.
        with pytest.raises(RuntimeError, match="before the grant"):
            accelerators.apply_visibility([0, 1, 2, 3], host_chips=4)
        assert "TPU_VISIBLE_CHIPS" not in os.environ
        assert os.environ["TPU_CHIPS_PER_HOST_BOUNDS"] == "2,2,1"
        assert os.environ["JAX_PLATFORMS"] == "tpu"  # and nothing after it
        with pytest.raises(RuntimeError, match="before the grant"):
            accelerators.apply_visibility([1], host_chips=4)
        assert os.environ["TPU_VISIBLE_CHIPS"] == "1"
        assert os.environ["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,1,1"
        assert os.environ["JAX_PLATFORMS"] == "tpu"

    def test_no_grant_leaves_platform_alone(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        accelerators.apply_visibility([], host_chips=4)
        assert os.environ["JAX_PLATFORMS"] == "cpu"


class TestWorkerEnv:
    #: What the one-chip v5e machine's environment held.
    HOST = {
        "JAX_PLATFORMS": "tpu,cpu",
        "JAX_COMPILATION_CACHE_DIR": "/somewhere/jax",
        "TPU_ACCELERATOR_TYPE": "v5litepod-4",
        "TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1",
        "TPU_HOST_BOUNDS": "1,1,1",
        "TPU_SKIP_MDS_QUERY": "true",
        "TPU_WORKER_HOSTNAMES": "localhost",
        "TPU_WORKER_ID": "0",
    }

    def test_keeps_host_config_drops_process_view(self):
        base = dict(self.HOST, TPU_VISIBLE_CHIPS="2", TPU_PROCESS_BOUNDS="1,1,1")
        env = accelerators.worker_env(base)
        for k, v in self.HOST.items():
            if k != "JAX_PLATFORMS":
                assert env[k] == v, k
        assert "TPU_VISIBLE_CHIPS" not in env
        assert "TPU_PROCESS_BOUNDS" not in env

    def test_worker_is_pinned_to_cpu_whatever_the_driver_says(self):
        assert accelerators.worker_env(self.HOST)["JAX_PLATFORMS"] == "cpu"
        assert accelerators.worker_env({})["JAX_PLATFORMS"] == "cpu"

    def test_pythonpath_leads_with_the_checkout(self):
        root = os.path.dirname(os.path.dirname(accelerators.__file__))
        assert accelerators.worker_env({})["PYTHONPATH"] == root
        env = accelerators.worker_env({"PYTHONPATH": "/x"})
        assert env["PYTHONPATH"] == root + os.pathsep + "/x"

    def test_zygote_forwards_the_cache_dir(self):
        """A zygote-forked worker gets only RT_/JAX_/PYTHON* of the spawn
        env: the compile-cache variable is among them."""
        from ray_tpu.core import zygote

        class Fake:
            def alive(self):
                return True

            def spawn(self, env, log=None):
                self.env = env
                return 1

        z = Fake()
        zygote.spawn_with_fallback(
            z, accelerators.worker_env(self.HOST), "/tmp/x.log")
        assert z.env["JAX_COMPILATION_CACHE_DIR"] == "/somewhere/jax"
        assert z.env["JAX_PLATFORMS"] == "cpu"


class TestCompileCache:
    def test_operator_directory_wins(self, monkeypatch):
        import jax

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/operator/dir")
        before = jax.config.jax_compilation_cache_dir
        accelerators.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == before
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/operator/dir"
        assert accelerators.compile_cache_dir() == "/operator/dir"

    def test_default_is_fixed_under_the_checkout(self, monkeypatch):
        import jax

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            accelerators.enable_compile_cache()
            root = os.path.dirname(os.path.dirname(accelerators.__file__))
            path = os.path.join(root, ".jax_cache")
            assert accelerators.compile_cache_dir() == path
            assert jax.config.jax_compilation_cache_dir == path
            assert "JAX_COMPILATION_CACHE_DIR" not in os.environ
        finally:
            jax.config.update("jax_compilation_cache_dir", before)

    def test_before_jax_import_it_is_the_variable(self, monkeypatch):
        import sys

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.delitem(sys.modules, "jax")
        path = accelerators.compile_cache_dir()
        accelerators.enable_compile_cache()
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == path
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")


class TestChipHandBack:
    """`wait_for_chips`: a granted worker outwaits the host's late hand-back
    of a chip's device file (EBUSY) before libtpu opens it."""

    @staticmethod
    def _opener(busy):
        """Opens /dev/null; each path is EBUSY ``busy[path]`` times first."""
        calls = []

        def opener(path):
            calls.append(path)
            if busy.get(path, 0) > 0:
                busy[path] -= 1
                raise OSError(errno.EBUSY, "Device or resource busy", path)
            return os.open(os.devnull, os.O_RDONLY)

        return opener, calls

    def test_a_device_busy_twice_and_then_free_is_waited_for(self):
        opener, calls = self._opener({"/dev/vfio/1": 2})
        naps = []
        accelerators.wait_for_chips(
            ["/dev/vfio/0", "/dev/vfio/1", "/dev/vfio/2"], opener=opener,
            sleep=naps.append)
        assert calls == ["/dev/vfio/0"] + ["/dev/vfio/1"] * 3 + ["/dev/vfio/2"]
        assert len(naps) == 2 and all(0 < n <= 1.0 for n in naps)

    def test_it_gives_up_with_the_devices_name_and_its_holder(self):
        opener, calls = self._opener({"/dev/vfio/3": 10 ** 6})
        with pytest.raises(RuntimeError,
                           match=r"/dev/vfio/3 is still busy .* held by "):
            accelerators.wait_for_chips(["/dev/vfio/3"], timeout_s=0.2,
                                        opener=opener)
        assert len(calls) >= 2

    def test_a_node_without_device_files_waits_for_nothing(self):
        # This sandbox has neither /dev/accel* nor /dev/vfio: no path, no
        # open.  Any error but EBUSY is left for libtpu to report.
        assert accelerators.chip_device_paths([0, 1, 2, 3]) == [] or \
            os.path.exists("/dev/vfio") or os.path.exists("/dev/accel0")

        def denied(path):
            raise PermissionError(errno.EACCES, "Permission denied", path)

        assert accelerators.wait_for_chips(["/dev/vfio/0"],
                                           opener=denied) < 1.0


class TestPeakTable:
    def test_v5e_by_device_kind(self):
        assert accelerators.peak_flops("TPU v5 lite") == 197e12

    @pytest.mark.parametrize("kind", ["cpu", "TPU v9", "", "tpu v5 lite"])
    def test_unknown_device_raises(self, kind):
        with pytest.raises(ValueError, match="no peak FLOP/s on record"):
            accelerators.peak_flops(kind)


class TestChipPool:
    def _sched(self, n_tpu=4):
        s = ClusterScheduler()
        nid = NodeID.from_random()
        s.add_node(nid, {"CPU": 4, "TPU": float(n_tpu)})
        return s, nid

    def test_allocate_and_free(self):
        s, nid = self._sched()
        chips = s.allocate_tpu_chips(nid, 2)
        assert chips == [0, 1]
        assert s.allocate_tpu_chips(nid, 2) == [2, 3]
        assert s.allocate_tpu_chips(nid, 1) is None  # pool exhausted
        s.free_tpu_chips(nid, chips)
        assert s.allocate_tpu_chips(nid, 2) == [0, 1]

    def test_double_free_is_idempotent(self):
        s, nid = self._sched()
        chips = s.allocate_tpu_chips(nid, 2)
        s.free_tpu_chips(nid, chips)
        s.free_tpu_chips(nid, chips)
        assert len(s.nodes[nid].tpu_free) == 4

    def test_free_on_dead_node_is_noop(self):
        s, nid = self._sched()
        chips = s.allocate_tpu_chips(nid, 2)
        s.remove_node(nid)
        s.free_tpu_chips(nid, chips)  # must not raise


class TestEndToEnd:
    def test_task_sees_visible_chips(self, monkeypatch):
        """A task requesting {"TPU": 1} runs with TPU_VISIBLE_CHIPS set to
        its granted chip, and the grant returns to the pool afterwards."""
        monkeypatch.setenv("RT_TPU_CHIPS", "2")
        import ray_tpu

        if ray_tpu.is_initialized():
            ray_tpu.shutdown()
        ray_tpu.init(num_cpus=4)
        try:
            @ray_tpu.remote(resources={"TPU": 1}, num_cpus=0)
            def which_chips():
                return os.environ.get("TPU_VISIBLE_CHIPS")

            seen = ray_tpu.get([which_chips.remote() for _ in range(2)])
            assert all(v in ("0", "1") for v in seen)

            # Pool drains and refills: run more rounds than chips.
            seen2 = ray_tpu.get([which_chips.remote() for _ in range(4)])
            assert all(v in ("0", "1") for v in seen2)
        finally:
            ray_tpu.shutdown()

    def test_actor_holds_chip_until_death(self, monkeypatch):
        monkeypatch.setenv("RT_TPU_CHIPS", "1")
        import ray_tpu

        if ray_tpu.is_initialized():
            ray_tpu.shutdown()
        ray_tpu.init(num_cpus=4)
        try:
            @ray_tpu.remote(resources={"TPU": 1}, num_cpus=0)
            class ChipHolder:
                def chips(self):
                    # Full-host grant: visibility stays default, and the
                    # worker pins JAX to the TPU platform and nothing else.
                    return (os.environ.get("TPU_VISIBLE_CHIPS"),
                            os.environ.get("JAX_PLATFORMS"))

            holder = ChipHolder.remote()
            assert ray_tpu.get(holder.chips.remote()) == (None, "tpu")

            # The sole chip is held: a second TPU task must not schedule.
            @ray_tpu.remote(resources={"TPU": 1}, num_cpus=0)
            def probe():
                return True

            ready, not_ready = ray_tpu.wait([probe.remote()], timeout=0.5)
            assert not ready

            ray_tpu.kill(holder)
            # After the actor dies the chip frees and the probe runs.
            assert ray_tpu.get(not_ready[0], timeout=20)
        finally:
            ray_tpu.shutdown()


def test_invalid_chip_request_rejected(monkeypatch):
    monkeypatch.setenv("RT_TPU_CHIPS", "8")
    import ray_tpu

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote(resources={"TPU": 3}, num_cpus=0)
        def bad():
            return 1

        with pytest.raises(ValueError, match="TPU=3"):
            bad.remote()
    finally:
        ray_tpu.shutdown()
