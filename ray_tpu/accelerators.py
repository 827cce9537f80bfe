"""TPU accelerator support: chip autodetect, visibility isolation, pod-slice
resources.

Role-equivalent to the reference's pluggable accelerator managers
(reference: python/ray/_private/accelerators/accelerator.py,
tpu.py:71 TPUAcceleratorManager) — re-designed for this framework:

- **Autodetect** (`num_chips`): counts ``/dev/accel*`` then ``/dev/vfio/<n>``
  device files (reference: tpu.py:97-117).  ``RT_TPU_CHIPS`` overrides for
  tests and for operators who want to advertise fewer chips than the host has.
- **Pod-slice resources** (`node_resources`): a host that knows its pod type
  (``TPU_ACCELERATOR_TYPE`` env, GKE-style) advertises ``TPU-<version>``
  (e.g. ``TPU-V5E``) alongside the ``TPU`` chip count, and worker 0 of a pod
  advertises the ``TPU-<pod_type>-head`` marker resource so exactly one
  framework task can claim slice leadership (reference: tpu.py:198-314).
  GCE metadata-server probing is gated behind ``RT_TPU_GCE_METADATA=1``
  because this build targets zero-egress environments.
- **Visibility isolation** (`visibility_env`): a task that requests
  ``{"TPU": n}`` with n < host chips gets ``TPU_VISIBLE_CHIPS`` plus the
  chip/host-bounds variables that make libtpu carve out a sub-host topology
  (reference: tpu.py:155-196; the 1-chip and 2-chip bounds come from the
  jax#14977 recipe).  n == all chips keeps the host's own bounds.
- **Chip hand-back** (`wait_for_chips`): a chip's device file opens for one
  holder at a time, and the host hands it back some seconds after the last
  holder died.  A granted worker waits for each of its device files to open
  (bounded) before JAX is imported, so that libtpu does not find one busy.
- **Worker environment** (`worker_env`): what every spawner hands a worker
  process — pinned to the CPU backend until a chip grant, and carrying the
  host's libtpu configuration so that a granted worker starts without a
  metadata server.
- **Peaks** (`peak_flops`): the one table of per-chip peak FLOP/s, keyed by
  ``device_kind``; an unknown device is an error.
- **Compile cache** (`enable_compile_cache`): where a process that compiles
  for the chip keeps JAX's persistent compilation cache.

The head's scheduler owns the per-node chip-ID pool (scheduler.py
``allocate_tpu_chips``); the worker applies the env right before running the
task's function, i.e. before user code first imports jax.
"""

from __future__ import annotations

import errno
import glob
import os
import re
import sys
import time
from typing import Callable, Dict, List, Mapping, Optional

TPU_VALID_CHIP_OPTIONS = (1, 2, 4, 8)

#: Versions whose devices expose 2 cores per chip (affects pod host math).
_MULTI_CORE_VERSIONS = {"v2", "v3", "v4"}

_POD_TYPE_RE = re.compile(r"^v\d+[a-zA-Z]*-\d+$")


def num_chips() -> int:
    """Number of TPU chips attached to this host (0 when none)."""
    override = os.environ.get("RT_TPU_CHIPS")
    if override is not None:
        try:
            return max(0, int(override))
        except ValueError:
            return 0
    n = len(glob.glob("/dev/accel*"))
    if n:
        return n
    try:
        return sum(1 for e in os.listdir("/dev/vfio") if e.isdigit())
    except (FileNotFoundError, NotADirectoryError, PermissionError):
        return 0


def is_valid_pod_type(pod_type: str) -> bool:
    """``v<generation>-<chips_or_cores>``, e.g. ``v5e-8`` / ``v4-16``."""
    return bool(_POD_TYPE_RE.match(pod_type))


def pod_type() -> Optional[str]:
    """The pod/slice type this host belongs to, if known."""
    t = os.environ.get("TPU_ACCELERATOR_TYPE", "")
    if not t and os.environ.get("RT_TPU_GCE_METADATA") == "1":
        t = _gce_metadata("accelerator-type") or ""
    return t if t and is_valid_pod_type(t) else None


def tpu_name() -> Optional[str]:
    name = os.environ.get("TPU_NAME")
    if not name and os.environ.get("RT_TPU_GCE_METADATA") == "1":
        name = _gce_metadata("instance-id")
    return name or None


def worker_id() -> Optional[int]:
    wid = os.environ.get("TPU_WORKER_ID")
    if not wid and os.environ.get("RT_TPU_GCE_METADATA") == "1":
        wid = _gce_metadata("agent-worker-number")
    try:
        return int(wid) if wid else None
    except ValueError:
        return None


def _gce_metadata(key: str) -> Optional[str]:
    """GCE VM metadata (requires network egress — opt-in only)."""
    import urllib.request

    url = f"http://metadata.google.internal/computeMetadata/v1/instance/attributes/{key}"
    try:
        req = urllib.request.Request(url, headers={"Metadata-Flavor": "Google"})
        with urllib.request.urlopen(req, timeout=2) as resp:
            if resp.status == 200:
                return resp.read().decode()
    except Exception:
        pass
    return None


def pod_worker_count(pod: str) -> int:
    """Hosts in a slice of the given pod type (v2-v4 count cores, 8/host;
    later generations count chips, 4/host)."""
    version, _, count = pod.partition("-")
    per_host = 8 if version in _MULTI_CORE_VERSIONS else 4
    return max(1, int(count) // per_host)


def accelerator_type(pod: Optional[str] = None) -> Optional[str]:
    """Version marker resource, e.g. ``TPU-V5E`` (reference: tpu.py:296)."""
    pod = pod or pod_type()
    if not pod:
        return None
    return "TPU-" + pod.split("-")[0].upper()


def validate_request(quantity: float) -> Optional[str]:
    """None when ``quantity`` is a supported per-task chip count, else an
    error message.  Fractional requests time-share one chip and are allowed."""
    if 0 < quantity < 1:
        return None
    if quantity in TPU_VALID_CHIP_OPTIONS:
        return None
    return (
        f"requested TPU={quantity}, but only {TPU_VALID_CHIP_OPTIONS} (or a "
        "fraction < 1) map to valid per-host chip topologies"
    )


def node_resources() -> Dict[str, float]:
    """Resources a node daemon should auto-advertise for its TPUs."""
    n = num_chips()
    if n == 0:
        return {}
    res: Dict[str, float] = {"TPU": float(n)}
    pod = pod_type()
    acc = accelerator_type(pod)
    if acc:
        res[acc] = float(n)
    if pod and (worker_id() or 0) == 0:
        res[f"TPU-{pod}-head"] = 1.0
    return res


def node_labels() -> Dict[str, str]:
    """Topology labels for affinity scheduling (slice name + host index)."""
    labels: Dict[str, str] = {}
    pod = pod_type()
    if pod:
        labels["tpu-pod-type"] = pod
    name = tpu_name()
    if name:
        labels["tpu-name"] = name
    wid = worker_id()
    if wid is not None:
        labels["tpu-worker-id"] = str(wid)
    return labels


def visibility_env(chip_ids: List[int], host_chips: Optional[int] = None) -> Dict[str, str]:
    """Env vars granting a process exactly ``chip_ids``.

    Empty-string values mean "unset this variable" (the worker applies them
    with ``os.environ.pop``).  Granting every chip on the host clears the
    per-process view and leaves the host's bounds as they are.
    """
    if host_chips is None:
        host_chips = num_chips()
    n = len(chip_ids)
    if n == 0 or n == host_chips:
        # The whole host: its own bounds (inherited through `worker_env`, or
        # libtpu's defaults) already describe it.  Unsetting them makes
        # libtpu ask the metadata server instead, which a host without a
        # network cannot answer.
        return {"TPU_VISIBLE_CHIPS": ""}
    env = {"TPU_VISIBLE_CHIPS": ",".join(str(c) for c in sorted(chip_ids))}
    if n == 1:
        env["TPU_CHIPS_PER_HOST_BOUNDS"] = "1,1,1"
        env["TPU_HOST_BOUNDS"] = "1,1,1"
    elif n == 2:
        env["TPU_CHIPS_PER_HOST_BOUNDS"] = "1,2,1"
        env["TPU_HOST_BOUNDS"] = "1,1,1"
    # 4-chip grants on an 8-chip host inherit default bounds: there is no
    # single sub-host topology that covers both v5e (2x4) and v6e layouts,
    # so only TPU_VISIBLE_CHIPS narrows the view.
    return env


def apply_visibility(chip_ids: List[int], host_chips: Optional[int] = None) -> None:
    """Apply `visibility_env` to this process.  Must run before the first
    ``import jax`` to take effect (reference applies the same env dance at
    task start: tpu.py:155 set_current_process_visible_accelerator_ids).

    A grant pins JAX to the TPU and to nothing after it: if libtpu cannot
    start, JAX raises instead of computing on the CPU.  A process whose JAX
    already came up on another platform cannot be re-pointed, so it raises
    here, before user code runs."""
    for k, v in visibility_env(chip_ids, host_chips).items():
        if v == "":
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    if not chip_ids:
        return
    # The worker was spawned with JAX_PLATFORMS=cpu so it could not steal
    # the host's chips; a task granted chips flips to the TPU.
    os.environ["JAX_PLATFORMS"] = "tpu"
    jax = sys.modules.get("jax")
    backend = jax.default_backend() if jax is not None else "tpu"
    if backend != "tpu":
        raise RuntimeError(
            f"granted TPU chips {sorted(chip_ids)}, but JAX was imported in "
            f"this process before the grant and runs on {backend!r}; chip "
            f"grants need a fresh worker")


#: How long a granted worker waits for a chip's device file to open.
CHIP_WAIT_S = 60.0


def chip_device_paths(chip_ids: List[int]) -> List[str]:
    """The device file of each granted chip: ``/dev/accel<n>``, or the n-th
    numbered entry of ``/dev/vfio`` (as `num_chips` counts them).  A chip
    this node has no such file for is left out."""
    if glob.glob("/dev/accel*"):
        paths = [f"/dev/accel{n}" for n in chip_ids]
        return [p for p in paths if os.path.exists(p)]
    try:
        groups = sorted((e for e in os.listdir("/dev/vfio") if e.isdigit()),
                        key=int)
    except (FileNotFoundError, NotADirectoryError, PermissionError):
        return []
    return [f"/dev/vfio/{groups[n]}" for n in chip_ids if n < len(groups)]


def _holder(path: str) -> str:
    """Who has ``path`` open, as far as ``/proc`` lets this process see."""
    found = []
    for fd in glob.glob("/proc/[0-9]*/fd/*"):
        try:
            if os.readlink(fd) != path:
                continue
            pid = fd.split("/")[2]
            with open(f"/proc/{pid}/comm") as f:
                found.append(f"pid {pid} ({f.read().strip()})")
        except OSError:
            continue
    return ", ".join(sorted(set(found))) or "no holder visible in /proc"


def wait_for_chips(paths: List[str], timeout_s: float = CHIP_WAIT_S, *,
                   opener: Optional[Callable[[str], int]] = None,
                   sleep: Callable[[float], None] = time.sleep) -> float:
    """Wait until each device file in ``paths`` can be opened, and close it
    again: one open and close a chip where the chips are free.  A file that
    is still busy (``EBUSY``: its last holder's death has not reached the
    host's driver yet) is polled for ``timeout_s`` seconds in all, then a
    ``RuntimeError`` names it and its holder.  Any other error is libtpu's
    to report in its own words.  Returns the seconds waited."""
    if opener is None:
        def opener(path: str) -> int:
            return os.open(path, os.O_RDWR | os.O_CLOEXEC)
    t0, delay = time.monotonic(), 0.05
    for path in paths:
        while True:
            try:
                os.close(opener(path))
                break
            except OSError as e:
                if e.errno != errno.EBUSY:
                    break
                waited = time.monotonic() - t0
                if waited >= timeout_s:
                    raise RuntimeError(
                        f"TPU device {path} is still busy {waited:.0f} s "
                        f"after it was granted to this worker; held by "
                        f"{_holder(path)}") from e
            sleep(delay)
            delay = min(1.0, 2 * delay)
    return time.monotonic() - t0


#: Variables that carve chips out for ONE process.  A worker never inherits
#: the spawner's; a chip grant sets its own (`visibility_env`).  Every other
#: ``TPU_*`` variable describes the HOST to libtpu (accelerator type,
#: topology, worker id and hostnames, ``TPU_SKIP_MDS_QUERY``) and must reach
#: the worker: without them libtpu queries the metadata server, and on a
#: host with no network that blocked a worker for over 200 s and gave it no
#: device (measured on the one-chip v5e machine).
_PER_PROCESS_TPU_ENV = (
    "TPU_VISIBLE_CHIPS", "TPU_VISIBLE_DEVICES", "TPU_PROCESS_BOUNDS",
    "TPU_PROCESS_ADDRESSES", "TPU_PROCESS_PORT", "CLOUD_TPU_TASK_ID",
)


def worker_env(base: Optional[Mapping[str, str]] = None) -> Dict[str, str]:
    """Environment for a process this runtime spawns (worker, zygote, node
    daemon, external head), from ``base`` (default: this process's own).

    - ``JAX_PLATFORMS=cpu`` whatever the spawner's own value: a worker only
      reaches a chip through a grant (`apply_visibility`), never because
      the driver's environment says ``tpu``.
    - per-process chip isolation is dropped, host-level libtpu
      configuration is kept (see ``_PER_PROCESS_TPU_ENV``);
    - ``JAX_COMPILATION_CACHE_DIR`` and everything else pass through;
    - the package's parent directory leads ``PYTHONPATH`` so the worker can
      import ray_tpu regardless of the spawner's cwd."""
    env = dict(os.environ if base is None else base)
    for k in _PER_PROCESS_TPU_ENV:
        env.pop(k, None)
    pkg_parent = _checkout_dir()
    env["PYTHONPATH"] = (
        pkg_parent + os.pathsep + env["PYTHONPATH"]
        if env.get("PYTHONPATH") else pkg_parent
    )
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _checkout_dir() -> str:
    """The directory that holds the ``ray_tpu`` package."""
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def compile_cache_dir() -> str:
    """Where the processes of this checkout keep JAX's persistent compile
    cache: ``JAX_COMPILATION_CACHE_DIR`` where it is set, otherwise
    ``<checkout>/.jax_cache``.  The path is part of the cache key, so it is
    never a temporary, per-pid or timed name."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _checkout_dir(), ".jax_cache")


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache at `compile_cache_dir`;
    call in every process that compiles for the chip, before it compiles.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself, and no
    directory is set in code."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax = sys.modules.get("jax")
    if jax is None:
        # Not imported yet: JAX reads the variable at import (and this
        # process's children inherit it).
        os.environ["JAX_COMPILATION_CACHE_DIR"] = compile_cache_dir()
    else:
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())


#: Peak dense bf16 FLOP/s of ONE chip, keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud TPU documentation, system architecture pages
#: ("TPU v5e": 197 TFLOP/s; "TPU v5p": 459; "TPU v4": 275; "TPU v3": 123;
#: "TPU v2": 45; "TPU v6e": 918).  Only "TPU v5 lite" has been seen on a
#: machine (one-chip v5e); the other kinds are JAX's names for those chips.
PEAK_BF16_FLOPS = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5": 459e12,       # v5p
    "TPU v6 lite": 918e12,  # v6e
}


def peak_flops(device_kind: str) -> float:
    """Peak bf16 FLOP/s of one chip of ``device_kind``.  A device that is
    not in the table is an error, not a default: a utilization against an
    invented peak is worse than none."""
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s on record for device_kind {device_kind!r}; add "
            f"it to ray_tpu.accelerators.PEAK_BF16_FLOPS with its source "
            f"(known: {sorted(PEAK_BF16_FLOPS)})") from None
