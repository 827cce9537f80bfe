"""Device-memory accounting: where did the HBM go, by named pool.

Role-equivalent to the reference's per-node GPU/object-store memory
panels (reference: dashboard memory view + `ray memory`), TPU-native:
the raw totals come from ``jax.local_devices()[i].memory_stats()`` (XLA's
allocator counters — absent on the CPU backend) and ``jax.live_arrays()``
(present on every backend), and the *attribution* comes from a
process-local registry of named byte-counting callables that the owners
of big device allocations register themselves:

    devmem.register_pool("kv_pool", lambda: k.nbytes + v.nbytes)

``snapshot()`` joins both: per-device allocator stats, live-array bytes,
per-pool bytes, the remainder as ``other`` — so the pools always sum to
the live total — plus compile observability (per-program jit trace
counts from ``models.paged.trace_count`` and the wall clock of the calls
that triggered them, recorded by the engine via :func:`record_compile`).

Workers ship ``maybe_snapshot()`` to the head on the metrics cadence
(``devmem_report``); the head joins the latest per-worker snapshot into
``list_state(kind="devmem")`` for ``ray_tpu top`` / ``status`` and the
dashboard.  Import of jax is never forced: a worker that hasn't touched
jax reports nothing.
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Any, Callable, Dict, Optional

_lock = threading.Lock()
_pools: Dict[str, Callable[[], int]] = {}
_compiles: Dict[str, Dict[str, float]] = {}  # program -> {count, wall_s}
_m_pool_bytes = None


def register_pool(name: str, nbytes_fn: Callable[[], int]) -> None:
    """Attribute device bytes to ``name``.  ``nbytes_fn`` is called at
    snapshot time and must be cheap and host-only (no device sync); a
    raising fn reports 0 for that pool rather than failing the snapshot.
    Re-registering replaces (an engine rebuild supersedes its pools)."""
    with _lock:
        _pools[name] = nbytes_fn


def unregister_pool(name: str) -> None:
    with _lock:
        _pools.pop(name, None)


def record_compile(program: str, wall_s: float) -> None:
    """Note one jit compile: ``wall_s`` is the wall clock of the call
    that triggered the trace (the engine compares ``trace_count`` before
    and after each program call, so the measured wall IS the user-visible
    compile stall)."""
    with _lock:
        row = _compiles.setdefault(program, {"count": 0, "wall_s": 0.0})
        row["count"] += 1
        row["wall_s"] += float(wall_s)


def compile_stats() -> Dict[str, Dict[str, float]]:
    with _lock:
        return {k: dict(v) for k, v in _compiles.items()}


#: jax.monitoring events totalled per process, by the key they are
#: reported under.  The three compile phases arrive as time spans and are
#: totalled as the union of their intervals: tracing one jitted function
#: traces the jitted functions it calls, each with a span of its own, and
#: summing those would count the inner seconds twice.  BACKEND_COMPILE
#: wraps the persistent cache's lookup, so it fires once an executable
#: whether compiled or read back: ``compiles`` counts every program this
#: process built.
_JAX_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
}
_JAX_DURATIONS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
}
_JAX_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
_jax_totals: Optional[Dict[str, float]] = None


def _add_span(counted: list, start: float, end: float) -> float:
    """Seconds of [start, end] that no interval in ``counted`` (disjoint,
    ascending; spans arrive in the order they end) covers yet; the list
    then holds their union."""
    new = end - start
    while counted and counted[-1][0] >= start:  # nested in this one
        s, e = counted.pop()
        new -= e - s
    if counted and counted[-1][1] > start:  # overlaps (another thread)
        new -= counted[-1][1] - start
        start = counted[-1][1]
    if end > start:
        counted.append((start, end))
        del counted[:-1024]
    return max(new, 0.0)


def compile_totals() -> Dict[str, float]:
    """Running totals of what JAX itself reports about tracing, lowering,
    compiling and the persistent cache in this process (the first call
    registers the ``jax.monitoring`` listeners).  Difference two calls to
    account a phase: ``InferenceEngine.warmup`` does so a program, the
    step record does so a step (``compiles``)."""
    global _jax_totals
    if _jax_totals is None:
        with _lock:
            if _jax_totals is None:
                import jax.monitoring

                totals = dict.fromkeys(
                    ["compiles", *_JAX_SPANS.values(),
                     *_JAX_DURATIONS.values(), *_JAX_EVENTS.values()], 0)
                counted: Dict[str, list] = {
                    k: [] for k in _JAX_SPANS.values()}

                def on_span(event: str, start: float, end: float,
                            **_kw) -> None:
                    key = _JAX_SPANS.get(event)
                    if key is not None:
                        totals[key] += _add_span(counted[key], start, end)
                        if key == "compile_s":
                            totals["compiles"] += 1

                def on_duration(event: str, duration: float, **_kw) -> None:
                    key = _JAX_DURATIONS.get(event)
                    if key is not None:
                        totals[key] += duration

                def on_event(event: str, **_kw) -> None:
                    key = _JAX_EVENTS.get(event)
                    if key is not None:
                        totals[key] += 1

                jax.monitoring.register_event_time_span_listener(on_span)
                jax.monitoring.register_event_duration_secs_listener(
                    on_duration)
                jax.monitoring.register_event_listener(on_event)
                _jax_totals = totals
    return dict(_jax_totals)


def compile_count() -> int:
    """Programs built so far (0 before :func:`compile_totals` first ran):
    one dict read, for the decode loop."""
    return _jax_totals["compiles"] if _jax_totals is not None else 0


def _device_stats() -> list:
    """Per-device allocator counters; [] on backends without them (CPU)."""
    import jax

    out = []
    for i, dev in enumerate(jax.local_devices()):
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        out.append({
            "device": i,
            "platform": getattr(dev, "platform", "unknown"),
            "bytes_in_use": int(stats.get("bytes_in_use", 0)),
            "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
            "bytes_limit": int(stats.get("bytes_limit", 0)),
        })
    return out


def snapshot() -> Dict[str, Any]:
    """One attribution snapshot.  Invariant the tests hold: the ``pools``
    values (including ``other``) sum to ``live_bytes`` exactly."""
    import jax

    live = 0
    for arr in jax.live_arrays():
        try:
            if not arr.is_deleted():
                live += int(arr.nbytes)
        except Exception:
            continue
    with _lock:
        fns = dict(_pools)
    pools: Dict[str, int] = {}
    for name, fn in fns.items():
        try:
            pools[name] = max(0, int(fn()))
        except Exception:
            pools[name] = 0
    named = sum(pools.values())
    # Attribution is bounded by what is actually live: a stale pool fn
    # (engine torn down mid-snapshot) must not drive "other" negative.
    if named > live:
        scale = live / named if named else 0.0
        pools = {k: int(v * scale) for k, v in pools.items()}
        named = sum(pools.values())
    pools["other"] = live - named
    snap = {
        "time": time.time(),
        "live_bytes": live,
        "pools": pools,
        "devices": _device_stats(),
        "compiles": compile_stats(),
    }
    try:
        # The jitguard registry is the superset view: the paged programs
        # plus any learner/kernel that joined (models.paged.trace_counts
        # is an alias over the same counters).
        from ..devtools import jitguard as _jitguard

        snap["trace_counts"] = _jitguard.counts()
    except Exception:
        snap["trace_counts"] = {}
    _set_gauges(pools)
    return snap


def maybe_snapshot() -> Optional[Dict[str, Any]]:
    """A snapshot IF this process has already imported jax (never force
    the import — that would drag the XLA runtime into every worker)."""
    if "jax" not in sys.modules:
        return None
    try:
        return snapshot()
    except Exception:
        return None


def _set_gauges(pools: Dict[str, int]) -> None:
    global _m_pool_bytes
    try:
        from .metrics import get_gauge

        if _m_pool_bytes is None:
            _m_pool_bytes = get_gauge(
                "ray_tpu_devmem_pool_bytes",
                "Live device bytes attributed to each named pool",
                tag_keys=("pool",))
        for name, nbytes in pools.items():
            _m_pool_bytes.set(nbytes, tags={"pool": name})
    except Exception:
        pass  # metrics must never fail the snapshot
