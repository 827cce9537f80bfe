"""The benchmark's deployment: a thin subclass of the program's
``LLMServer``, pickled to the replica like any user's deployment.  Inside
the replica it has the configuration's family (``spec.family``) register
the cell's configuration with the engine, runs ``LLMServer.__init__``
unchanged under the name the family gives back, and carries the benchmark's
replica-side methods: the window's marks, the device trace (only the
process that holds the chip can trace it), and the comparison with the
family's plain reference.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional

from ray_tpu.serve.engine import LLMServer

PHASE_TAG = "bench-phase="


def phase_error(phase: str, exc: BaseException) -> RuntimeError:
    """An exception whose text names the phase it was raised in, in a form
    that survives pickling between processes."""
    if PHASE_TAG in str(exc):
        return exc  # already tagged further in
    return RuntimeError(f"{PHASE_TAG}{phase}: {type(exc).__name__}: {exc}")


def device_report() -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


def compile_cache_report() -> Dict[str, Any]:
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    n = size = 0
    if d and os.path.isdir(d):
        for name in os.listdir(d):
            p = os.path.join(d, name)
            if os.path.isfile(p):
                n, size = n + 1, size + os.path.getsize(p)
    return {"dir": d, "entries": n, "bytes": size}


def trace_options():
    """Host TraceMe events on (our annotations), the Python call tracer
    off: it writes tens of thousands of events a second."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


class BenchLLMServer(LLMServer):
    def __init__(self, model: Dict[str, Any], engine: Dict[str, Any],
                 seed: int, platform: str, fail_phase: str = ""):
        try:
            if fail_phase == "libtpu_start":
                raise RuntimeError("forced failure")
            import jax

            devs = jax.devices()
            if devs[0].platform != platform:
                raise RuntimeError(
                    f"the replica runs on {devs[0].platform!r}, the cell "
                    f"needs {platform!r}: no fallback")
        except Exception as e:  # noqa: BLE001 — re-raised with its phase
            raise phase_error("libtpu_start", e) from e
        self._devices_ready_wall = time.time()
        try:
            if fail_phase == "compile":
                raise RuntimeError("forced failure")
            from ray_tpu.serve import engine as eng

            from .spec import family

            ec = eng.EngineConfig(**engine)
            name = family(model).register(
                model, max_seq=ec.pages_per_seq * ec.page_size)
            super().__init__(model=name, engine=engine,
                             seed=seed % (2 ** 31 - 1), warmup=True)
        except Exception as e:  # noqa: BLE001
            raise phase_error("compile", e) from e
        self._bench_model = model
        self._trace_dir: Optional[str] = None
        self._trace_t0 = 0.0

    # -------------------------------------------------------------- report

    def bench_info(self) -> Dict[str, Any]:
        return {
            "stats": self.stats(), "device": device_report(),
            "compile_cache": compile_cache_report(),
            "devices_ready_wall": self._devices_ready_wall,
        }

    def mark(self) -> Dict[str, Any]:
        """The engine's step count, how many first tokens it has timed, and
        the wall clock: called at each edge of the window."""
        return {"step": self.engine.step_count, "wall": time.time(),
                # Private: the per-request timestamps are not in stats()
                # (PERF.md, list for the tracing issue).
                "ttft_seen": len(self.engine._ttft_recent)}

    def engine_ttfts(self, first: int, last: int) -> List[float]:
        """The engine's own submit-to-first-token seconds of the requests
        whose first token came between two marks."""
        return list(self.engine._ttft_recent)[first:last]

    def flush_step_records(self) -> Dict[str, Any]:
        """Ship the buffered step records to the head now (the background
        flush would, within its cadence) and say how many were dropped."""
        from ray_tpu.util import steprec

        flushed = steprec.flush_steps()
        return {"flushed": flushed, "dropped": steprec.dropped_total()}

    # --------------------------------------------------------------- trace

    def trace_start(self, trace_dir: str) -> float:
        import jax

        eng = self.engine
        if not getattr(eng, "_bench_annotated", False):
            # Host annotations around the engine's admission prefill and
            # its step (decode program plus the token readback), from the
            # benchmark's side: spans inside the program are a later PR.
            def wrap(fn, name):
                def inner(*a, **kw):
                    with jax.profiler.TraceAnnotation("bench:" + name):
                        return fn(*a, **kw)
                return inner

            eng._prefill = wrap(eng._prefill, "prefill")
            eng._run_step = wrap(eng._run_step, "engine_step")
            eng._bench_annotated = True
        jax.profiler.start_trace(trace_dir, profiler_options=trace_options())
        self._trace_dir, self._trace_t0 = trace_dir, time.perf_counter()
        return time.time()

    def trace_stop(self) -> float:
        """Stops the trace; returns the traced window's seconds by this
        process's clock."""
        import jax

        self._trace_window_s = time.perf_counter() - self._trace_t0
        jax.profiler.stop_trace()
        return self._trace_window_s

    def trace_reduce(self) -> Dict[str, Any]:
        from . import trace_reduce

        return trace_reduce.reduce_trace_dir(self._trace_dir,
                                             self._trace_window_s)

    # ------------------------------------------------------------- compare

    def reference_gaps(self, samples: List[Dict[str, Any]]
                       ) -> List[List[float]]:
        """For each {"prompt", "output"}: per generated token, the plain
        reference's best logit minus its logit of the emitted token."""
        from .reference import teacher_forced_gaps
        from .spec import family

        ref = family(self._bench_model).reference(
            self._bench_model, self.engine.params)
        return [teacher_forced_gaps(ref, s["prompt"], s["output"])
                for s in samples]
