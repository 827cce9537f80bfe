"""Device profiling: XLA/TPU traces through jax.profiler.

Role-equivalent to the reference's profiling hooks (reference:
python/ray/_private/profiling.py + the nsight runtime-env plugin at
_private/runtime_env/nsight.py for CUDA) — on TPU the profiler of record
is XLA's own (jax.profiler → TensorBoard/XProf: device timelines, HLO
cost analysis, MXU utilization), so this module wraps it with the
framework's conventions instead of shipping a vendor plugin:

    from ray_tpu.util import profiling

    with profiling.device_trace("/tmp/tb"):       # whole-section trace
        for step in range(10):
            with profiling.step_annotation(step): # XLA StepMarker
                state, _ = train_step(state, batch)

View with ``tensorboard --logdir /tmp/tb`` (the trace lands under
``plugins/profile``).  Works on CPU too (host tracing only), so tests and
dry runs exercise the same code path as TPU runs.
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from typing import Iterator, Optional

logger = logging.getLogger(__name__)

#: jax.profiler is process-global: one capture at a time.  Guarded here so
#: a second ``device_trace`` fails TYPED instead of raising deep inside
#: start_trace and leaving the first capture wedged.
_active_lock = threading.Lock()
_active_dir: Optional[str] = None


class ProfilerBusyError(RuntimeError):
    """A device trace is already being captured in this process."""


def active_trace_dir() -> Optional[str]:
    """Log dir of the capture in flight, or None when idle."""
    return _active_dir


@contextlib.contextmanager
def device_trace(log_dir: str, *,
                 host_tracer_level: Optional[int] = None) -> Iterator[None]:
    """Capture a jax.profiler trace of the enclosed block into ``log_dir``.

    Raises :class:`ProfilerBusyError` when a capture is already active in
    this process (the underlying profiler is a process-global singleton).
    A failing ``stop_trace`` is logged, never raised: it must not mask the
    block's real exception, and the active flag is cleared either way so
    the next capture isn't wedged behind a corpse.
    """
    global _active_dir
    import jax

    with _active_lock:
        if _active_dir is not None:
            raise ProfilerBusyError(
                f"device trace already capturing into {_active_dir!r}")
        _active_dir = log_dir
    kwargs = {}
    if host_tracer_level is not None:
        # ProfileOptions takes no constructor arguments (jax 0.9).
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = host_tracer_level
        kwargs["profiler_options"] = options
    try:
        jax.profiler.start_trace(log_dir, **kwargs)
        try:
            yield
        finally:
            try:
                jax.profiler.stop_trace()
            except Exception:
                logger.warning("jax.profiler.stop_trace failed for %s",
                               log_dir, exc_info=True)
    finally:
        with _active_lock:
            _active_dir = None


@contextlib.contextmanager
def step_annotation(step: int, name: str = "train") -> Iterator[None]:
    """Mark one train step so XProf groups device ops per step
    (jax.profiler.StepTraceAnnotation)."""
    import jax

    with jax.profiler.StepTraceAnnotation(name, step_num=step):
        yield


_TraceAnnotation = None  # jax.profiler.TraceAnnotation, imported once


class annotation:
    """Named region on the profiler's host timeline (TraceAnnotation) that
    also adds its elapsed ``time.perf_counter()`` seconds to
    ``account[name]`` when an account (a dict) is given.  One object does
    both, so a phase begins and ends at the same instants in a step record
    and in a device trace; ``t0`` is the ``perf_counter`` at entry and
    ``t1`` the one at exit.  With no profiler session open the annotation
    is a flag test; the whole context costs about a microsecond."""

    __slots__ = ("name", "account", "_trace", "t0", "t1")

    def __init__(self, name: str, account: Optional[dict] = None):
        global _TraceAnnotation
        if _TraceAnnotation is None:
            import jax

            _TraceAnnotation = jax.profiler.TraceAnnotation
        self.name = name
        self.account = account
        self._trace = _TraceAnnotation(name)

    def __enter__(self) -> "annotation":
        self._trace.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        self._trace.__exit__(*exc)
        account = self.account
        if account is not None:
            account[self.name] = (account.get(self.name, 0.0)
                                  + self.t1 - self.t0)
