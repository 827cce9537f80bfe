"""Train goodput accounting: step time, tokens/sec, compile time, and MFU.

Role-equivalent to the telemetry TorchTitan treats as table stakes for LLM
training (arXiv:2410.06511 — per-step wall time, throughput in tokens/sec,
and model-flops utilization against the accelerator's peak), surfaced here
as ``ray_tpu_train_*`` gauges (flowing to the head's metrics plane and the
dashboard's history sparklines) and merged into ``train.session.report``
metrics.

MFU = (model FLOPs per step) / (step seconds) / (peak FLOP/s of the
devices the step ran on).  FLOPs per step come from XLA's own cost model
(``jax.jit(fn).lower(*args).cost_analysis()["flops"]``) when available,
else from the classic dense-transformer estimate ``6 * params * tokens``
(``transformer_flops``), else from an explicit number the caller provides.
The peak comes from the one table in ``ray_tpu.accelerators``, keyed by
``device_kind``.  On the CPU backend there is no peak and MFU is not
reported (``None``): a utilization against an invented peak is not one.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, Optional

def device_peak_flops(device: Optional[Any] = None) -> Optional[float]:
    """Peak bf16 FLOP/s of one device (``jax.devices()[0]`` when omitted):
    None on the CPU backend, the ``ray_tpu.accelerators`` table's entry on
    an accelerator.  An accelerator the table does not know raises."""
    from ..accelerators import peak_flops

    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    return peak_flops(device.device_kind)


def flops_per_step(fn, *args, **kwargs) -> Optional[float]:
    """Model FLOPs of one call of ``fn(*args, **kwargs)`` via XLA's cost
    analysis (reference technique: ``jax.jit(...).lower().cost_analysis()``;
    TorchTitan derives the same number analytically).  Returns None when the
    backend provides no cost model — callers fall back to
    ``transformer_flops`` or an explicit value."""
    try:
        import jax

        jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
        lowered = jitted.lower(*args, **kwargs)
        try:
            analysis = lowered.cost_analysis()  # no compile needed
        except Exception:
            analysis = lowered.compile().cost_analysis()
        if analysis:
            f = analysis.get("flops")
            if isinstance(f, (int, float)) and f > 0:
                return float(f)
    except Exception:
        pass
    return None


def transformer_flops(num_params: float, tokens: float) -> float:
    """Static fallback: the standard dense-transformer training estimate of
    ~6 FLOPs per parameter per token (fwd 2 + bwd 4)."""
    return 6.0 * float(num_params) * float(tokens)


class TrainTelemetry:
    """Per-process goodput recorder.  One instance per train worker (the
    session owns one); gauges flow to the head via the metrics flusher.

    ``flops_per_step`` and ``peak_flops`` may be set up front (or any time)
    so subsequent steps compute MFU; ``tokens_per_step`` likewise enables
    tokens/sec without passing tokens on every call."""

    def __init__(self, flops_per_step: Optional[float] = None,
                 peak_flops: Optional[float] = None,
                 num_devices: Optional[int] = None,
                 tokens_per_step: Optional[float] = None,
                 rank: Optional[int] = None):
        from ..util.metrics import get_gauge

        self.flops_per_step = flops_per_step
        self._peak_flops = peak_flops
        self._num_devices = num_devices
        self.tokens_per_step = tokens_per_step
        # Rank tag keeps each train worker's gauges a distinct series —
        # the head merges same-(name, tags) gauges last-writer-wins, so
        # untagged multi-worker gauges would flip between ranks.
        self._tags = {"rank": str(rank)} if rank is not None else None
        self.last: Dict[str, float] = {}
        self._g_step = get_gauge(
            "ray_tpu_train_step_seconds", "Wall time of the last train step",
            tag_keys=("rank",))
        self._g_tps = get_gauge(
            "ray_tpu_train_tokens_per_sec",
            "Training throughput of the last step", tag_keys=("rank",))
        self._g_mfu = get_gauge(
            "ray_tpu_train_mfu",
            "Model-flops utilization of the last step (0..1)",
            tag_keys=("rank",))
        self._g_compile = get_gauge(
            "ray_tpu_train_compile_seconds",
            "Cumulative compile/tracing seconds observed by this worker",
            tag_keys=("rank",))
        self._g_collectives = get_gauge(
            "ray_tpu_train_step_collectives",
            "Collective ops in the compiled train step, by kind",
            tag_keys=("rank", "kind"))
        self._compile_total = 0.0

    # -- configuration ---------------------------------------------------------

    def set_flops_per_step(self, flops: Optional[float]) -> None:
        self.flops_per_step = flops

    def peak_flops_total(self) -> Optional[float]:
        """Aggregate peak FLOP/s across the devices this step runs on, or
        None where the device has no peak on record (the CPU backend)."""
        peak = self._peak_flops
        if peak is None:
            peak = device_peak_flops()
        if peak is None:
            return None
        n = self._num_devices
        if n is None:
            import jax

            n = jax.local_device_count()
        return peak * max(1, n)

    # -- recording -------------------------------------------------------------

    def record_compile(self, seconds: float) -> None:
        self._compile_total += max(0.0, seconds)
        self._g_compile.set(self._compile_total, tags=self._tags)
        self.last["compile_time_s"] = seconds

    def record_compiled(self, compiled) -> Dict[str, int]:
        """Count the collectives of the step this worker runs, from what it
        compiled (``jitted.lower(...).compile()``), by kind: all-gather,
        all-reduce, reduce-scatter, all-to-all, collective-permute.  A loop
        hands its compiled step over once; an all-to-all in a dense
        fsdp x tp step is a layout lost (``parallel/sharding.py``)."""
        from ..parallel.sharding import count_collectives

        counts = count_collectives(compiled.as_text())
        for kind, n in counts.items():
            self._g_collectives.set(n, tags={**(self._tags or {}),
                                             "kind": kind})
        return counts

    def record_step(self, step_time_s: float,
                    tokens: Optional[float] = None,
                    flops: Optional[float] = None,
                    compile_time_s: Optional[float] = None
                    ) -> Dict[str, float]:
        """Record one finished step; returns the derived metrics
        ({step_time_s, tokens_per_sec?, mfu?, compile_time_s?}).  ``mfu``
        is there only where the device's peak is known."""
        out: Dict[str, float] = {"step_time_s": float(step_time_s)}
        self._g_step.set(step_time_s, tags=self._tags)
        if compile_time_s is not None:
            self.record_compile(compile_time_s)
            out["compile_time_s"] = compile_time_s
        tokens = tokens if tokens is not None else self.tokens_per_step
        if tokens and step_time_s > 0:
            out["tokens_per_sec"] = tokens / step_time_s
            self._g_tps.set(out["tokens_per_sec"], tags=self._tags)
        flops = flops if flops is not None else self.flops_per_step
        peak = self.peak_flops_total() if flops and step_time_s > 0 else None
        if peak:
            mfu = flops / step_time_s / peak
            out["mfu"] = mfu
            self._g_mfu.set(mfu, tags=self._tags)
        self.last = dict(out)
        return out

    @contextlib.contextmanager
    def step(self, tokens: Optional[float] = None,
             flops: Optional[float] = None):
        """Time a train step: ``with telemetry.step(tokens=...): ...``."""
        t0 = time.perf_counter()
        try:
            yield self
        finally:
            self.record_step(time.perf_counter() - t0,
                             tokens=tokens, flops=flops)
