"""What several readers share.  A reader is ``read(ctx) -> float | None``:
``ctx`` is the run as the harness gathered it (client samples, the
window's engine step records, the train worker's report, the reduced
trace, the device, the cell's model and traffic).  None means there was
nothing to read, and the harness leaves the metric out of the line."""

from __future__ import annotations

from typing import Any, Dict, Optional

from ..arith import load_peaks, median, mfu, roofline
from ..spec import family
from ..trace_reduce import ops_time

#: The Mosaic (Pallas) calls, as trace_reduce.label names them.  The Pallas
#: calls in ops/attention.py carry no ``name=``, so forward, dQ and dK/dV
#: cannot be told apart in today's traces and are read together.
MOSAIC_NEEDLES = ("mosaic:",)


def stall_share(ctx: Dict[str, Any]) -> Optional[float]:
    steps = ctx.get("steps") or []
    wall = sum(r["wall_s"] for r in steps) if ctx["kind"] != "train" else 0
    if not wall:
        return None
    return 100.0 * sum(r["stall_s"] for r in steps) / wall


def decode_step_ms(ctx: Dict[str, Any]) -> Optional[float]:
    if ctx["kind"] == "train":
        return None
    pure = [r["wall_s"] for r in ctx.get("steps") or []
            if r["stall_s"] == 0 and r["admitted"] == 0 and r["occupancy"]]
    return 1e3 * median(pure) if pure else None


def occupancy(ctx: Dict[str, Any]) -> Optional[float]:
    if ctx["kind"] == "train":
        return None
    decode = [r["occupancy"] / r["slots"] for r in ctx.get("steps") or []
              if r["occupancy"]]
    return 100.0 * sum(decode) / len(decode) if decode else None


def idle_share(ctx: Dict[str, Any]) -> Optional[float]:
    tr = ctx.get("trace") or {}
    if not tr.get("n_devices") or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def train_tok_s(ctx: Dict[str, Any]) -> Optional[float]:
    if ctx["kind"] != "train" or not ctx["steps"]:
        return None
    return ctx["steps"] * ctx["tokens_per_step"] / ctx["elapsed_s"]


def train_mfu(ctx: Dict[str, Any]) -> Optional[float]:
    """From the median step, not the window's rate: this is read in the
    traced run, whose window also holds the profiler's start and stop."""
    if ctx["kind"] != "train" or not ctx.get("step_s") \
            or ctx["device"]["platform"] != "tpu":
        return None  # a CPU has no peak on record: no device number
    rate = ctx["tokens_per_step"] / median(ctx["step_s"])
    peaks = load_peaks(ctx["device"]["kind"])
    flops = family(ctx["model"]).train_flops_per_token(ctx["model"],
                                                       ctx["seq"])
    return 100.0 * mfu(rate, flops, ctx["device"]["count"],
                       peaks["bf16_flops"])


def flash_roofline(ctx: Dict[str, Any]) -> Optional[float]:
    tr = ctx.get("trace") or {}
    if ctx["kind"] != "train" or not tr.get("n_devices"):
        return None
    seconds = ops_time(tr, *MOSAIC_NEEDLES)
    if not seconds or ctx["device"]["platform"] != "tpu":
        return None
    need = family(ctx["model"]).train_step_kernel_ops_bytes(
        ctx["model"], ctx["batch"], ctx["seq"], ctx["tpu_custom_calls"])
    peaks = load_peaks(ctx["device"]["kind"])
    steps = tr["traced_steps"]
    return 100.0 * roofline(need["ops"] * steps, need["bytes"] * steps,
                            seconds, peaks["bf16_flops"],
                            peaks["hbm_bytes_per_s"])["share"]


def collective_exposed_share(ctx: Dict[str, Any]) -> Optional[float]:
    tr = ctx.get("trace") or {}
    if not tr.get("n_devices") or not tr.get("window_s"):
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["window_s"]
