"""Readers of the engine loop's own time account: the phase seconds and the
``first_tokens`` entries the engine puts on each step record (PERF.md §3
has the table of keys).  A run against a program whose records lack them
(the parent of the PR that added them) reads nothing: every function here
then returns None and the harness leaves the metric out of the line."""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

from ..arith import median, percentile

PHASE_KEYS = ("between_s", "idle_s", "upload_s", "dispatch_s", "readback_s",
              "emit_s", "first_tokens")


def records(ctx: Dict[str, Any]) -> Optional[List[Dict[str, Any]]]:
    """The window's step records, if every one carries the account."""
    if ctx["kind"] == "train":
        return None
    steps = ctx.get("steps") or []
    if not steps or any(k not in r for r in steps for k in PHASE_KEYS):
        return None
    return steps


def pure_decode_median_ms(ctx: Dict[str, Any],
                          seconds: Callable[[Dict[str, Any]], float]
                          ) -> Optional[float]:
    """Median of ``seconds(record)`` over the pure decode steps, which are
    ``_common.decode_step_ms``'s: no admission, no stall, occupancy > 0."""
    pure = [seconds(r) for r in records(ctx) or ()
            if r["stall_s"] == 0 and r["admitted"] == 0 and r["occupancy"]]
    return 1e3 * median(pure) if pure else None


def first_tokens(ctx: Dict[str, Any], key: str, floor: int
                 ) -> Optional[List[float]]:
    """``key`` of every request prefilled in the window; None under
    ``floor`` of them (a percentile needs samples beyond it)."""
    values = [e[key] for r in records(ctx) or () for e in r["first_tokens"]]
    return values if len(values) >= floor else None


def decode_host_ms(ctx):
    return pure_decode_median_ms(
        ctx, lambda r: r["between_s"] + r["upload_s"] + r["dispatch_s"]
        + r["emit_s"])


def decode_device_wait_ms(ctx):
    return pure_decode_median_ms(ctx, lambda r: r["readback_s"])


def loop_accounted_share(ctx):
    steps = records(ctx)
    if steps is None or not ctx.get("seconds"):
        return None
    return 100.0 * sum(r["wall_s"] + r["between_s"] + r["idle_s"]
                       for r in steps) / ctx["seconds"]


def admit_queue_wait_p90_ms(ctx):
    waits = first_tokens(ctx, "queue_s", floor=50)
    return 1e3 * percentile(waits, 90.0) if waits else None


def prefill_ms(ctx):
    times = first_tokens(ctx, "prefill_s", floor=20)
    return 1e3 * median(times) if times else None
