"""Readers of who had the loop thread and of a token's way out of the
replica (PR 54): ``cpu_s``, ``wait_s``, ``proc_cpu_s`` and ``tokens_out``,
``wake_s``, ``store_s``, ``pull_s``, ``pull_waiting`` on each step record
(PERF.md §3 has the table of keys).  Every per-layer metric is read in the
``--trace 1`` run, whose loop is the profiler's while its session is open
and after it: these readers take the window's QUIET records only, and this
file states which those are, once.  A run against a program whose records
lack the keys (the parent of the PR that added them) reads nothing: every
function here then returns None and the harness leaves the metric out of
the line."""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from ..arith import median
from ._starved import _period, accounted

#: Quiet records under which nothing is read.
FLOOR = 20
KEYS = ("cpu_s", "wait_s", "proc_cpu_s", "tokens_out", "wake_s", "store_s",
        "pull_s", "pull_waiting")


def quiet(ctx: Dict[str, Any]) -> Optional[List[Dict[str, Any]]]:
    """The window's records that the profiler did not touch: no ``traced``
    key, and closed (``t``, the wall clock: one clock for every replica)
    before the window's first traced record, which is the window's first
    second (``serve_cell.py`` opens the session one second in).  A window
    with no traced record at all is quiet throughout.  None under
    ``FLOOR`` of them, and where a record lacks the keys."""
    steps = accounted(ctx)
    if steps is None or any(k not in r for r in steps for k in KEYS):
        return None
    opened = min((r["t"] for r in steps if r.get("traced")),
                 default=float("inf"))
    out = [r for r in steps if "traced" not in r and r["t"] < opened]
    return out if len(out) >= FLOOR else None


def quiet_decode(ctx: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Of the quiet records, the pure decode steps dispatched ahead:
    ``decode_period_ms.sat``'s filter."""
    return [r for r in quiet(ctx) or ()
            if r["stall_s"] == 0 and r["admitted"] == 0 and r["occupancy"]
            and r.get("ahead")]


def decode_period_ms(ctx):
    pure = [_period(r) for r in quiet_decode(ctx)]
    return 1e3 * median(pure) if pure else None


def _mean_ms(ctx, key: str) -> Optional[float]:
    """A step's MEAN, not its median: the chip machines' CPU clocks advance
    by ticks of 10 ms, so a record reads no CPU or a whole tick, and the
    median of such samples is one or the other.  Their sum is unbiased."""
    pure = quiet_decode(ctx)
    return 1e3 * sum(r[key] for r in pure) / len(pure) if pure else None


def loop_cpu_ms(ctx):
    return _mean_ms(ctx, "cpu_s")


def loop_wait_ms(ctx):
    return _mean_ms(ctx, "wait_s")


def replica_cpu_share(ctx):
    pure = quiet_decode(ctx)
    loop = sum(_period(r) for r in pure)
    return 100.0 * sum(r["proc_cpu_s"] for r in pure) / loop if loop else None


def _over_tokens_out(ctx, amount) -> Optional[float]:
    steps = quiet(ctx) or ()
    out = sum(r["tokens_out"] for r in steps)
    return sum(amount(r) for r in steps) / out if out else None


def token_exit_ms(ctx):
    exit_s = _over_tokens_out(
        ctx, lambda r: r["wake_s"] + r["store_s"] + r["pull_s"])
    return None if exit_s is None else 1e3 * exit_s


def stream_pull_waiting_share(ctx):
    share = _over_tokens_out(ctx, lambda r: r["pull_waiting"])
    return None if share is None else 100.0 * share
