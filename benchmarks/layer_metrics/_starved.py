"""Readers of the engine's starvation account (PR 38): ``starved_s`` and
``starved`` on each step record (the seconds of ``wall_s + between_s`` in
which the chip had no work of the engine's while the loop had some to give,
by loop phase), ``starved_s`` on each ``first_tokens`` entry (what of them
that admission caused), and ``traced`` (1 on a record closed while a
profiler session was open); PERF.md §3 has the table of keys.  The two
readers of the loop's period and of ``ahead`` sit here too: they are what
S3 and S5(d) are judged by beside the account.  A run against a program
whose records lack the account (the parent of the PR that added it) reads
nothing: every function here then returns None and the harness leaves the
metric out of the line."""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from ..arith import median
from ._common import idle_share
from ._phases import records

#: Entries under which a median of admissions is the order they came in.
FLOOR = 20


def accounted(ctx: Dict[str, Any]) -> Optional[List[Dict[str, Any]]]:
    """The window's step records, if every one carries the account."""
    steps = records(ctx)
    if steps is None or any("starved_s" not in r for r in steps):
        return None
    return steps


def _period(r: Dict[str, Any]) -> float:
    return r["wall_s"] + r["between_s"]


def device_starved_share(ctx):
    steps = accounted(ctx) or ()
    loop = sum(_period(r) for r in steps)
    return 100.0 * sum(r["starved_s"] for r in steps) / loop if loop else None


def admission_drain_ms(ctx):
    drains = [e.get("starved_s") for r in accounted(ctx) or ()
              for e in r["first_tokens"]]
    if len(drains) < FLOOR or None in drains:
        return None
    return 1e3 * median(drains)


def device_idle_unaccounted_share(ctx):
    """What the chip idled in the traced seconds and the loop did not
    count: launch latency after a dispatch, the copy and the wake after a
    result.  Under -1 the account counts seconds the chip worked."""
    idle = idle_share(ctx)
    traced = [r for r in accounted(ctx) or () if r.get("traced")]
    if idle is None or not traced:
        return None
    counted = sum(r["starved_s"] + r["idle_s"] for r in traced)
    return idle - 100.0 * counted / ctx["trace"]["window_s"]


def decode_period_ms(ctx):
    pure = [_period(r) for r in accounted(ctx) or ()
            if r["stall_s"] == 0 and r["admitted"] == 0 and r["occupancy"]
            and r.get("ahead")]
    return 1e3 * median(pure) if pure else None


def ahead_share(ctx):
    decode = [r["ahead"] for r in accounted(ctx) or ()
              if r["occupancy"] and "ahead" in r]
    return 100.0 * sum(decode) / len(decode) if decode else None
