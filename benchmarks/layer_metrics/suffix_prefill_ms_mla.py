"""Median ``prefill_s`` over the window's prefills that started from cached
pages (``first_tokens[]`` entries with ``cached`` > 0): the loop's seconds
for a suffix prefill, which here is a request's own part (32-1783 tokens)
through the suffix program over 128 cached latent pages, the wait for its
first token included.  None under 20 such entries (a median of a handful is
the order they came in), or where the records predate the entries."""

from ..arith import median
from ._phases import records

FLOOR = 20


def read(ctx):
    hits = [e["prefill_s"] for r in records(ctx) or ()
            for e in r["first_tokens"] if e.get("cached", 0) > 0]
    return 1e3 * median(hits) if len(hits) >= FLOOR else None
