"""Median over the window's chunked prefills (``first_tokens[]`` entries
with ``chunks`` > 1) of ``prefill_s / chunks``: the loop's seconds a
2048-token chunk, each a call of the suffix-prefill program over what the
chunks before it cached (the last chunk may be shorter, and the entry's
seconds end with the wait for the first token).  ``chunks`` is on the entry
since the engine prefills long prompts in chunks; a program that predates
it, or a window with no chunked prefill, reads nothing."""

from ..arith import median
from ._phases import records


def read(ctx):
    per_chunk = [e["prefill_s"] / e["chunks"]
                 for r in records(ctx) or () for e in r["first_tokens"]
                 if e.get("chunks", 0) > 1]
    return 1e3 * median(per_chunk) if per_chunk else None
