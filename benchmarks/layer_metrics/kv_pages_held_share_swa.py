"""Mean over the window's step records of ``(pages_window + pages_global)
/ pages_uniform``: the pages live sequences hold (a layer's pages each: of
the window layers' rings as much as a sequence's length fills, and the
whole-length layers' tables), over what one pool in which every layer kept every page would hold
for the same sequences.  The three are on the step record of a model with
window layers only; records with no live sequence (``pages_uniform`` 0) are
left out.  A program that predates them reads nothing."""

from ._phases import records


def read(ctx):
    shares = [(r["pages_window"] + r["pages_global"]) / r["pages_uniform"]
              for r in records(ctx) or () if r.get("pages_uniform")]
    return 100.0 * sum(shares) / len(shares) if shares else None
