"""Of the rows the recurrent layers' chunk form went over in the window's
prefill calls, the share that held no token: ``scan_rows_padded / (scan_rows
+ scan_rows_padded)``, summed over the window's ``first_tokens[]`` entries.
A prefill call runs a whole bucket, and a scan over positions pays a step for
each of its rows, real or not (a padded row leaves the state as it is and
costs what a real one costs), so this is the share of the scan's time the
bucket ladder wastes: 0 where every prompt fills its bucket, towards 50%
where each is just over half of one.  ``scan_rows`` and ``scan_rows_padded``
are on an entry since the engine records them for a model with recurrent
layers; a program without such layers, or one that predates the fields, or a
window with no prefill, reads nothing.  Counted on the host from lengths: no
device is asked, so a rehearsal on the CPU reads it too."""

from ._phases import records


def read(ctx):
    entries = [e for r in records(ctx) or () for e in r["first_tokens"]
               if "scan_rows" in e and "scan_rows_padded" in e]
    rows = sum(e["scan_rows"] + e["scan_rows_padded"] for e in entries)
    if not rows:
        return None
    return 100.0 * sum(e["scan_rows_padded"] for e in entries) / rows
