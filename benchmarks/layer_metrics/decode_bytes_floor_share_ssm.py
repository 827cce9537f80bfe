"""As ``decode_bytes_floor_share.kda``, for a model whose sequences keep a
selective state-space layer's state beside K/V rows and which has no routed
layer: over the pure decode steps dispatched ahead (``_phases``' own: no
admission, no stall, occupancy > 0; ``ahead`` 1, so that ``wall_s +
between_s`` is the step's period), the median of the seconds the chip's HBM
needs for the bytes ANY program computing the step's tokens must move, over
the step's period.

The bytes are the family's ``decode_floor_bytes(model, kv_rows_distinct,
occupancy)``: every weight once (the embedding among them: it is the tied
head), the ``kv_rows_distinct`` rows of K and V of the attention layers, and
the recurrent state of the ``occupancy`` live slots read once and written
once (every token rewrites all of it: the write is part of the floor).  The
record's ``state_bytes`` is what the PROGRAM moved of state (every slot's,
live or not) and is not used here, but marks a program that keeps one.  The
bandwidth is ``peaks.json``'s for the device.  It cannot pass 100%.  The
period is the loop's, from the step records' host clock, so the metric's
layer is the engine loop.  A family whose ``decode_floor_bytes`` takes
experts (a routed family's: ``...kda``, ``...mla`` and ``...swa`` read
those) or no occupancy, records without ``state_bytes`` or
``kv_rows_distinct`` (a program without recurrent layers, or the parent of
the PR that added them), or a device with no peak on record: None."""

import inspect

from ..arith import load_peaks, median
from ..spec import family
from ._phases import records


def read(ctx):
    floor = getattr(family(ctx["model"]), "decode_floor_bytes", None)
    if ctx["device"]["platform"] != "tpu" or floor is None or list(
            inspect.signature(floor).parameters)[1:] \
            != ["kv_rows_distinct", "occupancy"]:
        return None
    pure = [r for r in records(ctx) or ()
            if r["stall_s"] == 0 and r["admitted"] == 0 and r["occupancy"]
            and r.get("ahead") and "kv_rows_distinct" in r
            and "state_bytes" in r and r["wall_s"] + r["between_s"] > 0]
    if not pure:
        return None
    peak = load_peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * median([
        floor(ctx["model"], r["kv_rows_distinct"], r["occupancy"]) / peak
        / (r["wall_s"] + r["between_s"]) for r in pure])
