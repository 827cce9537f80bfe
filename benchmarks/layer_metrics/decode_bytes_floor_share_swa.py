"""Over the pure decode steps dispatched ahead (``_phases``' own: no
admission, no stall, occupancy > 0; ``ahead`` 1, so that ``wall_s +
between_s`` is the step's period and not a turn that also waited for a
dispatch): the median of the seconds the chip's HBM needs for the bytes ANY
program computing the step's tokens must read, over the step's period.

``decode_bytes_floor_share.mla``'s sibling for a model that keeps K and V in
window rings beside whole-length pages.  The bytes are the family's
``decode_floor_bytes``: every weight a step multiplies with once (attention
with its gate, the dense FFN, the shared experts, routers, the head),
``experts_hit`` routed experts, and ``kv_rows_live`` rows of K and V.  Both
counts are the decode program's own, on the step record: ``experts_hit``
summed over the routed layers, ``kv_rows_live`` the rows a query could see,
summed over layers and slots (``len + 1``, on a window layer at most the
window).  The bandwidth is ``peaks.json``'s for the device.  What the
program actually reads is more (every slot's whole table and whole ring:
``kv_rows_read``, the gather's copy), which is what the share shows: its
distance from ``moe_stream_roofline.moe`` is the dead gather (ROADMAP S4b)
and the host's share of the period.  It cannot pass 100%.  The period is the
LOOP's, from the step records' host clock, so the metric's layer is the
engine loop and not the compiled program.  A family without
``decode_floor_bytes``, records without the counters (a program that
predates them, a model without window layers), or a device with no peak on
record: None."""

from ..arith import load_peaks, median
from ..spec import family
from ._phases import records


def read(ctx):
    fam = family(ctx["model"])
    if ctx["device"]["platform"] != "tpu" \
            or not hasattr(fam, "decode_floor_bytes"):
        return None
    pure = [r for r in records(ctx) or ()
            if r["stall_s"] == 0 and r["admitted"] == 0 and r["occupancy"]
            and r.get("ahead") and "kv_rows_live" in r
            and "experts_hit" in r and r["wall_s"] + r["between_s"] > 0]
    if not pure:
        return None
    peak = load_peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * median([
        fam.decode_floor_bytes(ctx["model"], r["experts_hit"],
                               r["kv_rows_live"]) / peak
        / (r["wall_s"] + r["between_s"]) for r in pure])
