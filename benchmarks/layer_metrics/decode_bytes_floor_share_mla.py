"""Over the pure decode steps dispatched ahead (``_phases``' own: no
admission, no stall, occupancy > 0; ``ahead`` 1, so that ``wall_s +
between_s`` is the step's period and not a turn that also waited for a
dispatch): the median of the seconds the chip's HBM needs for the bytes ANY
program computing the step's tokens must read, over the step's period.

The bytes are the family's ``decode_floor_bytes``: every weight a step
multiplies with once, ``experts_hit`` routed experts, and
``kv_rows_distinct`` latent rows.  Both counts are on the step record:
``experts_hit`` the decode program's own, ``kv_rows_distinct`` the engine's
(the program's ``kv_rows_live`` less the rows of pages that several live
slots share, counted once).  The bandwidth is ``peaks.json``'s for the
device.  What the program actually reads is more (every slot's whole table,
shared pages once a slot, the gather's copy), which is what the share shows;
a kernel that reads live rows only, or shared pages once, moves it without
making the count stale.  It cannot pass 100%.  The period is the LOOP's,
from the step records' host clock (4-6% of it is the host's, with the chip
idle), so the metric's layer is the engine loop and not the compiled
program: ``moe_decode_roofline.moe`` is the share that reads device seconds
alone.  A family without
``decode_floor_bytes``, records without the counters, or a device with no
peak on record: None."""

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
            and r.get("ahead") and "kv_rows_distinct" in r
            and "experts_hit" in r and r["wall_s"] + r["between_s"] > 0]
    if not pure:
        return None
    peak = load_peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * median([
        fam.decode_floor_bytes(ctx["model"], r["experts_hit"],
                               r["kv_rows_distinct"]) / peak
        / (r["wall_s"] + r["between_s"]) for r in pure])
