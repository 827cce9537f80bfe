"""Least time the chip's HBM needs for the bytes the K/V-pair decode kernel
moves in the traced window, over the device time of that kernel there,
``mosaic:paged_decode*`` in the trace (``ray_tpu/ops/paged_decode.py``:
``pallas_call(name="paged_decode")``, one call a layer and decode step of a
model with window layers, on its whole-length layers and its rings alike).

Both sides are the traced window's, as ``moe_stream_roofline.moe`` has them.
The seconds come from the device trace alone and hold no host time.  The
bytes come from the decode program's own counter on the step records closed
while the profiler ran (``traced`` 1): ``kv_rows_read``, which where the
kernel runs is what it walks (over layers and slots, ``page x`` the pages
from the first visible position's to the last's, one page of an empty
slot), times the bytes of a token's K and V on a layer as they lie in the
pools (``2 x num_key_value_heads x head_dim`` numbers: 2048 bytes in both
cells).  A step or two at the trace's edges ran before their record closed
or after; of some three hundred.  The kernel is bound by those bytes (all
the query heads against a page's rows: 32 operations a byte at 32 heads),
so the share is the share of the HBM peak of ``peaks.json`` at which it
reads its pages, and cannot pass 100% as long as the counter counts every
page the kernel fetches and no more.  The queries and outputs (16 KB a
slot and layer beside 3 MB of pages) are left out.

No trace, no such call in it (the parent of the PR that added the kernel,
whose ``kv_rows_read`` counts a gather's whole tables; every backend but a
TPU; a latent model, whose walk is ``latent_decode``'s; a model of the one
whole-length kind), records without ``traced`` or the counter, or a device
with no peak on record: None."""

from ..arith import load_peaks
from ..trace_reduce import ops_time
from ._phases import records

KERNEL = "mosaic:paged_decode"


def row_bytes(model) -> int:
    """A token's K and V on one layer as the pools hold them."""
    return 2 * model["num_key_value_heads"] * model["head_dim"] \
        * {"bfloat16": 2, "float32": 4}[model["torch_dtype"]]


def read(ctx):
    tr = ctx.get("trace") or {}
    if not tr.get("n_devices") or ctx["device"]["platform"] != "tpu":
        return None
    seconds = ops_time(tr, KERNEL)
    rows = sum(r.get("kv_rows_read", 0) for r in records(ctx) or ()
               if r.get("traced"))
    if not seconds or not rows:
        return None
    peak = load_peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * rows * row_bytes(ctx["model"]) / peak / seconds
