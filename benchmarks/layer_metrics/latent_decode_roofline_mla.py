"""Least time the chip's HBM needs for the bytes the latent decode kernel
moves in the traced window, over the device time of that kernel there,
``mosaic:latent_decode*`` in the trace (``ray_tpu/ops/latent_decode.py``:
``pallas_call(name="latent_decode")``, one call a layer and decode step).

Both sides are the traced window's, as ``moe_decode_roofline.moe`` has them.
The seconds come from the device trace alone and hold no host time.  The
bytes come from the decode program's own counter on the step records that
ended in that window: ``kv_rows_read``, which where the kernel runs is what
it walks (over layers and slots, each slot's live pages, ``seq_lens // page
+ 1`` of them), times the width of a pool row AS IT LIES, the lane-tile
padding included (576 numbers in 640: a page is one DMA of whole rows),
times the bytes of a number.  The kernel is bound by those bytes (20 query
rows against a page: 36 operations a byte), so the share is the share of
the HBM peak of ``peaks.json`` at which it reads its pages, and cannot pass
100%.  The queries and outputs (41 KB + 33 KB a slot beside 22 MB of pages)
are left out.

The harness starts the profiler ``min(1, seconds / 10)`` s into the window
and does not pass on when the profiler was running, so the records taken
may lie a step or two beside the traced seconds; what a step reads changes
by a thousandth from one step to the next.  No trace, no such call in it
(the parent of the PR that added the kernel, whose ``kv_rows_read`` counts
a gather's whole tables; every model without a latent pool), records
without the counter, or a device with no peak on record: None."""

from ..arith import load_peaks
from ..trace_reduce import ops_time
from ._phases import records

KERNEL = "mosaic:latent_decode"
LANES = 128


def row_bytes(model) -> int:
    """A latent pool's row as ``paged.latent_row_width`` lays it out."""
    width = -(-(model["kv_lora_rank"] + model["qk_rope_head_dim"])
              // LANES) * LANES
    return width * {"bfloat16": 2, "float32": 4}[model["torch_dtype"]]


def read(ctx):
    tr, model = ctx.get("trace") or {}, ctx["model"]
    if not tr.get("n_devices") or ctx["device"]["platform"] != "tpu" \
            or "kv_lora_rank" not in model:
        return None
    seconds = ops_time(tr, KERNEL)
    start = ctx["window_wall"] + min(1.0, 0.1 * ctx["seconds"])
    rows = sum(r.get("kv_rows_read", 0) for r in records(ctx) or ()
               if start <= r["t"] < start + tr["window_s"])
    if not seconds or not rows:
        return None
    peak = load_peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * rows * row_bytes(model) / peak / seconds
