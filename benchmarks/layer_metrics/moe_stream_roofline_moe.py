"""Least time the chip could take for the grouped products of the DECODE
steps in the traced window (the family's ``routed_ffn_ops_bytes``: the
larger of operations over the bf16 peak and bytes over the HBM peak of
``peaks.json``) over the device time of the kernel that streams the hit
experts' weights there, ``mosaic:ragged-dot-stream*`` in the trace
(``ray_tpu/ops/grouped_ffn.py``: ``pallas_call(name="ragged-dot-stream")``,
one call a routed layer and decode step).

What ``moe_decode_roofline.moe`` cannot say: that reader finds every
grouped product by ``mosaic:ragged-dot`` (XLA's own kernel in the prefills,
this one in the decode steps) and divides the work of both by the seconds
of both; this is the decode step's own share, apart from the prefills'.

Both sides are the traced window's.  The seconds come from the device
trace alone and hold no host time.  The work comes from the decode
program's routing counters on the step records closed while the profiler
ran (``traced`` 1): ``expert_pairs`` and ``experts_hit`` of the record, not
of its ``first_tokens`` (a prefill's products are XLA's kernel).  A step or
two at the trace's edges ran before their record closed or after; of some
two hundred.  The kernel reads each hit expert's three matrices once and is
bound by those bytes (a pair's 12 million operations against an expert's
12 MB), so the share is the share of the HBM peak at which the decode step
reads its experts, and cannot pass 100%.

No trace, no such call in it (the parent of the PR that added the kernel;
every backend but a TPU), a dense model, or records without ``traced`` or
the counters: None."""

from ..arith import load_peaks, roofline
from ..spec import family
from ..trace_reduce import ops_time
from ._phases import records

KERNEL = "mosaic:ragged-dot-stream"


def read(ctx):
    tr, fam = ctx.get("trace") or {}, family(ctx["model"])
    if not tr.get("n_devices") or ctx["device"]["platform"] != "tpu" \
            or not hasattr(fam, "routed_ffn_ops_bytes"):
        return None
    seconds = ops_time(tr, KERNEL)
    steps = [r for r in records(ctx) or ()
             if r.get("traced") and "experts_hit" in r]
    if not seconds or not steps:
        return None
    need = fam.routed_ffn_ops_bytes(
        ctx["model"], pairs=sum(r["expert_pairs"] for r in steps),
        experts_hit=sum(r["experts_hit"] for r in steps))
    peaks = load_peaks(ctx["device"]["kind"])
    return 100.0 * roofline(need["ops"], need["bytes"], seconds,
                            peaks["bf16_flops"],
                            peaks["hbm_bytes_per_s"])["share"]
