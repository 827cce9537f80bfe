"""Least time the chip could take for the grouped products of the routed
FFN in the traced window (the family's ``routed_ffn_ops_bytes``: the larger
of operations over the bf16 peak and bytes over the HBM peak of
``peaks.json``) over the device time of XLA's grouped-matmul kernel there,
``mosaic:ragged-dot*`` in the trace.

Both sides are the traced window's.  The seconds come from the device
trace alone and hold no host time.  The work comes from the program's
routing counters on the step records that ended in that window: a decode
step's ``expert_pairs`` and ``experts_hit``, and those of every prefill on
its ``first_tokens`` entry, because a prefill's products carry the same
kernel name and cannot be told from a decode step's.  At 16 slots the
decode steps are ~9 in 10 of the calls and both kinds are bound by the
expert weights' bytes, so this is the share of the HBM peak at which the
experts are read.

The harness starts the profiler ``min(1, seconds / 10)`` s into the window
and does not pass on when the profiler was running, so the records taken
may lie a step or two beside the traced seconds; under a closed loop the
work of a window of that length changes by about a hundredth with such a
shift.  No trace, no ``ragged-dot`` call in it, a dense model, or records
without the counters: None."""

from ..arith import load_peaks, roofline
from ..spec import family
from ..trace_reduce import ops_time
from ._phases import records

KERNEL = "mosaic:ragged-dot"


def read(ctx):
    tr, fam = ctx.get("trace") or {}, family(ctx["model"])
    if not tr.get("n_devices") or ctx["device"]["platform"] != "tpu" \
            or not hasattr(fam, "routed_ffn_ops_bytes"):
        return None
    seconds = ops_time(tr, KERNEL)
    start = ctx["window_wall"] + min(1.0, 0.1 * ctx["seconds"])
    routed = [c for r in records(ctx) or ()
              if start <= r["t"] < start + tr["window_s"]
              for c in (r, *r["first_tokens"]) if "experts_hit" in c]
    if not seconds or not routed:
        return None
    need = fam.routed_ffn_ops_bytes(
        ctx["model"], pairs=sum(c["expert_pairs"] for c in routed),
        experts_hit=sum(c["experts_hit"] for c in routed))
    peaks = load_peaks(ctx["device"]["kind"])
    return 100.0 * roofline(need["ops"], need["bytes"], seconds,
                            peaks["bf16_flops"],
                            peaks["hbm_bytes_per_s"])["share"]
