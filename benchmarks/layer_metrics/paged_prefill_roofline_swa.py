"""Least time the chip's MXU needs for the attention the K/V-pair prefill
kernel has to do in the traced window, over the device time of that kernel
there, ``mosaic:paged_prefill*`` in the trace
(``ray_tpu/ops/paged_prefill.py``: ``pallas_call(name="paged_prefill")``,
one call a layer and prefill call of a model with window layers, on its
whole-length layers and its rings alike).

Both sides are the traced window's, as ``paged_decode_roofline.swa`` has
them.  The seconds come from the device trace alone and hold no host time.
The operations come from the engine's own count on the ``first_tokens``
entries of the step records closed while the profiler ran (``traced`` 1):
``attn_pairs``, the (query, key) pairs the real rows of a prompt's calls
could see, summed over its layers and calls (a row at position ``p`` sees
``p + 1`` keys on a whole-length layer, at most the window's on a window
layer; host arithmetic from each call's first and last position), times the
operations a pair needs on every query head: one multiply-add a number of
``head_dim`` for the score and one for the value, ``4 x
num_attention_heads x head_dim``.  A prompt's calls may lie on either side
of a trace's edge; of some dozens.  With 2048 query rows against a block of
keys the kernel is bound by those operations (14k a byte of K and V it
fetches), so the share is the share of the bf16 peak of ``peaks.json`` at
which it multiplies, and cannot pass 100%: the kernel can do no fewer than
the visible pairs' (what it does more, whole blocks at the diagonal, at the
window's edge and in a bucket's padding, lowers the share).  The softmax's
exponentials, which the MXU does not do, are left out.

No trace, no such call in it (the parent of the PR that added the kernel;
every backend but a TPU; a latent model; a model of the one whole-length
kind), records without ``traced`` or entries without the count, or a device
with no peak on record: None."""

from ..arith import load_peaks
from ..trace_reduce import ops_time
from ._phases import records

KERNEL = "mosaic:paged_prefill"


def pair_ops(model) -> int:
    """The MXU's operations for one (query, key) pair on every head."""
    return 4 * model["num_attention_heads"] * model["head_dim"]


def read(ctx):
    tr = ctx.get("trace") or {}
    if not tr.get("n_devices") or ctx["device"]["platform"] != "tpu":
        return None
    seconds = ops_time(tr, KERNEL)
    pairs = sum(e.get("attn_pairs", 0) for r in records(ctx) or ()
                if r.get("traced") for e in r["first_tokens"])
    if not seconds or not pairs:
        return None
    peak = load_peaks(ctx["device"]["kind"])["bf16_flops"]
    return 100.0 * pairs * pair_ops(ctx["model"]) / peak / seconds
