"""Least time the chip's MXU needs for the attention the LATENT prefill
kernel has to do in the traced window, over the device time of that kernel
there, ``mosaic:latent_prefill*`` in the trace
(``ray_tpu/ops/latent_prefill.py``: ``pallas_call(name="latent_prefill")``,
one call a latent layer and prefill call of a model whose cache is a latent
pool).

Both sides are the traced window's, as ``paged_prefill_roofline.swa`` has
them.  The seconds come from the device trace alone and hold no host time.
The operations come from the engine's own count on the ``first_tokens``
entries of the step records closed while the profiler ran (``traced`` 1):
``attn_pairs``, the (query, key) pairs the real rows of a prompt's calls
could see, summed over its latent layers and calls (a row at position ``p``
sees ``p + 1`` rows of the pool; host arithmetic from each call's first and
last position), times the operations a pair needs on every query head,
ABSORBED: one multiply-add a number of the latent and of the shared rotary
key for the score (``kv_lora_rank + qk_rope_head_dim``), one a number of
the latent for the value, ``num_attention_heads x (2 x (kv_lora_rank +
qk_rope_head_dim) + 2 x kv_lora_rank)``.  The pool's rows are wider (576 in
640, zeros behind), and the kernel multiplies those zeros too: counting the
576 and the 512 keeps the share under what the MXU does, so it cannot pass
100%.  With some dozens of query rows times every head against a block of
rows the kernel is bound by those operations (2176 a head and pair, one
exponential), so the share is the share of the bf16 peak of ``peaks.json``
at which it multiplies what its real rows can see (whole blocks at the
diagonal and the bucket's padding lower it).  A prompt's calls may lie on
either side of a trace's edge; of some dozens.

No trace, no such call in it (the parent of the PR that added the kernel;
every backend but a TPU; a model that keeps K/V pairs), records without
``traced`` or entries without the count, or a device with no peak on
record: None."""

from ..arith import load_peaks
from ..trace_reduce import ops_time
from ._phases import records

KERNEL = "mosaic:latent_prefill"


def pair_ops(model) -> int:
    """The MXU's operations for one (query, key) pair on every head."""
    rank = model["kv_lora_rank"]
    return model["num_attention_heads"] * (
        2 * (rank + model["qk_rope_head_dim"]) + 2 * rank)


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
