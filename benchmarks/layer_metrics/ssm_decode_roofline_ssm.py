"""Least time the chip's HBM needs for the bytes the state-space decode
kernel moves in the traced window, over the device time of that kernel
there, ``mosaic:ssm_decode*`` in the trace (``ray_tpu/ops/ssm_decode.py``:
``pallas_call(name="ssm_decode")``, one call a Mamba layer and decode step,
which reads every slot's ``[N, I]`` float32 state once and writes it once
where it lies).

Both sides are the traced window's, as ``paged_decode_roofline.swa`` has
them.  The seconds come from the device trace alone and hold no host time:
in a cell whose traced loop is the host's (the Jamba cell's is the
profiler's), this is the reader that sees the kernel.  The bytes are a
decode step's, times the step records closed while the profiler ran
(``traced`` 1) that carry ``state_bytes`` (the mark of a program that keeps
recurrent state): ``slots x Mamba layers x 2 x mamba_d_state x
(mamba_expand x hidden_size) x 4``, every slot's state read once and written
once, live or not (a dead slot's passes through the kernel as it is), from
the configuration's own keys (``step_bytes``).  A step or two at the trace's
edges ran before their record closed or after; of some ninety.  The kernel
is bound by those bytes (some ten operations an entry, each entry eight
bytes moved), so the share is the share of the HBM peak of ``peaks.json`` at
which it passes over the state, and cannot pass 100% while it reads and
writes every slot's state.  The rows beside the state (``delta``, ``xs``,
``y``, ``B`` and ``C``: 1% of it) are left out.

No trace, no such call in it (the parent of the PR that added the kernel,
whose recurrence is two XLA fusions; every backend but a TPU; a family
without state-space layers), records without ``traced`` or ``state_bytes``,
or a device with no peak on record: None."""

from ..arith import load_peaks
from ..trace_reduce import ops_time
from ._phases import records

KERNEL = "mosaic:ssm_decode"


def step_bytes(model, slots: int) -> int:
    """What one decode step's calls move of state: every slot's, on every
    Mamba layer, read once and written once, float32."""
    period, offset = model["attn_layer_period"], model["attn_layer_offset"]
    layers = sum(i % period != offset
                 for i in range(model["num_hidden_layers"]))
    return slots * layers * 2 * model["mamba_d_state"] \
        * model["mamba_expand"] * model["hidden_size"] * 4


def read(ctx):
    tr = ctx.get("trace") or {}
    if not tr.get("n_devices") or ctx["device"]["platform"] != "tpu":
        return None
    seconds = ops_time(tr, KERNEL)
    if not seconds or "mamba_d_state" not in ctx["model"]:
        return None
    moved = sum(step_bytes(ctx["model"], r["slots"])
                for r in records(ctx) or ()
                if r.get("traced") and "state_bytes" in r)
    if not moved:
        return None
    peak = load_peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * moved / peak / seconds
