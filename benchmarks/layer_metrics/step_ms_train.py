"""Median wall time of a train step of the window (upload, step, loss
readback)."""

from ..arith import median


def read(ctx):
    if ctx["kind"] != "train" or not ctx.get("step_s"):
        return None
    return 1e3 * median(ctx["step_s"])
