"""Gap between consecutive streamed tokens at the client, 95th percentile
over every gap that ended inside the window."""

from ..arith import percentile


def read(ctx):
    if not ctx.get("itl_s"):
        return None
    return 1e3 * percentile(ctx["itl_s"], 95.0)
