"""Due time to first streamed token at the client, 90th percentile over the
requests due in the window; a failed or shed request counts as the whole
window.  A per-layer metric for now: about 97 requests fall into a window,
and the 90th percentile of so few spread by 5% between runs of one schedule
(PERF.md, PR 23), more than a bound of 10% can carry."""

from ..arith import percentile


def read(ctx):
    if not ctx.get("ttft_s"):
        return None
    return 1e3 * percentile(ctx["ttft_s"], 90.0)
