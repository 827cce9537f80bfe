"""Median client-side time to first token (from the send) minus the engine's
own median submit-to-first-token, over the requests whose first token came
inside the window."""

from ..arith import median


def read(ctx):
    client, engine = ctx.get("client_first_token_s"), ctx.get("engine_ttft_s")
    if not client or not engine:
        return None
    return 1e3 * (median(client) - median(engine))
