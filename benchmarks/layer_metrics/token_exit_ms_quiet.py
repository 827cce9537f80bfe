"""``1e3 x sum(wake_s + store_s + pull_s) / sum(tokens_out)`` over the quiet
records: a token's mean way from the start of the pass that emitted it to
the reply that carries it out of the replica (the stream thread's wake, the
item's store, the consumer's pull)."""

from ._quiet import token_exit_ms as read  # noqa: F401
