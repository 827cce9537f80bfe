"""``100 x sum(pull_waiting) / sum(tokens_out)`` over the quiet records: the
share of the tokens that found their consumer's pull already waiting when
they were appended.  Under 50 the consumers lag, and the replica is not
what holds the tokens."""

from ._quiet import stream_pull_waiting_share as read  # noqa: F401
