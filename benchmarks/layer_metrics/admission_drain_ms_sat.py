"""Median ``first_tokens[].starved_s`` of the window: what one admission
costs the chip in standing still, from the read of the step in flight to
its prefill's first call, and from its first token to the next program
call.  None under 20 entries."""

from ._starved import admission_drain_ms as read  # noqa: F401
