"""Median ``readback_s`` over the pure decode steps: the loop blocked on the
step's tokens, which is the decode program's device time less what the host
overlapped with it."""

from ._phases import decode_device_wait_ms as read  # noqa: F401
