"""Tokens per second times the operations a token requires (recomputation
not counted) over chips times the peak in peaks.json."""

from ._common import train_mfu as read  # noqa: F401
