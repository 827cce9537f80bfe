"""Seconds the engine loop spent in admission prefills over the wall seconds
of the window's step records."""

from ._common import stall_share as read  # noqa: F401
