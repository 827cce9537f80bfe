"""Median wall time of the step records with no admission and no stall: the
compiled decode program plus its token readback."""

from ._common import decode_step_ms as read  # noqa: F401
