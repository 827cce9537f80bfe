"""Median ``wall_s + between_s`` of the pure decode steps dispatched ahead
(no admission, no stall, occupancy > 0, ``ahead`` 1): a decode step's
period, where ``decode_step_ms.sat`` reads one turn's ``wall_s``."""

from ._starved import decode_period_ms as read  # noqa: F401
