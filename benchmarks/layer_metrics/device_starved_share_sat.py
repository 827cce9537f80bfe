"""Sum of ``starved_s`` over sum of ``wall_s + between_s`` of the window's
step records: the share of the loop's time in which the chip had no work of
the engine's while the loop had some to give (host clock, every run, traced
or not).  Beside ``device_idle_share.serve``: what the host causes of it."""

from ._starved import device_starved_share as read  # noqa: F401
