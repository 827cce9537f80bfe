"""Sum of ``wall_s + between_s + idle_s`` of the window's step records over
the window's seconds: about 100, and lower the day work moves outside the
phases or records are lost."""

from ._phases import loop_accounted_share as read  # noqa: F401
