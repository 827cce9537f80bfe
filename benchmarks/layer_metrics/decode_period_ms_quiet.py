"""Median ``wall_s + between_s`` of the QUIET pure decode steps dispatched
ahead (``_quiet.quiet``: the records of the window's first second, before
the profiler's session opens): the untraced loop's period.  Beside
``decode_period_ms.sat`` on one line it is the profiler's distortion."""

from ._quiet import decode_period_ms as read  # noqa: F401
