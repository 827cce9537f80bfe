"""Share of the window's step records with ``occupancy`` > 0 whose ``ahead``
is 1: the decode steps that went out before the step ahead of them was
read (``ahead%`` of ``ray_tpu status``)."""

from ._starved import ahead_share as read  # noqa: F401
