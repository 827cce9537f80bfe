"""Mean share of the batch slots that held a sequence, over the window's
decode steps."""

from ._common import occupancy as read  # noqa: F401
