"""Share of the traced window in which a collective runs on a device and no
compute does, mean over the devices."""

from ._common import collective_exposed_share as read  # noqa: F401
