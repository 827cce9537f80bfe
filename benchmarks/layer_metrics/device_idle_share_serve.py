"""One minus the union of the device-operation intervals over the traced
window, mean over the devices."""

from ._common import idle_share as read  # noqa: F401
