"""Least time the chip could take for the flash calls of the traced steps
(the larger of operations over peak and bytes over peak) over the device
time of the Mosaic calls in the trace; forward, dQ and dK/dV together."""

from ._common import flash_roofline as read  # noqa: F401
