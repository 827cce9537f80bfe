"""Median over the pure decode steps of the loop's host seconds a step:
``between_s + upload_s + dispatch_s + emit_s`` of the step records, which is
everything but the wait for the device (``decode_device_wait_ms.sat``)."""

from ._phases import decode_host_ms as read  # noqa: F401
