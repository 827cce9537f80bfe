"""90th percentile of ``queue_s`` (engine submit to admission into a slot)
over the requests prefilled in the window; nothing under 50 of them."""

from ._phases import admit_queue_wait_p90_ms as read  # noqa: F401
