"""Median ``prefill_s`` (all of the engine's ``_prefill``) over the requests
prefilled in the window; nothing under 20 of them."""

from ._phases import prefill_ms as read  # noqa: F401
