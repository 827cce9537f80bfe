"""Mean ``wait_s`` of the quiet pure decode steps dispatched ahead: the
seconds of a step's period the loop thread was in a phase of its own and
neither ran nor waited for the chip (the interpreter lock, the engine's
lock, a core)."""

from ._quiet import loop_wait_ms as read  # noqa: F401
