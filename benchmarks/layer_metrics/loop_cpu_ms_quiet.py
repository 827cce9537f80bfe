"""Mean ``cpu_s`` of the quiet pure decode steps dispatched ahead: the
seconds of a step's period the loop thread really ran (its own CPU clock):
Python and the dispatch it executed, not what it waited for.  A mean: the
chip machines' CPU clocks tick by 10 ms, so one record is a sample."""

from ._quiet import loop_cpu_ms as read  # noqa: F401
