"""``100 x sum(proc_cpu_s) / sum(wall_s + between_s)`` of the quiet pure
decode steps dispatched ahead: the replica PROCESS's CPU seconds a second of
the loop; 100 is one core, where one interpreter is saturated and every
thread's Python is on the step's critical path (a bound, not a proof: the
runtime's own threads count too)."""

from ._quiet import replica_cpu_share as read  # noqa: F401
