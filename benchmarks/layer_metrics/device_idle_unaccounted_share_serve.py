"""Over the step records closed while the profiler ran (``traced`` 1): the
trace's idle share, ``100 x (1 - busy_s / window_s)``, less ``100 x
sum(starved_s + idle_s) / window_s`` of those records.  What the chip idled
and the loop did not cause: launch latency, the copy and the wake.  None
without a trace or without such records; under -1 the account is wrong."""

from ._starved import device_idle_unaccounted_share as read  # noqa: F401
