"""Process-global runtime context (driver or worker).

Analog of the reference's global worker singleton
(reference: python/ray/_private/worker.py global_worker).
"""

from __future__ import annotations

from typing import Any, Dict, Optional


class RuntimeContext:
    def __init__(self):
        self.client = None  # core.client.Client
        self.mode: Optional[str] = None  # "driver" | "worker" | None
        self.job_id = None
        self.node_id = None
        self.worker_id = None
        self.session: Optional[str] = None
        self.current_task_id = None
        self.current_actor_id = None
        self.head_process = None  # in-driver head thread, if we started one
        self.namespace: str = "default"
        self.dashboard = None  # dashboard.Dashboard, if started via init()

    @property
    def initialized(self) -> bool:
        return self.client is not None

    def reset(self):
        self.__init__()


ctx = RuntimeContext()


def get_runtime_context() -> RuntimeContext:
    return ctx


#: What this process's direct streams (streaming tasks whose items the
#: submitter pulls from the worker that runs them) have done, cumulative
#: since its start: items handed to a pull, the seconds from the producing
#: generator's ``next`` returning to the item's append (``store``:
#: serialising it, then the worker's ``_streams_lock``), the seconds from
#: the append to the reply that carries the item (``pull``), and the items
#: appended while a pull was already waiting for them (a consumer that
#: keeps pace).  Written by ``core.worker_main`` alone, every field under
#: ``_streams_lock``, where the append and the reply already are.
stream_counts = {"items": 0, "store_s": 0.0, "pull_s": 0.0, "waiting": 0}


def direct_stream_counts() -> Dict[str, Any]:
    """A copy of the direct streams' cumulative counters, for whoever
    differences them over a period of its own (the serving engine's step
    record, as it does ``devmem.compile_count()``)."""
    return dict(stream_counts)
