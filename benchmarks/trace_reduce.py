"""From a profiler trace (``.xplane.pb``) to numbers: device busy and idle
time, time by operation, kernel time, exposed collective time, and the
longest idle gaps named by what the host was doing.

Two stages, so that the arithmetic can be checked without a profiler:
``load_events`` reads the file with ``jax.profiler.ProfileData`` (the only
JAX use here) into plain lists, and ``reduce_events`` is pure Python.
The reduction is checked in the tests against a small trace recorded on a
v5e (``benchmarks/testdata``).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

#: Host annotations the benchmark's own code writes carry this prefix.
ANNOTATION_PREFIX = "bench:"
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
#: Operations that only enclose others (their time is their children's).
CONTAINERS = ("while", "conditional", "call")
TOP_N = 10


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def label(event_name: str) -> str:
    """A TPU trace names an operation by its whole HLO instruction
    (``%fusion.4 = bf16[..] fusion(...), kind=...``).  The label kept is
    ``<opcode>:<result name>``, e.g. ``fusion:fusion.4`` or
    ``all-gather-start:all-gather-start.2``; a Mosaic (Pallas) kernel is a
    custom-call to ``tpu_custom_call`` and is labelled ``mosaic:<name>``."""
    head, sep, rest = event_name.partition(" = ")
    if not sep:
        return event_name
    name = head.strip().lstrip("%")
    if 'custom_call_target="tpu_custom_call"' in rest:
        return "mosaic:" + name
    m = _OPCODE.search(" " + rest)
    return f"{m.group(1) if m else 'op'}:{name}"


def load_events(path: str) -> Dict[str, Any]:
    """{"devices": {plane: [(label, start_ns, dur_ns), ...]}, "async":
    {plane: [...]}, "host": [(name, start_ns, dur_ns), ...]}: each device's
    operations (its ``XLA Ops`` line), the spans of its asynchronous
    operations from start to done (``Async XLA Ops``), and the host's
    ``bench:`` annotations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    asyncs: Dict[str, List[Tuple[str, float, float]]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for ln in plane.lines:
                if ln.name in (OPS_LINE, ASYNC_LINE):
                    into = devices if ln.name == OPS_LINE else asyncs
                    into[plane.name] = [
                        (label(e.name), float(e.start_ns),
                         float(e.duration_ns)) for e in ln.events]
            devices.setdefault(plane.name, [])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        host.append((e.name[len(ANNOTATION_PREFIX):],
                                     float(e.start_ns), float(e.duration_ns)))
    return {"devices": devices, "async": asyncs, "host": host}


# ---------------------------------------------------------------- intervals


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of merged ``a`` that no interval of merged ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def is_collective(name: str) -> bool:
    return name.startswith(COLLECTIVES)


def is_container(name: str) -> bool:
    return name.partition(":")[0] in CONTAINERS


def _innermost(host: Sequence[Tuple[str, float, float]], t: float) -> str:
    best, best_d = "unannotated", None
    for name, s, d in host:
        if s <= t <= s + d and (best_d is None or d < best_d):
            best, best_d = name, d
    return best


def reduce_events(events: Dict[str, Any],
                  window_s: Optional[float] = None) -> Dict[str, Any]:
    """Seconds throughout.  ``window_s`` is the traced window as the host
    timed it; without it the window is the span of the device events.
    ``busy_s``, ``collective_s`` (a collective in flight, asynchronous
    spans included) and ``collective_exposed_s`` (a collective on the
    operations line while no other operation runs) are means over the
    devices; ``ops`` sums each operation's time over the devices
    (containers left out)."""
    ns = 1e-9
    per_device, ops, coll, exposed, spans = [], {}, [], [], []
    gaps_by_name: Dict[str, float] = {}
    first = True
    for plane in sorted(events["devices"]):
        evs = events["devices"][plane]
        leaves = [(n, s, s + d) for n, s, d in evs if not is_container(n)]
        busy = union((s, e) for _, s, e in leaves)
        c_iv = union((s, e) for n, s, e in leaves if is_collective(n))
        k_iv = union((s, e) for n, s, e in leaves if not is_collective(n))
        in_flight = union(
            [(s, s + d) for n, s, d in events.get("async", {}).get(plane, ())
             if is_collective(n)] + c_iv)
        per_device.append(total(busy) * ns)
        coll.append(total(in_flight) * ns)
        exposed.append(total(subtract(c_iv, k_iv)) * ns)
        if busy:
            spans.append((busy[0][0], busy[-1][1]))
        for n, s, e in leaves:
            ops[n] = ops.get(n, 0.0) + (e - s) * ns
        if first and busy:  # name the gaps of the first device
            first = False
            for (_, e0), (s1, _) in zip(busy, busy[1:]):
                name = _innermost(events["host"], (e0 + s1) / 2.0)
                gaps_by_name[name] = gaps_by_name.get(name, 0.0) \
                    + (s1 - e0) * ns
    n_dev = len(per_device)
    span_s = (max(e for _, e in spans) - min(s for s, _ in spans)) * ns \
        if spans else 0.0
    window = max(window_s or 0.0, span_s)
    mean = (lambda xs: sum(xs) / len(xs)) if n_dev else (lambda xs: 0.0)
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP_N]
    gaps = sorted(gaps_by_name.items(), key=lambda kv: -kv[1])[:TOP_N]
    return {
        "n_devices": n_dev, "window_s": window, "device_span_s": span_s,
        "busy_s": mean(per_device), "busy_s_per_device": per_device,
        "collective_s": mean(coll), "collective_exposed_s": mean(exposed),
        "ops": ops,
        "device_ops": [[n, s] for n, s in top],
        "idle_gaps": [[n, s] for n, s in gaps],
    }


def ops_time(reduced: Dict[str, Any], *needles: str) -> float:
    """Seconds (summed over devices) of operations whose name contains any
    of ``needles``."""
    return sum(s for n, s in reduced["ops"].items()
               if any(x in n for x in needles))


def reduce_trace_dir(trace_dir: str,
                     window_s: Optional[float] = None) -> Dict[str, Any]:
    path = find_xplane(trace_dir)
    if path is None:
        return {}
    return reduce_events(load_events(path), window_s)
