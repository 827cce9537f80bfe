"""Reads BENCHMARK.json and the files it names.  Nothing here knows a cell,
a configuration, a traffic mix, a metric or an architecture by name: a
``workloads`` entry names its ``config`` and ``traffic``, a configuration
file names its ``family``, and each is a file found by that name.  No JAX."""

from __future__ import annotations

import importlib
import json
import os
import re
from typing import Any, Dict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmarks")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TRAFFIC_KINDS = ("serve_closed", "serve_open", "train")
#: The family of a configuration file that names none.
DEFAULT_FAMILY = "llama"


class SpecError(ValueError):
    """BENCHMARK.json or a file it names is not what the contract allows."""


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _one_line(s: Any, what: str) -> None:
    if not (isinstance(s, str) and 1 <= len(s) <= 200
            and "\n" not in s and "\t" not in s):
        raise SpecError(f"{what}: 1 to 200 characters on one line, got {s!r}")


def validate(doc: Dict[str, Any]) -> None:
    """The contract's rules that can be checked without a run: names,
    units, sources, which metric a per-layer metric moves, and that every
    cell reports set-up, one more end-to-end metric and a per-layer one."""
    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(doc) != want:
        raise SpecError(f"keys {sorted(doc)} are not exactly {sorted(want)}")
    if not (isinstance(doc["run_seconds"], int)
            and 1 <= doc["run_seconds"] <= 51):
        raise SpecError("run_seconds is a whole number from 1 to 51")
    names: Dict[str, set] = {"config": set(), "cell": set(), "metric": set()}

    def fresh(kind: str, name: str) -> None:
        if not NAME_RE.match(name):
            raise SpecError(f"{kind} name {name!r} breaks the naming rule")
        if name in names[kind]:
            raise SpecError(f"two {kind}s are named {name!r}")
        names[kind].add(name)

    for c in doc["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            raise SpecError(f"config entry has keys {sorted(c)}")
        fresh("config", c["name"])
        _one_line(c["source"], "config source")
        _one_line(c["why"], "config why")
        if not any(c["file"].startswith(p.rstrip("/") + "/")
                   for p in doc["paths"]):
            raise SpecError(f"{c['file']} is not under paths")
        for k in c["reduced"]:
            if not NAME_RE.match(k):
                raise SpecError(f"reduced key {k!r}")
    for w in doc["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            raise SpecError(f"workload entry has keys {sorted(w)}")
        fresh("cell", w["name"])
        if not NAME_RE.match(w["traffic"]):
            raise SpecError(f"traffic name {w['traffic']!r}")
        if w["config"] not in names["config"]:
            raise SpecError(f"cell {w['name']} names no configuration")
        if w["chips"] not in (1, 4):
            raise SpecError("chips is 1 or 4")
        _one_line(w["why"], "workload why")
    four = sum(1 for w in doc["workloads"] if w["chips"] == 4)
    if four > max(1, len(doc["workloads"]) // 4):
        raise SpecError("more than a quarter of the cells ask for 4 chips")
    used = {w["config"] for w in doc["workloads"]}
    if used != names["config"]:
        raise SpecError("a configuration has no cell")
    e2e = {}
    for m in doc["end_to_end"]:
        if not set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"} or "bound" not in m:
            raise SpecError(f"end-to-end entry has keys {sorted(m)}")
        _check_metric(m, fresh, names["cell"])
        if m["source"] not in ("host_clock", "device_trace"):
            raise SpecError("an end-to-end metric is host_clock or "
                            "device_trace")
        if not 0.01 <= m["bound"] <= 0.1:
            raise SpecError(f"bound of {m['name']} outside 0.01..0.1")
        e2e[m["name"]] = m
    if "setup_s" not in e2e:
        raise SpecError("setup_s is not among the end-to-end metrics")
    for m in doc["per_layer"]:
        if not set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}:
            raise SpecError(f"per-layer entry has keys {sorted(m)}")
        _check_metric(m, fresh, names["cell"])
        _one_line(m["layer"], "layer")
        if m["moves"] not in e2e:
            raise SpecError(f"{m['name']} moves {m['moves']!r}, which is "
                            f"no end-to-end metric")
        for cell in m.get("workloads", names["cell"]):
            if not applies(e2e[m["moves"]], cell):
                raise SpecError(
                    f"{m['name']} is reported in {cell}, where "
                    f"{m['moves']} is not")
    for cell in names["cell"]:
        mine = [m for m in doc["end_to_end"] if applies(m, cell)]
        if len(mine) < 2 or not any(m["name"] == "setup_s" for m in mine):
            raise SpecError(f"cell {cell} lacks setup_s or a second metric")
        if not any(applies(m, cell) for m in doc["per_layer"]):
            raise SpecError(f"cell {cell} has no per-layer metric")


def _check_metric(m, fresh, cells) -> None:
    fresh("metric", m["name"])
    if not UNIT_RE.match(m["unit"]):
        raise SpecError(f"unit {m['unit']!r} of {m['name']}")
    if m["better"] not in ("lower", "higher"):
        raise SpecError(f"better of {m['name']}")
    if m["source"] not in SOURCES:
        raise SpecError(f"source of {m['name']}")
    for cell in m.get("workloads", ()):
        if cell not in cells:
            raise SpecError(f"{m['name']} lists unknown cell {cell!r}")


def applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def family(model: Dict[str, Any]):
    """The module that knows ``model``'s architecture:
    ``benchmarks/families/<family with . and - as _>.py``, by the ``family``
    key of the loaded configuration file."""
    name = model.get("family", DEFAULT_FAMILY)
    module = str(name).replace(".", "_").replace("-", "_")
    path = os.path.join(BENCH_DIR, "families", module + ".py")
    if not (isinstance(name, str) and NAME_RE.match(name)
            and os.path.isfile(path)):
        raise SpecError(f"configuration {model.get('name')!r} is of family "
                        f"{name!r}, and there is no {path}")
    return importlib.import_module(f"benchmarks.families.{module}")


def load_cell(name: str, root: str = ROOT) -> Dict[str, Any]:
    """Everything one run needs, from the names in BENCHMARK.json."""
    doc = load_benchmark(root)
    cells = {w["name"]: w for w in doc["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have: {sorted(cells)})")
    w = cells[name]
    cfg_entry = next(c for c in doc["configs"] if c["name"] == w["config"])
    traffic_path = os.path.join(root, "benchmarks", "traffic",
                                w["traffic"] + ".json")
    traffic = load_json(traffic_path)
    if traffic.get("kind") not in TRAFFIC_KINDS:
        raise SpecError(f"{traffic_path}: kind {traffic.get('kind')!r} is "
                        f"not one of {TRAFFIC_KINDS}")
    model = load_json(os.path.join(root, cfg_entry["file"]))
    family(model)  # a family with no file fails here, before any process
    return {
        "name": name, "chips": w["chips"], "why": w["why"],
        "config_name": w["config"], "model": model,
        "traffic_name": w["traffic"], "traffic": traffic,
        "run_seconds": doc["run_seconds"],
        "end_to_end": [m for m in doc["end_to_end"] if applies(m, name)],
        "per_layer": [m for m in doc["per_layer"] if applies(m, name)],
    }


def rehearsal_cell(cell: Dict[str, Any], root: str = ROOT) -> Dict[str, Any]:
    """The same cell for the CPU rehearsal: its family's tiny configuration,
    and the traffic file's own ``rehearsal`` overrides (sizes a CPU can
    run)."""
    out = dict(cell)
    out["model"] = load_json(os.path.join(
        root, "benchmarks", "configs",
        family(cell["model"]).REHEARSAL_CONFIG + ".json"))
    traffic = dict(cell["traffic"])
    for key, value in traffic.pop("rehearsal", {}).items():
        if isinstance(value, dict) and isinstance(traffic.get(key), dict):
            traffic[key] = {**traffic[key], **value}
        else:
            traffic[key] = value
    out["traffic"] = traffic
    return out
