#!/usr/bin/env python3
r"""One run of one cell of BENCHMARK.json:

    python benchmarks/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

This process never imports JAX: a chip belongs to one process at a time, and
it is the replica or the train worker that needs it.  It reads the cell's
entry, its configuration file and its traffic file, drives the system
through ``ray_tpu.init()`` and ``serve.run`` / ``JaxTrainer(...).fit()``,
checks correctness outside the window, tears everything down, and prints
the contract's one JSON object as the last line of stdout.

A run that fails prints ``BENCH-FAILED phase=<phase> ...`` and the chip
holder's last log lines to stderr, still tears down, prints no result and
exits 1.  A machine without the chips the cell asks for: exit 2, no result.

``--rehearse`` is the CPU rehearsal the tests use: the tiny configuration
and the traffic file's ``rehearsal`` sizes on the CPU backend.  Its line
says ``"platform": "cpu"``; it is never a measurement.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

PHASES = ("cluster_start", "worker_grant", "libtpu_start", "compile",
          "warmup", "measure", "compare", "teardown")


def log(record: dict) -> None:
    """An earlier line of stdout (the last one is the result)."""
    print(json.dumps(record), flush=True)


def proc_state(pid: int):
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0]
    except (FileNotFoundError, ProcessLookupError):
        return None


def descendants(root: int) -> list:
    """Pids of every live process below ``root`` (zombies left out)."""
    parent = {}
    for d in os.listdir("/proc"):
        if not d.isdigit():
            continue
        try:
            with open(f"/proc/{d}/stat") as f:
                rest = f.read().rsplit(")", 1)[1].split()
            if rest[0] != "Z":
                parent[int(d)] = int(rest[1])
        except (FileNotFoundError, ProcessLookupError, IndexError):
            continue
    out, frontier = [], [root]
    while frontier:
        cur = frontier.pop()
        kids = [p for p, pp in parent.items() if pp == cur]
        out += kids
        frontier += kids
    return out


def wait_dead(pids, timeout_s: float = 60.0) -> list:
    """Wait until every pid is gone or a zombie (its devices are closed);
    returns those still alive at the timeout."""
    deadline = time.monotonic() + timeout_s
    alive = list(pids)
    while alive and time.monotonic() < deadline:
        try:  # reap our own children as they end
            while os.waitpid(-1, os.WNOHANG)[0]:
                pass
        except ChildProcessError:
            pass
        alive = [p for p in alive if proc_state(p) not in (None, "Z")]
        if alive:
            time.sleep(0.1)
    return alive


def holder_log_tail(session: str, pid, n_lines: int = 20) -> str:
    """The last lines of the chip holder's log (the worker with ``pid``
    where known, else the session's most recently written worker log)."""
    from ray_tpu.core.node_main import LOG_ROOT

    paths = [p for p in glob.glob(
        os.path.join(LOG_ROOT, session or "*", "worker-*.log"))
        if os.path.getsize(p)
        and not p.endswith((".steps.log", ".rounds.log"))]
    if not paths:
        return "(no worker log)"
    # The holder's log is the one its pid wrote to; logs are named by
    # time, so look inside, and fall back to the largest.
    mine = [p for p in paths if pid and f"pid={pid}" in open(
        p, errors="replace").read(4096)]
    path = (mine or sorted(paths, key=os.path.getsize))[-1]
    with open(path, errors="replace") as f:
        return f"--- {path}\n" + "".join(f.readlines()[-n_lines:])


def session_leftovers(session: str) -> list:
    from ray_tpu.core.node_main import LOG_ROOT

    if not session:
        return []
    return (glob.glob(f"/dev/shm/rtpu-{session}-*")
            + glob.glob(f"/dev/shm/rtpu-pool-{session}")
            + glob.glob(os.path.join(LOG_ROOT, session))
            + glob.glob(os.path.join("/tmp/ray_tpu_fncache", session)))


def use_compile_cache(workload: str) -> None:
    """One cache directory to a cell, at a fixed path inside the checkout
    (or under the directory the machine names): each cell's programs then
    fit the machine's cap on the cache whatever ran before.  Set in the
    environment, which the workers inherit; no code sets another."""
    base = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(base, workload)
    os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)


def read_metrics(entries, package: str, ctx: dict) -> dict:
    """Each metric's own reader, found by the name in BENCHMARK.json:
    ``benchmarks/<package>/<name>.py`` (``.`` and ``-`` as ``_``) with
    ``read(ctx)``.  A reader that finds nothing to read returns None and
    the metric is left out of the line."""
    out = {}
    for m in entries:
        module = m["name"].replace(".", "_").replace("-", "_")
        mod = importlib.import_module(f"benchmarks.{package}.{module}")
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the tiny configuration")
    ap.add_argument("--fail-phase", default="", choices=("",) + PHASES,
                    help="rehearsal only: raise in this phase")
    args = ap.parse_args(argv)

    from benchmarks import spec

    if not os.path.isdir(os.path.join(ROOT, "ray_tpu")):
        print("benchmarks/run.py: no ray_tpu/ beside benchmarks/: the "
              "system under test is not in this directory",
              file=sys.stderr)
        return 2
    cell = spec.load_cell(args.workload, ROOT)
    seconds = args.seconds if args.seconds is not None \
        else cell["run_seconds"]
    chips = cell["chips"]
    if args.rehearse:
        cell = spec.rehearsal_cell(cell, ROOT)
        platform = "cpu"
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        os.environ["XLA_FLAGS"] = " ".join(
            flags + [f"--xla_force_host_platform_device_count={chips}"])
    else:
        if args.fail_phase:
            ap.error("--fail-phase is for --rehearse")
        platform = "tpu"
        from ray_tpu import accelerators

        wanted = os.environ.get("JAX_PLATFORMS", "")
        have = accelerators.num_chips()
        if (wanted and "tpu" not in wanted.split(",")) or have < chips:
            print(f"benchmarks/run.py: {args.workload} needs {chips} TPU "
                  f"chip(s); this host has {have} and JAX_PLATFORMS="
                  f"{wanted!r}.  No result: a CPU is not measured.",
                  file=sys.stderr)
            return 2
        use_compile_cache(args.workload)
        os.environ["RT_DEBUG_JIT"] = "1"  # a trace after warm-up raises
    os.environ["RT_LOG_TO_DRIVER"] = "0"

    state = {"phase": "cluster_start", "session": "", "holder": None}

    def phase(name: str) -> None:
        state["phase"] = name
        if args.fail_phase == name:
            raise RuntimeError("forced failure")

    import ray_tpu

    scratch = tempfile.mkdtemp(prefix="bench_run_")
    result, failure, my_pid = None, None, os.getpid()
    try:
        phase("cluster_start")
        # A rehearsal shares its machine with the rest of the tests: four
        # workers are enough for it.  A measuring run takes the defaults.
        ctx = ray_tpu.init(
            num_cpus=4 if args.rehearse else None,
            system_config=cell["traffic"].get("system_config"))
        state["session"] = ctx.session
        log({"phase": "cluster_start", "session": ctx.session,
             "seconds": seconds, "rehearsal": args.rehearse})
        if platform == "tpu":
            tpus = ray_tpu.cluster_resources().get("TPU", 0)
            if tpus < chips:
                raise RuntimeError(f"the node advertises TPU={tpus}, the "
                                   f"cell needs {chips}")
        kw = dict(seed=args.seed, seconds=seconds, trace=bool(args.trace),
                  platform=platform, fail_phase=args.fail_phase, log=log,
                  phase=phase)
        if cell["traffic"]["kind"] == "train":
            from benchmarks.train_cell import run_train

            result = run_train(cell, chips=chips, storage=scratch, **kw)
        else:
            from benchmarks.serve_cell import run_serve

            result = run_serve(
                cell, num_tpus=chips if platform == "tpu" else 0, **kw)
        state["holder"] = result["holder_pid"]
    except BaseException as e:  # noqa: BLE001 — reported, then torn down
        failure = e
    # Teardown is part of the run, whether it failed or not.
    try:
        if failure is not None:
            tagged = re.search(r"bench-phase=(\w+)", repr(failure)
                               + repr(failure.__cause__))
            where = tagged.group(1) if tagged else state["phase"]
            print(f"BENCH-FAILED phase={where} workload={args.workload} "
                  f"seed={args.seed} error={type(failure).__name__}: "
                  f"{failure}", file=sys.stderr)
            print(holder_log_tail(state["session"], state["holder"]),
                  file=sys.stderr, flush=True)
        state["phase"] = "teardown"
        started = descendants(my_pid)
        if ray_tpu.is_initialized():
            from ray_tpu import serve
            from ray_tpu.core.context import ctx as rt_ctx

            # Workers are daemons (their parent is init, not this
            # process): the head knows their pids.
            started += [w["pid"] for w in rt_ctx.client.call(
                "list_state", {"kind": "workers"})["items"]]
            if state["holder"]:
                started.append(state["holder"])
            started = sorted(set(started))
            log({"phase": "teardown", "pids": started})

            try:
                serve.shutdown()
            finally:
                ray_tpu.shutdown()
        alive = wait_dead(started)
        if alive:
            import signal

            for pid in alive:
                try:
                    os.kill(pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
            still = wait_dead(alive, 10.0)
            raise RuntimeError(
                f"processes {alive} outlived ray_tpu.shutdown() and were "
                f"killed ({still} even so)")
        for _ in range(3):  # a dying worker may write once more
            for path in session_leftovers(state["session"]):
                if os.path.isdir(path):
                    shutil.rmtree(path, ignore_errors=True)
                else:
                    os.remove(path)
            time.sleep(0.2)
            if not session_leftovers(state["session"]):
                break
        for path in (scratch, (result or {}).get("trace_dir")):
            if path:
                shutil.rmtree(path, ignore_errors=True)
        if args.fail_phase == "teardown" and failure is None:
            raise RuntimeError("forced failure")
    except BaseException as e:  # noqa: BLE001
        print(f"BENCH-FAILED phase=teardown workload={args.workload} "
              f"seed={args.seed} error={type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        failure = failure or e
    if failure is not None:
        if isinstance(failure, KeyboardInterrupt):
            raise failure
        return 1
    if "jax" in sys.modules:
        print("BENCH-FAILED phase=teardown error=the parent imported jax",
              file=sys.stderr)
        return 1

    result["setup_s"] = result["window_wall"] - T_PROCESS_START
    result["model"], result["cell"] = cell["model"], cell
    if args.trace:
        metrics = read_metrics(cell["per_layer"], "layer_metrics", result)
    else:
        metrics = read_metrics(cell["end_to_end"], "metrics", result)
    for reason in result["reasons"]:
        log({"phase": "incorrect", "reason": reason})
    device = dict(result["device"])
    out = {"correct": not result["reasons"],
           "attempted": result["attempted"], "failed": result["failed"],
           "metrics": metrics, "device": device}
    if args.trace and result["trace"].get("n_devices"):
        device["busy_s"] = result["trace"]["busy_s"]
        device["window_s"] = result["trace"]["window_s"]
        out["breakdown"] = {"device_ops": result["trace"]["device_ops"],
                            "idle_gaps": result["trace"]["idle_gaps"]}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
