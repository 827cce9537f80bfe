#!/usr/bin/env python3
r"""Find the knee of a serving cell: the highest rate the system sustains.

    python benchmarks/sweep.py --workload <open-loop cell> \
        --rates 1.5,2,2.5,3 --seconds 30

One deployment, one compile, then the cell's open-loop traffic at each rate
in turn (the traffic file's ``rate_rps`` replaced), each for ``--seconds``
after the file's ramp.  Prints one JSON row per rate: what was offered and
completed, the tails, what was shed, how many requests were still waiting
when the window closed and how long they took to drain.  The knee is the
last rate at which completions keep up with arrivals and the queue at the
end of the window is no longer than at the start; the cell's ``rate_rps`` is
about four fifths of it, written into the traffic file by hand with the
table in PERF.md.  Not run by the driver; never imports JAX in the parent.
"""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks import run as harness
    from benchmarks import spec
    from benchmarks.arith import median, percentile
    from benchmarks.serve_cell import deploy, offer

    cell = spec.load_cell(args.workload, ROOT)
    if args.rehearse:
        cell = spec.rehearsal_cell(cell, ROOT)
        os.environ["JAX_PLATFORMS"] = "cpu"
    else:
        harness.use_compile_cache(args.workload)
    os.environ["RT_LOG_TO_DRIVER"] = "0"
    import ray_tpu
    from ray_tpu import serve

    phase = lambda name: None  # noqa: E731
    ray_tpu.init(system_config=cell["traffic"].get("system_config"))
    try:
        served = deploy(
            cell, seed=args.seed, platform="cpu" if args.rehearse else "tpu",
            num_tpus=0 if args.rehearse else cell["chips"], fail_phase="",
            log=harness.log, phase=phase)
        for rate in (float(r) for r in args.rates.split(",")):
            tr = dict(cell["traffic"], kind="serve_open", rate_rps=rate)
            before = served.call("stats")
            m = offer(served, tr, cell["model"], seed=args.seed,
                      seconds=args.seconds, trace=False, phase=phase)
            after = served.call("stats")
            occ = [r["occupancy"] for r in m["steps"] if r["occupancy"]]
            harness.log({
                "rate_rps": rate, "seconds": args.seconds,
                "requests_in_window": m["requests_in_window"],
                "completed_rps": (after["completed"] - before["completed"])
                / (args.seconds + tr["ramp_s"] + m["drain_s"]),
                "tokens_per_s": m["window_tokens"] / args.seconds,
                "ttft_ms_p50": 1e3 * median(m["ttft_s"]),
                "ttft_ms_p90": 1e3 * percentile(m["ttft_s"], 90),
                "itl_ms_p50": 1e3 * median(m["itl_s"]),
                "itl_ms_p95": 1e3 * percentile(m["itl_s"], 95),
                "mean_occupancy": sum(occ) / max(1, len(occ)),
                "queued_max": max((r["queued"] for r in m["steps"]),
                                  default=0),
                "queued_last": m["steps"][-1]["queued"] if m["steps"] else 0,
                "shed": after["shed"] - before["shed"],
                "failed": m["failed"], "drain_s": m["drain_s"]})
            served.call("clear_prefix_cache")
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
