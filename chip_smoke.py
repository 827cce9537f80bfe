#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

Drives the two hot paths once, through the entry points a user calls, at the
full shape of ``LlamaConfig.b1`` (d_model 2048, 20 layers, 16 heads of 128,
d_ff 5632, vocab 32000, bf16; random weights from ``SEED``):

    python chip_smoke.py            # one chip: a serve phase, then a train phase
    python chip_smoke.py --chips 4  # one host of four: the sharded trainer and
                                    # its one-device comparison, nothing else

1. serve: ``ray_tpu.init()`` (chips autodetected) ->
   ``serve.run(llm_app(model="b1", warmup=True, ray_actor_options={"num_tpus":
   1}))`` -> streamed greedy requests of several bucket lengths through the
   handle, with the recompile sentinel armed (``RT_DEBUG_JIT=1``) -> the
   replica's own report -> app deleted, replica process confirmed dead.
2. train: ``JaxTrainer(ScalingConfig(num_workers=1, use_tpu=True))`` runs a
   few steps of the b1 train step (4 x 2048, remat) on a seeded batch.

This process never imports JAX: a chip belongs to one process at a time, and
it is the worker that needs it.  It prints one JSON line per phase and, as
the last line of stdout, ``{"ok": true, "device": {...}}`` with the device as
the worker saw it.  It exits non-zero, and prints no such line, when a phase
fails, when the host has no chip, or when ``JAX_PLATFORMS`` keeps JAX off it.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import sys
import time

SEED = 0
SERVE_APP = "smoke_llm"

#: The serving deployment: a pool of [20, 321, 16, 64, 128] x 2 bf16 pages
#: (3.4 GB) beside 2.3 GB of weights on the 16 GB chip.  The decode step's
#: temporaries grow with slots x page-table width (ROADMAP S4: 5.1 GB here),
#: so this is sized within that, not past it.  Pages of 64 tokens make five
#: prefill buckets (64 .. 1024), so warm-up compiles 11 large programs whose
#: cache entries (166 MB, 182 MB with the train step) fit the 192 MiB the
#: chip machine caps its compile cache at.  At 16 tokens a page there are
#: seven buckets and 205 MB, and since the cache evicts the least recently
#: used entry and a run reads them in the order it wrote them, a second run
#: then found none of them (measured: 185 s of compile after 194 s).
SERVE_ENGINE = dict(batch_slots=16, page_size=64, max_prompt_len=1024,
                    max_new_tokens_cap=256)
#: Prompt lengths, one per prefill bucket 64 / 128 / 256 / 1024 / 1024.  The
#: second is the one asked three times and held to the plain forward pass:
#: a multiple of 128, so the flash kernel takes it whole.
SERVE_PROMPT_LENS = (5, 128, 200, 700, 1024)
SERVE_NEW_TOKENS = 32
#: bf16 keeps 8 bits of mantissa: logits of a few units carry ~0.02 of
#: rounding, and the two paths sum in different orders.
LOGIT_TOL = 0.25

#: The b1 train step at 4 x 2048.
TRAIN = dict(model="b1", batch=4, seq=2048, steps=5, lr=3e-4)
#: Relative bands for the sharded loss against the one-device loss.  The
#: first step is one forward pass over identical weights: bf16 sums
#: reordered by the tp/fsdp collectives.  Later steps compare two runs of
#: an optimizer that each amplify their own rounding.
SHARDED_FIRST_LOSS_RTOL = 5e-3
SHARDED_LOSS_RTOL = 5e-2

#: Longest wait for a replica that compiles every program cold, and for the
#: first report of a train worker (its compile).  Well inside the 1200 s the
#: whole script has.
READY_TIMEOUT_S = 900.0


class SmokeFailure(Exception):
    """A phase ran and what came out is wrong."""


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def wait_dead(pid: int, timeout_s: float = 60.0) -> float:
    """Seconds until process ``pid`` is gone (or a zombie: its devices are
    closed).  The chip IDs return to the pool only at worker death."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        try:
            with open(f"/proc/{pid}/stat") as f:
                state = f.read().rsplit(")", 1)[1].split()[0]
        except (FileNotFoundError, ProcessLookupError):
            return time.monotonic() - t0
        if state == "Z":
            return time.monotonic() - t0
        time.sleep(0.1)
    raise SmokeFailure(f"worker process {pid} still alive after {timeout_s}s")


def check_device(device: dict, platform: str, count=None) -> None:
    """``count=None``: a CPU rehearsal, with however many virtual devices."""
    require(device["platform"] == platform
            and count in (None, device["count"]),
            f"worker ran on {device}, expected {count} x {platform!r}")


def train_model_config(model: str, seq: int):
    """What the train phase trains: b1 at 4 x 2048, or (rehearsals)
    tiny."""
    import jax.numpy as jnp

    from ray_tpu.models import LlamaConfig

    if model == "b1":
        return LlamaConfig.b1(remat=True, dtype=jnp.bfloat16, max_seq=seq,
                              remat_policy="cse_save_attn", loss_chunk=seq)
    return LlamaConfig.tiny(remat=True, dtype=jnp.float32)


# ------------------------------------------------------------------- serve


def serve_phase(*, model: str, engine: dict, prompt_lens, new_tokens: int,
                platform: str, num_tpus: int) -> dict:
    """Deploy the LLM app, stream a few greedy requests, check them, read
    the replica's own report, delete the app and see its process die."""
    import random

    from ray_tpu import serve
    from ray_tpu.serve.engine import llm_app

    rng = random.Random(SEED)
    prompts = [[rng.randrange(1, 500) for _ in range(n)] for n in prompt_lens]

    t0 = time.perf_counter()
    handle = serve.run(
        llm_app(model=model, engine=engine, warmup=True, seed=SEED,
                name=SERVE_APP,
                ray_actor_options={"num_tpus": num_tpus} if num_tpus else None),
        timeout=READY_TIMEOUT_S)
    ready_s = time.perf_counter() - t0
    stream = handle.options(stream=True)

    def call(method, *args):
        return handle.options(method).remote(*args).result(timeout=300)

    # All in flight at once: admission between decode steps, one prefill
    # bucket per length.
    t1 = time.perf_counter()
    outs = [list(g) for g in [stream.remote(p, new_tokens) for p in prompts]]
    serve_s = time.perf_counter() - t1
    vocab = call("stats")["vocab_size"]
    for p, out in zip(prompts, outs):
        require(len(out) == new_tokens
                and all(isinstance(t, int) and 0 <= t < vocab for t in out),
                f"prompt of {len(p)}: expected {new_tokens} token ids below "
                f"{vocab}, got {out}")

    # The same request again, through the same cold-prefill program (the
    # cache is cleared first): greedy decoding must repeat exactly.
    call("clear_prefix_cache")
    again = list(stream.remote(prompts[1], new_tokens))
    require(again == outs[1],
            f"greedy output did not repeat: {outs[1]} then {again}")
    # And once more WITH its prefix cached: the copy-on-write page and the
    # suffix prefill run under the sentinel.  bf16 sums in another order
    # there, so equality with the cold path is reported, not required.
    cached = list(stream.remote(prompts[1], new_tokens))
    require(len(cached) == new_tokens, f"prefix-cached request gave {cached}")

    # Both paths' first token against the plain full forward pass (no
    # cache, no pages; the flash kernel on the chip): its logit must be the
    # reference's best, within the dtype's rounding.
    ref = call("reference_logits", prompts[1], [outs[1][0], cached[0]])
    gaps = [ref["max"] - x for x in ref["logits"]]
    require(all(math.isfinite(g) and g <= LOGIT_TOL for g in gaps),
            f"first tokens {outs[1][0]} (cold prefill) and {cached[0]} "
            f"(cached prefix) have reference logits {ref['logits']}; the "
            f"best is {ref['max']} (token {ref['argmax']})")

    call("clear_prefix_cache")
    st = call("stats")
    check_device(st["device"], platform, num_tpus or None)
    require(st["decode_traces"] == 1,
            f"decode program traced {st['decode_traces']} times")
    require(st["free_pages"] == st["total_pages"] and not st["active_seqs"],
            f"page free list unbalanced after clear_prefix_cache: "
            f"{st['free_pages']} of {st['total_pages']} free")
    require(st["sentinel_armed"], "recompile sentinel was not armed")

    serve.delete(SERVE_APP)
    died_s = wait_dead(st["pid"])
    return {
        "phase": "serve", "model": model, "engine": engine,
        **st["device"],
        "requests": len(prompts) + 2, "prompt_lens": list(prompt_lens),
        "tokens_returned": sum(map(len, outs)) + len(again) + len(cached),
        "greedy_repeats": True, "prefix_cached_repeats": cached == outs[1],
        "reference_logit_gap": {"cold_prefill": gaps[0],
                                "cached_prefix": gaps[1]},
        "init_s": st["init_s"], "compile_s": st["warmup_s"],
        "ready_s": ready_s, "serve_s": serve_s,
        "decode_traces": st["decode_traces"],
        "prefill_traces": st["prefill_traces"],
        "prefill_prefix_traces": st["prefill_prefix_traces"],
        "sentinel_armed": st["sentinel_armed"],
        "free_pages": st["free_pages"], "total_pages": st["total_pages"],
        "completed": st["completed"], "replica_pid": st["pid"],
        "replica_dead_after_s": died_s,
    }


# ------------------------------------------------------------------- train


def _train_loop(config: dict) -> None:
    """Runs in the train worker: ``steps`` steps of the model's train step
    on one seeded batch, over the session mesh when there is one."""
    import contextlib
    import os
    import time

    import jax
    import jax.numpy as jnp

    from ray_tpu import train as rt_train
    from ray_tpu.models import (TrainState, llama_init, llama_loss,
                                llama_sharding_rules)
    from ray_tpu.models.train_state import (default_optimizer,
                                            make_train_step,
                                            shard_train_state)
    from ray_tpu.train.session import get_session

    seq, batch_size = config["seq"], config["batch"]
    cfg = train_model_config(config["model"], seq)
    tx = default_optimizer(lr=config["lr"], grad_clip=1.0)
    state = TrainState.create(
        llama_init(cfg, jax.random.PRNGKey(config["seed"])), tx)
    tokens = jax.random.randint(jax.random.PRNGKey(config["seed"] + 1),
                                (batch_size, seq), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}

    def loss_fn(p, b):
        return llama_loss(cfg, p, b["tokens"], b["targets"])

    mesh = rt_train.get_mesh()
    if mesh is None:
        step, scope = make_train_step(loss_fn, tx), contextlib.nullcontext()
    else:
        rules = llama_sharding_rules()
        state = shard_train_state(state, mesh, rules)
        step, scope = make_train_step(loss_fn, tx, mesh, rules), \
            jax.set_mesh(mesh)
    devices = jax.devices()
    with scope:
        t0 = time.perf_counter()
        compiled = step.lower(state, batch).compile()
        compile_s = time.perf_counter() - t0
        text = compiled.as_text()
        stats = [d.memory_stats() or {} for d in devices]
        info = {
            "pid": os.getpid(),
            "device": {"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(devices)},
            "vocab_size": cfg.vocab_size,
            "n_params": sum(x.size for x in jax.tree.leaves(state.params)),
            "compile_s": compile_s,
            "tpu_custom_calls": text.count("tpu_custom_call"),
            "collectives": get_session().telemetry.record_compiled(
                compiled),
            # Train state resident per device, before the first step.
            "bytes_in_use": [s.get("bytes_in_use") for s in stats],
            "param_shards": sorted({
                str(x.sharding.spec) for x in jax.tree.leaves(state.params)
            }) if mesh is not None else None,
            "mesh": dict(mesh.shape) if mesh is not None else None,
        }
        for i in range(config["steps"]):
            t = time.perf_counter()
            state, metrics = compiled(state, batch)
            loss = float(jax.block_until_ready(metrics["loss"]))
            step_s = time.perf_counter() - t
            rt_train.report({"step": i, "loss": loss, "step_s": step_s,
                             "tokens": batch_size * seq, **info})
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    rt_train.report({"step": config["steps"], "peak_bytes_in_use": peaks,
                     **info})


def train_phase(*, name: str, model: str, batch: int, seq: int, steps: int,
                lr: float, platform: str, chips: int, mesh=None) -> dict:
    """A few train steps through JaxTrainer in one worker holding ``chips``
    chips (0: a CPU rehearsal), checked, with the worker confirmed dead."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    result = JaxTrainer(
        _train_loop,
        train_loop_config=dict(model=model, batch=batch, seq=seq,
                               steps=steps, lr=lr, seed=SEED),
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=chips > 0,
            resources_per_worker={"TPU": chips} if chips else None,
            mesh=mesh),
        run_config=RunConfig(name=f"chip_smoke_{name}"),
    ).fit()
    if result.error is not None:
        raise result.error
    last = result.metrics
    rounds = [m for m in result.metrics_history if "loss" in m]
    losses = [m["loss"] for m in rounds]
    require(len(losses) == steps, f"{len(losses)} of {steps} steps reported")
    check_device(last["device"], platform, chips or None)
    require(all(math.isfinite(x) for x in losses), f"losses {losses}")
    require(abs(losses[0] - math.log(last["vocab_size"])) < 1.5,
            f"first loss {losses[0]} is not that of a random model "
            f"(ln {last['vocab_size']} = {math.log(last['vocab_size']):.2f})")
    require(losses[-1] < losses[0],
            f"loss did not fall on a repeated batch: {losses}")
    if platform == "tpu":
        require(last["tpu_custom_calls"] > 0,
                "the compiled step holds no tpu_custom_call: the Pallas "
                "flash kernel did not run")
    died_s = wait_dead(last["pid"])
    warm = sorted(m["step_s"] for m in rounds[1:])
    return {
        "phase": name, "model": model, "batch": batch, "seq": seq,
        **last["device"], "mesh": last["mesh"],
        "n_params": last["n_params"], "steps": steps, "losses": losses,
        "first_loss": losses[0], "last_loss": losses[-1],
        "compile_s": last["compile_s"], "first_step_s": rounds[0]["step_s"],
        "step_s_median_after_warmup": warm[len(warm) // 2],
        "tpu_custom_calls": last["tpu_custom_calls"],
        "collectives": last["collectives"],
        "bytes_in_use_per_device": last["bytes_in_use"],
        "peak_bytes_in_use_per_device": last["peak_bytes_in_use"],
        "param_shards": last["param_shards"],
        "worker_pid": last["pid"], "worker_dead_after_s": died_s,
    }


def sharded_phases(*, platform: str, chips: int, **train) -> list:
    """The sharded trainer on one worker holding every chip of the host
    (fsdp=2 x tp=2 over its local devices), then, once that process is
    dead, the same seeded batch on one device."""
    from ray_tpu.parallel import MeshConfig

    sharded = train_phase(name="train_sharded", platform=platform,
                          chips=chips, mesh=MeshConfig(fsdp=2, tp=2), **train)
    in_use = sharded["bytes_in_use_per_device"]
    if platform == "tpu":
        require(all(in_use) and max(in_use) < 2 * min(in_use),
                f"train state is not spread over the devices: {in_use}")
    require(any(s != "PartitionSpec()" for s in sharded["param_shards"]),
            f"every parameter is replicated: {sharded['param_shards']}")
    require(sum(sharded["collectives"].values()) > 0,
            "the sharded step holds no collective")
    single = train_phase(name="train_one_device", platform=platform,
                         chips=min(chips, 1), **train)
    for i, (a, b) in enumerate(zip(sharded["losses"], single["losses"])):
        rtol = SHARDED_LOSS_RTOL if i else SHARDED_FIRST_LOSS_RTOL
        require(abs(a - b) <= rtol * max(1.0, abs(b)),
                f"sharded losses {sharded['losses']} leave the one-device "
                f"losses {single['losses']} at step {i}")
    sharded["max_loss_diff_vs_one_device"] = max(
        abs(a - b) for a, b in zip(sharded["losses"], single["losses"]))
    return [sharded, single]


# -------------------------------------------------------------------- main


def worker_log_tails(since: float, n_bytes: int = 6000) -> str:
    from ray_tpu.core.node_main import LOG_ROOT

    out = []
    for path in sorted(glob.glob(os.path.join(LOG_ROOT, "*", "worker-*.log")),
                       key=os.path.getmtime):
        if os.path.getmtime(path) < since or not os.path.getsize(path):
            continue
        with open(path, "rb") as f:
            f.seek(max(0, os.path.getsize(path) - n_bytes))
            out.append(f"--- {path}\n{f.read().decode(errors='replace')}")
    return "\n".join(out[-4:])


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: only the sharded trainer on one host of "
                             "four chips, and its one-device comparison")
    args = parser.parse_args(argv)

    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        print(f"chip_smoke: JAX_PLATFORMS={platforms!r} keeps JAX off the "
              f"TPU; this script only runs on the chip", file=sys.stderr)
        return 2
    import ray_tpu
    from ray_tpu import _native, accelerators

    chips = accelerators.num_chips()
    if chips < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), this host has "
              f"{chips} (no /dev/accel*, no /dev/vfio/<n>)", file=sys.stderr)
        return 2
    # Armed in every worker: a trace after warm-up is an error, not a stall.
    os.environ["RT_DEBUG_JIT"] = "1"
    # stdout is the phase lines; the workers' output stays in their logs
    # (shown on failure) and is not mirrored here.
    os.environ["RT_LOG_TO_DRIVER"] = "0"
    started = time.time()
    ray_tpu.init()
    try:
        tpus = ray_tpu.cluster_resources().get("TPU", 0)
        emit({"phase": "start", "chips_detected": chips, "tpu_resource": tpus,
              "native_fastpath": _native.available,
              "compile_cache_dir": accelerators.compile_cache_dir()})
        require(tpus == chips, f"node advertises TPU={tpus}, host has {chips}")
        if args.chips == 1:
            serve = serve_phase(
                model="b1", engine=SERVE_ENGINE,
                prompt_lens=SERVE_PROMPT_LENS, new_tokens=SERVE_NEW_TOKENS,
                platform="tpu", num_tpus=1)
            emit(serve)
            phases = [serve, train_phase(name="train", platform="tpu",
                                         chips=1, **TRAIN)]
            emit(phases[1])
            require(serve["kind"] == phases[1]["kind"],
                    "the two workers saw different devices")
        else:
            phases = sharded_phases(platform="tpu", chips=args.chips, **TRAIN)
            for p in phases:
                emit(p)
    except BaseException:
        # Not a warning: the workers' own words, then the exception goes on
        # up with its traceback and the exit code is non-zero.
        print(worker_log_tails(started), file=sys.stderr)
        raise
    finally:
        ray_tpu.shutdown()
    if "jax" in sys.modules:
        raise SmokeFailure("the chip_smoke parent imported jax")
    emit({"ok": True, "device": {"platform": phases[0]["platform"],
                                 "kind": phases[0]["kind"],
                                 "count": phases[0]["count"]}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
