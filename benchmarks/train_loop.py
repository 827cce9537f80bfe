"""The train loop the benchmark hands to ``JaxTrainer``: a user function
like any other (``chip_smoke.py:_train_loop`` is the pattern).  It runs in
the one worker that holds the cell's chips and does the whole of a run
there: weights from the seed in one jitted call, the comparison with the
plain reference, the state, the compile, warm-up, the timed window, an
optional device trace, and one report at the end.
"""

from __future__ import annotations

from typing import Any, Dict


def train_loop(config: Dict[str, Any]) -> None:
    import contextlib
    import math
    import os
    import shutil
    import tempfile
    import time

    from benchmarks.deployment import (compile_cache_report, device_report,
                                       phase_error, trace_options)

    fail = config.get("fail_phase", "")
    phase = "libtpu_start"

    def enter(name: str) -> None:
        nonlocal phase
        phase = name
        if fail == name:
            raise RuntimeError("forced failure")

    try:
        enter("libtpu_start")
        import jax
        import jax.numpy as jnp

        devices = jax.devices()
        if devices[0].platform != config["platform"]:
            raise RuntimeError(
                f"the train worker runs on {devices[0].platform!r}, the "
                f"cell needs {config['platform']!r}: no fallback")
        devices_ready_wall = time.time()

        from benchmarks import spec, trace_reduce, traffic as gen
        from ray_tpu import train as rt_train
        from ray_tpu.models import TrainState
        from ray_tpu.models.train_state import (default_optimizer,
                                                make_train_step)
        from ray_tpu.parallel.sharding import named_sharding

        model, tr = config["model"], config["traffic"]
        batch_size, seq = tr["batch"], tr["seq"]
        seed = gen.seed32(config["seed"])
        fam = spec.family(model)
        cfg = fam.program_config(model, max_seq=seq, **tr["model_options"])
        tx = default_optimizer(lr=tr["lr"], grad_clip=tr["grad_clip"])
        mesh = rt_train.get_mesh()
        if (mesh is None) != (tr["mesh"] is None):
            raise RuntimeError(f"traffic asks for mesh {tr['mesh']}, the "
                               f"session gave {mesh}")

        def loss_fn(p, b):
            return fam.loss(cfg, p, b["tokens"], b["targets"])

        def host_batch(i: int):
            return gen.train_batch(config["seed"], i, batch_size, seq,
                                   model["vocab_size"])

        enter("compile")
        t_c = time.perf_counter()
        init = lambda key: fam.init(cfg, key)  # noqa: E731
        if mesh is None:
            rules, scope = None, contextlib.nullcontext
            make_params = jax.jit(init)
            make_state = jax.jit(lambda p: TrainState.create(p, tx),
                                 donate_argnums=0)
            step = make_train_step(loss_fn, tx)
        else:
            # A fresh context each time: jax.set_mesh's is spent after one
            # use, and entering it again sets no mesh, without a word.
            rules = fam.sharding_rules(cfg)
            scope = lambda: jax.set_mesh(mesh)  # noqa: E731
            shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
            make_params = jax.jit(init, out_shardings=named_sharding(
                mesh, rules.tree_specs(shapes)))
            st_shapes = jax.eval_shape(
                lambda p: TrainState.create(p, tx), shapes)
            make_state = jax.jit(
                lambda p: TrainState.create(p, tx), donate_argnums=0,
                out_shardings=TrainState(
                    params=named_sharding(
                        mesh, rules.tree_specs(st_shapes.params)),
                    opt_state=named_sharding(
                        mesh, rules.tree_specs(st_shapes.opt_state)),
                    step=named_sharding(mesh, jax.sharding.PartitionSpec())))
            step = make_train_step(loss_fn, tx, mesh, rules)
        with scope():
            params = jax.block_until_ready(
                make_params(jax.random.PRNGKey(seed)))
        weights_s = time.perf_counter() - t_c

        # The family's plain reference on the same weights and the first
        # batch, before the optimizer state takes its room.
        enter("compare")
        t_r = time.perf_counter()
        b0 = host_batch(0)
        ref_loss, ref_gnorm = fam.reference(
            model, params, devices[0]).loss_and_grad_norm(
                b0["tokens"], b0["targets"])
        reference_s = time.perf_counter() - t_r

        enter("compile")
        with scope():
            if mesh is not None and jax.sharding.get_abstract_mesh().empty:
                raise RuntimeError("no ambient mesh while compiling the "
                                   "sharded step")
            state = make_state(params)
            del params
            t_c = time.perf_counter()
            compiled = step.lower(
                state, jax.tree.map(jnp.asarray, b0)).compile()
            compile_s = time.perf_counter() - t_c
            text = compiled.as_text()

            enter("warmup")
            losses, gnorms = [], []
            for i in range(1 + tr["warmup_steps"]):
                state, m = compiled(state, jax.tree.map(jnp.asarray,
                                                        host_batch(i)))
                losses.append(float(jax.block_until_ready(m["loss"])))
                gnorms.append(float(m["grad_norm"]))
            first_loss, first_gnorm = losses[0], gnorms[0]

            enter("measure")
            seconds, tracing = config["seconds"], config["trace"]
            trace_dir, traced, trace_window = None, {}, 0.0
            trace_from, trace_steps = 2, int(tr.get("trace_steps", 4))
            step_s = []
            i = 1 + tr["warmup_steps"]
            window_wall = time.time()
            t0 = time.perf_counter()
            while True:
                n = len(step_s)
                if tracing and n == trace_from:
                    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
                    jax.profiler.start_trace(
                        trace_dir, profiler_options=trace_options())
                    t_tr = time.perf_counter()
                t = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench:batch_upload"):
                    batch = jax.tree.map(jnp.asarray, host_batch(i))
                with jax.profiler.TraceAnnotation("bench:train_step"):
                    state, m = compiled(state, batch)
                with jax.profiler.TraceAnnotation("bench:loss_readback"):
                    losses.append(float(jax.block_until_ready(m["loss"])))
                now = time.perf_counter()
                step_s.append(now - t)
                i += 1
                if tracing and trace_dir and not trace_window \
                        and len(step_s) == trace_from + trace_steps:
                    trace_window = time.perf_counter() - t_tr
                    jax.profiler.stop_trace()
                if now - t0 >= seconds:
                    break
            elapsed = now - t0
        if trace_window:  # read the trace outside the window
            traced = trace_reduce.reduce_trace_dir(trace_dir, trace_window)
            traced["traced_steps"] = trace_steps
            shutil.rmtree(trace_dir, ignore_errors=True)
        rt_train.report({
            "pid": os.getpid(), "device": device_report(),
            "compile_cache": compile_cache_report(),
            "devices_ready_wall": devices_ready_wall,
            "window_wall": window_wall, "elapsed_s": elapsed,
            "steps": len(step_s), "step_s": step_s,
            "tokens_per_step": batch_size * seq,
            "losses": losses, "first_loss": first_loss,
            "first_grad_norm": first_gnorm, "ref_loss": ref_loss,
            "ref_grad_norm": ref_gnorm, "weights_s": weights_s,
            "reference_s": reference_s, "compile_s": compile_s,
            "tpu_custom_calls": text.count("tpu_custom_call"),
            "mesh": dict(mesh.shape) if mesh is not None else None,
            "all_finite": all(math.isfinite(x) for x in losses),
            "trace": traced,
        })
    except Exception as e:  # noqa: BLE001 — re-raised with its phase
        raise phase_error(phase, e) from e
