#!/usr/bin/env python3
r"""The glm4_moe_lite family through the paged cache (latent rows, absorbed
decode, long prompts prefilled in chunks over cached rows) against its plain
reference, at the configuration's own widths, on the device this process
holds:

    python3 benchmarks/reference/glm4_moe_lite_compare.py \
        --config glm-4.7-flash-L6 --seed <n> [--seed <m> ...] \
        [--faults] [--rehearse]

One process (it holds the chip; no cluster), outside any timed window, the
sibling of ``smallthinker_compare.py``, whose two-part comparison it uses as
it stands.  With seeded weights (norm weights drawn from 0.5..1.5 so that a
misplaced norm shows) it prefills the serving check's four prompts the way
the engine does (``engine._prefill_body``): the first chunk through
``paged.prefill_logits`` (expanded within the chunk), every further chunk of
``prefill_chunk`` tokens through ``paged.prefill_prefix_logits`` over the
latent rows the chunks before it cached (absorbed), with the engine's own
geometry (one latent pool of ``pool_pages``, page tables of
``pages_per_seq``).  At the cell's sizes (chunk 2048) the prompts of 300,
3000, 9000 and 17000 tokens are: inside one bucket; two chunks; five; the
cell's own prefix length, nine.  It then decodes 16 seeded tokens through
``paged.decode_logits`` (absorbed; one live slot of the engine's at a time,
so that the step's per-expert counts are that row's experts) and holds every
logit row (4 x 17) to ``glm4_moe_lite_ref.Reference.logits`` of the same
token sequence: a full forward pass in float32 at the highest matmul
precision, in the EXPANDED form, with no cache.  The last line of stdout is
one JSON object; exit 1 if the sound program is not correct or a fault is.

It also takes the reading the serving cell's own check takes
(``serve_cell.compare``, the traffic file's ``check.logit_tol``): the
system's greedy ``new_tokens`` after each prompt through the same pages,
each held to the reference by ``teacher_forced_gaps``; ``cell_gap_max`` is
the largest.

``--faults`` runs the comparison again for nine different computations,
each of which has to come out over the tolerance (``FAULTS``): the cached
latent rows rounded to float8_e4m3, the latent cached without its norm, the
selection bias entering the experts' weights, the routed weights without
``routed_scaling_factor``, the shared expert left out, rotary applied to the
first ``rope`` of q's unrotated dimensions too, the scores scaled by
``qk_nope_head_dim ** -0.5``, the experts' weights rounded to float8_e4m3,
and ``float8``: the whole model in the nearest precision under the bfloat16
the configuration states, as a deployment would run it (every matrix's
weights, embedding and head among them, and the cached latent rows in
float8_e4m3; the norms and the float32 router stay).  That last one is the
control of the serving cell's own limit (``check.logit_tol``): its
``cell_gap_max`` has to read over it.

A third part, CACHE, holds what the system CACHED to the reference: the
latent rows ``[norm(c_kv) ; rope(k_r)]`` of every position of every
sequence, read back out of the pool's pages, against
``Reference.latent_rows`` of the same tokens (the decode rows' experts
pinned), by the relative error of a row (the norm of the difference over
the norm of the reference's row), averaged over positions, layers and
sequences: ``cache_row_err_mean`` under ``ROW_TOL``.  The mean and not the
largest: a PROMPT token's routing is not pinned (the program counts a
chunk's experts together), and a token that took the other expert of a tie
caches another row in every later layer (the largest reads 0.68-0.78 in
every sound run).  With weights drawn from a seed, attention over thousands
of positions is nearly flat and its output a few hundredths of the residual
stream, so a cached row in float8 moves no logit past bfloat16's own
rounding (0.106-0.132 against the sound program's 0.071-0.145, my chip
runs, PR 36): the logits cannot see it, the rows can (mean error 0.015-0.020
sound over 48 seeds, 0.038-0.040 in float8 over 8).

Tolerance, and what routing has to do with it: as
``smallthinker_compare.py`` says of its own.  The model renormalises its
top-4 weights (x 1.8), so a swap between the fourth and fifth expert moves a
row's logits as a fault does; the experts the system took at every decode
row are handed to the reference (ROUTING: their ``reach`` from its own
choice, in score + bias, under ``SWAP_MARGIN`` or a violation), and with the
routing so pinned every decode row is held to ``LOGIT_TOL`` (LOGITS).  Both
constants lie between the readings PERF.md section 6 gives.

``--rehearse`` is the same at the family's tiny configuration and the
traffic file's ``rehearsal`` sizes, for the tests on the CPU.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

LOGIT_TOL = {"bfloat16": 0.30, "float32": 1e-4}  # by the model's dtype
#: The mean relative error of a cached latent row (see CACHE above).
ROW_TOL = {"bfloat16": 0.028, "float32": 1e-5}
#: Under this margin between the reference's k-th and next selection score
#: (sigmoid score + bias, so in 0..1 and not in logits) the system may take
#: either expert.
SWAP_MARGIN = {"bfloat16": 0.03, "float32": 0.0}
FAULTS = ("float8-latent", "unnormalised-latent", "bias-in-weights",
          "no-scaling", "no-shared-expert", "rotary-on-nope",
          "scale-by-nope", "float8-experts", "float8")


@contextlib.contextmanager
def faulted(cfg, fault):
    """``cfg`` computing something else, the named fault: a configuration
    that says so where a field does, else the program's own function
    swapped for the while (``float8-experts`` rounds the weights, in
    ``main``)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import block, moe, paged

    swaps = []

    def swap(module, name, fn):
        swaps.append((module, name, getattr(module, name)))
        setattr(module, name, fn)

    if fault == "no-scaling":
        cfg = dataclasses.replace(cfg, routed_scaling_factor=1.0)
    elif fault == "no-shared-expert":
        cfg = dataclasses.replace(cfg, n_shared_experts=0)
    elif fault in ("float8-latent", "float8"):
        real_row = paged._latent_row
        swap(paged, "_latent_row", lambda *a: jax.lax.reduce_precision(
            real_row(*a), 4, 3))
    elif fault == "unnormalised-latent":
        def project(config, a, h):
            q, _, k_r = real_project(config, a, h)
            return q, (h @ a["wkv_a"])[..., :config.kv_lora_rank], k_r
        real_project = block.project_latent
        swap(block, "project_latent", project)
    elif fault == "bias-in-weights":
        def route(config, m, xf, logits=None):
            probs, _, top_e = real_route(config, m, xf, logits)
            if logits is None:
                logits = moe.router_logits(m, xf)
            top_p = jnp.take_along_axis(
                jax.nn.sigmoid(logits) + m["router_bias"], top_e, axis=-1)
            top_p = top_p / (top_p.sum(-1, keepdims=True) + 1e-20)
            return probs, top_p * config.routed_scaling_factor, top_e
        real_route = moe._route
        swap(moe, "_route", route)
    elif fault in ("rotary-on-nope", "scale-by-nope"):
        def attend(config, pools, i, q, *rest, cos, sin, positions, **kw):
            rope = config.qk_rope_head_dim
            if fault == "rotary-on-nope":
                q = jnp.concatenate([paged._rotary_single(
                    q[..., :rope], cos, sin, positions), q[..., rope:]], -1)
            else:  # what dividing by sqrt(nope) does to the scores
                q = (q * (config.head_dim / config.qk_nope_head_dim) ** 0.5
                     ).astype(q.dtype)
            return real_attend(config, pools, i, q, *rest, cos=cos, sin=sin,
                               positions=positions, **kw)
        real_attend = paged._latent_attend
        swap(paged, "_latent_attend", attend)
    try:
        yield cfg
    finally:
        for module, name, was in swaps:
            setattr(module, name, was)


def _float8(params, everything):
    """The routed and shared experts' weights (``everything``: every
    matrix's, embedding and head among them; the norms, the float32 router
    and its bias stay) rounded to float8_e4m3's 4 exponent and 3 mantissa
    bits, a matrix at a time (no second copy of the tree).
    ``reduce_precision`` and not a cast there and back, which the TPU
    compiler is free to drop (it allows excess precision)."""
    import jax

    rnd = jax.jit(lambda w: jax.lax.reduce_precision(w, 4, 3),
                  donate_argnums=0)
    if everything:
        return jax.tree_util.tree_map_with_path(
            lambda path, w: w if w.ndim < 2 or "router" in str(path[-1])
            else rnd(w), params)
    for layer in params["layers"]:
        if "moe" not in layer:
            continue
        for name in ("w1", "w2", "w3"):
            layer["moe"][name] = rnd(layer["moe"][name])
            layer["moe"]["shared"][name] = rnd(layer["moe"]["shared"][name])
    return params


def programs():
    """The three paged programs, jitted apart from every other caller's (a
    fault swaps a module's function: a trace another caller cached would
    not see it)."""
    import jax

    from ray_tpu.models import paged

    return tuple(
        jax.jit(lambda *a, f=f: f(*a), static_argnums=0, donate_argnums=2)
        for f in (paged.prefill_logits, paged.prefill_prefix_logits,
                  paged.decode_logits))


def system_logits(cfg, params, ec, seqs, new, progs, greedy=False):
    """Prefill all but the last ``new`` tokens of each sequence as the
    engine does (in chunks of the largest bucket), then ``new`` decode
    steps, each fed the sequence's next token (``greedy``: the best token
    of its last row instead, as the engine at temperature 0).  The
    sequences go one after the other, each in a slot and in pages of its
    own and alone live while it decodes.  Returns, a sequence: logits
    [1 + new, V], the experts of each decode row [new, L, k] (the
    step's per-expert counts, which with one live row are its experts; -1
    in a dense layer), and the latent rows its pages hold at the end
    [L, len(seq), rank + rope] (on the device, the padding dropped)."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import paged

    prefill, suffix, decode = progs
    ps, maxp, b = ec.page_size, ec.pages_per_seq, ec.batch_slots
    buckets = ec.prefill_buckets()
    chunk = buckets[-1]
    pools = paged.init_paged_pools(cfg, ec.pool_pages, ps)
    adapters = paged.init_adapter_pool(cfg, ec.max_adapters, ec.lora_rank)
    zero = jnp.asarray(ec.max_adapters, jnp.int32)
    ids = jnp.full((b,), ec.max_adapters, jnp.int32)
    out = []
    for slot, seq in enumerate(seqs):
        n = len(seq) - new
        tables = np.full((b, maxp), ec.pool_pages, np.int32)
        tables[slot] = slot * maxp + np.arange(maxp)  # pages of its own
        table = jnp.asarray(tables[slot])
        for start in range(0, n, chunk):
            end = min(start + chunk, n)
            bucket = next(x for x in buckets if x >= end - start)
            pad = np.zeros((1, bucket), np.int32)
            pad[0, :end - start] = seq[start:end]
            if start:
                logits, pools, _ = suffix(
                    cfg, params, pools, adapters, jnp.asarray(pad),
                    jnp.asarray(start), jnp.asarray(end), table, zero)
            else:
                logits, pools, _ = prefill(
                    cfg, params, pools, adapters, jnp.asarray(pad),
                    jnp.asarray(end), table, zero)
        rows, experts = [np.asarray(logits[0])], []
        for i in range(new):
            toks, lens = np.zeros((b,), np.int32), np.zeros((b,), np.int32)
            toks[slot] = rows[-1].argmax() if greedy else seq[n + i]
            lens[slot] = n + i
            logits, pools, counts = decode(
                cfg, params, pools, adapters, jnp.asarray(toks),
                jnp.asarray(tables), jnp.asarray(lens),
                jnp.asarray(np.arange(b) == slot), ids)
            rows.append(np.asarray(logits)[slot])
            experts.append(np.stack(
                [np.full((cfg.top_k,), -1) if c is None
                 else np.nonzero(np.asarray(c))[0] for c in counts]))
        width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        cached = pools["kv"][:, table].reshape(
            cfg.n_layers, maxp * ps, -1)[:, :len(seq), :width]
        out.append((np.stack(rows), np.stack(experts), cached))
    return out


def cache_errors(ref, seqs, new, system):
    """CACHE: over every sequence, layer and position, the relative error
    of the row the system cached against the reference's (the decode rows'
    experts pinned, as ``compare`` pins them)."""
    import jax.numpy as jnp
    import numpy as np

    worst, means = 0.0, []
    for seq, (_, experts, cached) in zip(seqs, system):
        n = len(seq) - new
        given = np.full((experts.shape[1], len(seq), experts.shape[2]), -1,
                        np.int32)
        given[:, n:] = experts.transpose(1, 0, 2)
        want = ref.latent_rows(seq, given)
        err = jnp.linalg.norm(cached.astype(jnp.float32) - want, axis=-1) \
            / jnp.linalg.norm(want, axis=-1)
        worst = max(worst, float(err.max()))
        means.append(float(err.mean()))
    return {"cache_row_err_max": worst,
            "cache_row_err_mean": float(np.mean(means))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="glm-4.7-flash-L6")
    ap.add_argument("--traffic", default="serve-agent-shared-context",
                    help="the traffic file whose engine geometry is used")
    ap.add_argument("--seed", type=int, action="append")
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--fault", action="append", choices=FAULTS,
                    help="only these faults (with --faults: all)")
    ap.add_argument("--rehearse", action="store_true",
                    help="the family's tiny configuration, on the CPU")
    args = ap.parse_args(argv)

    import jax

    from benchmarks import spec
    from benchmarks.reference.olmoe_compare import (_weights, cell_gap_max,
                                                    sequences)
    from benchmarks.reference.smallthinker_compare import compare
    from ray_tpu.serve.engine import EngineConfig

    model = spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", args.config + ".json"))
    tr = spec.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", args.traffic + ".json"))
    if args.rehearse:
        cell = spec.rehearsal_cell({"model": model, "traffic": tr}, ROOT)
        model, tr = cell["model"], cell["traffic"]
    fam = spec.family(model)
    ec = EngineConfig(**tr["engine"])
    check, new = tr["check"], tr["check"]["new_tokens"]
    tol = LOGIT_TOL[model["torch_dtype"]]
    row_tol = ROW_TOL[model["torch_dtype"]]
    faults = FAULTS if args.faults else tuple(
        f for f in FAULTS if f in (args.fault or ()))
    swap_margin = SWAP_MARGIN[model["torch_dtype"]]
    cfg = fam.program_config(model, remat=False,
                             max_seq=ec.pages_per_seq * ec.page_size)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    # One trace for every seed's run of a program, sound or faulted (a
    # faulted one keeps the function that was swapped in while it traced).
    traced = {}
    results, ok = [], True
    for seed in args.seed or [0]:
        weights = functools.partial(_weights, fam, cfg, seed % (2 ** 31 - 1))
        params = weights()
        seqs = sequences(model, check, seed)
        # The system first, sound and faulted (the reference is handed
        # what it took): the chip holds one copy of the weights at a time.
        runs, systems, emitted = [], [], []
        for fault in (None, *faults):
            if fault in ("float8-experts", "float8"):
                # Last, and in this order: they round the weights in place.
                params = _float8(params, fault == "float8")
            t0 = time.time()
            with faulted(cfg, fault) as fcfg:
                progs = traced.setdefault(fault, programs())
                systems.append(system_logits(fcfg, params, ec, seqs, new,
                                             progs))
                emitted.append([
                    out[0][:new].argmax(-1).tolist() for out in
                    system_logits(fcfg, params, ec, seqs, new, progs,
                                  greedy=True)])
            runs.append({"seed": seed, "fault": fault,
                         "system_seconds": round(time.time() - t0, 1)})
        if {"float8-experts", "float8"} & set(faults):  # sound weights again
            del params
            params = weights()
        ref = fam.reference(model, params)
        for r, system in zip(runs, systems):
            r.update(compare(ref, seqs, new, [s[:2] for s in system], tol,
                             swap_margin))
            r.update(cache_errors(ref, seqs, new, system))
        for r, outputs in zip(runs, emitted):
            r["cell_gap_max"] = cell_gap_max(ref, seqs, new, outputs)
            r["cell_check_passes"] = r["cell_gap_max"] <= check["logit_tol"]
            r["correct"] = not (r["rows_over"] or r["routing_violations"]
                                or r["cache_row_err_mean"] > row_tol)
            ok &= r["correct"] == (r["fault"] is None)
            print(json.dumps(r), flush=True)
        results += runs
        del ref, params, systems  # one copy of the weights at a time
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    print(json.dumps({"ok": ok, "logit_tol": tol, "row_tol": row_tol,
                      "memory_peak_bytes": peak,
                      "swap_margin": swap_margin,
                      "cell_logit_tol": check["logit_tol"], "device": device,
                      "config": model["name"],
                      "layers": model["num_hidden_layers"],
                      "prompt_lens": check["prompt_lens"],
                      "results": results}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
