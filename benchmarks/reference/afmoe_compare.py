#!/usr/bin/env python3
r"""The afmoe family (Trinity-Mini) through the paged cache (window rings
beside one whole-length table, a dense window layer before routed ones, long
prompts prefilled in chunks) against its plain reference, at the
configuration's own widths, on the device this process holds:

    python3 benchmarks/reference/afmoe_compare.py \
        --config trinity-mini-L5 --seed <n> [--seed <m> ...] \
        [--faults | --fault <name> ...] [--rehearse]

One process (it holds the chip; no cluster), outside any timed window, the
sibling of ``smallthinker_compare.py`` and ``glm4_moe_lite_compare.py``,
whose two-part comparison (``smallthinker_compare.compare``) it uses as it
stands.  With seeded weights (norm weights drawn from 0.5..1.5 so that a
misplaced norm shows: the four of a layer and the two of a head; the two
post-norms at the embedding's scale, ``weights``) it prefills
the serving check's prompts the way the engine does
(``engine._prefill_body``): the first chunk through ``paged.prefill_logits``,
every further chunk of ``prefill_chunk`` tokens through
``paged.prefill_prefix_logits`` over what the chunks before it cached, with
the engine's own geometry (page tables of ``pages_per_seq``, rings of
``paged.ring_entries``).  At the cell's sizes (window 2048, chunk 2048, ring
4096) the prompts of 2040 and 4090 tokens are: inside one bucket and just
under the window, so that the 16 decode steps cross it; two chunks and just
under ``window + chunk``, so that they cross the ring's wrap.  It then
decodes 16 seeded tokens through ``paged.decode_logits`` (one live slot of
the engine's at a time, so that the step's per-expert counts are that row's
experts) and holds every logit row (2 x 17) to
``afmoe_ref.Reference.logits`` of the same token sequence: a full forward
pass in float32 at the highest matmul precision, with no cache.  The last
line of stdout is one JSON object; exit 1 if the sound program is not
correct or a fault is.

It also takes the reading the serving cell's own check takes
(``serve_cell.compare``, the traffic file's ``check.logit_tol``): the
system's greedy ``new_tokens`` after each prompt through the same pages,
each held to the reference by ``teacher_forced_gaps``; ``cell_gap_max`` is
the largest.

``--faults`` runs the comparison again for ten different computations, each
of which has to come out over the tolerance (``FAULTS``): attention without
its gate, the attention half's post-norm left out, QK-norm over the
projection's whole width instead of a head, rotary applied on the full layer
too, the window left out of the sliding layers, the selection bias entering
the experts' weights, the routed weights without ``route_scale``, the
embedding without its ``sqrt(hidden_size)``, the experts' weights rounded to
float8_e4m3, and ``float8``: the whole model in the nearest precision under
the bfloat16 the configuration states, as a deployment would run it (every
matrix's weights, embedding and head among them, and the cached K and V rows
in float8_e4m3; the norms and the float32 router stay).  That last one is
the control of the serving cell's own limit (``check.logit_tol``): its
``cell_gap_max`` has to read over it.  One of the ten, the bias in the
weights, is under bfloat16's rounding at the bias's assumed size and is held
by a float32 configuration only (``UNDER_BFLOAT16``).

Tolerance, and what routing has to do with it: as
``smallthinker_compare.py`` says of its own.  The model renormalises its
top-8 weights (x 2.826), so a swap between the eighth and ninth expert moves
a row's logits as a fault does; the experts the system took at every decode
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

#: By the model's dtype.  bfloat16's lies between two readings on the chip
#: (PR 41, PERF.md section 6): the sound program's worst decode row over 14
#: seeds, 0.051-0.059, and the nearest fault's, rotary on the full layer,
#: 0.42-0.44 (then QK-norm over the width 0.92-0.94).
LOGIT_TOL = {"bfloat16": 0.15, "float32": 1e-4}
#: Under this margin between the reference's k-th and next selection score
#: (sigmoid score + bias, so in 0..1 and not in logits) the system may take
#: either expert.
SWAP_MARGIN = {"bfloat16": 0.03, "float32": 0.0}
FAULTS = ("no-gate", "no-attn-post-norm", "qk-norm-over-width",
          "rotary-on-full", "no-window", "bias-in-weights", "no-route-scale",
          "no-embed-scale", "float8-experts", "float8")
#: Faults that bfloat16's own rounding hides, so that only a float32
#: configuration (the rehearsal, the CPU tests) is held to refuse them: the
#: selection bias is drawn at normal / (2 x 128), so adding it to a chosen
#: score of 0.8-0.9 moves an expert's weight by half a percent, a routed
#: layer's output by less than one bfloat16 rounding.  On the chip it reads
#: as the sound program does (0.052-0.054 against 0.051-0.059, PR 41); in
#: float32 it reads 3000 times the tolerance.  Reported either way.
UNDER_BFLOAT16 = ("bias-in-weights",)


@contextlib.contextmanager
def faulted(cfg, fault):
    """``cfg`` computing something else, the named fault: a configuration
    that says so where a field does, else the program's own function
    swapped for the while (the two float8 faults round the weights, in
    ``main``; ``float8`` the cached rows too, here)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import block, llama, moe, paged
    from ray_tpu.ops.norms import rms_norm

    swaps = []

    def swap(module, name, fn):
        swaps.append((module, name, getattr(module, name)))
        setattr(module, name, fn)

    if fault == "no-gate":
        cfg = dataclasses.replace(cfg, attn_gate=False)
    elif fault == "rotary-on-full":
        cfg = dataclasses.replace(cfg, rope_layout=(1,) * cfg.n_layers)
    elif fault == "no-window":  # same rings, nothing masked by age
        cfg = dataclasses.replace(cfg, window=1 << 30)
    elif fault == "no-route-scale":
        cfg = dataclasses.replace(cfg, routed_scaling_factor=1.0)
    elif fault == "no-embed-scale":
        cfg = dataclasses.replace(cfg, embed_scale=1.0)
    elif fault == "no-attn-post-norm":
        real_norm = block.post_norm
        swap(block, "post_norm", lambda config, layer, name, out: out
             if name == "attn_post_norm"
             else real_norm(config, layer, name, out))
    elif fault == "qk-norm-over-width":
        def over_width(config, a, q, k):
            hd = config.head_dim
            return tuple(
                rms_norm(x, jnp.tile(a[w], x.shape[-1] // hd),
                         config.norm_eps)
                for x, w in ((q, "q_norm"), (k, "k_norm")))
        swap(llama, "_qk_norm", over_width)
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
    elif fault == "float8":
        real_write = paged._write_kv
        swap(paged, "_write_kv", lambda pools, layer, page_idx, off, **rows:
             real_write(pools, layer, page_idx, off, **{
                 name: jax.lax.reduce_precision(new, 4, 3)
                 for name, new in rows.items()}))
    try:
        yield cfg
    finally:
        for module, name, was in swaps:
            setattr(module, name, was)


def weights(fam, cfg, seed):
    """``olmoe_compare._weights`` (seeded, every norm weight drawn from
    0.5..1.5), with the two post-norms of a layer multiplied by
    ``embed_scale``, where ``moe_init`` puts them: a half-block's output
    is then of the scaled embedding's size.  Drawn around 1 they would
    make the five layers a fiftieth of the residual stream, and at
    bfloat16 no fault in a layer would show in a logit (my chip run,
    PR 41: the gate left out read 0.12, the sound program 0.03)."""
    import jax

    from benchmarks.reference.olmoe_compare import _weights

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: (leaf * cfg.embed_scale).astype(leaf.dtype)
        if "post_norm" in jax.tree_util.keystr(path) else leaf,
        _weights(fam, cfg, seed))


def system_logits(cfg, params, ec, seqs, new, progs, greedy=False):
    """Prefill all but the last ``new`` tokens of each sequence as the
    engine does (in chunks of the largest bucket), then ``new`` decode
    steps, each fed the sequence's next token (``greedy``: the best token
    of its last row instead, as the engine at temperature 0).  The
    sequences go one after the other, each in a slot, in pages and in a
    ring of its own and alone live while it decodes.  Returns, a sequence:
    logits [1 + new, V], and the experts of each decode row [new, L, k]
    (the step's per-expert counts, which with one live row are its
    experts; -1 in a dense layer)."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import paged

    prefill, suffix, decode = progs
    ps, maxp, b = ec.page_size, ec.pages_per_seq, ec.batch_slots
    buckets = ec.prefill_buckets()
    chunk = buckets[-1]
    ring = min(maxp, paged.ring_entries(cfg, ps, chunk))
    pools = paged.init_paged_pools(cfg, ec.pool_pages, ps, b * ring)
    adapters = paged.init_adapter_pool(cfg, ec.max_adapters, ec.lora_rank)
    zero = jnp.asarray(ec.max_adapters, jnp.int32)
    ids = jnp.full((b,), ec.max_adapters, jnp.int32)
    out = []
    for slot, seq in enumerate(seqs):
        n = len(seq) - new
        tables = np.full((b, maxp), ec.pool_pages, np.int32)
        rings = np.full((b, ring), b * ring, np.int32)
        tables[slot] = slot * maxp + np.arange(maxp)  # pages of its own
        rings[slot] = slot * ring + np.arange(ring)
        table, rt = jnp.asarray(tables[slot]), jnp.asarray(rings[slot])
        for start in range(0, n, chunk):
            end = min(start + chunk, n)
            bucket = next(x for x in buckets if x >= end - start)
            pad = np.zeros((1, bucket), np.int32)
            pad[0, :end - start] = seq[start:end]
            if start:
                logits, pools, _ = suffix(
                    cfg, params, pools, adapters, jnp.asarray(pad),
                    jnp.asarray(start), jnp.asarray(end), table, zero, rt)
            else:
                logits, pools, _ = prefill(
                    cfg, params, pools, adapters, jnp.asarray(pad),
                    jnp.asarray(end), table, zero, rt)
        rows, experts = [np.asarray(logits[0])], []
        for i in range(new):
            toks, lens = np.zeros((b,), np.int32), np.zeros((b,), np.int32)
            toks[slot] = rows[-1].argmax() if greedy else seq[n + i]
            lens[slot] = n + i
            logits, pools, counts = decode(
                cfg, params, pools, adapters, jnp.asarray(toks),
                jnp.asarray(tables), jnp.asarray(lens),
                jnp.asarray(np.arange(b) == slot), ids, jnp.asarray(rings))
            rows.append(np.asarray(logits)[slot])
            experts.append(np.stack(
                [np.full((cfg.top_k,), -1) if c is None
                 else np.nonzero(np.asarray(c))[0] for c in counts]))
        out.append((np.stack(rows), np.stack(experts)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="trinity-mini-L5")
    ap.add_argument("--traffic", default="serve-reasoning-long-decode",
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
    from benchmarks.reference.glm4_moe_lite_compare import _float8, programs
    from benchmarks.reference.olmoe_compare import cell_gap_max, sequences
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
    swap_margin = SWAP_MARGIN[model["torch_dtype"]]
    faults = FAULTS if args.faults else tuple(
        f for f in FAULTS if f in (args.fault or ()))
    cfg = fam.program_config(model, remat=False,
                             max_seq=ec.pages_per_seq * ec.page_size)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    # One trace for every seed's run of a program, sound or faulted (a
    # faulted one keeps the function that was swapped in while it traced).
    traced = {}
    results, ok = [], True
    for seed in args.seed or [0]:
        fresh = functools.partial(weights, fam, cfg, seed % (2 ** 31 - 1))
        params = fresh()
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
                    rows[:new].argmax(-1).tolist() for rows, _ in
                    system_logits(fcfg, params, ec, seqs, new, progs,
                                  greedy=True)])
            runs.append({"seed": seed, "fault": fault,
                         "system_seconds": round(time.time() - t0, 1)})
        if {"float8-experts", "float8"} & set(faults):  # sound weights again
            del params
            params = fresh()
        ref = fam.reference(model, params)
        for r, system in zip(runs, systems):
            r.update(compare(ref, seqs, new, system, tol, swap_margin))
        for r, outputs in zip(runs, emitted):
            r["cell_gap_max"] = cell_gap_max(ref, seqs, new, outputs)
            r["cell_check_passes"] = r["cell_gap_max"] <= check["logit_tol"]
            r["correct"] = not (r["rows_over"] or r["routing_violations"])
            if not (r["fault"] in UNDER_BFLOAT16
                    and model["torch_dtype"] == "bfloat16"):
                ok &= r["correct"] == (r["fault"] is None)
            print(json.dumps(r), flush=True)
        results += runs
        del ref, params, systems  # one copy of the weights at a time
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    print(json.dumps({"ok": ok, "logit_tol": tol,
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
