#!/usr/bin/env python3
r"""The kimi_linear family through the engine's programs (gated delta-rule
layers whose state lives in a slot, the chunk form in every prefill and the
recurrent form in decode, latent rows for the latent layers only, a chip's
share of the experts) against its plain reference, at the configuration's
own widths, on the device this process holds:

    python3 benchmarks/reference/kimi_linear_compare.py \
        --config kimi-linear-48b-a3b-L13 --seed <n> [--seed <m> ...] \
        [--faults | --fault <name>] [--rehearse]

One process (it holds the chip; no cluster), outside any timed window, the
sibling of ``glm4_moe_lite_compare.py``, whose two-part comparison
(``smallthinker_compare.compare``) it uses as it stands.  With seeded
weights (norm weights drawn from 0.5..1.5 so that a misplaced norm shows) it
prefills the serving check's four prompts the way the engine does
(``engine._prefill_body``): the first chunk through ``paged.prefill_logits``
(state from zeros), every further chunk of ``prefill_chunk`` tokens through
``paged.prefill_prefix_logits`` (the slot's state taken in and handed on,
the latent layers over the rows the chunks before cached), with the
engine's own geometry.  At the cell's sizes (chunk 2048, blocks of 64) the
prompts of 300, 1500, 3000 and 7000 tokens are: five blocks in one bucket;
the 2048 bucket with padded rows behind the last real one; two chunks; four
chunks.  It then decodes 16 seeded tokens through ``paged.decode_logits``
(the recurrent form; one live slot at a time) and holds every logit row
(4 x 17) to ``kimi_linear_ref.Reference.logits`` of the same token sequence:
a full forward pass in float32 at the highest matmul precision, the
token-by-token recurrence, no chunk, no cache.  The last line of stdout is
one JSON object; exit 1 if the sound program is not correct or a fault is
(but see ``FLOAT32_ONLY``).

Routing is pinned as in the siblings: the experts the system's ROUTER took
at every decode row (all eight, held here or not: recorded from the
program's own ``moe._route`` while it is traced) are handed to the
reference, their ``reach`` from its own choice is held under
``SWAP_MARGIN``, and every decode row's logits are held to ``LOGIT_TOL``.
A swap between the eighth and ninth expert matters where either is one of
the 32 held: a whole expert's term enters or leaves this chip's partial sum.

A third part, STATE, holds what the system KEEPS to the reference: the
matrix state of every KDA layer in the sequence's slot after the last decode
step, against ``Reference.states`` of the same tokens (the decode rows'
experts pinned), by the relative error of a head's state (the Frobenius norm
of the difference over that of the reference's), averaged over heads, layers
and sequences: ``state_err_mean`` under ``STATE_TOL``.  At the published
widths in bfloat16 the sound program's states read 0.034-0.042 from the
float32 reference's (my chip runs, PR 43): the keys and values written into
them come from bfloat16 activations.  That is also why ONE fault is held in
float32 only (``FLOAT32_ONLY``): the state held in bfloat16 reads 0.035 in
its states and 0.17-0.25 in its logits there, as the sound program does (a
decaying state forgets its rounding as it forgets its tokens), and 0.0036
against 6e-7 at the tiny configuration in float32, where the tests hold it.

It also takes the reading the serving cell's own check takes
(``serve_cell.compare``, the traffic file's ``check.logit_tol``): the
system's greedy ``new_tokens`` after each prompt, each held to the reference
by ``teacher_forced_gaps``; ``cell_gap_max`` is the largest.

``--faults`` runs the comparison again for twelve different computations,
each of which has to come out not correct (``FAULTS``): the decay left out
(a = 1); the head-wise mean of the decay in place of the channel-wise one;
beta left out (1); the convolution left out (its last tap alone); q and k
not normalised; the output gate left out; the state held in bfloat16
(``FLOAT32_ONLY``); rotary
applied on the latent layers; the layout shifted by one layer (each latent
layer's attention one place early, with the weights of its kind); the
absent experts' pairs computed with held weights (expert e on the weights
of ``first + e mod held``); the experts' weights in float8_e4m3; and
``float8``: the whole model in the nearest precision under what the
configuration states (every matrix in float8_e4m3, the cached latent rows
too, the recurrent state in bfloat16), the control of the serving cell's own
limit: its ``cell_gap_max`` has to read over ``check.logit_tol``.

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

# Both constants lie between the readings PERF.md section 6 gives.
LOGIT_TOL = {"bfloat16": 0.33, "float32": 2e-4}  # by the model's dtype
#: The mean relative error of a head's final state (see STATE above).
STATE_TOL = {"bfloat16": 0.15, "float32": 1e-4}
#: Under this margin between the reference's k-th and next selection score
#: (sigmoid score + bias) the system may take either expert.
SWAP_MARGIN = {"bfloat16": 0.03, "float32": 0.0}
FAULTS = ("no-decay", "headwise-decay", "no-beta", "no-conv", "no-qk-norm",
          "no-gate", "bf16-state", "rotary-on-latent", "layout-shift",
          "absent-experts-computed", "float8-experts", "float8")
#: Faults a bfloat16 model's own rounding hides from a float32 reference:
#: run and reported at any precision, REQUIRED to read incorrect in float32.
FLOAT32_ONLY = ("bf16-state",)


def _shifted(cfg):
    """``cfg`` with every latent layer one place early, and the pairs of
    layers whose attention changes places."""
    layout = list(cfg.attn_layout)
    pairs = [(i - 1, i) for i, a in enumerate(layout)
             if a == "latent" and i and layout[i - 1] == "kda"]
    for i, j in pairs:
        layout[i], layout[j] = layout[j], layout[i]
    return dataclasses.replace(cfg, attn_layout=tuple(layout)), pairs


@contextlib.contextmanager
def faulted(cfg, fault):
    """``cfg`` computing something else, the named fault: a configuration
    that says so where a field does, else the program's own function
    swapped for the while (the float8 faults round the weights and
    ``layout-shift`` reorders them, in ``main``)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import kda, moe, paged

    swaps = []

    def swap(module, name, fn):
        swaps.append((module, name, getattr(module, name)))
        setattr(module, name, fn)

    def bf16(S):
        return jax.lax.reduce_precision(S, 8, 7)

    if fault == "rotary-on-latent":
        cfg = dataclasses.replace(cfg, rope_layout=(1,) * cfg.n_layers)
    elif fault == "layout-shift":
        cfg = _shifted(cfg)[0]
    elif fault in ("no-decay", "headwise-decay", "no-beta"):
        def project(config, a, x):
            pre, g, beta = real(config, a, x)
            if fault == "no-decay":
                g = jnp.zeros_like(g)
            elif fault == "headwise-decay":
                g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
            else:
                beta = jnp.ones_like(beta)
            return pre, g, beta
        real = kda.project
        swap(kda, "project", project)
    elif fault == "no-conv":
        def conv(config, a, pre, prev, length=None):
            last = {n: jnp.zeros_like(a[n]).at[-1].set(1)
                    for n in ("conv_q", "conv_k", "conv_v")}
            return real(config, {**a, **last}, pre, prev, length)
        real = kda.conv
        swap(kda, "conv", conv)
    elif fault == "no-qk-norm":
        swap(kda, "_l2norm", lambda x: x)
    elif fault == "no-gate":
        def output(config, a, x, o):
            o = kda.rms_norm(o, a["o_norm"].astype(jnp.float32),
                             config.norm_eps)
            return o.reshape(*x.shape[:-1], -1).astype(x.dtype) @ a["wo"]
        swap(kda, "output", output)
    elif fault == "absent-experts-computed":
        def route(config, m, xf, logits=None):
            probs, top_p, top_e = real(config, m, xf, logits)
            return probs, top_p, config.first_expert \
                + (top_e - config.first_expert) % config.n_experts
        real = moe._route
        swap(moe, "_route", route)
    if fault in ("bf16-state", "float8"):
        def recurrent(S, *a):
            o, new = real_r(bf16(S), *a)
            return o, bf16(new)

        def chunked(S, *a, **kw):
            # Between blocks too: the scan's carry is the state held.
            real_block = kda._block
            kda._block = lambda S, xs: (
                lambda out: (bf16(out[0]), out[1]))(real_block(bf16(S), xs))
            try:
                return real_c(S, *a, **kw)
            finally:
                kda._block = real_block
        real_r, real_c = kda.recurrent, kda.chunked
        swap(kda, "recurrent", recurrent)
        swap(kda, "chunked", chunked)
    if fault == "float8":
        real_row = paged._latent_row
        swap(paged, "_latent_row", lambda *a: jax.lax.reduce_precision(
            real_row(*a), 4, 3))
    try:
        yield cfg
    finally:
        for module, name, was in swaps:
            setattr(module, name, was)


def _shift_weights(params, pairs):
    """``attn`` and ``attn_norm`` of each pair of layers change places."""
    layers = [dict(layer) for layer in params["layers"]]
    for i, j in pairs:
        for name in ("attn", "attn_norm"):
            layers[i][name], layers[j][name] = layers[j][name], \
                layers[i][name]
    return {**params, "layers": layers}


def programs():
    """The three paged programs, jitted apart from every other caller's (a
    fault swaps a module's function: a trace another caller cached would
    not see it).  Each also returns the experts the program's own router
    took, [routed layers, rows, k] (``moe._route`` as the sound program has
    it, recorded while the program is traced)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import moe, paged

    sound = moe._route

    def recording(f):
        def run(*a):
            taken, installed = [], moe._route

            def route(config, m, xf, logits=None):
                taken.append(sound(config, m, xf, logits)[2])
                return installed(config, m, xf, logits)
            moe._route = route
            try:
                logits, pools, _ = f(*a)
            finally:
                moe._route = installed
            return logits, pools, jnp.stack(taken)
        return jax.jit(run, static_argnums=0, donate_argnums=2)

    return tuple(recording(f) for f in (
        paged.prefill_logits, paged.prefill_prefix_logits,
        paged.decode_logits))


def system_logits(cfg, params, ec, seqs, new, progs, greedy=False):
    """Prefill all but the last ``new`` tokens of each sequence as the
    engine does (in chunks of the largest bucket, the state carried in the
    sequence's slot), then ``new`` decode steps, each fed the sequence's
    next token (``greedy``: the best token of its last row instead, as the
    engine at temperature 0).  The sequences go one after the other, each
    in a slot and in pages of its own and alone live while it decodes; a
    slot is used again (``len(seqs)`` may pass the slots), so a prompt
    starts on the state the one before it left.  Returns, a sequence:
    logits [1 + new, V], the experts its router took at each decode row
    [new, L, k] (-1 in a dense layer), and the state its slot holds at the
    end [KDA layers, H, D, D] (on the device)."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import block, paged

    prefill, suffix, decode = progs
    ps, maxp, b = ec.page_size, ec.pages_per_seq, ec.batch_slots
    buckets = ec.prefill_buckets()
    chunk = buckets[-1]
    pools = paged.init_paged_pools(cfg, ec.pool_pages, ps, 0, b)
    adapters = paged.init_adapter_pool(cfg, ec.max_adapters, ec.lora_rank)
    zero = jnp.asarray(ec.max_adapters, jnp.int32)
    ids = jnp.full((b,), ec.max_adapters, jnp.int32)
    routed = [i for i in range(cfg.n_layers) if block.is_routed(cfg, i)]
    out = []
    for index, seq in enumerate(seqs):
        slot = index % 2  # two slots, each used again
        n = len(seq) - new
        tables = np.full((b, maxp), ec.pool_pages, np.int32)
        tables[slot] = slot * maxp + np.arange(maxp)  # pages of its own
        table, state = jnp.asarray(tables[slot]), jnp.asarray(slot, jnp.int32)
        for start in range(0, n, chunk):
            end = min(start + chunk, n)
            bucket = next(x for x in buckets if x >= end - start)
            pad = np.zeros((1, bucket), np.int32)
            pad[0, :end - start] = seq[start:end]
            if start:
                logits, pools, _ = suffix(
                    cfg, params, pools, adapters, jnp.asarray(pad),
                    jnp.asarray(start), jnp.asarray(end), table, zero, None,
                    state)
            else:
                logits, pools, _ = prefill(
                    cfg, params, pools, adapters, jnp.asarray(pad),
                    jnp.asarray(end), table, zero, None, state)
        rows, experts = [np.asarray(logits[0])], []
        for i in range(new):
            toks, lens = np.zeros((b,), np.int32), np.zeros((b,), np.int32)
            toks[slot] = rows[-1].argmax() if greedy else seq[n + i]
            lens[slot] = n + i
            logits, pools, taken = decode(
                cfg, params, pools, adapters, jnp.asarray(toks),
                jnp.asarray(tables), jnp.asarray(lens),
                jnp.asarray(np.arange(b) == slot), ids)
            rows.append(np.asarray(logits)[slot])
            mine = np.full((cfg.n_layers, cfg.top_k), -1, np.int32)
            mine[routed] = np.asarray(taken)[:, slot]
            experts.append(mine)
        out.append((np.stack(rows), np.stack(experts),
                    pools["S"][:, slot] + 0))  # a copy: the pools go on
    return out


def state_errors(ref, seqs, new, system):
    """STATE: over every sequence, KDA layer and head, the relative error
    of the state the system's slot holds after the last decode step against
    the reference's (the decode rows' experts pinned, as ``compare`` pins
    them)."""
    import jax.numpy as jnp
    import numpy as np

    worst, means = 0.0, []
    for seq, (_, experts, held) in zip(seqs, system):
        n = len(seq) - new
        given = np.full((experts.shape[1], len(seq), experts.shape[2]), -1,
                        np.int32)
        given[:, n:] = experts.transpose(1, 0, 2)
        want = ref.states(seq, given)
        err = jnp.linalg.norm(held - want, axis=(-2, -1)) \
            / jnp.linalg.norm(want, axis=(-2, -1))
        worst = max(worst, float(jnp.nan_to_num(err, nan=jnp.inf).max()))
        means.append(float(jnp.nan_to_num(err, nan=jnp.inf).mean()))
    return {"state_err_max": worst, "state_err_mean": float(np.mean(means))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="kimi-linear-48b-a3b-L13")
    ap.add_argument("--traffic", default="serve-long-decode-doc-tail",
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
    from benchmarks.reference.glm4_moe_lite_compare import _float8
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
    state_tol = STATE_TOL[model["torch_dtype"]]
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
                run_params = params if fault != "layout-shift" \
                    else _shift_weights(params, _shifted(cfg)[1])
                progs = traced.setdefault(fault, programs())
                systems.append(system_logits(fcfg, run_params, ec, seqs,
                                             new, progs))
                emitted.append([
                    out[0][:new].argmax(-1).tolist() for out in
                    system_logits(fcfg, run_params, ec, seqs, new, progs,
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
            r.update(state_errors(ref, seqs, new, system))
        for r, outputs in zip(runs, emitted):
            r["cell_gap_max"] = cell_gap_max(ref, seqs, new, outputs)
            r["cell_check_passes"] = r["cell_gap_max"] <= check["logit_tol"]
            # Not (... > tol): a row that is not a number is over too.
            r["correct"] = bool(
                not (r["rows_over"] or r["routing_violations"])
                and r["max_abs_logit_diff"] <= tol
                and r["state_err_mean"] <= state_tol)
            if r["fault"] in FLOAT32_ONLY \
                    and model["torch_dtype"] != "float32":
                r["held_in_float32_only"] = True
            else:
                ok &= r["correct"] == (r["fault"] is None)
            print(json.dumps(r), flush=True)
        results += runs
        del ref, params, systems  # one copy of the weights at a time
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    print(json.dumps({"ok": ok, "logit_tol": tol, "state_tol": state_tol,
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
