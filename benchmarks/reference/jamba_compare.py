#!/usr/bin/env python3
r"""The jamba family through the engine's programs (selective state-space
layers whose state lives in a slot, the chunk form in every prefill and the
recurrent form in decode, K/V rows for the attention layers only, a tied
head) against its plain reference, at the configuration's own widths, on the
device this process holds:

    python3 benchmarks/reference/jamba_compare.py \
        --config jamba2-3b --seed <n> [--seed <m> ...] \
        [--faults | --fault <name>] [--rehearse]

One process (it holds the chip; no cluster), outside any timed window, the
sibling of ``kimi_linear_compare.py``.  With seeded weights (norm weights,
the three inner ones among them, drawn from 0.5..1.5 so that a misplaced norm
shows) it prefills the serving check's four prompts the way the engine does
(``engine._prefill_body``): in chunks of ``prefill_chunk`` tokens, the
slot's state and convolution rows taken in and handed on, the attention
layers over the rows the chunks before cached; every call through
``paged.prefill_prefix_logits`` where the prefills walk the live pages (a
TPU), the first through ``paged.prefill_logits`` elsewhere, with the
engine's own geometry.  At the cell's sizes (chunk 2048) the prompts of 300,
1500, 3000 and 6000 tokens are: one bucket; the 2048 bucket with padded rows
behind the last real one; two chunks; three chunks.  It then decodes 16
seeded tokens through ``paged.decode_logits`` (the recurrent form; one live
slot at a time, a slot used again by a later sequence) and holds every logit
row (4 x 17) to ``jamba_ref.Reference.logits`` of the same token sequence: a
full forward pass in float32 at the highest matmul precision, the
position-by-position recurrence, no chunk, no cache: ``LOGIT_TOL``.  The
last line of stdout is one JSON object; exit 1 if the sound program is not
correct or a fault is (but see ``FLOAT32_ONLY``).

A second part, STATE, holds what the system KEEPS to the reference: the
state of every Mamba layer in the sequence's slot after the last decode
step, against ``Reference.states`` of the same tokens, by the relative error
of a layer's state (the Frobenius norm of the difference over that of the
reference's), averaged over layers and sequences: ``state_err_mean`` under
``STATE_TOL``.

It also takes the reading the serving cell's own check takes
(``serve_cell.compare``, the traffic file's ``check.logit_tol``): the
system's greedy ``new_tokens`` after each prompt, each held to the reference
by ``teacher_forced_gaps``; ``cell_gap_max`` is the largest.

``--faults`` runs the comparison again for six different computations, each
of which has to come out not correct (``FAULTS``): the inner norm on Delta's
path left out; the convolution's bias left out; the ``D`` skip left out;
rotary applied in the attention layers; the state held in bfloat16
(``FLOAT32_ONLY``: a bfloat16 model's own rounding hides it from a float32
reference, as ``kimi_linear_compare.py`` found of its state); and
``float8``: the whole model in the nearest precision under what the
configuration states (every matrix in float8_e4m3, embedding and so the head
among them, the cached K/V rows too, the recurrent state in bfloat16), the
control of the serving cell's own limit: its ``cell_gap_max`` has to read
over ``check.logit_tol``.

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
LOGIT_TOL = {"bfloat16": 0.5, "float32": 2e-4}  # by the model's dtype
#: The mean relative error of a layer's final state (see STATE above).
STATE_TOL = {"bfloat16": 0.05, "float32": 1e-4}
FAULTS = ("no-dt-norm", "no-conv-bias", "no-D", "rotary-on-attention",
          "bf16-state", "float8")
#: Faults a bfloat16 model's own rounding hides from a float32 reference:
#: run and reported at any precision, REQUIRED to read incorrect in float32.
FLOAT32_ONLY = ("bf16-state",)


@contextlib.contextmanager
def faulted(cfg, fault):
    """``cfg`` computing something else, the named fault: a configuration
    that says so where a field does, else the program's own function swapped
    for the while (``float8`` also rounds the weights, in ``main``)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import mamba, paged

    swaps = []

    def swap(module, name, fn):
        swaps.append((module, name, getattr(module, name)))
        setattr(module, name, fn)

    def without(fn, name, where=2):
        """``fn`` with weight ``name`` zeroed in its ``a`` argument."""
        def run(*args, **kw):
            a = args[where - 1]
            args = (*args[:where - 1],
                    {**a, name: jnp.zeros_like(a[name])}, *args[where:])
            return fn(*args, **kw)
        return run

    def bf16(H):
        return jax.lax.reduce_precision(H, 8, 7)

    if fault == "rotary-on-attention":
        cfg = dataclasses.replace(cfg, rope_layout=(1,) * cfg.n_layers)
    elif fault == "no-dt-norm":
        real_norm = mamba.rms_norm
        swap(mamba, "rms_norm", lambda x, w, eps: x if w.shape[-1]
             == cfg.ssm_dt_rank else real_norm(x, w, eps))
    elif fault == "no-conv-bias":
        swap(mamba, "conv", without(mamba.conv, "conv_b"))
        swap(mamba, "_conv_row", without(mamba._conv_row, "conv_b"))
    elif fault == "no-D":
        swap(mamba, "output", without(mamba.output, "D"))
    if fault in ("bf16-state", "float8"):
        real = mamba.recurrent

        def recurrent(a, H, *rows):
            y, new = real(a, bf16(H), *rows)
            return y, bf16(new)

        def chunked(a, H, xs, delta, bm, cm, valid=None):
            # Between positions too: the scan's carry is the state held.
            if valid is not None:
                delta = jnp.where(valid[..., None], delta, 0.0)
            H, y = jax.lax.scan(
                lambda H, t: recurrent(a, H, *t)[::-1], H,
                tuple(jnp.moveaxis(t, 1, 0) for t in (xs, delta, bm, cm)))
            return jnp.moveaxis(y, 0, 1), H

        swap(mamba, "recurrent", recurrent)
        swap(mamba, "chunked", chunked)
    if fault == "float8":
        real_write = paged._write_rows
        swap(paged, "_write_rows", lambda pool, layer, page_idx, off, rows:
             real_write(pool, layer, page_idx, off,
                        jax.lax.reduce_precision(rows, 4, 3)))
    try:
        yield cfg
    finally:
        for module, name, was in swaps:
            setattr(module, name, was)


def _float8(params):
    """Every matrix (embedding, and so the head, among them; the norms, the
    biases and the float32 ``A_log`` stay) rounded to float8_e4m3's 4
    exponent and 3 mantissa bits, a matrix at a time (no second copy of the
    tree).  ``reduce_precision`` and not a cast there and back, which the
    TPU compiler is free to drop (it allows excess precision)."""
    import jax

    rnd = jax.jit(lambda w: jax.lax.reduce_precision(w, 4, 3),
                  donate_argnums=0)
    return jax.tree_util.tree_map_with_path(
        lambda path, w: w if w.ndim < 2 or "A_log" in str(path[-1])
        else rnd(w), params)


def programs():
    """The three paged programs, jitted apart from every other caller's and
    from each other call's of this (a fault swaps a module's function, and
    jit keeps its traces by the function it was given: a trace another
    caller cached would not see the swap, so each is wrapped anew)."""
    import jax

    from ray_tpu.models import paged

    def fresh(f):
        return jax.jit(lambda *a: f(*a), static_argnums=0, donate_argnums=2)

    return tuple(map(fresh, (paged.prefill_logits,
                             paged.prefill_prefix_logits,
                             paged.decode_logits)))


def system_logits(cfg, params, ec, seqs, new, progs, greedy=False):
    """Prefill all but the last ``new`` tokens of each sequence as the
    engine does (in chunks of the largest bucket, the state carried in the
    sequence's slot), then ``new`` decode steps, each fed the sequence's
    next token (``greedy``: the best token of its last row instead, as the
    engine at temperature 0).  The sequences go one after the other, each
    in a slot and in pages of its own and alone live while it decodes; a
    slot is used again (two slots for the four sequences), so a prompt
    starts on the state the one before it left.  Returns, a sequence:
    logits [1 + new, V] and the state its slot holds at the end
    [Mamba layers, I, N] (on the device, turned as the reference has it)."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import paged

    prefill, suffix, decode = progs
    ps, maxp, b = ec.page_size, ec.pages_per_seq, ec.batch_slots
    buckets = ec.prefill_buckets()
    chunk = buckets[-1]
    walks = paged.prefill_attention_form(cfg) == "walk"
    pools = paged.init_paged_pools(cfg, ec.pool_pages, ps, 0, b)
    adapters = paged.init_adapter_pool(cfg, ec.max_adapters, ec.lora_rank)
    zero = jnp.asarray(ec.max_adapters, jnp.int32)
    ids = jnp.full((b,), ec.max_adapters, jnp.int32)
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
            if start or walks:
                logits, pools, _ = suffix(
                    cfg, params, pools, adapters, jnp.asarray(pad),
                    jnp.asarray(start), jnp.asarray(end), table, zero, None,
                    state)
            else:
                logits, pools, _ = prefill(
                    cfg, params, pools, adapters, jnp.asarray(pad),
                    jnp.asarray(end), table, zero, None, state)
        rows = [np.asarray(logits[0])]
        for i in range(new):
            toks, lens = np.zeros((b,), np.int32), np.zeros((b,), np.int32)
            toks[slot] = rows[-1].argmax() if greedy else seq[n + i]
            lens[slot] = n + i
            logits, pools, _ = decode(
                cfg, params, pools, adapters, jnp.asarray(toks),
                jnp.asarray(tables), jnp.asarray(lens),
                jnp.asarray(np.arange(b) == slot), ids)
            rows.append(np.asarray(logits)[slot])
        # A copy (the pools go on), turned to the reference's [I, N].
        out.append((np.stack(rows),
                    jnp.swapaxes(pools["S"][:, slot], -1, -2)))
    return out


def compare(ref, seqs, new, system, tol):
    """What ``system_logits`` gave (``system``) against the reference: every
    row's logits, and STATE."""
    import jax.numpy as jnp
    import numpy as np

    t0 = time.time()
    rows, by_prompt, agree, errs = [], {}, 0, []
    for seq, (got, held) in zip(seqs, system):
        n = len(seq) - new
        want = ref.logits(seq, range(n - 1, len(seq)))
        diff = np.abs(got - want).max(-1)
        diff = np.where(np.isnan(diff), np.inf, diff)
        agree += int((got.argmax(-1) == want.argmax(-1)).sum())
        rows += diff.tolist()
        by_prompt[str(n)] = float(diff.max())
        state = ref.states(seq)
        err = jnp.linalg.norm(held - state, axis=(-2, -1)) \
            / jnp.linalg.norm(state, axis=(-2, -1))
        errs.append(np.asarray(jnp.nan_to_num(err, nan=jnp.inf)))
    errs = np.stack(errs)
    return {
        "rows": len(rows), "rows_over": int(sum(d > tol for d in rows)),
        "max_abs_logit_diff": float(max(rows)), "by_prompt": by_prompt,
        "median_row": float(np.median(rows)), "argmax_agree": agree,
        "state_err_max": float(errs.max()),
        "state_err_mean": float(errs.mean()),
        "seconds": round(time.time() - t0, 1)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="jamba2-3b")
    ap.add_argument("--traffic", default="serve-reasoning-wide-batch",
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
    from ray_tpu.models import paged
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
        # The system first, sound and faulted: the chip holds one copy of
        # the weights at a time.
        runs, systems, emitted = [], [], []
        for fault in (None, *faults):
            if fault == "float8":  # last: it rounds the weights in place
                params = _float8(params)
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
        if "float8" in faults:  # the sound weights again
            del params
            params = weights()
        ref = fam.reference(model, params)
        for r, system, outputs in zip(runs, systems, emitted):
            r.update(compare(ref, seqs, new, system, tol))
            r["cell_gap_max"] = cell_gap_max(ref, seqs, new, outputs)
            r["cell_check_passes"] = r["cell_gap_max"] <= check["logit_tol"]
            # Not (... > tol): a row that is not a number is over too.
            r["correct"] = bool(not r["rows_over"]
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
                      "decode_attention": paged.decode_attention_form(cfg),
                      "prefill_attention": paged.prefill_attention_form(cfg),
                      "cell_logit_tol": check["logit_tol"], "device": device,
                      "config": model["name"],
                      "layers": model["num_hidden_layers"],
                      "prompt_lens": check["prompt_lens"],
                      "results": results}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
