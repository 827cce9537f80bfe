#!/usr/bin/env python3
r"""The smallthinker family through the paged cache (window rings beside
whole-length pages, long prompts prefilled in chunks) against its plain
reference, at the configuration's own widths, on the device this process
holds:

    python3 benchmarks/reference/smallthinker_compare.py \
        --config smallthinker-21b-a3b-L8 --seed <n> [--seed <m> ...] \
        [--faults] [--rehearse]

One process (it holds the chip; no cluster), outside any timed window, the
sibling of ``olmoe_compare.py``.  With seeded weights (norm weights drawn
from 0.5..1.5 so that a misplaced norm shows) it prefills the serving check's
four prompts the way the engine does (``engine._prefill_body``): the first
chunk through ``paged.prefill_logits``, every further chunk of
``prefill_chunk`` tokens through ``paged.prefill_prefix_logits`` over what
the chunks before it cached, with the engine's own geometry (page tables of
``pages_per_seq``, rings of ``paged.ring_entries``).  At the cell's sizes
(window 4096, chunk 2048, ring 6144) the prompts of 300, 3000, 7000 and
13000 tokens are: inside one bucket; chunked under the window; past window +
chunk, so that a ring has wrapped once; wrapped twice, over seven chunks.
It then decodes 16 seeded tokens through ``paged.decode_logits`` (one
live slot of the engine's sixteen at a time, so that the step's per-expert
counts are that row's experts) and holds every logit row (4 x 17) to
``smallthinker_ref.Reference.logits`` of the same token sequence: a full
forward pass in float32 at the highest matmul precision, with no cache.
The last line of stdout is one JSON object; exit 1 if the sound program is
not correct or a fault is.

It also takes the reading the serving cell's own check takes
(``serve_cell.compare``, the traffic file's ``check.logit_tol``): the
system's greedy ``new_tokens`` after each prompt through the same pages,
each held to the reference by ``teacher_forced_gaps``; ``cell_gap_max`` is
the largest.

``--faults`` runs the comparison again for four different computations,
each of which has to come out over the tolerance: the window left out of the
window layers (a query sees whatever its ring holds), rotary applied on the
global layers too, the router fed from after attention (the FFN's own
normalised input), and the experts' weights rounded to float8_e4m3 (the
nearest precision under the bfloat16 the configuration states).

Tolerance, and what routing has to do with it.  As ``olmoe_compare.py``'s:
the system multiplies bfloat16 by bfloat16 into float32 and rounds
activations to bfloat16 between operations, the reference never rounds, so
logits differ by bfloat16's accumulated rounding.  What is new here is that
top-k routing with RENORMALISED weights is discontinuous: the sixth and the
seventh expert of a token each carry about a tenth of the FFN's output, and
where their router logits are closer than the rounding of the hidden state
the system takes the other one.  That is no fault (either choice is the
model's within its precision), it happens to a few rows in a hundred, and it
moves that row's logits by 0.3 to 1.5, as much as a structural fault does
(PERF.md section 6 has the readings; at OLMoE's unnormalised weights near
1/64 a swap stayed inside the noise).  So the comparison is in two parts,
and both have to hold.  ROUTING: the experts the system took at every decode
row (read from the program's own per-expert counts, one sequence live at a
time) are handed to the reference, which computes with them and says of
each (token, layer) how far they reach from its own router's choice: the
largest router logit it would have taken and the system left out, less the
smallest the system took in its place (0: the same experts).  A reach under
``SWAP_MARGIN`` is a tie that rounding decided (the widest seen on the chip
over three seeds is 0.033, PERF.md section 6; a fault's reach far past
it, by the tens to the hundreds); one at or over it is a violation.  LOGITS: with the routing so pinned, every decode row must
be within ``LOGIT_TOL``, which lies between the two readings PERF.md
section 6 gives (the worst row of the sound program over its seeds, the
nearest fault).  The row after the prefill (whose experts the program
counts only together with the rest of its chunk's) is judged against the
reference's own routing where its margin is at least ``SWAP_MARGIN`` in
every layer, and reported otherwise.  A float32 configuration (the
rehearsal's) has no rounding to speak of: no swap is allowed, every row is
judged, at the tests' float32 tolerance.

``--rehearse`` is the same at the family's tiny configuration and the
traffic file's ``rehearsal`` sizes, for the tests on the CPU.
"""

from __future__ import annotations

import argparse
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
#: Under this margin between the reference's k-th and next router logit the
#: system may take either expert.
SWAP_MARGIN = {"bfloat16": 0.10, "float32": 0.0}
FAULTS = ("no-window", "rotary-on-global", "router-after-attention",
          "float8-experts")


def faulted(cfg, fault):
    """``cfg`` computing something else: the named fault."""
    if fault == "no-window":  # same rings, nothing masked by age
        return dataclasses.replace(cfg, window=1 << 30)
    if fault == "rotary-on-global":
        return dataclasses.replace(cfg, rope_layout=(1,) * cfg.n_layers)
    if fault == "router-after-attention":
        return dataclasses.replace(cfg, router_before_attn=False)
    return cfg  # float8-experts rounds the weights


def system_logits(cfg, params, ec, seqs, new, greedy=False):
    """Prefill all but the last ``new`` tokens of each sequence as the
    engine does (in chunks of the largest bucket), then ``new`` decode
    steps, each fed the sequence's next token (``greedy``: the best token
    of its last row instead, as the engine at temperature 0).  The
    sequences go one after the other, each in a slot and in pages of its
    own and alone live while it decodes.  Returns, a sequence: logits
    [1 + new, V], and the experts of each decode row [new, L, k] (the
    step's per-expert counts, which with one live row are its experts)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import paged

    ps, maxp, b = ec.page_size, ec.pages_per_seq, ec.batch_slots
    buckets = ec.prefill_buckets()
    chunk = buckets[-1]
    ring = min(maxp, paged.ring_entries(cfg, ps, chunk))
    pools = paged.init_paged_pools(cfg, ec.pool_pages, ps, b * ring)
    adapters = paged.init_adapter_pool(cfg, ec.max_adapters, ec.lora_rank)
    zero = jnp.asarray(ec.max_adapters, jnp.int32)
    prefill = jax.jit(paged.prefill_logits, static_argnums=0,
                      donate_argnums=2)
    suffix = jax.jit(paged.prefill_prefix_logits, static_argnums=0,
                     donate_argnums=2)
    decode = jax.jit(paged.decode_logits, static_argnums=0, donate_argnums=2)
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
                [np.nonzero(np.asarray(c))[0] for c in counts]))
        out.append((np.stack(rows), np.stack(experts)))
    return out


def greedy_outputs(cfg, params, ec, seqs, new):
    """The ``new`` tokens the system emits after each prompt at temperature
    0 (the prompt is the sequence less its last ``new`` tokens)."""
    return [rows[:new].argmax(-1).tolist() for rows, _ in system_logits(
        cfg, params, ec, seqs, new, greedy=True)]


def compare(ref, seqs, new, system, tol, swap_margin):
    """What ``system_logits`` gave (``system``) against the reference, in
    the docstring's two parts: the decode rows' routing, and every judged
    row's logits."""
    import numpy as np

    t0 = time.time()
    judged, unjudged, swaps, violations, by_prompt = [], [], [], 0, {}
    agree = 0
    for seq, (got, experts) in zip(seqs, system):
        n = len(seq) - new
        given = np.full((experts.shape[1], len(seq), experts.shape[2]), -1,
                        np.int32)
        given[:, n:] = experts.transpose(1, 0, 2)  # decode row i: token n+i
        want = ref.logits(seq, range(n - 1, len(seq)), given)
        margins, reach = ref.routing(seq, given)
        reach = reach[:, n:]
        diff = np.abs(got - want).max(-1)
        agree += int((got.argmax(-1) == want.argmax(-1)).sum())
        swaps += reach[(reach > 0) & (reach < swap_margin)].tolist()
        violations += int((reach >= swap_margin).sum()) if swap_margin \
            else int((reach > 0).sum())
        rows = list(diff[1:])
        if margins[:, n - 1].min() >= swap_margin:
            rows.append(diff[0])
        else:
            unjudged.append(float(diff[0]))
        judged += rows
        by_prompt[str(n)] = float(max(rows))
    return {
        "rows_judged": len(judged), "rows_over": int(sum(
            d > tol for d in judged)),
        "max_abs_logit_diff": float(max(judged)), "by_prompt": by_prompt,
        "median_row": float(np.median(judged)),
        "tie_swaps": len(swaps), "routing_violations": violations,
        "widest_swaps": [round(m, 4) for m in sorted(swaps)[-5:]],
        "prefill_rows_unjudged": [round(d, 4) for d in unjudged],
        "argmax_agree": agree, "rows": len(seqs) * (1 + new),
        "seconds": round(time.time() - t0, 1)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="smallthinker-21b-a3b-L8")
    ap.add_argument("--traffic", default="serve-long-mixed",
                    help="the traffic file whose engine geometry is used")
    ap.add_argument("--seed", type=int, action="append")
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="the family's tiny configuration, on the CPU")
    args = ap.parse_args(argv)

    import jax

    from benchmarks import spec
    from benchmarks.reference.olmoe_compare import (
        _float8_experts, _weights, cell_gap_max, sequences)
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
    cfg = fam.program_config(model, remat=False,
                             max_seq=ec.pages_per_seq * ec.page_size)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    results, ok = [], True
    for seed in args.seed or [0]:
        weights = functools.partial(_weights, fam, cfg, seed % (2 ** 31 - 1))
        params = weights()
        seqs = sequences(model, check, seed)
        # The system first, sound and faulted (the reference is handed
        # what it took): the chip holds one copy of the weights at a time.
        runs, systems, emitted = [], [], []
        for fault in (None, *(FAULTS if args.faults else ())):
            if fault == "float8-experts":  # last: it rounds them in place
                params = _float8_experts(params)
            fcfg = faulted(cfg, fault)
            t0 = time.time()
            systems.append(system_logits(fcfg, params, ec, seqs, new))
            emitted.append(greedy_outputs(fcfg, params, ec, seqs, new))
            runs.append({"seed": seed, "fault": fault,
                         "system_seconds": round(time.time() - t0, 1)})
        if args.faults:  # the sound weights again, for the reference
            del params
            params = weights()
        ref = fam.reference(model, params)
        for r, system in zip(runs, systems):
            r.update(compare(ref, seqs, new, system, tol, swap_margin))
        for r, outputs in zip(runs, emitted):
            r["cell_gap_max"] = cell_gap_max(ref, seqs, new, outputs)
            r["cell_check_passes"] = r["cell_gap_max"] <= check["logit_tol"]
            r["correct"] = not (r["rows_over"] or r["routing_violations"])
            ok &= r["correct"] == (r["fault"] is None)
            print(json.dumps(r), flush=True)
        results += runs
        del ref
    print(json.dumps({"ok": ok, "logit_tol": tol,
                      "swap_margin": swap_margin,
                      "cell_logit_tol": check["logit_tol"], "device": device,
                      "config": model["name"],
                      "layers": model["num_hidden_layers"],
                      "prompt_lens": check["prompt_lens"],
                      "results": results}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
