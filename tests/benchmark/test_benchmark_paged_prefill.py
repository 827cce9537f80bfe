"""The benchmark's share of PR 44: the reader of
``paged_prefill_roofline.swa`` on a synthetic trace and records, its entry in
``BENCHMARK.json`` (looked up by name and membership, never by place), the
engine's ``attn_pairs`` of a chunked prompt against the sum over its calls,
``stats()["prefill_attention"]`` off the chip, and that no older reader
answers to the new kernel's name.  No topology; the engine's part imports
JAX on the CPU."""

import os

import pytest

from bench_testlib import ROOT

from benchmarks import spec
from benchmarks.layer_metrics import (latent_decode_roofline_mla,
                                      moe_decode_roofline_moe,
                                      moe_stream_roofline_moe,
                                      paged_decode_roofline_swa,
                                      paged_prefill_roofline_swa)

CELLS = {"smallthinker-21b-a3b-L8": "smallthinker-21b-a3b-L8.serve-long-mixed",
         "trinity-mini-L5": "trinity-mini-L5.serve-reasoning-long-decode"}
T0 = 1000.0  # the window's first second on the host's clock

#: A trace in which SmallThinker's eight layers' calls ran 20 chunks of 2048
#: rows behind 2048 cached ones: two whole-length layers' rows see 2049 ..
#: 4096 keys, six window layers' (4096) the same, and the kernel took 1.5 ms
#: a call (my chip run of the kernel alone, PR 44: 1.43-1.60).
CHUNKS, CALL_S = 20, 1.5e-3
PAIRS_A_CHUNK = 8 * sum(range(2049, 4097))
OPS = {**{f"mosaic:paged_prefill.{8 + i}": CHUNKS * CALL_S for i in range(8)},
       "mosaic:paged_decode.5": 0.2, "mosaic:ragged-dot-stream.4": 0.56,
       "mosaic:ragged-dot-none.2": 0.63, "fusion:fusion.386": 0.33}


def _model(config="smallthinker-21b-a3b-L8"):
    return spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", config + ".json"))


def _ctx(steps, ops=OPS, **over):
    return {"kind": "serve_closed", "seconds": 51.0, "steps": steps,
            "window_wall": T0, "model": _model(),
            "trace": {"n_devices": 1, "window_s": 5.03, "busy_s": 4.9,
                      "ops": ops},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            **over}


def _steps(n, traced=1, pairs=PAIRS_A_CHUNK, **over):
    """``n`` records that each admitted one prompt whose one call was that
    chunk, between records that admitted none."""
    phases = {k: 0.0 for k in ("between_s", "idle_s", "upload_s",
                               "dispatch_s", "readback_s", "emit_s")}
    mark = {"traced": 1} if traced else {}
    entry = {"queue_s": 0.1, "prefill_s": 0.08, "prompt": 4096,
             "bucket": 4096, "cached": 0, "chunks": 2, "experts_hit": 400}
    if pairs is not None:
        entry["attn_pairs"] = pairs
    steps = []
    for i in range(2 * n):
        steps.append(dict(
            phases, t=T0 + 1 + i * 0.0125, stall_s=0.08 * (i % 2),
            admitted=i % 2, occupancy=16, slots=16, wall_s=0.0125,
            first_tokens=[dict(entry)] if i % 2 else [], kv_rows_read=9000,
            **mark, **over))
    return steps


@pytest.mark.parametrize("config, ops", [("smallthinker-21b-a3b-L8", 14336),
                                         ("trinity-mini-L5", 16384)])
def test_a_pair_costs_a_score_and_a_value_on_every_head(config, ops):
    """``4 x num_attention_heads x head_dim``: 28 and 32 heads of 128."""
    assert paged_prefill_roofline_swa.pair_ops(_model(config)) == ops


def test_the_reader_divides_the_pairs_operations_by_the_kernels_seconds():
    """The entries on records closed while the profiler ran (``traced`` 1)
    count; those of the rest of the window do not."""
    steps = _steps(5, traced=0) + _steps(CHUNKS) + _steps(7, traced=0)
    got = paged_prefill_roofline_swa.read(_ctx(steps))
    seconds = sum(s for n, s in OPS.items() if "paged_prefill" in n)
    assert seconds == pytest.approx(8 * CHUNKS * CALL_S)
    assert got == pytest.approx(
        100.0 * CHUNKS * PAIRS_A_CHUNK * 4 * 28 * 128 / 197e12 / seconds)
    # A layer's call: 6.29 M pairs x 14336 operations in 1.5 ms.
    assert got == pytest.approx(
        100.0 * sum(range(2049, 4097)) * 14336 / 197e12 / CALL_S)
    assert 30 < got < 31
    # A kernel exactly as fast as the MXU allows reads 100%, and no more.
    least = CHUNKS * PAIRS_A_CHUNK * 14336 / 197e12
    assert paged_prefill_roofline_swa.read(_ctx(
        steps, ops={"mosaic:paged_prefill.9": least})) == pytest.approx(100.0)
    # Trinity-Mini's cell: the same reader at its own model's 32 heads.
    assert paged_prefill_roofline_swa.read(_ctx(
        steps, model=_model("trinity-mini-L5"))) == pytest.approx(
            got * 32 / 28)


@pytest.mark.parametrize("what, over", [
    ("the parent: the gather's fusions, no such call",
     dict(ops={n: s for n, s in OPS.items() if "paged_prefill" not in n})),
    ("no trace", dict(trace={})), ("no trace at all", dict(trace=None)),
    ("a train run", dict(kind="train", steps=3)),
    ("no records", dict(steps=[])),
    ("no record closed while the profiler ran",
     dict(steps=_steps(CHUNKS, traced=0))),
    ("entries without the count (a program that gathers)",
     dict(steps=_steps(CHUNKS, pairs=None))),
    ("no admission in the traced records",
     dict(steps=[dict(r, first_tokens=[]) for r in _steps(CHUNKS)])),
    ("off a TPU",
     dict(device={"platform": "cpu", "kind": "cpu", "count": 1}))],
    ids=lambda x: x.replace(" ", "-") if isinstance(x, str) else "")
def test_the_reader_reads_nothing_where_there_is_nothing_to_read(what, over):
    assert paged_prefill_roofline_swa.read(_ctx(_steps(CHUNKS))) is not None
    over = dict(over)
    ctx = _ctx(over.pop("steps", _steps(CHUNKS)), **over)
    assert paged_prefill_roofline_swa.read(ctx) is None, what


def test_each_kernels_reader_reads_only_its_own_kernel():
    """``mosaic:paged_prefill`` answers to none of the older needles
    (``mosaic:paged_decode``, ``mosaic:ragged-dot``,
    ``mosaic:ragged-dot-stream``, ``mosaic:latent_decode``), and the new
    reader to none of theirs."""
    from benchmarks.trace_reduce import ops_time

    tr = {"ops": {**OPS, "mosaic:latent_decode.6": 0.18}}
    assert ops_time(tr, paged_prefill_roofline_swa.KERNEL) \
        == pytest.approx(8 * CHUNKS * CALL_S)
    assert ops_time(tr, paged_decode_roofline_swa.KERNEL) \
        == pytest.approx(0.2)
    assert ops_time(tr, latent_decode_roofline_mla.KERNEL) \
        == pytest.approx(0.18)
    assert ops_time(tr, moe_stream_roofline_moe.KERNEL) \
        == pytest.approx(0.56)
    assert ops_time(tr, moe_decode_roofline_moe.KERNEL) \
        == pytest.approx(1.19)
    for needle in (paged_decode_roofline_swa.KERNEL,
                   latent_decode_roofline_mla.KERNEL,
                   moe_decode_roofline_moe.KERNEL):
        assert not paged_prefill_roofline_swa.KERNEL.startswith(needle)
    steps = _steps(CHUNKS)
    without = {n: s for n, s in OPS.items() if "paged_prefill" not in n}
    # The decode walk's share reads what it read without the new calls.
    assert paged_decode_roofline_swa.read(_ctx(steps)) \
        == paged_decode_roofline_swa.read(_ctx(steps, ops=without)) \
        is not None
    # A trace with the decode kernel alone is not this reader's.
    assert paged_prefill_roofline_swa.read(_ctx(steps, ops={
        "mosaic:paged_decode.5": 0.2})) is None


def test_the_metric_is_in_the_benchmark_as_the_issue_names_it():
    doc = spec.load_benchmark(ROOT)
    spec.validate(doc)
    entry, = [m for m in doc["per_layer"]
              if m["name"] == "paged_prefill_roofline.swa"]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": "paged_prefill_roofline.swa", "unit": "%",
        "better": "higher", "source": "device_trace", "layer": "kernels",
        "moves": "serve_tok_s"}
    assert set(CELLS.values()) <= set(entry["workloads"])
    assert "kernels" in {m["layer"] for m in doc["per_layer"]
                         if m["name"] != entry["name"]}
    serve_tok_s, = [m for m in doc["end_to_end"]
                    if m["name"] == "serve_tok_s"]
    assert set(entry["workloads"]) <= set(serve_tok_s["workloads"])
    # Only cells of a configuration with window layers and K/V pairs.
    cells = {w["name"]: w["config"] for w in doc["workloads"]}
    for cell in entry["workloads"]:
        assert cells[cell] in CELLS, cell
    # The reader's module is where the harness looks for it.
    module = entry["name"].replace(".", "_").replace("-", "_")
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "layer_metrics", module + ".py"))


@pytest.mark.parametrize("cell", CELLS.values())
def test_the_cell_reports_the_shares_that_move_its_metric(cell):
    """Both cells with window layers list the new share beside the accepted
    ones of the decode walk and the experts' stream, and the loop's share
    that the prefills hold (``prefill_stall_share.sat``)."""
    doc = spec.load_benchmark(ROOT)
    listed = {m["name"] for m in doc["per_layer"]
              if cell in m.get("workloads", ())}
    assert {"paged_prefill_roofline.swa", "paged_decode_roofline.swa",
            "moe_stream_roofline.moe", "prefill_stall_share.sat",
            "decode_period_ms.sat"} <= listed


# ------------------------------------------------------ the engine's counts

FAMILIES = {"rehearsal-tiny": False, "olmoe-tiny": False,
            "smallthinker-tiny": True, "trinity-mini-tiny": True,
            "glm4-moe-lite-tiny": False, "kimi-linear-tiny": False}


def _tiny(name, max_seq=256):
    model = spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", name + ".json"))
    return spec.family(model).program_config(model, remat=False,
                                             max_seq=max_seq)


@pytest.mark.parametrize("name", FAMILIES)
def test_the_predicate_is_walks_live_pages_for_kv_pair_models(name,
                                                              monkeypatch):
    """On the CPU every family's prefills gather.  With both kernels'
    ``on_tpu`` steered: a model with window layers and K/V pairs walks (what
    ``_walks_live_pages`` says of its decode step); a latent model does not
    though its decode step does; one whole-length kind never."""
    from ray_tpu.models import paged
    from ray_tpu.ops import latent_decode, paged_decode

    cfg = _tiny(name)
    assert paged.prefill_attention_form(cfg) == "gather"
    assert paged.decode_attention_form(cfg) == "gather"
    monkeypatch.setattr(paged_decode, "on_tpu", lambda: True)
    monkeypatch.setattr(latent_decode, "on_tpu", lambda: True)
    from ray_tpu.models import block
    walks = paged._walks_live_pages(cfg)
    assert (paged.prefill_attention_form(cfg) == "walk") == FAMILIES[name]
    assert (paged.prefill_attention_form(cfg) == "walk") \
        == (walks and not block.is_latent(cfg))
    assert walks == (FAMILIES[name] or block.is_latent(cfg))


def _first_tokens(name, monkeypatch, walk, prompt):
    """The ``first_tokens`` entry and the stats of one prompt through an
    engine of the tiny configuration (pages of 8, chunks of 16).  ``walk``:
    the engine is told the prefills walk; its programs stay the CPU's (the
    count is host arithmetic, and jit is not asked to interpret a kernel)."""
    import time

    import jax
    import numpy as np

    from ray_tpu.util import steprec
    from ray_tpu.models import init_and_apply
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    cfg = _tiny(name, max_seq=128)
    params = init_and_apply(cfg)[0](cfg, jax.random.PRNGKey(0))
    if walk:
        monkeypatch.setattr("ray_tpu.models.paged.prefill_attention_form",
                            lambda config: "walk")
    steprec.drain_buffered()
    engine = InferenceEngine(cfg, params, EngineConfig(
        batch_slots=2, page_size=8, max_prompt_len=96,
        prefill_chunk=16, prefix_cache=False, max_new_tokens_cap=8), seed=0)
    try:
        stats = engine.stats()
        tokens = np.random.default_rng(prompt).integers(1, 500, prompt)
        stats["tokens_out"] = list(engine.submit(tokens, max_new_tokens=2))
        assert len(stats["tokens_out"]) == 2
        stats["cold_traces"] = engine.stats()["prefill_traces"] \
            - stats["prefill_traces"]
        entries, deadline = [], time.time() + 10
        while not entries and time.time() < deadline:
            entries = [e for r in steprec.drain_buffered()
                       if r.get("engine") == engine.engine_id
                       for e in r["first_tokens"]]
            time.sleep(0.05)
    finally:
        engine.shutdown()
    entry, = entries
    return cfg, entry, stats


@pytest.mark.parametrize("prompt", [5, 16, 37, 90])
def test_attn_pairs_of_a_chunked_prompt_is_the_sum_over_its_calls(
        prompt, monkeypatch):
    """A prompt of one call inside a bucket, of one whole chunk, of three
    calls past the window (8) and of six: the entry's ``attn_pairs`` is the
    formula's sum over the calls' ``(start, end)``, which is the count row
    by row."""
    from ray_tpu.models import paged

    cfg, entry, stats = _first_tokens("smallthinker-tiny", monkeypatch, True,
                                      prompt)
    assert stats["prefill_attention"] == "walk"
    assert entry["prompt"] == prompt and entry["chunks"] == -(-prompt // 16)
    calls = [(s, min(s + 16, prompt)) for s in range(0, prompt, 16)]
    assert entry["attn_pairs"] == sum(
        paged.attn_pairs(cfg, s, e) for s, e in calls)
    whole, window = paged.kv_layers(cfg)
    assert entry["attn_pairs"] == sum(
        len(whole) * (p + 1) + len(window) * min(p + 1, cfg.window)
        for p in range(prompt))
    assert isinstance(entry["attn_pairs"], int)


@pytest.mark.parametrize("prompt", [5, 37])
def test_where_the_prefills_walk_a_first_call_is_a_suffix_behind_nothing(
        prompt, monkeypatch):
    """Told that its prefills walk, the engine sends a prompt's first rows
    through the suffix program at ``prefix_len`` 0 and never traces the
    cold one (its set-up seconds are not paid); the tokens are the cold
    program's, here in the gather form on both sides."""
    _, entry, stats = _first_tokens("smallthinker-tiny", monkeypatch, True,
                                    prompt)
    assert stats["cold_traces"] == 0 and "attn_pairs" in entry
    monkeypatch.undo()
    _, entry, cold = _first_tokens("smallthinker-tiny", monkeypatch, False,
                                   prompt)
    assert "attn_pairs" not in entry
    assert cold["tokens_out"] == stats["tokens_out"]


@pytest.mark.parametrize("name", ["smallthinker-tiny", "rehearsal-tiny"])
def test_a_program_that_gathers_leaves_the_count_out(name, monkeypatch):
    """On the CPU ``stats()["prefill_attention"]`` reads ``"gather"`` and
    the entry has no ``attn_pairs``."""
    _, entry, stats = _first_tokens(name, monkeypatch, False, 19)
    assert stats["prefill_attention"] == "gather"
    assert stats["decode_attention"] == "gather"
    assert "attn_pairs" not in entry and entry["chunks"] == 2
