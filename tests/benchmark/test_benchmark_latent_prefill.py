"""The benchmark's share of PR 45: the reader of
``latent_prefill_roofline.mla`` on a synthetic trace and records, its entry
in ``BENCHMARK.json`` (looked up by name and membership, never by place), the
engine's ``attn_pairs`` of a chunked latent prompt against the sum over its
calls, ``stats()["prefill_attention"]`` off the chip, and that no older
reader answers to the new kernel's name.  No topology; the engine's part
imports JAX on the CPU."""

import os

import pytest

from bench_testlib import ROOT
from test_benchmark_paged_prefill import _first_tokens, _tiny

from benchmarks import spec
from benchmarks.layer_metrics import (latent_decode_roofline_mla,
                                      latent_prefill_roofline_mla,
                                      moe_decode_roofline_moe,
                                      moe_stream_roofline_moe,
                                      paged_decode_roofline_swa,
                                      paged_prefill_roofline_swa)

CELLS = {"glm-4.7-flash-L6": "glm-4.7-flash-L6.serve-agent-shared-context",
         "kimi-linear-48b-a3b-L13":
             "kimi-linear-48b-a3b-L13.serve-long-decode-doc-tail"}
T0 = 1000.0  # the window's first second on the host's clock

#: A trace in which GLM's six layers' calls ran 23 suffixes of 562 rows behind
#: 16384 cached ones (the cell's mean): a row sees 16385 .. 16946 keys on
#: each layer, and the kernel took 3.8 ms a call.
SUFFIXES, CALL_S = 23, 3.8e-3
PAIRS_A_SUFFIX = 6 * sum(range(16385, 16947))
OPS = {**{f"mosaic:latent_prefill.{7 + i}": SUFFIXES * CALL_S
          for i in range(6)},
       "mosaic:latent_decode.6": 0.18, "mosaic:ragged-dot-stream.4": 0.56,
       "mosaic:ragged-dot-none.2": 0.63, "fusion:fusion.386": 0.33}


def _model(config="glm-4.7-flash-L6"):
    return spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", config + ".json"))


def _ctx(steps, ops=OPS, **over):
    return {"kind": "serve_closed", "seconds": 51.0, "steps": steps,
            "window_wall": T0, "model": _model(),
            "trace": {"n_devices": 1, "window_s": 5.03, "busy_s": 4.5,
                      "ops": ops},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            **over}


def _steps(n, traced=1, pairs=PAIRS_A_SUFFIX, **over):
    """``n`` records that each admitted one prompt whose one call was that
    suffix, between records that admitted none."""
    phases = {k: 0.0 for k in ("between_s", "idle_s", "upload_s",
                               "dispatch_s", "readback_s", "emit_s")}
    mark = {"traced": 1} if traced else {}
    entry = {"queue_s": 0.1, "prefill_s": 0.05, "prompt": 16946,
             "bucket": 1024, "cached": 16384, "chunks": 1, "experts_hit": 300}
    if pairs is not None:
        entry["attn_pairs"] = pairs
    steps = []
    for i in range(2 * n):
        steps.append(dict(
            phases, t=T0 + 1 + i * 0.0143, stall_s=0.05 * (i % 2),
            admitted=i % 2, occupancy=32, slots=32, wall_s=0.0143,
            first_tokens=[dict(entry)] if i % 2 else [], kv_rows_read=3300000,
            **mark, **over))
    return steps


@pytest.mark.parametrize("config, ops", [
    ("glm-4.7-flash-L6", 20 * 2176), ("kimi-linear-48b-a3b-L13", 32 * 2176)])
def test_a_pair_costs_an_absorbed_score_and_a_value_on_every_head(config,
                                                                  ops):
    """``heads x (2 x (512 + 64) + 2 x 512)``: the REAL 576 and 512 columns,
    not the 640 the pool's rows are padded to, so the kernel can do no
    fewer."""
    model = _model(config)
    assert (model["kv_lora_rank"], model["qk_rope_head_dim"]) == (512, 64)
    assert latent_prefill_roofline_mla.pair_ops(model) == ops
    padded = model["num_attention_heads"] * (2 * 640 + 2 * 512)
    assert ops < padded


def test_the_reader_divides_the_pairs_operations_by_the_kernels_seconds():
    """The entries on records closed while the profiler ran (``traced`` 1)
    count; those of the rest of the window do not."""
    steps = _steps(5, traced=0) + _steps(SUFFIXES) + _steps(7, traced=0)
    got = latent_prefill_roofline_mla.read(_ctx(steps))
    seconds = sum(s for n, s in OPS.items() if "latent_prefill" in n)
    assert seconds == pytest.approx(6 * SUFFIXES * CALL_S)
    assert got == pytest.approx(
        100.0 * SUFFIXES * PAIRS_A_SUFFIX * 20 * 2176 / 197e12 / seconds)
    # A layer's call: 9.37 M pairs x 43520 operations in 3.8 ms.
    assert got == pytest.approx(
        100.0 * sum(range(16385, 16947)) * 43520 / 197e12 / CALL_S)
    assert 54 < got < 55
    # A kernel exactly as fast as the MXU allows reads 100%, and no more.
    least = SUFFIXES * PAIRS_A_SUFFIX * 43520 / 197e12
    assert latent_prefill_roofline_mla.read(_ctx(
        steps, ops={"mosaic:latent_prefill.9": least})) \
        == pytest.approx(100.0)
    # Kimi-Linear's cell: the same reader at its own model's 32 heads.
    assert latent_prefill_roofline_mla.read(_ctx(
        steps, model=_model("kimi-linear-48b-a3b-L13"))) == pytest.approx(
            got * 32 / 20)


@pytest.mark.parametrize("what, over", [
    ("the parent: the gather's fusions, no such call",
     dict(ops={n: s for n, s in OPS.items() if "latent_prefill" not in n})),
    ("no trace", dict(trace={})), ("no trace at all", dict(trace=None)),
    ("a train run", dict(kind="train", steps=3)),
    ("no records", dict(steps=[])),
    ("no record closed while the profiler ran",
     dict(steps=_steps(SUFFIXES, traced=0))),
    ("entries without the count (a program that gathers)",
     dict(steps=_steps(SUFFIXES, pairs=None))),
    ("no admission in the traced records",
     dict(steps=[dict(r, first_tokens=[]) for r in _steps(SUFFIXES)])),
    ("off a TPU",
     dict(device={"platform": "cpu", "kind": "cpu", "count": 1}))],
    ids=lambda x: x.replace(" ", "-") if isinstance(x, str) else "")
def test_the_reader_reads_nothing_where_there_is_nothing_to_read(what, over):
    assert latent_prefill_roofline_mla.read(_ctx(_steps(SUFFIXES))) \
        is not None
    over = dict(over)
    ctx = _ctx(over.pop("steps", _steps(SUFFIXES)), **over)
    assert latent_prefill_roofline_mla.read(ctx) is None, what


def test_each_kernels_reader_reads_only_its_own_kernel():
    """``mosaic:latent_prefill`` answers to none of the older needles
    (``mosaic:latent_decode``, ``mosaic:paged_prefill``,
    ``mosaic:paged_decode``, ``mosaic:ragged-dot``,
    ``mosaic:ragged-dot-stream``), and the new reader to none of theirs."""
    from benchmarks.trace_reduce import ops_time

    tr = {"ops": {**OPS, "mosaic:paged_prefill.3": 0.4,
                  "mosaic:paged_decode.5": 0.2}}
    assert ops_time(tr, latent_prefill_roofline_mla.KERNEL) \
        == pytest.approx(6 * SUFFIXES * CALL_S)
    assert ops_time(tr, latent_decode_roofline_mla.KERNEL) \
        == pytest.approx(0.18)
    assert ops_time(tr, paged_prefill_roofline_swa.KERNEL) \
        == pytest.approx(0.4)
    assert ops_time(tr, paged_decode_roofline_swa.KERNEL) \
        == pytest.approx(0.2)
    assert ops_time(tr, moe_stream_roofline_moe.KERNEL) \
        == pytest.approx(0.56)
    assert ops_time(tr, moe_decode_roofline_moe.KERNEL) \
        == pytest.approx(1.19)
    older = (latent_decode_roofline_mla, paged_prefill_roofline_swa,
             paged_decode_roofline_swa, moe_stream_roofline_moe,
             moe_decode_roofline_moe)
    for reader in older:
        assert reader.KERNEL not in "mosaic:latent_prefill.12", reader
        assert latent_prefill_roofline_mla.KERNEL \
            not in reader.KERNEL + ".12", reader
    steps = _steps(SUFFIXES)
    without = {n: s for n, s in OPS.items() if "latent_prefill" not in n}
    # The decode walk's share reads what it read without the new calls.
    assert latent_decode_roofline_mla.read(_ctx(steps)) \
        == latent_decode_roofline_mla.read(_ctx(steps, ops=without)) \
        is not None
    # A trace with the decode kernel alone is not this reader's, nor one
    # with the K/V-pair prefill kernel alone.
    for other in ("mosaic:latent_decode.6", "mosaic:paged_prefill.3"):
        assert latent_prefill_roofline_mla.read(
            _ctx(steps, ops={other: 0.2})) is None


def test_the_metric_is_in_the_benchmark_as_the_issue_names_it():
    doc = spec.load_benchmark(ROOT)
    spec.validate(doc)
    entry, = [m for m in doc["per_layer"]
              if m["name"] == "latent_prefill_roofline.mla"]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": "latent_prefill_roofline.mla", "unit": "%",
        "better": "higher", "source": "device_trace", "layer": "kernels",
        "moves": "serve_tok_s"}
    assert set(CELLS.values()) <= set(entry["workloads"])
    assert "kernels" in {m["layer"] for m in doc["per_layer"]
                         if m["name"] != entry["name"]}
    serve_tok_s, = [m for m in doc["end_to_end"]
                    if m["name"] == "serve_tok_s"]
    assert set(entry["workloads"]) <= set(serve_tok_s["workloads"])
    # Only cells of a configuration whose cache is a latent pool.
    cells = {w["name"]: w["config"] for w in doc["workloads"]}
    for cell in entry["workloads"]:
        assert cells[cell] in CELLS, cell
        assert _model(cells[cell])["kv_lora_rank"] > 0
    # The reader's module is where the harness looks for it.
    module = entry["name"].replace(".", "_").replace("-", "_")
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "layer_metrics", module + ".py"))


@pytest.mark.parametrize("cell", CELLS.values())
def test_the_cell_reports_the_shares_that_move_its_metric(cell):
    """Both latent cells list the new share beside the accepted ones of the
    decode walk and the experts' stream, and the loop's share that the
    prefills hold (``prefill_stall_share.sat``)."""
    doc = spec.load_benchmark(ROOT)
    listed = {m["name"] for m in doc["per_layer"]
              if cell in m.get("workloads", ())}
    assert {"latent_prefill_roofline.mla", "moe_stream_roofline.moe",
            "prefill_stall_share.sat", "decode_period_ms.sat"} <= listed
    assert "paged_prefill_roofline.swa" not in listed


# ------------------------------------------------------ the engine's counts

LATENT = {"glm4-moe-lite-tiny": 3, "kimi-linear-tiny": 1}


@pytest.mark.parametrize("prompt", [5, 16, 37, 90])
@pytest.mark.parametrize("name", LATENT)
def test_attn_pairs_of_a_chunked_latent_prompt_is_the_sum_over_its_calls(
        name, prompt, monkeypatch):
    """A prompt of one call inside a bucket, of one whole chunk, of three
    calls and of six: the entry's ``attn_pairs`` is the formula's sum over
    the calls' ``(start, end)``, which is the count row by row over the
    latent layers (every one whole-length; a KDA layer keeps no rows)."""
    from ray_tpu.models import paged

    cfg, entry, stats = _first_tokens(name, monkeypatch, True, prompt)
    assert stats["prefill_attention"] == "walk"
    assert entry["prompt"] == prompt and entry["chunks"] == -(-prompt // 16)
    calls = [(s, min(s + 16, prompt)) for s in range(0, prompt, 16)]
    assert entry["attn_pairs"] == sum(
        paged.attn_pairs(cfg, s, e) for s, e in calls)
    whole, window = paged.kv_layers(cfg)
    assert (len(whole), len(window)) == (LATENT[name], 0)
    assert entry["attn_pairs"] == LATENT[name] * sum(
        p + 1 for p in range(prompt))
    assert isinstance(entry["attn_pairs"], int)


@pytest.mark.parametrize("name", LATENT)
def test_where_the_prefills_walk_a_latent_prompts_first_call_is_a_suffix(
        name, monkeypatch):
    """Told that its prefills walk, the engine sends a latent prompt's first
    rows through the suffix program at ``prefix_len`` 0 (absorbed, where the
    cold program expands its rows) and never traces the cold one; the tokens
    are the cold program's, here in the gather form on both sides."""
    _, entry, stats = _first_tokens(name, monkeypatch, True, 37)
    assert stats["cold_traces"] == 0 and "attn_pairs" in entry
    monkeypatch.undo()
    _, entry, cold = _first_tokens(name, monkeypatch, False, 37)
    assert "attn_pairs" not in entry and cold["cold_traces"] == 1
    assert cold["tokens_out"] == stats["tokens_out"]


@pytest.mark.parametrize("name", LATENT)
def test_a_latent_program_that_gathers_leaves_the_count_out(name,
                                                            monkeypatch):
    """On the CPU ``stats()["prefill_attention"]`` reads ``"gather"`` for
    both latent families and the entry has no ``attn_pairs``."""
    _, entry, stats = _first_tokens(name, monkeypatch, False, 19)
    assert stats["prefill_attention"] == "gather"
    assert stats["decode_attention"] == "gather"
    assert "attn_pairs" not in entry and entry["chunks"] == 2


@pytest.mark.parametrize("name", LATENT)
def test_the_predicate_is_the_decode_steps(name, monkeypatch):
    """On the CPU a latent family's prefills gather; with the latent
    kernels' ``on_tpu`` steered they walk as the decode step does
    (``_walks_live_pages``, the one place that chooses), whatever the
    K/V-pair kernels' predicate answers."""
    from ray_tpu.models import paged
    from ray_tpu.ops import latent_decode, paged_decode

    cfg = _tiny(name)
    assert paged.prefill_attention_form(cfg) == "gather"
    monkeypatch.setattr(paged_decode, "on_tpu", lambda: True)
    assert paged.prefill_attention_form(cfg) == "gather"
    monkeypatch.setattr(latent_decode, "on_tpu", lambda: True)
    monkeypatch.setattr(paged_decode, "on_tpu", lambda: False)
    assert paged._walks_live_pages(cfg)
    assert paged.prefill_attention_form(cfg) == "walk"
    assert paged.decode_attention_form(cfg) == "walk"
