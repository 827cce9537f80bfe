"""The benchmark's share of PR 42: the reader of
``paged_decode_roofline.swa`` on a synthetic trace and records, its entry in
``BENCHMARK.json`` (looked up by name and membership, never by place), and
that no older reader answers to the new kernel's name.  No JAX, no
topology."""

import os

import pytest

from bench_testlib import ROOT

from benchmarks import spec
from benchmarks.layer_metrics import (latent_decode_roofline_mla,
                                      moe_decode_roofline_moe,
                                      moe_stream_roofline_moe,
                                      paged_decode_roofline_swa)

CELLS = {"trinity-mini-L5": "trinity-mini-L5.serve-reasoning-long-decode",
         "smallthinker-21b-a3b-L8": "smallthinker-21b-a3b-L8.serve-long-mixed"}
T0 = 1000.0  # the window's first second on the host's clock

#: A trace of 300 decode steps of the Trinity-Mini cell as the kernel alone
#: ran them on one v5e (my chip run, PR 42: 1940 pages of 128 rows walked in
#: 0.765 ms by the five layers' calls), beside the four stream calls and
#: the head; and the rows those steps' records count.
STEPS, ROWS_A_STEP, STEP_S = 300, 1940 * 128, 0.765e-3
OPS = {**{f"mosaic:paged_decode.{5 + i}": STEPS * STEP_S / 5
          for i in range(5)},
       "mosaic:ragged-dot-stream.4": 0.56, "mosaic:ragged-dot-stream.3": 0.56,
       "mosaic:ragged-dot-none.2": 0.05, "fusion:fusion.386": 0.33}


def _model(config="trinity-mini-L5"):
    return spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", config + ".json"))


def _ctx(steps, ops=OPS, **over):
    return {"kind": "serve_closed", "seconds": 51.0, "steps": steps,
            "window_wall": T0, "model": _model(),
            "trace": {"n_devices": 1, "window_s": 5.03, "busy_s": 4.9,
                      "ops": ops},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            **over}


def _steps(n, traced=1, **over):
    phases = {k: 0.0 for k in ("between_s", "idle_s", "upload_s",
                               "dispatch_s", "readback_s", "emit_s")}
    mark = {"traced": 1} if traced else {}
    return [dict(phases, t=T0 + 1 + i * 0.0125, stall_s=0.0, admitted=0,
                 occupancy=32, slots=32, wall_s=0.0125, first_tokens=[],
                 kv_rows_read=ROWS_A_STEP, kv_rows_live=ROWS_A_STEP - 40_000,
                 experts_hit=444, expert_pairs=1024, **mark, **over)
            for i in range(n)]


@pytest.mark.parametrize("config", CELLS)
def test_a_row_is_a_tokens_k_and_v_on_a_layer_as_they_lie(config):
    """4 KV heads x 128 x bfloat16, K and V: 2048 bytes in both cells."""
    assert paged_decode_roofline_swa.row_bytes(_model(config)) == 2048
    assert paged_decode_roofline_swa.row_bytes(
        {"num_key_value_heads": 8, "head_dim": 64,
         "torch_dtype": "float32"}) == 2 * 8 * 64 * 4


def test_the_reader_divides_the_pages_bytes_by_the_kernels_seconds():
    """The records closed while the profiler ran (``traced`` 1) count, also
    one that admitted (its decode step ran the kernel too); the others of
    the window do not."""
    steps = _steps(40, traced=0) + _steps(STEPS) + _steps(60, traced=0)
    steps[50]["admitted"], steps[50]["stall_s"] = 2, 0.05
    got = paged_decode_roofline_swa.read(_ctx(steps))
    seconds = sum(s for n, s in OPS.items() if "paged_decode" in n)
    assert seconds == pytest.approx(STEPS * STEP_S)
    assert got == pytest.approx(
        100.0 * STEPS * ROWS_A_STEP * 2048 / 819e9 / seconds)
    assert got == pytest.approx(100.0 * 1940 * 128 * 2048 / 819e9 / STEP_S)
    assert 80 < got < 82  # what the kernel alone read
    # A kernel exactly as fast as the HBM allows reads 100%, and no more.
    least = STEPS * ROWS_A_STEP * 2048 / 819e9
    assert paged_decode_roofline_swa.read(_ctx(
        steps, ops={"mosaic:paged_decode.7": least})) == pytest.approx(100.0)
    # SmallThinker's cell: the same reader on its own model's rows.
    assert paged_decode_roofline_swa.read(_ctx(
        steps, model=_model("smallthinker-21b-a3b-L8"))) == pytest.approx(got)


@pytest.mark.parametrize("what, over", [
    ("the parent: the gather's fusions, no such call",
     dict(ops={n: s for n, s in OPS.items() if "paged_decode" not in n})),
    ("no trace", dict(trace={})), ("no trace at all", dict(trace=None)),
    ("a train run", dict(kind="train", steps=3)),
    ("no records", dict(steps=[])),
    ("no record closed while the profiler ran",
     dict(steps=_steps(STEPS, traced=0))),
    ("records without the counter",
     dict(steps=[{k: v for k, v in r.items() if not k.startswith("kv_rows")}
                 for r in _steps(STEPS)])),
    ("off a TPU",
     dict(device={"platform": "cpu", "kind": "cpu", "count": 1}))],
    ids=lambda x: x.replace(" ", "-") if isinstance(x, str) else "")
def test_the_reader_reads_nothing_where_there_is_nothing_to_read(what, over):
    assert paged_decode_roofline_swa.read(_ctx(_steps(STEPS))) is not None
    over = dict(over)
    ctx = _ctx(over.pop("steps", _steps(STEPS)), **over)
    assert paged_decode_roofline_swa.read(ctx) is None, what


def test_each_kernels_reader_reads_only_its_own_kernel():
    """``mosaic:paged_decode`` answers to none of the older needles
    (``mosaic:ragged-dot``, ``mosaic:ragged-dot-stream``,
    ``mosaic:latent_decode``), and the new reader to none of theirs."""
    from benchmarks.trace_reduce import ops_time

    tr = {"ops": {**OPS, "mosaic:latent_decode.6": 0.18}}
    assert ops_time(tr, paged_decode_roofline_swa.KERNEL) \
        == pytest.approx(STEPS * STEP_S)
    assert ops_time(tr, latent_decode_roofline_mla.KERNEL) \
        == pytest.approx(0.18)
    assert ops_time(tr, moe_stream_roofline_moe.KERNEL) \
        == pytest.approx(1.12)
    assert ops_time(tr, moe_decode_roofline_moe.KERNEL) \
        == pytest.approx(1.17)
    steps = _steps(STEPS)
    without = {n: s for n, s in OPS.items() if "paged_decode" not in n}
    for reader in (moe_stream_roofline_moe, moe_decode_roofline_moe):
        assert reader.read(_ctx(steps)) == reader.read(
            _ctx(steps, ops=without)) is not None
    # GLM's trace (its own kernel, its own counter) is not this reader's.
    assert paged_decode_roofline_swa.read(_ctx(steps, ops={
        "mosaic:latent_decode.6": 0.18})) is None


def test_the_metric_is_in_the_benchmark_as_the_issue_names_it():
    doc = spec.load_benchmark(ROOT)
    spec.validate(doc)
    entry, = [m for m in doc["per_layer"]
              if m["name"] == "paged_decode_roofline.swa"]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": "paged_decode_roofline.swa", "unit": "%",
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


@pytest.mark.parametrize("cell", CELLS.values())
def test_the_cell_reports_every_share_of_a_roofline_that_moves_its_metric(
        cell):
    """The claimed cell and its sibling list the new share beside the
    accepted one of the experts' stream, and the count of rows the walk
    engages by (``kv_gather_live_share.swa``)."""
    doc = spec.load_benchmark(ROOT)
    listed = {m["name"] for m in doc["per_layer"]
              if cell in m.get("workloads", ())}
    assert {"paged_decode_roofline.swa", "moe_stream_roofline.moe",
            "moe_decode_roofline.moe", "kv_gather_live_share.swa",
            "decode_period_ms.sat", "decode_device_wait_ms.sat"} <= listed
    # The reader's module is where the harness looks for it.
    entry = next(m for m in doc["per_layer"]
                 if m["name"] == "paged_decode_roofline.swa")
    module = entry["name"].replace(".", "_").replace("-", "_")
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "layer_metrics", module + ".py"))
