"""The benchmark's share of PR 37: the reader of
``latent_decode_roofline.mla`` on recorded numbers, its entry in
``BENCHMARK.json``, and that the reader of the grouped products' roofline
still reads only its own kernel.  No JAX, no topology."""

import pytest

from bench_testlib import ROOT

from benchmarks import spec
from benchmarks.layer_metrics import (latent_decode_roofline_mla,
                                      moe_decode_roofline_moe)

CONFIG = "glm-4.7-flash-L6"
CELL = CONFIG + ".serve-agent-shared-context"
T0 = 1000.0  # the window's first second on the host's clock

#: Of a traced run of the cell on one v5e (my chip run, PR 37, seed
#: 3700000101): the ten heaviest device operations of the 5.03 s traced as the
#: result line had them (the six layers' kernel among them), and the sum of
#: ``kv_rows_read`` over the decode records of those seconds (the line's
#: share worked back: it carries no records), here spread over 188 steps of
#: ~4303 live pages x 128 rows x 6 layers.  The line read 89.047%.
RECORDED_OPS = {
    "mosaic:latent_decode.11": 0.181961, "mosaic:latent_decode.9": 0.181885,
    "mosaic:latent_decode.10": 0.181873, "mosaic:latent_decode.8": 0.181867,
    "mosaic:latent_decode.6": 0.181429, "mosaic:latent_decode.7": 0.181390,
    "fusion:fusion.193": 0.159520, "mosaic:ragged-dot-none.12": 0.147598,
    "mosaic:ragged-dot-none": 0.146972, "mosaic:ragged-dot-none.9": 0.146586,
}
KERNEL_S = 1.090405
RECORDED_ROWS, TRACED_STEPS = 621_271_296, 188


def _model():
    import os

    return spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", CONFIG + ".json"))


def _ctx(steps, ops=RECORDED_OPS, **over):
    return {"kind": "serve_closed", "seconds": 51.0, "steps": steps,
            "window_wall": T0, "model": _model(),
            "trace": {"n_devices": 1, "window_s": 5.029270135,
                      "busy_s": 4.529598862,
                      "ops": ops},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            **over}


def _steps(n, t_first, period=0.0265, **over):
    phases = {k: 0.0 for k in ("between_s", "idle_s", "upload_s",
                               "dispatch_s", "readback_s", "emit_s")}
    rows = RECORDED_ROWS // TRACED_STEPS
    return [dict(phases, t=t_first + i * period, stall_s=0.0, admitted=0,
                 occupancy=32, slots=32, wall_s=period, first_tokens=[],
                 kv_rows_read=rows, kv_rows_live=rows - 64 * 6 * 32,
                 experts_hit=278, expert_pairs=640, **over)
            for i in range(n)]


def test_the_reader_divides_the_pages_bytes_by_the_kernels_seconds():
    """The profiler starts 1 s into the window and runs 5.03 s: the records
    that ended in [T0 + 1, T0 + 6.03) are the traced ones, 188 of these; a
    record that admitted (its decode step ran the kernel too) counts, one
    before or after the traced seconds does not."""
    steps = _steps(30, T0) + _steps(TRACED_STEPS, T0 + 1.0) \
        + _steps(50, T0 + 6.03)
    steps[30]["kv_rows_read"] += RECORDED_ROWS % TRACED_STEPS
    steps[40]["admitted"], steps[40]["stall_s"] = 2, 0.07
    model = _model()
    assert latent_decode_roofline_mla.row_bytes(model) == 640 * 2
    seconds = sum(s for n, s in RECORDED_OPS.items() if "latent_decode" in n)
    assert seconds == pytest.approx(KERNEL_S)
    got = latent_decode_roofline_mla.read(_ctx(steps))
    assert got == pytest.approx(
        100.0 * RECORDED_ROWS * 1280 / 819e9 / seconds)
    assert got == pytest.approx(89.047, abs=1e-3)  # what the line read
    # A kernel exactly as fast as the HBM allows reads 100%, and no more.
    least = RECORDED_ROWS * 1280 / 819e9
    assert latent_decode_roofline_mla.read(_ctx(
        steps, ops={"mosaic:latent_decode.1": least})) == pytest.approx(100.0)


def test_the_reader_reads_nothing_where_there_is_nothing_to_read():
    steps = _steps(TRACED_STEPS, T0 + 1.0)
    assert latent_decode_roofline_mla.read(_ctx(steps)) is not None
    # The parent of the PR that added the kernel: the trace has the gather's
    # fusions and no such call, and its counter counts whole tables.
    parent_ops = {n: s for n, s in RECORDED_OPS.items()
                  if "latent_decode" not in n}
    assert latent_decode_roofline_mla.read(_ctx(steps, ops=parent_ops)) is None
    # Records without the counter, no records, no trace, a train run.
    bare = [{k: v for k, v in r.items() if not k.startswith("kv_rows")}
            for r in steps]
    assert latent_decode_roofline_mla.read(_ctx(bare)) is None
    assert latent_decode_roofline_mla.read(_ctx([])) is None
    assert latent_decode_roofline_mla.read(_ctx(steps, trace={})) is None
    assert latent_decode_roofline_mla.read(_ctx(steps, trace=None)) is None
    assert latent_decode_roofline_mla.read(_ctx(3, kind="train")) is None
    # A model without a latent pool; a device with no chip.
    other = spec.load_json(f"{ROOT}/benchmarks/configs/olmoe-1b-7b-0125.json")
    assert latent_decode_roofline_mla.read(_ctx(steps, model=other)) is None
    assert latent_decode_roofline_mla.read(_ctx(steps, device={
        "platform": "cpu", "kind": "cpu", "count": 1})) is None


def test_the_grouped_products_reader_still_reads_only_its_own_kernel():
    """``moe_decode_roofline.moe`` finds its kernel by the prefix
    ``mosaic:ragged-dot``; the new kernel's name does not answer to it, and
    the new reader's prefix not to the grouped products."""
    from benchmarks.trace_reduce import ops_time

    tr = {"ops": RECORDED_OPS}
    assert ops_time(tr, moe_decode_roofline_moe.KERNEL) \
        == pytest.approx(0.147598 + 0.146972 + 0.146586)
    assert ops_time(tr, latent_decode_roofline_mla.KERNEL) \
        == pytest.approx(KERNEL_S)
    steps = _steps(TRACED_STEPS, T0 + 1.0)
    with_kernel = moe_decode_roofline_moe.read(_ctx(steps))
    without = moe_decode_roofline_moe.read(_ctx(steps, ops={
        n: s for n, s in RECORDED_OPS.items() if "latent_decode" not in n}))
    assert with_kernel == without and with_kernel is not None


def test_the_metric_is_in_the_benchmark_as_the_issue_names_it():
    doc = spec.load_benchmark(ROOT)
    spec.validate(doc)
    # By name and by membership, not by place: the next entry goes behind.
    entry, = [m for m in doc["per_layer"]
              if m["name"] == "latent_decode_roofline.mla"]
    assert entry == {
        "name": "latent_decode_roofline.mla", "unit": "%",
        "better": "higher", "source": "device_trace", "layer": "kernels",
        "moves": "serve_tok_s", "workloads": [CELL]}
    assert "kernels" in {m["layer"] for m in doc["per_layer"]
                         if m["name"] != entry["name"]}
    serve_tok_s, = [m for m in doc["end_to_end"]
                    if m["name"] == "serve_tok_s"]
    assert CELL in serve_tok_s["workloads"]
    # Every share of a roofline the cell reports the end-to-end metric of.
    rooflines = {m["name"] for m in doc["per_layer"]
                 if "roofline" in m["name"] and CELL in m.get("workloads", ())}
    assert rooflines >= {"moe_decode_roofline.moe",
                         "latent_decode_roofline.mla"}
