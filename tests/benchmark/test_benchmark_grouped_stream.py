"""The benchmark's share of PR 39: the reader of ``moe_stream_roofline.moe``
on a hand-made trace and records, its entry in ``BENCHMARK.json``, and that
the older reader of the grouped products' roofline counts both kernels (the
streaming one carries its needle in its name).  No JAX, no topology."""

import os

import pytest

from bench_testlib import ROOT

from benchmarks import spec
from benchmarks.layer_metrics import (moe_decode_roofline_moe,
                                      moe_stream_roofline_moe)
from benchmarks.trace_reduce import label, ops_time

CELLS = ["olmoe-1b-7b-0125.serve-saturated",
         "smallthinker-21b-a3b-L8.serve-long-mixed",
         "glm-4.7-flash-L6.serve-agent-shared-context"]
T0 = 1000.0  # the window's first second on the host's clock
WINDOW_S = 5.0

#: A traced window of the OLMoE cell, made by hand: twelve layers' calls of
#: the streaming kernel (the decode steps), three of XLA's own (the
#: prefills' products), a fusion.
OPS = {**{f"mosaic:ragged-dot-stream.{i}": 0.2 for i in range(1, 13)},
       "mosaic:ragged-dot-none.7": 0.10, "mosaic:ragged-dot-none.8": 0.10,
       "mosaic:ragged-dot-none": 0.10, "fusion:fusion.3": 1.0}
STREAM_S, XLA_S = 2.4, 0.3
EXPERT = 3 * 2048 * 1024 * 2  # one OLMoE expert's three matrices, bytes
ROW = 2 * 2048 * 2            # a pair's row in and row out, bytes


def _model(name="olmoe-1b-7b-0125"):
    return spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", name + ".json"))


def _steps(n, t_first, traced, period=0.02, prefill=False):
    """``n`` decode records of 12 layers x 56 experts hit, 128 pairs a
    layer; with ``prefill``, each admitted one prompt of 300 tokens whose
    own counters ride on its ``first_tokens`` entry."""
    phases = {k: 0.0 for k in ("between_s", "idle_s", "upload_s",
                               "dispatch_s", "readback_s", "emit_s")}
    entry = {"experts_hit": 12 * 64, "expert_pairs": 12 * 8 * 300,
             "queue_s": 0.0, "prefill_s": 0.03, "prompt": 300, "cached": 0}
    return [dict(phases, t=t_first + i * period, stall_s=0.0,
                 admitted=int(prefill), occupancy=16, slots=16,
                 wall_s=period, experts_hit=12 * 56, expert_pairs=12 * 128,
                 first_tokens=[dict(entry)] if prefill else [],
                 **({"traced": 1} if traced else {}))
            for i in range(n)]


def _ctx(steps, ops=OPS, **over):
    return {"kind": "serve_closed", "seconds": 51.0, "steps": steps,
            "window_wall": T0, "model": _model(),
            "trace": {"n_devices": 1, "window_s": WINDOW_S, "busy_s": 4.6,
                      "ops": ops},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            **over}


def test_the_name_the_compiler_gives_the_kernel_is_the_label_read():
    """What a v5e's compiler makes of ``pallas_call(name=
    "ragged-dot-stream")`` (``tests/test_chip_compile.py``) through
    ``trace_reduce.label``; XLA's own grouped matmul beside it."""
    ours = ('%ragged-dot-stream.7 = f32[128,2048]{1,0:T(8,128)} custom-call('
            '%c, %xs, %w1, %w3, %w2), custom_call_target="tpu_custom_call"')
    xla = ('%ragged-dot-none.12 = f32[2400,1024]{1,0:T(8,128)} custom-call('
           '%a, %b, %c), custom_call_target="tpu_custom_call"')
    assert label(ours) == "mosaic:ragged-dot-stream.7"
    assert label(xla) == "mosaic:ragged-dot-none.12"
    assert moe_stream_roofline_moe.KERNEL in label(ours)
    assert moe_stream_roofline_moe.KERNEL not in label(xla)
    assert moe_decode_roofline_moe.KERNEL in label(ours)
    assert moe_decode_roofline_moe.KERNEL in label(xla)


def test_the_reader_divides_the_decode_steps_bytes_by_the_kernels_seconds():
    """The records closed while the profiler ran (``traced`` 1), their own
    counters and not their prefills'; the seconds of the streaming kernel
    and not of XLA's."""
    steps = _steps(40, T0, False) + _steps(200, T0 + 1.0, True) \
        + _steps(30, T0 + 1.0 + 4.0, True, prefill=True) \
        + _steps(50, T0 + 6.1, False)
    assert ops_time({"ops": OPS}, moe_stream_roofline_moe.KERNEL) \
        == pytest.approx(STREAM_S)
    need = 230 * (12 * 56 * EXPERT + 12 * 128 * ROW)
    got = moe_stream_roofline_moe.read(_ctx(steps))
    assert got == pytest.approx(100.0 * need / 819e9 / STREAM_S, rel=1e-12)
    assert 60 < got < 100
    # A kernel exactly as fast as the HBM allows reads 100%, and no more.
    assert moe_stream_roofline_moe.read(_ctx(steps, ops={
        "mosaic:ragged-dot-stream.1": need / 819e9})) \
        == pytest.approx(100.0)


def test_the_reader_reads_nothing_where_there_is_nothing_to_read():
    steps = _steps(200, T0 + 1.0, True)
    assert moe_stream_roofline_moe.read(_ctx(steps)) is not None
    # The parent of the PR that added the kernel: every grouped product is
    # XLA's own, in the decode steps too.
    parent = {n: s for n, s in OPS.items() if "stream" not in n}
    assert moe_stream_roofline_moe.read(_ctx(steps, ops=parent)) is None
    # Records that do not say they were traced (a program before PR 38);
    # records without the routing counters; none; no trace; a train run.
    assert moe_stream_roofline_moe.read(
        _ctx(_steps(200, T0 + 1.0, False))) is None
    bare = [{k: v for k, v in r.items() if not k.startswith("expert")}
            for r in steps]
    assert moe_stream_roofline_moe.read(_ctx(bare)) is None
    assert moe_stream_roofline_moe.read(_ctx([])) is None
    assert moe_stream_roofline_moe.read(_ctx(steps, trace={})) is None
    assert moe_stream_roofline_moe.read(_ctx(steps, trace=None)) is None
    assert moe_stream_roofline_moe.read(_ctx(3, kind="train")) is None
    # A dense model (its family counts no routed FFN); a device with no chip.
    dense = _model("internlm2-1.8b")
    assert moe_stream_roofline_moe.read(_ctx(steps, model=dense)) is None
    assert moe_stream_roofline_moe.read(_ctx(steps, device={
        "platform": "cpu", "kind": "cpu", "count": 1})) is None


def test_the_older_reader_still_counts_every_grouped_product():
    """``moe_decode_roofline.moe`` finds both kernels by ``mosaic:
    ragged-dot``: the decode steps' AND the prefills' work over the seconds
    of both, the same quantity as before the decode steps changed form,
    still under 100%.  Had the new kernel another name it would divide all
    of that work by the prefills' seconds alone."""
    steps = _steps(40, T0 + 1.0, True) \
        + _steps(30, T0 + 1.0 + 4.0, True, prefill=True)
    tr = {"ops": OPS}
    assert ops_time(tr, moe_decode_roofline_moe.KERNEL) \
        == pytest.approx(STREAM_S + XLA_S)
    need = 70 * (12 * 56 * EXPERT + 12 * 128 * ROW) \
        + 30 * (12 * 64 * EXPERT + 12 * 8 * 300 * ROW)
    both = moe_decode_roofline_moe.read(_ctx(steps))
    assert both == pytest.approx(
        100.0 * need / 819e9 / (STREAM_S + XLA_S), rel=1e-12)
    assert both < 100
    renamed = {n.replace("ragged-dot-stream", "expert_stream"): s
               for n, s in OPS.items()}
    assert moe_decode_roofline_moe.read(_ctx(steps, ops=renamed)) > 100


def test_the_metric_is_in_the_benchmark_as_the_issue_names_it():
    doc = spec.load_benchmark(ROOT)
    spec.validate(doc)
    # By name and by membership, not by place: the next entry goes behind.
    entry, = [m for m in doc["per_layer"]
              if m["name"] == "moe_stream_roofline.moe"]
    assert entry == {
        "name": "moe_stream_roofline.moe", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "serve_tok_s", "workloads": CELLS}
    serve_tok_s, = [m for m in doc["end_to_end"]
                    if m["name"] == "serve_tok_s"]
    older, = [m for m in doc["per_layer"]
              if m["name"] == "moe_decode_roofline.moe"]
    for cell in CELLS:
        assert cell in serve_tok_s["workloads"]
        # Every cell whose decode step streams already reports the older
        # share, which counts the same calls.
        assert cell in older["workloads"]
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "layer_metrics", "moe_stream_roofline_moe.py"))
