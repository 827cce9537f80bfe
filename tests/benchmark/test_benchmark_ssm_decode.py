"""The benchmark's share of PR 50: the reader of ``ssm_decode_roofline.ssm``
on a made-up trace and records, its entry in ``BENCHMARK.json`` (looked up
by name and membership, never by place), and that no older reader answers
to the new kernel's name.  No JAX, no topology."""

import os

import pytest

from bench_testlib import ROOT

from benchmarks import spec
from benchmarks.layer_metrics import (paged_decode_roofline_swa,
                                      ssm_decode_roofline_ssm)

CONFIG = "jamba2-3b"
CELL = "jamba2-3b.serve-reasoning-wide-batch"
T0 = 1000.0  # the window's first second on the host's clock

#: A traced window of 90 decode steps of the cell, each of 26 calls of the
#: kernel over 128 slots' states, at 0.12 ms a call (made up: the issue's
#: prediction), beside the two attention walks and the head.
STEPS, SLOTS, LAYERS, CALL_S = 90, 128, 26, 0.12e-3
STATE = 16 * 5120 * 4  # a slot's state on a layer
OPS = {**{f"mosaic:ssm_decode.{26 + i}": STEPS * CALL_S
          for i in range(LAYERS)},
       "mosaic:paged_decode.2": 0.028, "mosaic:paged_decode.3": 0.028,
       "fusion:fusion.386": 0.046}


def _model(config=CONFIG):
    return spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", config + ".json"))


def _ctx(steps, ops=OPS, **over):
    return {"kind": "serve_closed", "seconds": 51.0, "steps": steps,
            "window_wall": T0, "model": _model(),
            "trace": {"n_devices": 1, "window_s": 1.5, "busy_s": 1.4,
                      "ops": ops},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            **over}


def _steps(n, traced=1, **over):
    phases = {k: 0.0 for k in ("between_s", "idle_s", "upload_s",
                               "dispatch_s", "readback_s", "emit_s")}
    mark = {"traced": 1} if traced else {}
    return [dict(phases, t=T0 + 1 + i * 0.017, stall_s=0.0, admitted=0,
                 occupancy=SLOTS, slots=SLOTS, wall_s=0.017, first_tokens=[],
                 state_bytes=2 * SLOTS * 9_318_400, kv_rows_distinct=190_000,
                 **mark, **over)
            for i in range(n)]


def test_a_steps_bytes_are_every_slots_state_read_once_and_written_once():
    """26 Mamba layers of 28 (7 and 21 attend), 16 x 5120 float32 a slot:
    2.18 GB a step at 128 slots, the state pool's own bytes twice; less
    than the record's ``state_bytes``, which holds the convolution rows."""
    model = _model()
    assert ssm_decode_roofline_ssm.step_bytes(model, 1) == 2 * LAYERS * STATE
    assert ssm_decode_roofline_ssm.step_bytes(model, SLOTS) \
        == 2 * 26 * 128 * 16 * 5120 * 4 == 2_181_038_080
    assert ssm_decode_roofline_ssm.step_bytes(model, SLOTS) \
        < _steps(1)[0]["state_bytes"]
    tiny = _model("jamba-tiny")  # 13 Mamba layers of 14, 16 x 128
    assert ssm_decode_roofline_ssm.step_bytes(tiny, 4) \
        == 4 * 13 * 2 * 16 * 128 * 4


def test_the_reader_divides_the_states_bytes_by_the_kernels_seconds():
    """The records closed while the profiler ran (``traced`` 1) count, also
    one that admitted (its decode step ran the kernel too) and whatever the
    occupancy (a dead slot's state passes through the kernel as it is); the
    others of the window do not."""
    steps = _steps(40, traced=0) + _steps(STEPS) + _steps(60, traced=0)
    steps[50]["admitted"], steps[50]["stall_s"] = 2, 0.05
    steps[51]["occupancy"] = 97
    got = ssm_decode_roofline_ssm.read(_ctx(steps))
    seconds = sum(s for n, s in OPS.items() if "ssm_decode" in n)
    assert seconds == pytest.approx(STEPS * LAYERS * CALL_S)
    assert got == pytest.approx(
        100.0 * STEPS * SLOTS * LAYERS * 2 * STATE / 819e9 / seconds)
    assert got == pytest.approx(100.0 * SLOTS * 2 * STATE / 819e9 / CALL_S)
    assert 85 < got < 86
    # A kernel exactly as fast as the HBM allows reads 100%, and no more.
    least = STEPS * SLOTS * LAYERS * 2 * STATE / 819e9
    assert ssm_decode_roofline_ssm.read(_ctx(
        steps, ops={"mosaic:ssm_decode.26": least})) == pytest.approx(100.0)


def test_the_share_stays_under_100_when_the_records_count_every_slot():
    """The fastest a call can be is the HBM's time for what it moves: every
    slot's state in and out.  With the records counting exactly that (the
    engine's ``slots``, not the live ones), a share over 100 would need a
    call faster than the HBM."""
    least_call = SLOTS * 2 * STATE / 819e9
    for slower in (1.0, 1.02, 1.2, 2.0):
        ops = {"mosaic:ssm_decode.26": STEPS * LAYERS * least_call * slower}
        got = ssm_decode_roofline_ssm.read(_ctx(_steps(STEPS), ops=ops))
        assert got == pytest.approx(100.0 / slower) and got <= 100.0 + 1e-9


@pytest.mark.parametrize("what, over", [
    ("the parent: the recurrence's two fusions, no such call",
     dict(ops={**{n: s for n, s in OPS.items() if "ssm_decode" not in n},
               "fusion:select_dynamic-update-slice_fusion.3": 0.0083})),
    ("no trace", dict(trace={})), ("no trace at all", dict(trace=None)),
    ("a train run", dict(kind="train", steps=3)),
    ("no records", dict(steps=[])),
    ("no record closed while the profiler ran",
     dict(steps=_steps(STEPS, traced=0))),
    ("records of a program without recurrent state",
     dict(steps=[{k: v for k, v in r.items() if k != "state_bytes"}
                 for r in _steps(STEPS)])),
    ("a family without state-space layers",
     dict(model=_model("trinity-mini-L5"))),
    ("off a TPU",
     dict(device={"platform": "cpu", "kind": "cpu", "count": 1}))],
    ids=lambda x: x.replace(" ", "-") if isinstance(x, str) else "")
def test_the_reader_reads_nothing_where_there_is_nothing_to_read(what, over):
    assert ssm_decode_roofline_ssm.read(_ctx(_steps(STEPS))) is not None
    over = dict(over)
    ctx = _ctx(over.pop("steps", _steps(STEPS)), **over)
    assert ssm_decode_roofline_ssm.read(ctx) is None, what


def test_each_kernels_reader_reads_only_its_own_kernel():
    """``mosaic:ssm_decode`` answers to no older needle, and this reader to
    none of theirs: the attention layers' walk is in the same trace."""
    from benchmarks.trace_reduce import ops_time

    tr = {"ops": OPS}
    assert ops_time(tr, ssm_decode_roofline_ssm.KERNEL) \
        == pytest.approx(STEPS * LAYERS * CALL_S)
    assert ops_time(tr, paged_decode_roofline_swa.KERNEL) \
        == pytest.approx(0.056)
    for needle in ("mosaic:ragged-dot", "mosaic:latent_decode",
                   "mosaic:paged_prefill", "mosaic:latent_prefill"):
        assert ops_time(tr, needle) == 0.0
    assert ssm_decode_roofline_ssm.read(_ctx(_steps(STEPS), ops={
        "mosaic:paged_decode.2": 0.028})) is None


def test_the_metric_is_in_the_benchmark_as_the_issue_names_it():
    doc = spec.load_benchmark(ROOT)
    spec.validate(doc)
    entry, = [m for m in doc["per_layer"]
              if m["name"] == "ssm_decode_roofline.ssm"]
    assert entry == {
        "name": "ssm_decode_roofline.ssm", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "serve_tok_s", "workloads": [CELL]}
    assert "kernels" in {m["layer"] for m in doc["per_layer"]
                         if m["name"] != entry["name"]}
    serve_tok_s, = [m for m in doc["end_to_end"]
                    if m["name"] == "serve_tok_s"]
    assert CELL in serve_tok_s["workloads"]
    cells = {w["name"]: w["config"] for w in doc["workloads"]}
    assert cells[CELL] == CONFIG
    # The reader's module is where the harness looks for it.
    module = entry["name"].replace(".", "_").replace("-", "_")
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "layer_metrics", module + ".py"))
    # The cell keeps the per-layer metrics it had.
    listed = {m["name"] for m in doc["per_layer"]
              if CELL in m.get("workloads", ())}
    assert {"decode_bytes_floor_share.ssm", "scan_padding_share.ssm",
            "decode_period_ms.sat", "decode_device_wait_ms.sat",
            "ssm_decode_roofline.ssm"} <= listed
