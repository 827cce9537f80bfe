"""The benchmark's yardstick on hand-worked cases: percentiles, spreads,
the operations a token requires, roofline shares, the traffic generator,
the trace reduction, and the rules on BENCHMARK.json's names and units."""

import json
import os

import pytest

from bench_testlib import ROOT, repo_copy  # noqa: F401

from benchmarks import arith, spec, trace_reduce, traffic


# -------------------------------------------------------------- arithmetic


@pytest.mark.parametrize("values,p,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    ([10, 20, 30, 40, 50], 90, 46.0),      # rank 3.6: 40 + 0.6 * 10
    (list(range(1, 101)), 95, 95.05),      # rank 94.05
    ([7], 99, 7.0),
])
def test_percentile_hand_worked(values, p, want):
    assert arith.percentile(values, p) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error_not_zero():
    with pytest.raises(ValueError):
        arith.percentile([], 50)


def test_spread_is_the_quartile_distance_over_the_median():
    # statistics.quantiles([1..6], n=4) -> 1.75, 3.5, 5.25
    assert arith.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
    assert arith.spread([100, 100, 100, 100, 100, 100]) == 0.0


TOY = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
       "num_attention_heads": 2, "num_key_value_heads": 1, "vocab_size": 10}


def test_train_flops_per_token_hand_worked():
    # per layer: wq 64 + wo 64 + wk 32 + wv 32 + mlp 3*128 = 576; x2 = 1152;
    # head 80 -> 1232 matmul parameters.  6 * 1232 = 7392; attention at
    # S = 4: 6 * 2 layers * 4 * 8 = 384.
    assert arith.matmul_params(TOY) == 1232
    assert arith.train_flops_per_token(TOY, 4) == 7392 + 384


def test_published_parameter_counts():
    from benchmarks.modelcfg import param_count

    def cfg(name):
        return spec.load_json(os.path.join(
            ROOT, "benchmarks", "configs", name + ".json"))

    assert param_count(cfg("internlm2-1.8b")) == 1_889_110_016
    assert param_count(cfg("mistral-7b-v0.3-L4")) == 1_140_887_552


def test_mfu_hand_worked():
    # 1000 tokens/s x 1e9 operations on 2 chips of 1e12: 0.5
    assert arith.mfu(1000.0, 1e9, 2, 1e12) == pytest.approx(0.5)


def test_flash_ops_and_bytes_hand_worked():
    f = arith.flash_forward_ops_bytes(1, 2, 1, 4, 8)
    # 2 matmuls * 2 ops * 2 heads * 4 * 4 * 8 = 1024, causal half: 512
    assert f["ops"] == 512
    # q and o: 2 * (2*4*8*2 B) = 256; k and v: 2 * (1*4*8*2) = 128; lse 32
    assert f["bytes"] == 256 + 128 + 32
    b = arith.flash_backward_ops_bytes(1, 2, 1, 4, 8)
    assert b["ops"] == 2.5 * 512
    assert b["bytes"] == 4 * 128 + 4 * 64 + 2 * 32


def test_roofline_says_which_bound():
    r = arith.roofline(ops=2e12, nbytes=1e9, seconds=4.0, peak_flops=1e12,
                       peak_bytes_per_s=1e9)
    assert r == {"share": 0.5, "bound": "compute", "least_s": 2.0}
    r = arith.roofline(ops=1e12, nbytes=3e9, seconds=4.0, peak_flops=1e12,
                       peak_bytes_per_s=1e9)
    assert r["bound"] == "memory" and r["share"] == pytest.approx(0.75)


def test_unknown_device_kind_is_an_error():
    assert arith.load_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(ValueError, match="no peaks on record"):
        arith.load_peaks("TPU v9 imaginary")
    with pytest.raises(ValueError):
        arith.load_peaks("_source")


# ------------------------------------------------------------------ traffic


MIX = {"prompt_len": {"dist": "lognormal", "median": 64, "sigma": 0.8,
                      "min": 8, "max": 256},
       "output_len": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                      "min": 4, "max": 64}, "pool": 16}


def test_every_seed_gets_the_same_schedule_and_its_own_token_ids():
    a = traffic.requests(MIX, 32, 1, 1000)
    b = traffic.requests(MIX, 32, 3_000_000_019, 1000)
    def sizes(rs):
        return [(len(r["prompt"]), r["max_new"]) for r in rs]

    assert sizes(a) == sizes(b)          # same sizes in the same order
    assert len(set(sizes(a)[:16])) > 8   # and they do vary
    assert sorted(sizes(a)[:16]) == sorted(sizes(a)[16:])  # pool cycles
    assert sizes(a)[:16] != sizes(a)[16:]  # each pass in a new order
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    assert a == traffic.requests(MIX, 32, 1, 1000)  # same seed, same inputs
    other = traffic.requests(dict(MIX, schedule_seed=1), 32, 1, 1000)
    assert sorted(len(r["prompt"]) for r in other) == sorted(
        len(r["prompt"]) for r in a) and sizes(other) != sizes(a)
    assert all(8 <= len(r["prompt"]) <= 256 and 4 <= r["max_new"] <= 64
               and all(0 < t < 1000 for t in r["prompt"]) for r in a)


def test_arrivals_are_the_exponentials_quantiles_in_a_fixed_order():
    a, b = traffic.arrivals(2.0, 100), traffic.arrivals(2.0, 100, 1)
    assert a[-1] == pytest.approx(b[-1])          # same sum of gaps
    assert a[-1] == pytest.approx(100 / 2.0, rel=0.05)
    assert a != b and a == sorted(a) and a == traffic.arrivals(2.0, 100)


def test_shared_prefix_is_shared():
    rs = traffic.requests(dict(MIX, shared_prefix=6), 4, 5, 1000)
    assert len({tuple(r["prompt"][:6]) for r in rs}) == 1
    assert len({tuple(r["prompt"][6:12]) for r in rs}) == 4


def test_train_batches_are_seeded_and_shifted():
    b = traffic.train_batch(2 ** 31 + 11, 3, 2, 8, 100)
    assert (b["tokens"][:, 1:] == b["targets"][:, :-1]).all()
    again = traffic.train_batch(2 ** 31 + 11, 3, 2, 8, 100)
    assert (again["tokens"] == b["tokens"]).all()
    assert (traffic.train_batch(2 ** 31 + 11, 4, 2, 8, 100)["tokens"]
            != b["tokens"]).any()


# ---------------------------------------------------------- trace reduction


def test_interval_arithmetic():
    u = trace_reduce.union([(0, 2), (1, 3), (5, 6), (6, 6)])
    assert u == [(0, 3), (5, 6)] and trace_reduce.total(u) == 4
    assert trace_reduce.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert trace_reduce.subtract([(0, 4), (6, 8)], [(3, 7)]) == \
        [(0, 3), (7, 8)]


def test_reduction_on_hand_made_events():
    ns = 1e9
    ev = {"devices": {
        "/device:TPU:0": [
            ("while:while.1", 0 * ns, 10 * ns),      # container: left out
            ("fusion:fusion.1", 0 * ns, 2 * ns),
            ("all-gather-start:ags.1", 2 * ns, 1 * ns),  # exposed: 1 s
            ("all-reduce:ar.2", 3 * ns, 2 * ns),     # 1 s under fusion.2
            ("fusion:fusion.2", 4 * ns, 2 * ns),
            ("mosaic:_lambda_.7", 8 * ns, 2 * ns)],  # gap 6..8
        "/device:TPU:1": [("fusion:fusion.1", 0 * ns, 5 * ns)]},
        "async": {"/device:TPU:0": [("all-gather-start:ags.1", 2 * ns,
                                     3 * ns)]},   # in flight 2..5
        "host": [("engine_step", 5 * ns, 4 * ns),
                 ("prefill", 6.5 * ns, 1 * ns)]}
    r = trace_reduce.reduce_events(ev, window_s=10.0)
    assert r["n_devices"] == 2 and r["window_s"] == 10.0
    assert r["busy_s_per_device"] == [8.0, 5.0] and r["busy_s"] == 6.5
    assert r["collective_s"] == pytest.approx(3.0 / 2)
    assert r["collective_exposed_s"] == pytest.approx(2.0 / 2)
    assert r["ops"]["fusion:fusion.1"] == 7.0
    assert "while:while.1" not in r["ops"]
    assert r["idle_gaps"] == [["prefill", 2.0]]      # innermost at t = 7
    assert trace_reduce.ops_time(r, "mosaic:") == 2.0
    assert r["device_ops"][0] == ["fusion:fusion.1", 7.0]


@pytest.mark.parametrize("text,want", [
    ('%fusion.4 = bf16[4,8]{1,0:T(4,128)(2,1)S(1)} fusion(bf16[4,8]{1,0} '
     '%p.1), kind=kOutput, calls=%fused_computation.2', "fusion:fusion.4"),
    ('%_lambda_.1 = (bf16[1,2]{1,0:T(8,128)(2,1)S(1)}, f32[1,2]{1,0}) '
     'custom-call(s32[1]{0:T(128)} %c.3), '
     'custom_call_target="tpu_custom_call"', "mosaic:_lambda_.1"),
    ('%all-gather-start.2 = (bf16[8]{0}, bf16[16]{0}) all-gather-start('
     'bf16[8]{0} %x), dimensions={0}', "all-gather-start:all-gather-start.2"),
    ('%while.3 = (s32[]{:T(128)}, f32[2]{0}) while((s32[], f32[2]{0}) %t), '
     'condition=%c, body=%b', "while:while.3"),
    ("ThreadpoolListener::StartRegion", "ThreadpoolListener::StartRegion"),
])
def test_operation_labels(text, want):
    got = trace_reduce.label(text)
    assert got == want
    assert trace_reduce.is_container(got) == want.startswith("while:")
    assert trace_reduce.is_collective(got) == want.startswith("all-gather")


def test_reduction_of_the_recorded_trace():
    """A few steps of a small jitted program with one Pallas flash call,
    traced on one TPU v5e (my chip run, PR 23)."""
    path = os.path.join(ROOT, "benchmarks", "testdata", "small.xplane.pb")
    want = spec.load_json(os.path.join(
        ROOT, "benchmarks", "testdata", "small.expected.json"))
    ev = trace_reduce.load_events(path)
    assert sorted(ev["devices"]) == want["devices"]
    assert sorted({n for n, _, _ in ev["host"]}) == want["annotations"]
    r = trace_reduce.reduce_events(ev)
    assert r["n_devices"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-6)
    mosaic = trace_reduce.ops_time(r, *want["mosaic_needles"])
    assert mosaic == pytest.approx(want["mosaic_s"], rel=1e-6)
    assert r["collective_s"] == 0.0
    assert {n for n, _ in r["idle_gaps"]} <= set(want["annotations"]) \
        | {"unannotated"}


# ------------------------------------------------------------ BENCHMARK.json


def test_benchmark_json_keeps_the_contract():
    doc = spec.load_benchmark(ROOT)
    spec.validate(doc)
    assert len(json.dumps(doc)) < 64 * 1024
    assert doc["command"][-1] == "benchmarks/run.py"
    for c in doc["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in doc["workloads"]:
        cell = spec.load_cell(w["name"], ROOT)
        assert cell["traffic"]["kind"] in spec.TRAFFIC_KINDS
        for m in cell["end_to_end"]:
            __import__("benchmarks.metrics." + _module(m["name"]))
        for m in cell["per_layer"]:
            __import__("benchmarks.layer_metrics." + _module(m["name"]))
    units = {m["unit"] for m in doc["end_to_end"] + doc["per_layer"]}
    assert units <= {"tokens/s", "ms", "s", "%"}
    assert {m["name"] for m in doc["end_to_end"]} == {
        "serve_tok_s", "itl_p95_ms", "train_tok_s", "setup_s"}


def _module(name):
    return name.replace(".", "_").replace("-", "_")


@pytest.mark.parametrize("edit,message", [
    (lambda d: d["end_to_end"][0].update(unit="tokens per second"), "unit"),
    (lambda d: d["end_to_end"][0].update(name="tok/s"), "naming rule"),
    (lambda d: d["end_to_end"][0].update(unit="µs"), "unit"),
    (lambda d: d["workloads"][0].update(name="a b"), "naming rule"),
    (lambda d: d["workloads"][0].update(why="x" * 201), "200 characters"),
    (lambda d: d["workloads"][0].update(chips=2), "chips"),
    (lambda d: d["per_layer"][0].update(moves="nothing"), "moves"),
    (lambda d: d["per_layer"][0].update(why="because"), "keys"),
    (lambda d: d["end_to_end"][0].update(bound=0.2), "bound"),
    (lambda d: d["end_to_end"][0].update(source="program_counter"),
     "host_clock"),
    (lambda d: d["per_layer"][0].pop("workloads"), "is not"),
    (lambda d: d.update(run_seconds=52), "run_seconds"),
    (lambda d: d["workloads"].append(dict(d["workloads"][0])), "two cells"),
    (lambda d: [w.update(chips=4) for w in d["workloads"][:2]], "quarter"),
])
def test_validation_refuses(edit, message):
    doc = json.loads(json.dumps(spec.load_benchmark(ROOT)))
    edit(doc)
    with pytest.raises(spec.SpecError, match=message):
        spec.validate(doc)


# ----------------------------------------------------------------- families

#: What the dense counts gave before the harness asked a family for them
#: (PR 26), at the cells' own sequence lengths and batches; a compiled step
#: with three or four ``tpu_custom_call`` a layer ran the flash forward once
#: or twice.
COUNTS = {
    "internlm2-1.8b": {
        "param_count": 1_889_110_016, "matmul_params": 1_699_479_552,
        "flops": {4096: 11_404_836_864.0, 2048: 10_800_857_088.0},
        "kernels": {(4, 4096, 3): (23_089_744_183_296.0, 14_571_012_096.0),
                    (4, 4096, 4): (29_686_813_949_952.0, 19_428_016_128.0),
                    (8, 2048, 3): (11_544_872_091_648.0, 14_571_012_096.0),
                    (8, 2048, 4): (14_843_406_974_976.0, 19_428_016_128.0)}},
    "mistral-7b-v0.3-L4": {
        "param_count": 1_140_887_552, "matmul_params": 1_006_632_960,
        "flops": {4096: 6_442_450_944.0, 2048: 6_241_124_352.0},
        "kernels": {(4, 4096, 3): (7_696_581_394_432.0, 4_051_697_664.0),
                    (4, 4096, 4): (9_895_604_649_984.0, 5_402_263_552.0),
                    (8, 2048, 3): (3_848_290_697_216.0, 4_051_697_664.0),
                    (8, 2048, 4): (4_947_802_324_992.0, 5_402_263_552.0)}},
}


def _config(name):
    return spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", name + ".json"))


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_a_file_without_the_key_is_of_the_llama_family(name):
    model = _config(name)
    assert "family" not in model
    assert spec.family(model).__name__ == "benchmarks.families.llama"
    assert spec.family(model).REHEARSAL_CONFIG == "rehearsal-tiny"


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_the_familys_parameter_counts_did_not_move(name):
    model = _config(name)
    fam = spec.family(model)
    assert fam.param_count(model) == COUNTS[name]["param_count"]
    assert fam.matmul_params(model) == COUNTS[name]["matmul_params"]


@pytest.mark.parametrize("seq", [4096, 2048])
@pytest.mark.parametrize("name", sorted(COUNTS))
def test_the_familys_train_flops_did_not_move(name, seq):
    model = _config(name)
    got = spec.family(model).train_flops_per_token(model, seq)
    assert got == COUNTS[name]["flops"][seq]
    assert got == arith.train_flops_per_token(model, seq)


@pytest.mark.parametrize("calls_per_layer", [3, 4])
@pytest.mark.parametrize("batch,seq", [(4, 4096), (8, 2048)])
@pytest.mark.parametrize("name", sorted(COUNTS))
def test_the_familys_kernel_counts_did_not_move(name, batch, seq,
                                                calls_per_layer):
    model = _config(name)
    calls = calls_per_layer * model["num_hidden_layers"]
    got = spec.family(model).train_step_kernel_ops_bytes(
        model, batch, seq, calls)
    assert (got["ops"], got["bytes"]) \
        == COUNTS[name]["kernels"][batch, seq, calls_per_layer]
    assert got == arith.flash_train_step_ops_bytes(
        model, batch, seq, calls_per_layer - 2)


def test_a_step_with_no_kernel_count_reads_as_one_forward():
    """``max(1, ...)``: as before, a step whose text was not counted does
    not read as a flash-free step."""
    model = _config("mistral-7b-v0.3-L4")
    assert spec.family(model).train_step_kernel_ops_bytes(
        model, 4, 4096, 0) == arith.flash_train_step_ops_bytes(
            model, 4, 4096, 1)


@pytest.mark.parametrize("name", ["no-such", "../spec", 7])
def test_a_family_with_no_file_is_refused_by_name_and_path(name):
    model = dict(_config("internlm2-1.8b"), family=name)
    with pytest.raises(spec.SpecError) as e:
        spec.family(model)
    assert repr(name) in str(e.value) and "internlm2-1.8b" in str(e.value)
    assert os.path.join("benchmarks", "families") in str(e.value)


def test_a_cell_of_a_family_with_no_file_fails_before_any_process(
        repo_copy):  # noqa: F811
    path = repo_copy / "benchmarks" / "configs" / "internlm2-1.8b.json"
    model = dict(json.load(open(path)), family="olmoe")
    json.dump(model, open(path, "w"))
    with pytest.raises(spec.SpecError, match="'olmoe'") as e:
        spec.load_cell("internlm2-1.8b.serve-mixed", str(repo_copy))
    assert os.path.join(ROOT, "benchmarks", "families", "olmoe.py") \
        in str(e.value)


def test_the_llama_family_refuses_what_it_does_not_compute():
    model = dict(_config("internlm2-1.8b"), tie_word_embeddings=True)
    with pytest.raises(ValueError, match="the llama family computes only"):
        spec.family(model).check_supported(model)
