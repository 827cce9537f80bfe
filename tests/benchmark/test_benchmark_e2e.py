"""The harness end to end at the tiny configuration on the CPU (its explicit
rehearsal mode), once for each traffic kind: the last line of stdout has
exactly the contract's keys, and a run leaves nothing behind.  A rehearsal
says ``"platform": "cpu"`` and is never a measurement."""

import hashlib
import json
import os
import shutil

from bench_testlib import (RESULT_KEYS, ROOT,  # noqa: F401
                           assert_nothing_left, repo_copy, run_bench)

SAT = "internlm2-1.8b.serve-saturated"
MIX = "internlm2-1.8b.serve-mixed"
T1 = "mistral-7b-v0.3-L4.train-1chip"


def check_result(lines, metrics, trace=False):
    out = lines[-1]
    assert set(out) == RESULT_KEYS | ({"breakdown"} & set(out))
    assert out["correct"] is True, lines
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert out["device"]["platform"] == "cpu"  # a rehearsal, and says so
    assert set(out["metrics"]) == set(metrics)
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    return out


def test_closed_loop_three_times_in_a_row_leaves_nothing_behind():
    for seed in (7, 2 ** 31 + 7, 9):
        rc, lines, err = run_bench(
            "--workload", SAT, "--seed", str(seed), "--seconds", "2",
            "--trace", "0", "--rehearse")
        assert rc == 0, err[-2000:]
        check_result(lines, {"serve_tok_s", "setup_s"})
        assert_nothing_left(lines)


def test_open_loop_times_each_request_from_when_it_was_due():
    rc, lines, err = run_bench(
        "--workload", MIX, "--seed", "5", "--seconds", "3", "--trace", "0",
        "--rehearse")
    assert rc == 0, err[-2000:]
    check_result(lines, {"itl_p95_ms", "setup_s"})
    samples = next(l for l in lines if l.get("phase") == "samples")
    assert samples["requests_in_window"] >= 8
    assert samples["generator_late_ms_max"] < 500
    assert samples["step_records_in_window"] > 0
    assert_nothing_left(lines)


def test_open_loop_per_layer_metrics_come_from_step_records():
    rc, lines, err = run_bench(
        "--workload", MIX, "--seed", "6", "--seconds", "3", "--trace", "1",
        "--rehearse")
    assert rc == 0, err[-2000:]
    # No device plane in a CPU trace: the device readers return nothing
    # and the harness leaves them out; no device number from a CPU.
    check_result(lines, {"handle_overhead_ms", "ttft_p90_ms",
                         "prefill_stall_share.mixed",
                         "decode_step_ms.mixed", "worker_start_s",
                         "warmup_compile_s"})
    assert "busy_s" not in lines[-1]["device"]


def test_train_on_one_device_holds_the_first_step_to_the_reference():
    rc, lines, err = run_bench(
        "--workload", T1, "--seed", "3000000011", "--seconds", "2",
        "--trace", "0", "--rehearse")
    assert rc == 0, err[-2000:]
    check_result(lines, {"train_tok_s", "setup_s"})
    samples = next(l for l in lines if l.get("phase") == "samples")
    assert samples["loss_rel_err"] < 1e-4
    assert samples["grad_norm_rel_err"] < 1e-3
    assert_nothing_left(lines)


def test_a_cell_a_configuration_a_mix_and_a_metric_are_added_as_files(
        repo_copy):  # noqa: F811
    """In a temporary copy: one new file each and new entries, no edit of a
    file that was there.  The new cell trains on four virtual devices
    (fsdp=2 x tp=2), which is also the fourth cell's rehearsal."""
    b = repo_copy / "benchmarks"
    tiny = json.load(open(b / "configs" / "rehearsal-tiny.json"))
    tiny["name"] = "added-config"
    json.dump(tiny, open(b / "configs" / "added-config.json", "w"))
    mix = json.load(open(b / "traffic" / "train-fsdp2tp2.json"))
    json.dump(mix, open(b / "traffic" / "added-mix.json", "w"))
    (b / "layer_metrics" / "added_metric.py").write_text(
        '"""Steps of the window, as a count."""\n\n\n'
        'def read(ctx):\n    return ctx["steps"]\n')
    doc = json.load(open(repo_copy / "BENCHMARK.json"))
    doc["configs"].append({
        "name": "added-config", "source": "a test",
        "file": "benchmarks/configs/added-config.json", "reduced": [],
        "why": "a test"})
    doc["workloads"].append({
        "name": "added-config.added-mix", "config": "added-config",
        "traffic": "added-mix", "chips": 4, "why": "a test"})
    for m in doc["end_to_end"]:
        if m["name"] == "train_tok_s":
            m["workloads"].append("added-config.added-mix")
    doc["per_layer"].append({
        "name": "added_metric", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "train step",
        "moves": "train_tok_s", "workloads": ["added-config.added-mix"]})
    json.dump(doc, open(repo_copy / "BENCHMARK.json", "w"))
    rc, lines, err = run_bench(
        "--workload", "added-config.added-mix", "--seed", "4", "--seconds",
        "2", "--trace", "1", "--rehearse", root=str(repo_copy))
    assert rc == 0, err[-2000:]
    out = check_result(lines, {"added_metric", "worker_start_s",
                               "warmup_compile_s"})
    assert out["device"]["count"] == 4
    ready = next(l for l in lines if l.get("phase") == "ready")
    assert ready["mesh"]["fsdp"] == 2 and ready["mesh"]["tp"] == 2
    samples = next(l for l in lines if l.get("phase") == "samples")
    assert samples["loss_rel_err"] < 1e-4
    assert samples["grad_norm_rel_err"] < 1e-3


def _digests(root):
    """{relative path: sha256} of every file under ``root`` but
    BENCHMARK.json (which takes the entries) and what a run leaves."""
    out = {}
    for d, _, names in os.walk(root):
        if "__pycache__" in d:
            continue
        for n in names:
            p = os.path.join(d, n)
            if os.path.isfile(p) and n != "BENCHMARK.json":
                with open(p, "rb") as f:
                    out[os.path.relpath(p, root)] = hashlib.sha256(
                        f.read()).hexdigest()
    return out


def test_a_family_is_added_as_files(repo_copy):  # noqa: F811
    """In a temporary copy: a family whose head is tied to the embedding
    (``tied_family/``: its module, its reference, its tiny configuration),
    one configuration of it and two cells, as files and entries only.  The
    training cell is held to that family's reference.  The serving cell
    gets as far as the program lets it: the family's registration serves
    the configuration, and the family's reference then finds the engine's
    head untied, because ``LLMServer`` makes its weights with ``llama_init``
    whatever the family (ROADMAP D2)."""
    before = _digests(repo_copy)
    b = repo_copy / "benchmarks"
    src = os.path.join(ROOT, "tests", "benchmark", "tied_family")
    shutil.copy(os.path.join(src, "family.py"), b / "families" / "tied.py")
    shutil.copy(os.path.join(src, "reference.py"),
                b / "reference" / "tied_ref.py")
    shutil.copy(os.path.join(src, "tied-tiny.json"), b / "configs")
    added = json.load(open(b / "configs" / "tied-tiny.json"))
    added["name"] = "added-tied"
    json.dump(added, open(b / "configs" / "added-tied.json", "w"))
    doc = json.load(open(repo_copy / "BENCHMARK.json"))
    doc["configs"].append({
        "name": "added-tied", "source": "a test",
        "file": "benchmarks/configs/added-tied.json", "reduced": [],
        "why": "a test"})
    for traffic, like in (("train-1chip", T1), ("serve-mixed", MIX)):
        name = "added-tied." + traffic
        doc["workloads"].append({
            "name": name, "config": "added-tied", "traffic": traffic,
            "chips": 1, "why": "a test"})
        for m in doc["end_to_end"] + doc["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(name)
    json.dump(doc, open(repo_copy / "BENCHMARK.json", "w"))

    rc, lines, err = run_bench(
        "--workload", "added-tied.train-1chip", "--seed", "3000000013",
        "--seconds", "2", "--trace", "0", "--rehearse", root=str(repo_copy))
    assert rc == 0, err[-2000:]
    check_result(lines, {"train_tok_s", "setup_s"})
    samples = next(l for l in lines if l.get("phase") == "samples")
    assert samples["loss_rel_err"] < 1e-4
    assert samples["grad_norm_rel_err"] < 1e-3
    assert_nothing_left(lines)

    rc, lines, err = run_bench(
        "--workload", "added-tied.serve-mixed", "--seed", "8", "--seconds",
        "3", "--trace", "0", "--rehearse", root=str(repo_copy))
    assert rc == 0, err[-2000:]
    out = lines[-1]
    assert set(out["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    # The llama family refuses a tied head and its reference agrees with
    # whatever tree it is given, so this verdict is the tied family's.
    assert out["correct"] is False
    reasons = [l["reason"] for l in lines if l.get("phase") == "incorrect"]
    assert len(reasons) == 1 and "reference logit" in reasons[0], reasons
    assert_nothing_left(lines)

    after = _digests(repo_copy)
    assert {p: h for p, h in after.items() if p in before} == before
