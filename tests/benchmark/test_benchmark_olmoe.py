"""The olmoe-1b-7b family at a tiny size on the CPU: the system's routed decoder
(``ray_tpu/models/moe.py``, and ``models/paged.py`` through the paged cache)
against the family's plain reference on seeded weights, logits and not
tokens; what the comparison has to catch; the family's counts; the routing
counters on the step record; and both new cells' rehearsal.

Tolerance.  System and reference both compute in float32 here, in different
orders (a grouped product over sorted pairs against a masked loop over the
experts; pages against a full forward), so they differ by float32 rounding
through two layers: the largest logit difference seen is 1.4e-6 (logits are
of order 1).  ``LOGIT_TOL`` leaves that a factor of 70 and is still 100
times under the least any of the faults below moves a logit (an FFN in
bfloat16: 1.2e-2; the others 0.4 to 1.6): on the chip the configuration IS bfloat16 with float32
accumulation, the rounding floor is bfloat16's, and the tolerance written in
``benchmarks/reference/olmoe_compare.py`` takes this one's place there."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_testlib import ROOT, run_bench

from benchmarks import spec
from benchmarks.families import olmoe_1b_7b as olmoe

LOGIT_TOL = 1e-4
ENGINE = dict(batch_slots=2, page_size=8, max_prompt_len=32,
              max_new_tokens_cap=16)


def _model(**over):
    return {**spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "olmoe-tiny.json")), **over}


def _weights(cfg, seed=0):
    """Seeded weights whose norm weights are not all ones, so that a norm
    left out (or put in the wrong place) shows."""
    params = olmoe.init(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 1000))

    def jitter(path, leaf):
        if "norm" not in jax.tree_util.keystr(path):
            return leaf
        return jax.random.uniform(next(keys), leaf.shape, leaf.dtype,
                                  0.5, 1.5)

    return jax.tree_util.tree_map_with_path(jitter, params)


def _tokens(model, shape, seed=2):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), shape, 1, model["vocab_size"]), np.int32)


def _system_logits(cfg, params, tokens):
    from ray_tpu.models import moe_apply

    return np.asarray(moe_apply(cfg, params, jnp.asarray(tokens)[None])[0][0])


# ------------------------------------------------------------ train forward


@pytest.mark.parametrize("norm_topk_prob", [False, True])
@pytest.mark.parametrize("qk_norm", [True, False])
def test_moe_apply_logits_match_the_reference(norm_topk_prob, qk_norm):
    model = _model(norm_topk_prob=norm_topk_prob, qk_norm=qk_norm)
    cfg = olmoe.program_config(model, max_seq=64, remat=False)
    assert (cfg.norm_topk_prob, cfg.qk_norm) == (norm_topk_prob, qk_norm)
    params = _weights(cfg)
    assert ("q_norm" in params["layers"][0]["attn"]) == qk_norm
    toks = _tokens(model, (48,))
    want = olmoe.reference(model, params).logits(toks, range(48))
    got = _system_logits(cfg, params, toks)
    assert np.abs(got - want).max() < LOGIT_TOL


def test_moe_loss_and_gradient_norm_match_the_reference():
    model = _model()
    cfg = olmoe.program_config(model, max_seq=32, remat=True)
    params = _weights(cfg)
    toks = _tokens(model, (3, 32))
    targets = np.roll(toks, -1, axis=1)
    loss, grads = jax.value_and_grad(
        lambda p: olmoe.loss(cfg, p, jnp.asarray(toks),
                             jnp.asarray(targets)))(params)
    norm = float(jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                              for g in jax.tree.leaves(grads))))
    ref_loss, ref_norm = olmoe.reference(model, params).loss_and_grad_norm(
        toks, targets)
    assert abs(float(loss) - ref_loss) / ref_loss < 1e-5
    assert abs(norm - ref_norm) / ref_norm < 1e-4
    # The auxiliary term is in both: without it the loss is lower by
    # about aux_loss_coeff x 1 (balanced routing gives an aux near 1).
    from ray_tpu.models import moe_apply
    from ray_tpu.ops.losses import masked_cross_entropy

    nll = float(masked_cross_entropy(
        moe_apply(cfg, params, jnp.asarray(toks))[0], jnp.asarray(targets)))
    assert 0.005 < ref_loss - nll < 0.03


# ----------------------------------------------------------------- routing


def _dense_ffn(moe, e, x):
    return (jax.nn.silu(x @ moe["w1"][e]) * (x @ moe["w3"][e])) @ moe["w2"][e]


def test_every_token_on_one_expert_is_that_experts_dense_ffn():
    """No capacity: an expert that gets every token computes every token.
    The old dispatch gave an expert ceil(1.25 * G * k / E) slots and dropped
    the rest."""
    from ray_tpu.models.moe import _moe_ffn

    model = _model(num_experts_per_tok=1)
    cfg = olmoe.program_config(model, max_seq=64, remat=False)
    moe = _weights(cfg)["layers"][0]["moe"]
    d, e = cfg.d_model, cfg.n_experts
    # Every token's first feature is 1 and the router reads only that one:
    # logit 20 for expert 5, 0 for the others.
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 40, d)).at[..., 0].set(
        1.0)
    moe["router"] = jnp.zeros((d, e)).at[0, 5].set(20.0)
    out, _, counts = _moe_ffn(cfg, moe, x)
    assert counts.tolist() == [0, 0, 0, 0, 0, 80, 0, 0]
    p5 = float(jax.nn.softmax(jnp.zeros((e,)).at[5].set(20.0))[5])
    np.testing.assert_allclose(np.asarray(out),
                               p5 * np.asarray(_dense_ffn(moe, 5, x)),
                               rtol=1e-4, atol=1e-5)


def test_rows_that_hold_no_token_reach_no_expert():
    from ray_tpu.models.moe import _moe_ffn

    model = _model()
    cfg = olmoe.program_config(model, max_seq=64, remat=False)
    moe = _weights(cfg)["layers"][1]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(4), (24, cfg.d_model))
    valid = jnp.arange(24) % 4 != 1
    full, _, all_counts = _moe_ffn(cfg, moe, x)
    out, _, counts = _moe_ffn(cfg, moe, x, valid)
    assert int(all_counts.sum()) == 24 * cfg.top_k
    assert int(counts.sum()) == int(valid.sum()) * cfg.top_k
    np.testing.assert_allclose(np.asarray(out[valid]),
                               np.asarray(full[valid]), atol=1e-6)
    assert not np.asarray(out[~valid]).any()


# ------------------------------------------------------- the paged programs


def _paged(cfg, params, model, *, prompt, new, prefix_len=0):
    """Logits of a prefill (the last ``prompt - prefix_len`` tokens through
    ``prefill_prefix_logits`` when a prefix is given, after a cold prefill
    of the prefix's pages) and then of ``new`` decode steps through the
    paged cache, teacher-forced with seeded tokens: [1 + new, V], the
    routing counters of every call, and the token sequence."""
    from ray_tpu.models import paged
    from ray_tpu.serve.engine import EngineConfig

    ec = EngineConfig(**ENGINE)
    ps, maxp, b = ec.page_size, ec.pages_per_seq, ec.batch_slots
    pools = paged.init_paged_pools(cfg, ec.pool_pages, ps)
    adapters = paged.init_adapter_pool(cfg, ec.max_adapters, ec.lora_rank)
    zero = jnp.asarray(ec.max_adapters, jnp.int32)  # the zero adapter
    seq = _tokens(model, (prompt + new,), seed=5)
    table = np.full((maxp,), ec.pool_pages, np.int32)
    table[:(prompt + new) // ps + 1] = 3 + np.arange(
        (prompt + new) // ps + 1)
    bucket = next(x for x in ec.prefill_buckets() if x >= prompt)
    pad = np.zeros((1, bucket), np.int32)

    if prefix_len:
        pad[0, :prefix_len] = seq[:prefix_len]
        _, pools, _ = paged.prefill_logits(
            cfg, params, pools, adapters, jnp.asarray(pad),
            jnp.asarray(prefix_len), jnp.asarray(table), zero)
        pad = np.zeros((1, bucket), np.int32)
        pad[0, :prompt - prefix_len] = seq[prefix_len:prompt]
        logits, pools, counts = paged.prefill_prefix_logits(
            cfg, params, pools, adapters, jnp.asarray(pad),
            jnp.asarray(prefix_len), jnp.asarray(prompt),
            jnp.asarray(table), zero)
    else:
        pad[0, :prompt] = seq[:prompt]
        logits, pools, counts = paged.prefill_logits(
            cfg, params, pools, adapters, jnp.asarray(pad),
            jnp.asarray(prompt), jnp.asarray(table), zero)
    rows, routed = [np.asarray(logits[0])], [jnp.stack(counts)]
    # Slot 1 decodes; slot 0 is empty (all scratch, inactive).
    tables = np.full((b, maxp), ec.pool_pages, np.int32)
    tables[1] = table
    active = jnp.asarray([False, True])
    for i in range(new):
        toks = jnp.asarray([0, seq[prompt + i]], jnp.int32)
        lens = jnp.asarray([0, prompt + i], jnp.int32)
        logits, pools, counts = paged.decode_logits(
            cfg, params, pools, adapters, toks, jnp.asarray(tables), lens,
            active, jnp.asarray([ec.max_adapters] * b, jnp.int32))
        rows.append(np.asarray(logits[1]))
        routed.append(jnp.stack(counts))
    return np.stack(rows), [np.asarray(r) for r in routed], seq


@pytest.mark.parametrize("prompt,prefix_len", [(21, 0), (30, 16), (27, 19)],
                         ids=["cold", "prefix-2-pages", "prefix-mid-page"])
def test_prefill_and_decode_through_pages_match_the_reference(prompt,
                                                              prefix_len):
    model = _model()
    cfg = olmoe.program_config(model, max_seq=64, remat=False)
    params = _weights(cfg)
    new = 6
    got, routed, seq = _paged(cfg, params, model, prompt=prompt, new=new,
                              prefix_len=prefix_len)
    want = olmoe.reference(model, params).logits(
        seq, range(prompt - 1, prompt + new))
    assert np.abs(got - want).max() < LOGIT_TOL
    # The counters: a prefill routes its real rows only (not the bucket's
    # padding), a decode step its one active slot.
    k, layers = cfg.top_k, cfg.n_layers
    assert routed[0].sum() == (prompt - prefix_len) * k * layers
    for r in routed[1:]:
        assert r.sum() == k * layers and (r > 0).sum() == k * layers


FAULTS = ["renormalised-top-k", "no-qk-norm", "qk-norm-after-rope",
          "dropped-token", "bfloat16-ffn"]


@pytest.mark.parametrize("fault", FAULTS)
def test_the_comparison_catches(fault, monkeypatch):
    """Each of these is a different model, or the same one in a lower
    precision, and has to read as incorrect: at this tolerance here, at
    bfloat16's on the chip."""
    from ray_tpu.models import llama, moe

    model = _model()
    cfg = olmoe.program_config(model, max_seq=64, remat=False)
    params = _weights(cfg)
    toks = _tokens(model, (48,))
    want = olmoe.reference(model, params).logits(toks, range(48))
    assert np.abs(_system_logits(cfg, params, toks) - want).max() < LOGIT_TOL
    real_ffn = moe._moe_ffn
    if fault == "renormalised-top-k":
        cfg = dataclasses.replace(cfg, norm_topk_prob=True)
    elif fault == "no-qk-norm":
        cfg = dataclasses.replace(cfg, qk_norm=False)
    elif fault == "qk-norm-after-rope":
        # Per head (over 16 of the 64) instead of over the whole width.
        def per_head(config, a, q, k):
            hd = config.head_dim
            def norm(x, w):
                shape = x.shape
                x = x.reshape(*shape[:-1], -1, hd)
                x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                      + config.norm_eps)
                return x.reshape(shape) * w
            return norm(q, a["q_norm"]), norm(k, a["k_norm"])
        monkeypatch.setattr(llama, "_qk_norm", per_head)
    elif fault == "dropped-token":
        def drop(config, m, x, valid=None):
            keep = jnp.ones(x.shape[:-1], bool).at[..., 7].set(False)
            return real_ffn(config, m, x, keep)
        monkeypatch.setattr(moe, "_moe_ffn", drop)
    elif fault == "bfloat16-ffn":
        def low(config, m, x, valid=None):
            bf = dataclasses.replace(config, dtype=jnp.bfloat16)
            m16 = {k: v.astype(jnp.bfloat16) if k != "router" else v
                   for k, v in m.items()}
            out, aux, c = real_ffn(bf, m16, x.astype(jnp.bfloat16), valid)
            return out.astype(x.dtype), aux, c
        monkeypatch.setattr(moe, "_moe_ffn", low)
    moved = np.abs(_system_logits(cfg, params, toks) - want).max()
    assert moved > 50 * LOGIT_TOL, moved


# ------------------------------------------------------------------ counts


def test_the_familys_counts_are_pinned():
    model = spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "olmoe-1b-7b-0125.json"))
    published = {**model, **model["published"]}
    assert model["reduced"] == ["num_hidden_layers"]
    assert olmoe.param_count(published) == 6_919_161_856
    assert olmoe.param_count(model) == 5_240_883_200
    assert olmoe.matmul_params(published) == 1_178_861_568
    assert olmoe.matmul_params(model) == 909_901_824
    assert olmoe.train_flops_per_token(published, 4096) \
        == 6.0 * 1_178_861_568 + 6.0 * 16 * 4096 * 2048
    # The grouped products of one decode step of 16 slots (128 pairs a
    # layer) that hits 56 of 64 experts in each of 12 layers, by hand.
    need = olmoe.routed_ffn_ops_bytes(model, 12 * 128, 12 * 56)
    assert need == {"ops": 12 * 128 * 3 * 2.0 * 2048 * 1024,
                    "bytes": (12 * 56 * 3 * 2048 * 1024
                              + 12 * 128 * 2 * 2048) * 2}
    assert 8.4e9 < need["bytes"] < 8.5e9
    # The program's own count agrees with the family's.
    cfg = olmoe.program_config(published, max_seq=4096)
    assert cfg.param_count() == 6_919_161_856
    shapes = jax.eval_shape(lambda: olmoe.init(cfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) \
        == 6_919_161_856


def _roofline_ctx(**over):
    """A traced run as the harness hands it to a reader: 1 s of the
    grouped-matmul kernel in a 5 s trace that started 1 s into the window,
    three decode steps and one prefill, one decode step of them before the
    profiler ran."""
    phases = {k: 0.0 for k in ("between_s", "idle_s", "upload_s",
                               "dispatch_s", "readback_s", "emit_s")}
    routing = {"experts_hit": 12 * 56, "expert_pairs": 12 * 128,
               "expert_load_max": 5}
    prefill = {"experts_hit": 12 * 64, "expert_pairs": 12 * 8 * 300,
               "expert_load_max": 60, "prompt": 300, "cached": 0}
    steps = [dict(phases, t=1000.0 + t, first_tokens=first, **routing)
             for t, first in ((0.5, []), (1.5, []), (3.0, [prefill]),
                              (5.9, []))]
    model = spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "olmoe-1b-7b-0125.json"))
    return {"kind": "serve_closed", "model": model, "seconds": 51.0,
            "window_wall": 1000.0, "steps": steps,
            "device": {"platform": "tpu", "kind": "TPU v5 lite"},
            "trace": {"n_devices": 1, "window_s": 5.0, "ops": {
                "mosaic:ragged-dot-none.7": 0.75,
                "mosaic:ragged-dot-none.9": 0.25, "fusion:fusion.3": 2.0}},
            **over}


def test_the_roofline_reader_takes_its_seconds_from_the_device_trace():
    from benchmarks.layer_metrics import moe_decode_roofline_moe as reader

    expert = 3 * 2048 * 1024 * 2  # one expert's three matrices, bytes
    need = (3 * 12 * 56 + 12 * 64) * expert \
        + (3 * 12 * 128 + 12 * 8 * 300) * 2 * 2048 * 2
    assert reader.read(_roofline_ctx()) \
        == pytest.approx(100.0 * need / 819e9 / 1.0, rel=1e-12)
    # Nothing to read: no trace, no call of the kernel in it, a CPU, a
    # dense model's records, a family that has no such count.
    tr = _roofline_ctx()["trace"]
    dense = [{k: v for k, v in r.items() if k != "experts_hit"}
             for r in _roofline_ctx()["steps"]]
    for r in dense:
        r["first_tokens"] = []
    llama = spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "internlm2-1.8b.json"))
    for over in ({"trace": {}}, {"trace": {**tr, "ops": {"fusion:f": 1.0}}},
                 {"device": {"platform": "cpu", "kind": "cpu"}},
                 {"steps": dense}, {"model": llama}):
        assert reader.read(_roofline_ctx(**over)) is None, over
    doc = spec.load_benchmark(ROOT)
    entry = {m["name"]: m for m in doc["per_layer"]}["moe_decode_roofline.moe"]
    assert entry["source"] == "device_trace" and entry["unit"] == "%"


@pytest.mark.parametrize("key,value", [
    ("clip_qkv", 8.0), ("rope_scaling", {"type": "linear", "factor": 2.0}),
    ("tie_word_embeddings", True), ("attention_bias", True),
    ("hidden_act", "gelu")])
def test_the_family_refuses_what_the_program_does_not_compute(key, value):
    with pytest.raises(ValueError, match=key):
        olmoe.check_supported(_model(**{key: value}))


# ------------------------------------------------------ engine and records


def _records(eng, prompts):
    from ray_tpu.util import steprec

    steprec.drain_buffered()
    for p in prompts:
        assert len(list(eng.submit(p, max_new_tokens=5))) == 5
    deadline, recs = time.time() + 5, []
    while time.time() < deadline:
        recs += [r for r in steprec.drain_buffered()
                 if r.get("engine") == eng.engine_id]
        if sum(len(r["first_tokens"]) for r in recs) == len(prompts) \
                and sum(1 for r in recs if r["occupancy"]) >= 4:
            break
        time.sleep(0.05)
    return recs


def test_a_registered_olmoe_is_served_and_its_records_carry_the_routing():
    from ray_tpu.models.paged import ROUTING_KEYS, trace_count
    from ray_tpu.serve.engine import LLMServer, register_model

    model = _model()
    cfg = olmoe.program_config(model, max_seq=48, remat=False)
    register_model("olmoe-tiny-test", lambda: cfg)
    traced = trace_count("decode")  # by whatever ran in this process before
    server = LLMServer(model="olmoe-tiny-test", engine=ENGINE, seed=3)
    try:
        eng = server.engine
        prompt = _tokens(model, (20,), seed=9).tolist()
        recs = _records(eng, [prompt, prompt])
        k, layers, experts = cfg.top_k, cfg.n_layers, cfg.n_experts
        decode = [r for r in recs if r["occupancy"]]
        assert decode and all(set(ROUTING_KEYS) <= set(r) for r in decode)
        for r in decode:  # one live slot a step: k pairs a layer
            assert r["expert_pairs"] == k * layers * r["occupancy"]
            assert 1 <= r["expert_load_max"] <= r["occupancy"]
            assert k * layers <= r["experts_hit"] <= experts * layers
        first = [e for r in recs for e in r["first_tokens"]]
        assert [e["cached"] for e in first] == [0, 16]
        # The cold prefill routed its 20 tokens, the second the 4 behind
        # the two cached pages: not the bucket's 32 rows.
        assert [e["expert_pairs"] for e in first] \
            == [20 * k * layers, 4 * k * layers]
        assert all(set(ROUTING_KEYS) <= set(e) for e in first)
        # The engine's greedy token is the model's own full forward's.
        out = list(server(prompt, 1))
        assert server.reference_logits(prompt)["argmax"] == out[0]
        # ... which is the plain reference's too.
        ref = olmoe.reference(model, eng.params).logits(
            np.asarray(prompt, np.int32), [19])[0]
        assert int(ref.argmax()) == out[0]
        assert server.stats()["decode_traces"] == traced + 1
    finally:
        server.engine.shutdown()


def test_a_dense_models_records_carry_no_routing_key():
    from ray_tpu.models.paged import ROUTING_KEYS
    from ray_tpu.serve.engine import LLMServer

    server = LLMServer(model="tiny", engine=ENGINE, seed=3)
    try:
        recs = _records(server.engine, [[3, 5, 7, 9]])
        assert recs and any(r["occupancy"] for r in recs)
        for r in recs:
            assert not set(ROUTING_KEYS) & set(r)
            assert all(not set(ROUTING_KEYS) & set(e)
                       for e in r["first_tokens"])
    finally:
        server.engine.shutdown()


# --------------------------------------------------------------- rehearsals


def test_the_olmoe_cell_rehearses_and_prints_its_metrics():
    rc, lines, err = run_bench(
        "--workload", "olmoe-1b-7b-0125.serve-saturated", "--seed",
        str(2 ** 31 + 11), "--seconds", "3", "--trace", "1", "--rehearse")
    assert rc == 0, err[-3000:]
    out = lines[-1]
    assert out["correct"] is True, lines
    assert out["attempted"] > 0 and out["failed"] == 0
    got = out["metrics"]
    # No roofline share from a CPU: it has no peak on record.
    assert "moe_decode_roofline.moe" not in got
    hit = got["experts_hit_share.moe"]
    assert hit["unit"] == "%" and 2 / 8 * 100 <= hit["value"] <= 100
    assert {"decode_step_ms.sat", "decode_host_ms.sat",
            "batch_occupancy.sat", "prefill_stall_share.sat"} <= set(got)
    samples = next(l for l in lines if l.get("phase") == "samples")
    assert samples["reference_gap_max"] <= 1e-3


def test_the_prefix_cell_rehearses_and_most_prompt_tokens_come_cached():
    rc, lines, err = run_bench(
        "--workload", "internlm2-1.8b.serve-prefix-sessions", "--seed", "12",
        "--seconds", "3", "--trace", "1", "--rehearse")
    assert rc == 0, err[-3000:]
    out = lines[-1]
    assert out["correct"] is True, lines
    assert out["attempted"] > 0 and out["failed"] == 0
    share = out["metrics"]["prefix_cached_token_share.prefix"]
    # The rehearsal's prompts are 20-32 tokens behind a shared 16.
    assert share["unit"] == "%" and 40 <= share["value"] <= 80
    assert "experts_hit_share.moe" not in out["metrics"]


def test_a_program_without_register_model_fails_before_any_process(
        tmp_path):
    """The parent of this PR under this PR's benchmark files: the family
    says why where the harness finds it, exit 1 in about a second, no
    replica started and restarted until the deployment times out."""
    root = tmp_path / "old"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for pkg in ("ray_tpu", "ray_tpu/serve"):
        os.makedirs(root / pkg)
        (root / pkg / "__init__.py").write_text("")
    (root / "ray_tpu" / "serve" / "engine.py").write_text(
        "_MODEL_BUILDERS = {}\n")
    t0 = time.time()
    rc, lines, err = run_bench(
        "--workload", "olmoe-1b-7b-0125.serve-saturated", "--seed", "1",
        "--seconds", "2", "--trace", "0", "--rehearse", root=str(root),
        timeout=60)
    assert rc == 1 and not lines and time.time() - t0 < 30
    assert "no public register_model" in err


def test_the_chip_comparison_rehearses_and_refuses_each_fault():
    """``benchmarks/reference/olmoe_compare.py`` at the tiny configuration:
    logits through the pages within the float32 tolerance, no top-k set
    apart, and each of its three faults read as incorrect; the serving
    cell's own reading (the greedy tokens' gap) is taken for every one of
    them and passes the sound program."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "benchmarks/reference/olmoe_compare.py",
         "--rehearse", "--seed", str(2 ** 31 + 3), "--faults"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    assert out["ok"] is True and out["logit_tol"] == LOGIT_TOL
    base, *faults = out["results"]
    assert base["correct"] and base["top_k_sets_differ"] == 0
    assert base["rows"] == 2 * (1 + 4) == base["argmax_agree"]
    assert [f["fault"] for f in faults] == [
        "renormalised-top-k", "no-qk-norm", "float8-experts"]
    assert not any(f["correct"] for f in faults)
    assert out["cell_logit_tol"] == 1e-3 and base["cell_check_passes"]
    assert all(f["cell_gap_max"] >= 0.0 for f in faults)


def test_the_new_cells_are_in_the_benchmark_as_the_issue_names_them():
    doc = spec.load_benchmark(ROOT)
    spec.validate(doc)
    cells = {w["name"]: w for w in doc["workloads"]}
    assert cells["olmoe-1b-7b-0125.serve-saturated"]["traffic"] \
        == "serve-saturated"
    assert cells["internlm2-1.8b.serve-prefix-sessions"]["chips"] == 1
    assert [w["name"] for w in doc["workloads"]][-2:] == [
        "olmoe-1b-7b-0125.serve-saturated",
        "internlm2-1.8b.serve-prefix-sessions"]
    sat = json.load(open(os.path.join(
        ROOT, "benchmarks", "traffic", "serve-saturated.json")))
    pre = json.load(open(os.path.join(
        ROOT, "benchmarks", "traffic", "serve-prefix-sessions.json")))
    for key in ("engine", "max_concurrent_queries", "system_config",
                "check", "clients", "output_len", "temperature"):
        assert pre[key] == sat[key], key  # so the cells share programs
    assert pre["shared_prefix"] == 512 == 4 * pre["engine"]["page_size"]
    assert doc["configs"][-1]["reduced"] == ["num_hidden_layers"]
