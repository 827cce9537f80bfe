"""The afmoe family (Trinity-Mini) at a tiny size on the CPU (window 8, pages
of 4, chunks of 8, a dense sliding layer before one period of routed ones,
float32, heads wider than hidden / heads): the system's decoder
(``ray_tpu/models/moe.py`` over ``block.py``, and ``models/paged.py`` through
the two kinds of paged cache) against the family's plain reference on seeded
weights, logits and not tokens; what the comparison has to catch; that the
four new trace-time branches add nothing to the five older configurations'
decode programs; the family's counts; the engine with a dense layer in a
ring and a shared expert beside rings; the new reader; and the new cell's
rehearsal.

Tolerance.  System and reference both compute in float32 here, in different
orders (pages and rings against a full forward, a grouped product over
sorted pairs against a masked loop over the experts), so they differ by
float32 rounding through five layers: the largest logit difference seen is
1.7e-6 (logits are of order 1, the largest about 4.6).  ``LOGIT_TOL`` leaves
that a factor of 50 and is 400 times under the least any structural fault
below moves a logit (the selection bias in the weights: 4.2e-2; float8
experts 6.9e-2; the gate left out 0.45; the routed experts alone in bfloat16:
3.2e-3, 32 times): on the chip the configuration IS bfloat16 and the
tolerance written in ``benchmarks/reference/afmoe_compare.py`` takes this
one's place."""

import dataclasses
import json
import math
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_testlib import ROOT, run_bench

from benchmarks import spec
from benchmarks.families import afmoe
from benchmarks.reference import afmoe_compare

LOGIT_TOL = 1e-4
CONFIG = "trinity-mini-L5"
TRAFFIC = "serve-reasoning-long-decode"
CELL = f"{CONFIG}.{TRAFFIC}"
READER = "decode_bytes_floor_share.swa"
#: Window 8 and chunks of 8 over pages of 4: a ring of 4 pages, 16 tokens;
#: a sequence may grow to 96, so that decoding laps the ring twice.
ENGINE = dict(batch_slots=2, page_size=4, max_prompt_len=48,
              max_new_tokens_cap=48, prefill_chunk=8, prefix_cache=False)


def _model(name="trinity-mini-tiny", **over):
    return {**spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", name + ".json")), **over}


def _weights(cfg, seed=0):
    """Seeded weights whose norm weights are not all ones, so that a norm
    left out (or put in the wrong place) shows."""
    return afmoe_compare.weights(afmoe, cfg, seed)


def _tokens(model, shape, seed=2):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), shape, 1, model["vocab_size"]), np.int32)


@pytest.fixture(scope="module")
def tiny():
    """(model file, program configuration, weights, reference)."""
    model = _model()
    cfg = afmoe.program_config(model, max_seq=96, remat=False)
    params = _weights(cfg)
    return model, cfg, params, afmoe.reference(model, params)


def _system_logits(cfg, params, tokens):
    from ray_tpu.models import moe_apply

    return np.asarray(moe_apply(cfg, params, jnp.asarray(tokens)[None])[0][0])


# ------------------------------------------------------------- full forward


def test_the_configuration_object_carries_what_the_equations_need(tiny):
    from ray_tpu.models import MoEConfig, block, paged

    _, cfg, params, _ = tiny
    assert (cfg.qk_norm, cfg.attn_gate, cfg.post_norm) == ("head", True, True)
    assert cfg.embed_scale == math.sqrt(64)
    assert (cfg.router_score, cfg.routed_scaling_factor,
            cfg.norm_topk_prob, cfg.n_shared_experts) == (
                "sigmoid", 2.826, True, 1)
    assert cfg.head_dim == 32 != cfg.d_model // cfg.n_heads
    # Sliding layers rotate and have the window; the full layer neither.
    assert [block.layer_kind(cfg, i) for i in range(5)] == [
        (8, True), (8, True), (8, True), (0, False), (8, True)]
    # The first configuration with BOTH layouts: the dense layer is a
    # window layer (its K/V in a ring), the full layer is routed.
    assert [block.is_routed(cfg, i) for i in range(5)] == [
        False, True, True, True, True]
    assert paged.kv_layers(cfg) == ([3], [0, 1, 2, 4])
    assert paged.counter_keys(cfg) == paged.ROUTING_KEYS + paged.KV_KEYS
    assert paged.ring_entries(cfg, 4, 8) == 4
    dense, routed = params["layers"][0], params["layers"][1]
    a = routed["attn"]
    assert a["wq"].shape == a["wg"].shape == (64, 4 * 32)
    assert a["wk"].shape == (64, 2 * 32) and a["wo"].shape == (4 * 32, 64)
    assert a["q_norm"].shape == a["k_norm"].shape == (32,)  # a head's
    assert {"attn_norm", "attn_post_norm", "mlp_norm", "ffn_post_norm",
            "mlp", "attn"} == set(dense)
    assert {"attn_norm", "attn_post_norm", "moe_norm", "ffn_post_norm",
            "moe", "attn"} == set(routed)
    assert dense["mlp"]["w1"].shape == (64, 96)
    assert routed["moe"]["w1"].shape == (8, 64, 32)
    assert routed["moe"]["shared"]["w1"].shape == (64, 32)
    assert routed["moe"]["router_bias"].shape == (8,)
    # The older architectures' weights are what they were: the new leaves
    # draw from keys of their own.
    plain = dataclasses.replace(cfg, attn_gate=False, post_norm=False)
    old = afmoe.init(plain, jax.random.PRNGKey(0))
    new = afmoe.init(cfg, jax.random.PRNGKey(0))
    assert "wg" not in old["layers"][1]["attn"]
    np.testing.assert_array_equal(old["layers"][1]["attn"]["wq"],
                                  new["layers"][1]["attn"]["wq"])
    np.testing.assert_array_equal(old["layers"][1]["moe"]["w2"],
                                  new["layers"][1]["moe"]["w2"])
    with pytest.raises(ValueError, match="qk_norm"):
        MoEConfig.tiny(qk_norm="heads")


def test_moe_apply_and_loss_match_the_reference(tiny):
    model, cfg, params, ref = tiny
    toks = _tokens(model, (40,))
    want = ref.logits(toks, range(40))
    assert np.abs(_system_logits(cfg, params, toks) - want).max() < LOGIT_TOL
    batch = _tokens(model, (2, 24), seed=7)
    targets = np.roll(batch, -1, axis=1)
    rcfg = dataclasses.replace(cfg, remat=True)
    loss, grads = jax.value_and_grad(
        lambda p: afmoe.loss(rcfg, p, jnp.asarray(batch),
                             jnp.asarray(targets)))(params)
    norm = float(jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                              for g in jax.tree.leaves(grads))))
    ref_loss, ref_norm = ref.loss_and_grad_norm(batch, targets)
    assert abs(float(loss) - ref_loss) / ref_loss < 1e-5
    assert abs(norm - ref_norm) / ref_norm < 1e-4


# ------------------------------------------------------- the paged programs


def _paged(cfg, params, seq, prompt, *, engine=ENGINE):
    """``test_benchmark_smallthinker._paged`` (the engine's way through the
    three programs by hand, called untraced, so that a function a fault
    swapped in is the one that runs) at this file's geometry: (logits
    [1 + new, V], the pools' final state)."""
    from test_benchmark_smallthinker import _paged as through_the_programs

    return through_the_programs(cfg, params, seq, prompt, engine=engine)


@pytest.mark.parametrize("prompt,new", [
    (6, 6), (14, 6), (6, 44), (21, 6), (45, 3)], ids=[
        "decoded-across-the-window", "decoded-across-the-rings-wrap",
        "the-ring-wrapped-twice-by-decoding", "chunked",
        "the-ring-wrapped-twice-by-prefill"])
def test_prefill_and_decode_through_the_rings_match_the_reference(
        tiny, prompt, new):
    """The cell's two check prompts at the tiny size (6 tokens, just under
    the window of 8, decoded across it; 14, just under the 16-token ring,
    decoded across its wrap); a prompt of 6 DECODED to 50, so that the
    ring is lapped at 16, 32 and 48 by decode steps alone; a prompt in
    three chunks; and one whose 45 tokens lap the ring in prefill."""
    model, cfg, params, ref = tiny
    seq = _tokens(model, (prompt + new,), seed=5)
    got, _ = _paged(cfg, params, seq, prompt)
    want = ref.logits(seq, range(prompt - 1, prompt + new))
    assert np.abs(got - want).max() < LOGIT_TOL


def test_a_chunked_prefill_equals_the_one_shot_program(tiny):
    """The same 29-token prompt through four chunks of 8 (the ring wraps)
    and through one 32-token bucket (``prefill_chunk`` 0): the same
    logits, and the same K/V in the whole-length layer's pages."""
    model, cfg, params, _ = tiny
    seq = _tokens(model, (29 + 3,), seed=6)
    chunked, pools_c = _paged(cfg, params, seq, 29)
    whole, pools_w = _paged(cfg, params, seq, 29,
                            engine=dict(ENGINE, prefill_chunk=0))
    assert np.abs(chunked - whole).max() < LOGIT_TOL
    # Pages 3.. hold the sequence in both (the scratch page differs).
    np.testing.assert_allclose(np.asarray(pools_c["k"][:, 3:11]),
                               np.asarray(pools_w["k"][:, 3:11]), atol=1e-5)
    assert pools_c["k"].shape[0] == 1 and pools_c["kw"].shape[0] == 4
    assert pools_c["kw"].shape[1] == 2 * 4 + 1


def test_the_reference_computes_with_the_experts_it_is_given(tiny):
    """Top-k routing is discontinuous, so the chip's comparison hands the
    reference the experts the system took: its own choice changes nothing
    (the dense layer's -1 is no choice), another set reaches from it and
    moves that token's logits and no earlier one's."""
    model, _, _, ref = tiny
    seq = _tokens(model, (20,), seed=9)
    plain = ref.logits(seq, range(20))
    margins, reach = ref.routing(seq)
    assert margins.shape == reach.shape == (5, 20)
    assert np.isinf(margins[0]).all() and (margins[1:] > 0).all()
    assert (reach == 0).all()
    own = ref.top_experts(seq)  # [L, S, k]
    assert (own[0] == -1).all() and (own[1:] >= 0).all()
    given = np.full((5, 20, 2), -1, np.int32)
    given[:, 12:] = own[:, 12:]
    assert np.array_equal(ref.logits(seq, range(20), given), plain)
    a, b = own[2, 15]
    other = next(e for e in range(8) if e not in (a, b))
    given[2, 15] = [a, other]
    moved = ref.logits(seq, range(20), given)
    assert ref.routing(seq, given)[1][2, 15] >= margins[2, 15] * (1 - 1e-5)
    assert np.abs(moved[:15] - plain[:15]).max() == 0  # causal
    assert np.abs(moved[15] - plain[15]).max() > 50 * LOGIT_TOL


@pytest.mark.parametrize("fault", [*afmoe_compare.FAULTS, "no-ffn-post-norm",
                                   "no-shared-expert", "bfloat16-experts"])
def test_the_comparison_catches(tiny, fault):
    """Each of ``afmoe_compare.py``'s faults, and three more, is a
    different model, or the same one in a lower precision, and has to read
    as incorrect, in the full forward and through the pages: at this
    tolerance here, at bfloat16's on the chip."""
    from benchmarks.reference.glm4_moe_lite_compare import _float8
    from ray_tpu.models import block

    model, cfg, params, ref = tiny
    seq = _tokens(model, (30,), seed=8)
    want = ref.logits(seq, range(30))
    # The routed experts alone in bfloat16, under the FFN's post-norm, move
    # a logit 3.2e-3: 32 times the tolerance, the least of all of these.
    floor = (20 if fault == "bfloat16-experts" else 50) * LOGIT_TOL
    bad, swapped = params, None
    if fault in ("float8-experts", "float8"):
        bad = _float8(jax.tree.map(jnp.copy, params), fault == "float8")
    elif fault == "bfloat16-experts":
        bad = jax.tree.map(jnp.copy, params)
        for layer in bad["layers"][1:]:
            for name in ("w1", "w2", "w3"):
                layer["moe"][name] = layer["moe"][name].astype(
                    jnp.bfloat16).astype(jnp.float32)
    elif fault == "no-ffn-post-norm":
        swapped = block.post_norm
        block.post_norm = lambda config, layer, name, out: out \
            if name == "ffn_post_norm" else swapped(config, layer, name, out)
    try:
        known = fault in afmoe_compare.FAULTS
        with afmoe_compare.faulted(cfg, fault if known else None) as fcfg:
            if fault == "no-shared-expert":
                fcfg = dataclasses.replace(fcfg, n_shared_experts=0)
            assert np.abs(_system_logits(fcfg, bad, seq) - want).max() \
                > floor
            got, _ = _paged(fcfg, bad, seq, 25)
            assert np.abs(got - want[24:]).max() > floor
    finally:
        if swapped is not None:
            block.post_norm = swapped
    # And the sound program, after every swap was undone, is sound.
    assert np.abs(_system_logits(cfg, params, seq) - want).max() < LOGIT_TOL


# --------------------------------- the older configurations' decode programs


OLDER = ["internlm2-1.8b", "mistral-7b-v0.3-L4", "olmoe-1b-7b-0125",
         "smallthinker-21b-a3b-L8", "glm-4.7-flash-L6"]


def _decode_text(cfg):
    """The decode program of ``cfg`` at a small engine geometry, lowered
    from shapes (nothing compiles, nothing runs)."""
    from ray_tpu.models import block, paged
    from ray_tpu.serve.engine import EngineConfig

    ec = EngineConfig(batch_slots=4, page_size=8, max_prompt_len=32,
                      max_new_tokens_cap=32, prefill_chunk=16,
                      prefix_cache=False)
    b, i32 = ec.batch_slots, jnp.int32
    ring = min(ec.pages_per_seq,
               paged.ring_entries(cfg, ec.page_size, ec.prefill_buckets()[-1]))
    init, _ = block.init_and_apply(cfg)
    shape = jax.ShapeDtypeStruct
    args = [
        jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0))),
        jax.eval_shape(lambda: paged.init_paged_pools(
            cfg, ec.pool_pages, ec.page_size, b * ring)),
        jax.eval_shape(lambda: paged.init_adapter_pool(
            cfg, ec.max_adapters, ec.lora_rank)),
        shape((b + paged.routing_width(cfg),), i32),
        shape((b, ec.pages_per_seq), i32), shape((b,), i32),
        shape((b,), bool), shape((b,), jnp.float32), shape((b,), i32),
        jax.eval_shape(lambda: jax.random.PRNGKey(0))]
    if ring:
        args.append(shape((b, ring), i32))
    return paged.paged_decode_step.lower(cfg, *args).as_text(debug_info=True)


@pytest.mark.parametrize("name", OLDER)
def test_an_older_configurations_decode_program_has_none_of_the_branches(
        name):
    """The five configurations the benchmark had, at their published
    widths: the gate, the post-norms, the head's QK-norm and the
    embedding's scale are trace-time branches, so the decode program
    lowers to the same text with the four fields left at their defaults
    and stated off, and holds none of the new scopes.  (Against the parent
    commit's own text: PERF.md section 6, PR 41; a test cannot hold the
    parent.)"""
    from ray_tpu.models import MoEConfig

    model = _model(name)
    fam = spec.family(model)
    build = getattr(fam, "program_config", None) or fam.llama_config
    cfg = build(model, max_seq=64, remat=False)
    text = _decode_text(cfg)
    assert "attn_gate" not in text and "post_norm" not in text
    if isinstance(cfg, MoEConfig):
        assert (cfg.attn_gate, cfg.post_norm, cfg.embed_scale) \
            == (False, False, 1.0) and cfg.qk_norm in (False, True)
        off = dataclasses.replace(cfg, attn_gate=False, post_norm=False,
                                  embed_scale=1.0, qk_norm=bool(cfg.qk_norm))
        assert _decode_text(off) == text
    else:
        assert not hasattr(cfg, "attn_gate")


def test_the_new_configurations_decode_program_has_all_four():
    cfg = afmoe.program_config(_model(), max_seq=64, remat=False)
    text = _decode_text(cfg)
    assert text.count("attn_gate") >= 5 and text.count("post_norm") >= 10
    for off in (dict(attn_gate=False), dict(post_norm=False),
                dict(embed_scale=1.0), dict(qk_norm=True)):
        assert _decode_text(dataclasses.replace(cfg, **off)) != text, off


# ------------------------------------------------------------------ counts


def test_the_familys_counts_are_pinned_at_the_cells_configuration():
    model = _model(CONFIG)
    assert model["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                "layer_types"]
    assert model["layer_types"] == ["sliding_attention"] * 3 + [
        "full_attention", "sliding_attention"]
    published = {**model, "num_hidden_layers": 32, "num_dense_layers": 2,
                 "layer_types": (["sliding_attention"] * 3
                                 + ["full_attention"]) * 8}
    afmoe.check_supported(published)
    # ISSUE 41's arithmetic, with a head's two norms counted at 128 each
    # (the issue's 27,263,488 a layer of attention counts 512 for them).
    assert afmoe._attention_params(model) == 27_263_232
    assert afmoe._expert_params(model) == 6_291_456
    assert afmoe.param_count(model) == 4_241_534_720       # 8.48 GB
    assert afmoe.param_count(published) == 26_123_974_400  # "26B"
    assert afmoe.matmul_params(published) == 3_064_463_360  # "A3B"
    for m in (model, _model()):
        cfg = afmoe.program_config(m, max_seq=256)
        shapes = jax.eval_shape(lambda: afmoe.init(cfg, jax.random.PRNGKey(0)))
        leaves = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
        assert leaves == afmoe.param_count(m) == cfg.param_count()
    # A decode step of 32 slots (256 pairs a routed layer) that hits 111
    # of 128 experts in each of the four routed layers, by hand.
    need = afmoe.routed_ffn_ops_bytes(model, 4 * 256, 4 * 111)
    assert need == {"ops": 4 * 256 * 2.0 * 6_291_456,
                    "bytes": (4 * 111 * 6_291_456 + 4 * 256 * 2 * 2048) * 2}
    assert afmoe.kv_row_bytes(model) == 2048
    once = (2048 * 200192 + 2048 + 5 * (27_263_232 + 4 * 2048)
            + 3 * 2048 * 6144 + 4 * 6_291_456) * 2 + 4 * (2048 * 128 + 128) * 4
    assert once == 1_222_730_240
    assert afmoe.decode_floor_bytes(model, 444, 228_800) \
        == once + 444 * 12_582_912 + 228_800 * 2048
    with pytest.raises(NotImplementedError):
        afmoe.train_step_kernel_ops_bytes(model, 1, 4096, 24)
    assert model["num_experts"] == 128 and model["vocab_size"] == 200192
    assert model["torch_dtype"] == "bfloat16"


@pytest.mark.parametrize("key,value", [
    ("rope_scaling", {"type": "yarn", "factor": 4.0}),
    ("tie_word_embeddings", True), ("score_func", "softmax"),
    ("n_group", 2), ("topk_group", 2), ("hidden_act", "gelu"),
    ("layer_types", ["sliding_attention"] * 4),
    ("layer_types", ["full_attention"] * 5), ("sliding_window", 0),
    ("num_dense_layers", 6), ("num_experts_per_tok", 9)])
def test_the_family_refuses_what_the_program_does_not_compute(key, value):
    with pytest.raises(ValueError, match=key):
        afmoe.check_supported(_model(**{key: value}))


def test_the_catalogs_numbers_are_in_the_file_under_their_keys():
    """Every number of the published config is in the cell's file under
    the same key, but the three the file lists as reduced."""
    model = _model(CONFIG)
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "load_balance_coeff": 0.001,
        "max_position_embeddings": 131072, "model_type": "afmoe",
        "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 32, "num_expert_groups": 1,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
        "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 2048, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192}
    for key, value in published.items():
        assert model[key] == value, key
    assert (model["num_hidden_layers"], model["num_dense_layers"]) == (5, 1)
    assert model["published"]["num_hidden_layers"] == 32
    assert model["published"]["num_dense_layers"] == 2
    assert model["source"] == ("https://huggingface.co/arcee-ai/"
                               "Trinity-Mini/blob/main/config.json")
    assert "4,241,534,720" in model["deployment"]
    assert set(model["assumed"]) >= {
        "attention_gate", "qk_norm", "rope", "sandwich_norms",
        "expert_bias", "torch_dtype", "num_experts"}


# ------------------------------------------------------ engine and records


def test_the_model_is_served_with_a_dense_layer_in_a_ring(tiny):
    """``register_model`` -> ``LLMServer`` -> ``InferenceEngine``, the
    normal path: one whole-length pool of one layer beside rings of four
    (the dense layer's among them), the prefix cache off, the records'
    counters as PR 34 and PR 36 counted them (the dense layer and the
    shared expert in no routing counter), answers decoded past two laps of
    the ring, and the reference's greedy tokens."""
    from test_benchmark_smallthinker import _drain

    from ray_tpu.models.paged import KV_KEYS, ROUTING_KEYS, trace_count
    from ray_tpu.serve.engine import LLMServer, register_model
    from ray_tpu.util import steprec

    model, cfg, params, _ = tiny
    register_model("trinity-mini-tiny-test", lambda: cfg)
    server = LLMServer(model="trinity-mini-tiny-test",
                       engine=dict(ENGINE, prefix_cache=True), seed=3,
                       warmup=True)
    try:
        eng = server.engine
        st0 = server.stats()
        assert st0["prefix_cache"] is None
        assert st0["prefix_cache_off"] == "window layers"
        assert st0["grouped_ffn"] == "ragged_dot"  # no TPU here
        assert eng.ring == 4 and eng.maxp == 24
        assert set(eng.pools) == {"k", "v", "kw", "vw"}
        assert eng.pools["k"].shape[:2] == (1, 2 * 24 + 1)
        assert eng.pools["kw"].shape[:2] == (4, 2 * 4 + 1)
        traced = {p: trace_count(p)
                  for p in ("decode", "prefill", "prefill_prefix")}
        steprec.drain_buffered()
        short = _tokens(model, (6,), seed=11).tolist()
        long = _tokens(model, (41,), seed=12).tolist()
        a = eng.submit(short, max_new_tokens=44)   # laps the ring twice
        b = eng.submit(long, max_new_tokens=9)
        out_a, out_b = list(a), list(b)
        assert (len(out_a), len(out_b)) == (44, 9)
        recs = _drain(eng, 2)
        first = sorted((e for r in recs for e in r["first_tokens"]),
                       key=lambda e: e["prompt"])
        assert [(e["prompt"], e["chunks"], e["cached"]) for e in first] \
            == [(6, 1, 0), (41, 6, 0)]
        # The four ROUTED layers' pairs: the dense layer is in no counter.
        assert [e["expert_pairs"] for e in first] == [6 * 2 * 4, 41 * 2 * 4]
        decode = [r for r in recs if r["occupancy"]]
        assert decode and all(
            set(KV_KEYS) | set(ROUTING_KEYS) <= set(r) for r in decode)
        for r in decode:
            # Two experts a live row in each of the four routed layers (a
            # record's occupancy is taken after its step's dispatch).
            assert r["expert_pairs"] in (2 * 4, 2 * 2 * 4)
            assert 0 < r["experts_hit"] <= r["expert_pairs"]
            # Every slot's whole table and four whole rings are gathered.
            assert r["kv_rows_read"] == 2 * (24 + 4 * 4) * 4
            assert 0 < r["kv_rows_live"] <= r["kv_rows_read"]
            assert {"pages_window", "pages_global", "pages_uniform"} <= set(r)
        # One live sequence of length n: (n + 1) + 4 min(n + 1, 8).
        lone = [r for r in decode if r["occupancy"] == 1
                and not r["first_tokens"]]
        assert lone and all(r["kv_rows_live"] >= 9 + 4 * 8 for r in lone)
        ref = afmoe.reference(model, eng.params)
        for prompt, out in ((short, out_a), (long, out_b)):
            seq = np.asarray(prompt + out[:-1], np.int32)
            want = ref.logits(seq, range(len(prompt) - 1, len(seq)))
            assert want.argmax(-1).tolist() == out
        assert {p: trace_count(p) for p in traced} == traced
        st1 = server.stats()
        assert st1["free_pages"] == st1["total_pages"] == 2 * 24 + 2 * 4
    finally:
        server.engine.shutdown()


# ----------------------------------------------------- the benchmark's files


def _ctx(steps, **over):
    return {"kind": "serve_closed", "seconds": 51.0, "steps": steps,
            "model": _model(CONFIG),
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            **over}


def test_the_reader_reads_the_records_and_nothing_from_a_parent():
    from benchmarks.layer_metrics import decode_bytes_floor_share_swa as reader

    phases = {k: 0.0 for k in ("between_s", "idle_s", "upload_s",
                               "dispatch_s", "readback_s", "emit_s")}
    base = dict(phases, stall_s=0.0, admitted=0, occupancy=32, wall_s=0.014,
                between_s=0.002, ahead=1, first_tokens=[], experts_hit=444,
                expert_pairs=1024, kv_rows_read=753_664,
                kv_rows_live=228_800)
    steps = [
        dict(base),
        dict(base, wall_s=0.030, experts_hit=460),  # the median's other side
        dict(base, wall_s=0.020),
        dict(base, ahead=0, wall_s=1.0),            # not dispatched ahead
        dict(base, stall_s=0.9, admitted=1, wall_s=0.95),
    ]
    ctx = _ctx(steps)
    floor = afmoe.decode_floor_bytes(ctx["model"], 444, 228_800)
    assert floor == 1_222_730_240 + 444 * 12_582_912 + 228_800 * 2048
    assert reader.read(ctx) == pytest.approx(100.0 * floor / 819e9 / 0.022)
    assert 35 < reader.read(ctx) < 45
    # It cannot pass 100%: a step as fast as the floor's bytes allow.
    fast = _ctx([dict(base, wall_s=floor / 819e9, between_s=0.0)])
    assert reader.read(fast) == pytest.approx(100.0)
    # The parent's records have none of the keys; a family without the
    # function no floor; a CPU no peak; a train run no records.
    old = [{k: v for k, v in r.items() if not k.startswith("kv_rows")}
           for r in steps]
    assert reader.read(_ctx(old)) is None
    assert reader.read(_ctx([])) is None
    assert reader.read(_ctx(3, kind="train")) is None
    assert reader.read(_ctx(steps, model=_model("olmoe-1b-7b-0125"))) is None
    assert reader.read(_ctx(steps, device={
        "platform": "cpu", "kind": "cpu", "count": 1})) is None


def test_the_new_cell_is_in_the_benchmark_by_name_and_membership():
    doc = spec.load_benchmark(ROOT)
    spec.validate(doc)
    # By name and membership, never by a list's tail: the next PR's
    # entries go behind these.
    cell, = [w for w in doc["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": TRAFFIC,
                    "chips": 1, "why": cell["why"]}
    config, = [c for c in doc["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "layer_types"]
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert config["source"] == _model(CONFIG)["source"]
    new, = [m for m in doc["per_layer"] if m["name"] == READER]
    assert new == {"name": READER, "unit": "%", "better": "higher",
                   "source": "program_counter",
                   "layer": "engine loop (host)", "moves": "serve_tok_s",
                   "workloads": [CELL]}
    joined = {m["name"] for m in doc["per_layer"] + doc["end_to_end"]
              if CELL in m.get("workloads", ())}
    assert joined == {
        READER, "serve_tok_s", "prefill_stall_share.sat",
        "batch_occupancy.sat", "decode_step_ms.sat", "decode_host_ms.sat",
        "decode_device_wait_ms.sat", "loop_accounted_share.sat",
        "device_starved_share.sat", "admission_drain_ms.sat",
        "decode_period_ms.sat", "ahead_share.sat", "device_idle_share.serve",
        "device_idle_unaccounted_share.serve", "experts_hit_share.moe",
        "moe_decode_roofline.moe", "moe_stream_roofline.moe",
        "kv_gather_live_share.swa", "kv_pages_held_share.swa",
        "prefill_chunk_ms.swa"}
    # Nothing the benchmark had lost a cell to make room.
    for m in doc["per_layer"] + doc["end_to_end"]:
        if "serve_tok_s" in (m["name"], m.get("moves")) \
                and "workloads" in m:
            assert len(set(m["workloads"])) == len(m["workloads"])
    tr = spec.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", TRAFFIC + ".json"))
    assert tr["kind"] == "serve_closed"
    assert tr["engine"] == {
        "batch_slots": 32, "page_size": 128, "max_prompt_len": 4096,
        "max_new_tokens_cap": 3072, "prefill_chunk": 2048,
        "prefix_cache": False, "max_queue": 64, "ttft_window": 4096}
    assert (tr["clients"], tr["pool"], tr["schedule_seed"],
            tr["shared_prefix"], tr["temperature"],
            tr["max_concurrent_queries"]) == (64, 64, 0, 0, 0.0, 128)
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 512,
                                "sigma": 0.9, "min": 64, "max": 4096}
    assert tr["output_len"] == {"dist": "lognormal", "median": 1280,
                                "sigma": 0.5, "min": 256, "max": 3072}
    assert tr["check"]["prompt_lens"] == [2040, 4090]
    assert tr["check"]["new_tokens"] == 16
    assert 45.0 <= tr["ramp_s"] <= 60.0
    assert tr["system_config"] == {
        "engine_steps_max_records": 16384, "step_ring_size": 16384,
        "peer_call_deadline_s": 240.0}
    from benchmarks.traffic import quantile_lengths

    prompts = quantile_lengths(tr["prompt_len"], 64)
    outputs = quantile_lengths(tr["output_len"], 64)
    assert [p for p in prompts if p > 2048] == [2163, 2499, 3063, 4096]
    assert (min(outputs), max(outputs), outputs.count(3072)) \
        == (382, 3072, 3)
    assert 1400 < sum(outputs) / 64 < 1450


def test_the_cell_rehearses_and_prints_its_metrics():
    rc, lines, err = run_bench(
        "--workload", CELL, "--seed", str(2 ** 31 + 41), "--seconds", "3",
        "--trace", "1", "--rehearse")
    assert rc == 0, err[-3000:]
    out = lines[-1]
    assert out["correct"] is True, lines
    assert out["attempted"] > 0 and out["failed"] == 0
    got = out["metrics"]
    live = got["kv_gather_live_share.swa"]
    held = got["kv_pages_held_share.swa"]
    assert live["unit"] == "%" and 0 < live["value"] <= 100
    assert held["unit"] == "%" and 25 < held["value"] < 100
    # Four routed layers of five, as the reader divides: under 80%.
    assert 0 < got["experts_hit_share.moe"]["value"] <= 80
    assert {"prefill_chunk_ms.swa", "decode_step_ms.sat",
            "batch_occupancy.sat", "prefill_stall_share.sat",
            "decode_period_ms.sat", "ahead_share.sat",
            "device_starved_share.sat"} <= set(got)
    # No peak for a CPU: no share of one is printed.
    assert READER not in got and "moe_stream_roofline.moe" not in got
    samples = next(l for l in lines if l.get("phase") == "samples")
    assert samples["reference_gap_max"] <= 1e-3 and samples["shed"] == 0


def test_a_program_without_the_four_fields_fails_before_any_process(
        tmp_path):
    """The parent of this PR under this PR's benchmark files: the family
    says why where the harness finds it (``spec.load_cell``), exit 1 in
    about a second, no replica started and restarted until the
    deployment's time runs out."""
    import shutil

    root = tmp_path / "old"
    root.mkdir()
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for pkg in ("ray_tpu", "ray_tpu/serve", "ray_tpu/models"):
        os.makedirs(root / pkg)
        (root / pkg / "__init__.py").write_text("")
    (root / "ray_tpu" / "models" / "moe.py").write_text(
        "import dataclasses\n\n@dataclasses.dataclass\n"
        "class MoEConfig:\n    n_experts: int = 8\n"
        "    kv_lora_rank: int = 0\n    qk_norm: bool = False\n")
    (root / "ray_tpu" / "serve" / "engine.py").write_text(
        "import dataclasses\n\n@dataclasses.dataclass\n"
        "class EngineConfig:\n    batch_slots: int = 8\n"
        "    prefill_chunk: int = 0\n\n"
        "def register_model(name, builder):\n    pass\n")
    t0 = time.time()
    rc, lines, err = run_bench(
        "--workload", CELL, "--seed", "1", "--seconds", "2", "--trace", "0",
        "--rehearse", root=str(root), timeout=60)
    assert rc == 1 and not lines and time.time() - t0 < 30
    assert "ray_tpu/models/moe.py has no MoEConfig.attn_gate" in err


def test_the_chip_comparison_rehearses_and_refuses_each_fault():
    """``benchmarks/reference/afmoe_compare.py`` at the tiny configuration:
    the decode rows' experts are the reference's own (in float32 nothing
    rounds a choice the other way), logits through pages and rings within
    the float32 tolerance at both of the check's lengths (decoded across
    the window, decoded across the ring's wrap), and each of its ten
    faults read as incorrect."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "benchmarks/reference/afmoe_compare.py",
         "--rehearse", "--seed", str(2 ** 31 + 3), "--faults"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    assert out["ok"] is True and out["logit_tol"] == LOGIT_TOL
    assert out["prompt_lens"] == [6, 14] and out["swap_margin"] == 0.0
    base, *faults = out["results"]
    assert base["correct"] and base["rows"] == 2 * (1 + 4)
    assert base["rows_judged"] == base["argmax_agree"] == base["rows"]
    assert base["tie_swaps"] == base["routing_violations"] == 0
    assert set(base["by_prompt"]) == {"6", "14"}
    assert [f["fault"] for f in faults] == list(afmoe_compare.FAULTS)
    assert not any(f["correct"] for f in faults)
    assert all(f["max_abs_logit_diff"] > 50 * LOGIT_TOL for f in faults)
    assert base["cell_check_passes"] and out["cell_logit_tol"] == 1e-3
