"""The jamba family (AI21-Jamba2-3B: selective state-space layers whose state
lives in a slot beside two multi-query K/V layers without position, dense
FFNs, a tied head) at its tiny configuration on the CPU, float32: the program
against the family's plain reference (the position-by-position recurrence),
prefill and decode through state and cache, the comparison's faults, the
counts, the older configurations' programs, and the benchmark's new files
(the cell's rehearsals: ``test_benchmark_jamba_cell.py``).  Entries of
BENCHMARK.json are looked up by name and membership, never by a list's tail
or whole."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_testlib import ROOT
from test_benchmark_kimi_linear import _paged

from benchmarks import spec
from benchmarks.families import jamba
from benchmarks.reference import jamba_compare

LOGIT_TOL = 2e-4
CONFIG = "jamba2-3b"
TRAFFIC = "serve-reasoning-wide-batch"
CELL = f"{CONFIG}.{TRAFFIC}"
READERS = ("decode_bytes_floor_share.ssm", "scan_padding_share.ssm")
#: Chunks of 8 over pages of 4: buckets of 4 and 8.
ENGINE = dict(batch_slots=2, page_size=4, max_prompt_len=48,
              max_new_tokens_cap=48, prefill_chunk=8, prefix_cache=False)


def _model(name="jamba-tiny", **over):
    return {**spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", name + ".json")), **over}


def _weights(cfg, seed=0):
    """Seeded weights whose norm weights (the three inner ones among them)
    are not all ones, so that a norm left out shows."""
    from benchmarks.reference.olmoe_compare import _weights as draw

    return draw(jamba, cfg, seed)


def _tokens(model, shape, seed=2):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), shape, 1, model["vocab_size"]), np.int32)


@pytest.fixture(scope="module")
def tiny():
    """(model file, program configuration, weights, reference)."""
    model = _model()
    cfg = jamba.program_config(model, max_seq=96, remat=False)
    params = _weights(cfg)
    return model, cfg, params, jamba.reference(model, params)


# ---------------------------------------------------- family and counts


def test_the_family_is_found_as_files():
    model = _model(CONFIG)
    assert spec.family(model) is jamba
    assert jamba.REHEARSAL_CONFIG == "jamba-tiny"
    cell = spec.load_cell(CELL)
    assert cell["model"]["name"] == CONFIG and cell["chips"] == 1
    assert spec.rehearsal_cell(cell)["model"]["name"] == "jamba-tiny"
    for path in ("families/jamba.py", "reference/jamba_ref.py",
                 "reference/jamba_compare.py", f"configs/{CONFIG}.json",
                 "configs/jamba-tiny.json", f"traffic/{TRAFFIC}.json",
                 "layer_metrics/decode_bytes_floor_share_ssm.py",
                 "layer_metrics/scan_padding_share_ssm.py"):
        assert os.path.isfile(os.path.join(ROOT, "benchmarks", path)), path


def test_the_catalogs_numbers_are_in_the_file_under_their_keys():
    """Every key of the catalog row's ``config``, as published: nothing is
    reduced."""
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14,
        "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 8192, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
        "mamba_expand": 2, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "model_type": "jamba",
        "num_attention_heads": 20, "num_experts": 1,
        "num_experts_per_tok": 1, "num_hidden_layers": 28,
        "num_key_value_heads": 1, "num_logits_to_keep": 1,
        "rms_norm_eps": 1e-06, "sliding_window": None,
        "tie_word_embeddings": True, "use_mamba_kernels": True,
        "vocab_size": 65536}
    model = _model(CONFIG)
    assert {k: model[k] for k in published} == published
    assert model["reduced"] == [] and model["family"] == "jamba"
    assert model["source"].endswith("AI21-Jamba2-3B/blob/main/config.json")
    assert {"torch_dtype", "inner_norms", "mamba_init", "attention",
            "head_dim", "dense_ffn", "silent_keys"} <= set(model["assumed"])
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == [] and entry["source"] == model["source"]


def test_the_counts_are_the_issues_and_the_trees(tiny):
    model, cfg, params, _ = tiny
    full = _model(CONFIG)
    assert jamba.param_count(full) == 3_029_337_472
    assert jamba._mamba_params(full) == 41_241_792
    assert jamba._attn_params(full) == 13_762_560
    assert jamba._ffn_params(full) == 62_914_560
    assert jamba.layer_kinds(full).count("ssm") == 26
    assert [i for i, k in enumerate(jamba.layer_kinds(full)) if k == "kv"] \
        == [7, 21]
    assert jamba.state_slot_bytes(full) == 26 * (327_680 + 30_720)
    assert jamba.kv_row_bytes(full) == 512   # one layer; 1,024 B on the two
    # Every weight once, the float32 leaves at four bytes.
    f32 = 26 * (5120 * 16 + 2 * 5120)
    assert jamba.decode_floor_bytes(full, 0, 0) \
        == 2 * (3_029_337_472 - f32) + 4 * f32
    assert jamba.decode_floor_bytes(full, 1000, 3) \
        - jamba.decode_floor_bytes(full, 0, 0) \
        == 1000 * 512 + 3 * 2 * 9_318_400
    assert jamba.matmul_params(full) < jamba.param_count(full)
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == cfg.param_count() == jamba.param_count(model)
    big = jamba.program_config(full, max_seq=64, remat=False)
    assert big.param_count() == 3_029_337_472


@pytest.mark.parametrize("over, message", [
    ({"num_experts": 16}, "num_experts"),
    ({"sliding_window": 4096}, "sliding_window"),
    ({"rope_theta": 10000.0}, "no position signal"),
    ({"rope_scaling": {"type": "yarn"}}, "no position signal"),
    ({"tie_word_embeddings": False}, "tie_word_embeddings"),
    ({"attn_layer_period": 1, "attn_layer_offset": 0}, "both kinds"),
])
def test_what_the_program_does_not_compute_is_refused(over, message):
    with pytest.raises(ValueError, match=message):
        jamba.check_supported(_model(**over))


def test_the_configuration_object_carries_what_the_equations_need(tiny):
    from ray_tpu.models import MoEConfig, block, mamba, paged

    model, cfg, params, _ = tiny
    assert isinstance(cfg, MoEConfig)  # the object that reads a layout
    assert cfg.attn_layout == ("ssm",) * 7 + ("kv",) + ("ssm",) * 6
    assert not block.is_routed(cfg) and not block.is_latent(cfg)
    assert block.is_ssm(cfg) and not block.is_kda(cfg)
    assert block.recurrent(cfg) is mamba
    assert not any(block.layer_rotary(cfg, i) for i in range(14))
    assert (cfg.ssm_inner, cfg.ssm_state, cfg.ssm_dt_rank, cfg.ssm_conv) \
        == (128, 16, 4, 4)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (4, 1, 16)
    assert paged.kv_layers(cfg) == ([7], [])
    assert paged.state_layers(cfg) == [i for i in range(14) if i != 7]
    assert paged.routing_keys(cfg) == ()
    assert paged.counter_keys(cfg) == paged.KV_KEYS
    assert all("mlp" in layer and "moe" not in layer
               for layer in params["layers"])
    assert set(params["layers"][0]["attn"]) == {
        "w_in", "conv_w", "conv_b", "w_x", "dt_norm", "b_norm", "c_norm",
        "w_dt", "dt_bias", "A_log", "D", "w_out"}
    assert set(params["layers"][7]["attn"]) == {"wq", "wk", "wv", "wo"}
    a = params["layers"][0]["attn"]
    assert a["A_log"].shape == (16, 128) and a["A_log"].dtype == jnp.float32
    np.testing.assert_allclose(np.exp(np.asarray(a["A_log"])[:, 5]),
                               np.arange(1, 17), rtol=1e-6)


def test_the_tied_head_holds_one_matrix(tiny):
    """No ``lm_head`` in the tree, and the logits are the hidden state by
    the embedding's own rows."""
    from ray_tpu.models import block

    model, cfg, params, _ = tiny
    assert "lm_head" not in params and cfg.tie_embeddings
    x = jax.random.normal(jax.random.PRNGKey(3), (5, 64), jnp.float32)
    np.testing.assert_allclose(
        block.head(cfg, params, x),
        np.asarray(x) @ np.asarray(params["embed"]).T, atol=1e-5)
    text = jax.jit(lambda p, x: block.head(cfg, p, x)).lower(
        params, x).as_text()
    assert "512x64" in text and "64x512" not in text  # never turned over


# ----------------------------------------------------------- the reference


def test_moe_apply_matches_the_reference(tiny):
    """The full forward (the chunk form from a zero state) against the
    position-by-position recurrence."""
    from ray_tpu.models import init_and_apply, moe_apply

    model, cfg, params, ref = tiny
    seq = _tokens(model, (37,), seed=3)
    got = np.asarray(moe_apply(cfg, params, jnp.asarray(seq)[None])[0][0])
    want = ref.logits(seq, range(len(seq)))
    assert np.abs(got - want).max() < LOGIT_TOL
    # An all-dense configuration with a layout is still ``moe.py``'s.
    init, apply = init_and_apply(cfg)
    assert init.__name__ == "moe_init"
    np.testing.assert_array_equal(
        np.asarray(apply(cfg, params, jnp.asarray(seq)[None])[0]), got)


@pytest.mark.parametrize("prompt,new", [(8, 6), (6, 6), (14, 6), (21, 5)],
                         ids=["one-bucket", "a-padded-bucket", "two-chunks",
                              "three-chunks"])
def test_prefill_and_decode_through_state_and_cache_match_the_reference(
        tiny, prompt, new):
    """The cell's check prompts at the tiny size: the state from zeros
    through the chunk form, carried across chunk edges in the slot, then
    through the recurrent form; the attention layer's rows in its pool."""
    model, cfg, params, ref = tiny
    seq = _tokens(model, (prompt + new,), seed=5)
    got, pools = _paged(cfg, params, seq, prompt, engine=ENGINE)
    want = ref.logits(seq, range(prompt - 1, prompt + new))
    assert np.abs(got - want).max() < LOGIT_TOL
    assert set(pools) == {"k", "v", "S", "conv"}
    assert pools["k"].shape[0] == 1                 # the ONE attention layer
    assert pools["S"].shape == (13, 2, 16, 128)
    assert pools["conv"].shape == (13, 2, 3 * 128)
    assert not np.asarray(pools["S"][:, 0]).any()   # the other slot's
    held = np.asarray(pools["S"][:, 1]).transpose(0, 2, 1)
    np.testing.assert_allclose(held, np.asarray(ref.states(seq)), atol=1e-4)


@pytest.mark.parametrize("fault", jamba_compare.FAULTS)
def test_each_of_the_comparisons_faults_fails_it(tiny, fault):
    """The computations the chip comparison has to refuse, through the same
    programs at the tiny size: each moves a logit by far more than the
    tolerance (the state in bfloat16 the least: fifty times it)."""
    model, cfg, params, ref = tiny
    seq = _tokens(model, (11 + 2,), seed=7)  # two chunks, two steps
    want = ref.logits(seq, range(10, 13))
    if fault == "float8":
        params = jamba_compare._float8(jax.tree.map(jnp.copy, params))
    # ``_paged`` calls the programs unjitted: no trace outlives the swap.
    with jamba_compare.faulted(cfg, fault) as fcfg:
        got, _ = _paged(fcfg, params, seq, 11, engine=ENGINE)
    assert np.abs(got - want).max() > 25 * LOGIT_TOL, fault


# ---------------------------------------- older configurations' programs


#: The lines of each older configuration's decode and largest-prefill
#: (the suffix program's) texts at the small geometry below, lowered at the
#: parent commit (da29d84: the sha256 of each text equal to this tree's when
#: this was written; a test cannot hold the parent).
OLDER = {"internlm2-1.8b": [6747, 6765], "olmoe-1b-7b-0125": [5705, 5732],
         "smallthinker-21b-a3b-L8": [3817, 3793],
         "glm-4.7-flash-L6": [2907, 2923], "trinity-mini-L5": [3159, 3140],
         "kimi-linear-48b-a3b-L13": [5484, 6495]}


def _program_texts(cfg, debug_info=False):
    """The decode step and the suffix prefill of ``cfg`` at a small engine
    geometry, lowered from shapes (nothing compiles, nothing runs);
    ``debug_info``: with the named scopes in the text."""
    from ray_tpu.models import block, paged
    from ray_tpu.serve.engine import EngineConfig

    ec = EngineConfig(batch_slots=4, page_size=8, max_prompt_len=32,
                      max_new_tokens_cap=32, prefill_chunk=16,
                      prefix_cache=False)
    b, i32, shape = ec.batch_slots, jnp.int32, jax.ShapeDtypeStruct
    ring = min(ec.pages_per_seq, paged.ring_entries(
        cfg, ec.page_size, ec.prefill_buckets()[-1]))
    state = bool(paged.state_layers(cfg))
    init, _ = block.init_and_apply(cfg)
    head = [jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0))),
            jax.eval_shape(lambda: paged.init_paged_pools(
                cfg, ec.pool_pages, ec.page_size, b * ring,
                b if state else 0)),
            jax.eval_shape(lambda: paged.init_adapter_pool(
                cfg, ec.max_adapters, ec.lora_rank))]
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    scalar = shape((), i32)
    decode = paged.paged_decode_step.lower(
        cfg, *head, shape((b + paged.routing_width(cfg),), i32),
        shape((b, ec.pages_per_seq), i32), shape((b,), i32),
        shape((b,), bool), shape((b,), jnp.float32), shape((b,), i32), key,
        shape((b, ring), i32) if ring else None).as_text(
            debug_info=debug_info)
    prefix = paged.paged_prefill_prefix.lower(
        cfg, *head, shape((1, 16), i32), scalar, scalar,
        shape((ec.pages_per_seq,), i32), scalar, shape((), jnp.float32),
        key, shape((ring,), i32) if ring else None,
        scalar if state else None).as_text(debug_info=debug_info)
    return decode, prefix


@pytest.mark.parametrize("name", list(OLDER))
def test_an_older_configurations_programs_lower_as_at_the_parent(name):
    """The state-space entry of the layout, the K/V entry beside recurrent
    layers and the tied head are trace-time branches: the five older
    architectures' decode and largest-prefill programs, and Kimi-Linear's
    (whose recurrent closures this PR made the two kinds'), hold none of the
    new scopes, lower to the parent's count of lines, and to the same text
    with the new fields stated at their defaults."""
    from ray_tpu.models import MoEConfig, paged

    model = _model(name)
    fam = spec.family(model)
    cfg = fam.program_config(model, max_seq=64, remat=False)
    decode, prefix = _program_texts(cfg)
    assert [len(decode.splitlines()), len(prefix.splitlines())] \
        == OLDER[name]
    for text in (decode, prefix):
        assert "attn_ssm" not in text and "ssm_conv" not in text
    assert "lm_head" in jax.eval_shape(
        lambda: fam.init(cfg, jax.random.PRNGKey(0)))
    if name.startswith("kimi"):
        assert paged.counter_keys(cfg)[-2:] == paged.KV_KEYS
    else:
        assert paged.state_layers(cfg) == [] and paged.state_bytes(cfg) == 0
    if isinstance(cfg, MoEConfig):
        assert "ssm" not in cfg.attn_layout and "kv" not in cfg.attn_layout
        off = dataclasses.replace(cfg, ssm_inner=0, ssm_state=0,
                                  ssm_dt_rank=0, ssm_conv=0,
                                  tie_embeddings=False)
        assert _program_texts(off) == (decode, prefix)


def test_the_new_configurations_programs_have_the_state_and_the_scopes(tiny):
    from ray_tpu.models import paged

    _, cfg, _, _ = tiny
    for text in _program_texts(cfg, debug_info=True):
        assert "attn_ssm" in text and "ssm_conv" in text
        assert "attn_global" in text and "attn_kda" not in text
    pools = jax.eval_shape(lambda: paged.init_paged_pools(cfg, 8, 8, 0, 4))
    assert paged.state_bytes(cfg, 4) == sum(
        pools[n].size * pools[n].dtype.itemsize for n in ("S", "conv"))
    assert paged.state_bytes(cfg) == jamba.state_slot_bytes(_model())
    with pytest.raises(ValueError, match="state_slots"):
        paged.init_paged_pools(cfg, 8, 8)


# --------------------------------------------------- through the engine


def test_the_engine_serves_it_and_records_what_the_readers_read(tiny):
    """``register_model`` -> ``LLMServer`` -> ``InferenceEngine``, the
    normal path: the state beside the K/V pool of the one attention layer,
    the prefix cache off with its reason, ``state_bytes`` and the attention
    layer's rows on a decode step's record, the rows scanned on a prefill's
    entry, a chunked prompt, slots used again (each answer is the
    reference's greedy one, so each started from zero state), no
    recompile."""
    from test_benchmark_smallthinker import _drain

    from ray_tpu.models.paged import trace_count
    from ray_tpu.serve.engine import LLMServer, register_model
    from ray_tpu.util import steprec

    model, cfg, _, _ = tiny
    register_model("jamba-tiny-test", lambda: cfg)
    server = LLMServer(model="jamba-tiny-test",
                       engine=dict(ENGINE, prefix_cache=True), seed=3,
                       warmup=True)
    try:
        eng = server.engine
        st = server.stats()
        assert st["prefix_cache"] is None
        assert st["prefix_cache_off"] == "recurrent layers"
        slot_bytes = jamba.state_slot_bytes(model)
        assert st["state"] == {"layers": 13, "slot_bytes": slot_bytes,
                               "total_bytes": 2 * slot_bytes}
        assert st["decode_attention"] == "gather"  # no TPU here
        assert set(eng.pools) == {"k", "v", "S", "conv"}
        assert eng.pools["k"].shape[:2] == (1, 2 * 24 + 1)
        traced = {p: trace_count(p)
                  for p in ("decode", "prefill", "prefill_prefix")}
        steprec.drain_buffered()
        prompts = [_tokens(model, (n,), seed=20 + n).tolist()
                   for n in (6, 41, 13, 22, 5)]
        outs = [list(s) for s in
                [eng.submit(p, max_new_tokens=7) for p in prompts]]
        assert [len(o) for o in outs] == [7] * 5  # five requests, two slots
        recs = _drain(eng, 5)
        first = {e["prompt"]: e for r in recs for e in r["first_tokens"]}
        assert {n: first[n]["chunks"] for n in first} \
            == {6: 1, 41: 6, 13: 2, 22: 3, 5: 1}
        assert {n: (e["scan_rows"], e["scan_rows_padded"])
                for n, e in first.items()} \
            == {6: (6, 2), 41: (41, 3), 13: (13, 3), 22: (22, 2),
                5: (5, 3)}
        decode = [r for r in recs if r["occupancy"]]
        assert decode
        for r in decode:
            assert r["state_bytes"] == 2 * 2 * slot_bytes
            # One attention layer: every slot's whole table is gathered.
            assert r["kv_rows_read"] == 2 * 24 * 4
            assert 0 < r["kv_rows_distinct"] == r["kv_rows_live"] \
                <= r["kv_rows_read"]
            assert "experts_hit" not in r
        ref = jamba.reference(model, eng.params)
        for prompt, out in zip(prompts, outs):
            seq = np.asarray(prompt + out[:-1], np.int32)
            want = ref.logits(seq, range(len(prompt) - 1, len(seq)))
            assert want.argmax(-1).tolist() == out
        assert {p: trace_count(p) for p in traced} == traced
        st1 = server.stats()
        assert st1["free_pages"] == st1["total_pages"] == 2 * 24
    finally:
        server.engine.shutdown()


# ----------------------------------------------------- the benchmark's files


def _ctx(steps, model=None, **over):
    return {"kind": "serve_closed", "steps": steps, "seconds": 51.0,
            "model": model or _model(CONFIG),
            "device": {"platform": "tpu", "kind": "TPU v5 lite"}, **over}


def _record(**over):
    rec = {"t": 1.0, "wall_s": 0.012, "between_s": 0.004, "idle_s": 0.0,
           "upload_s": 0.0, "dispatch_s": 0.001, "readback_s": 0.008,
           "emit_s": 0.001, "first_tokens": [], "stall_s": 0.0,
           "admitted": 0, "occupancy": 128, "slots": 128, "ahead": 1,
           "kv_rows_read": 2 * 128 * 2048, "kv_rows_live": 2 * 128 * 2000,
           "kv_rows_distinct": 2 * 128 * 2000,
           "state_bytes": 2 * 128 * 9_318_400}
    return {**rec, **over}


def test_the_readers_read_the_records_and_nothing_from_a_parent():
    from benchmarks.layer_metrics import (decode_bytes_floor_share_ssm,
                                          scan_padding_share_ssm)

    entry = {"prompt": 300, "bucket": 512, "scan_rows": 300,
             "scan_rows_padded": 212}
    chunked = {"prompt": 3000, "bucket": 3072, "scan_rows": 3000,
               "scan_rows_padded": 72}
    steps = [_record(), _record(first_tokens=[entry, chunked], admitted=2,
                                stall_s=0.2), _record(occupancy=100, ahead=0)]
    share = decode_bytes_floor_share_ssm.read(_ctx(steps))
    floor = jamba.decode_floor_bytes(_model(CONFIG), 2 * 128 * 2000, 128)
    assert share == pytest.approx(100 * floor / 819e9 / 0.016, rel=0.02)
    assert 60 < share <= 100
    assert scan_padding_share_ssm.read(_ctx(steps)) \
        == pytest.approx(100 * 284 / 3584)
    # A CPU's records read the padding (counted on the host) and no share
    # of a peak it has not.
    cpu = {"device": {"platform": "cpu", "kind": "cpu"}}
    assert decode_bytes_floor_share_ssm.read(_ctx(steps, **cpu)) is None
    assert scan_padding_share_ssm.read(_ctx(steps, **cpu)) is not None
    # A parent's records have neither field; a routed family's floor takes
    # experts; a window with no prefill has no rows.
    old = [{k: v for k, v in _record(first_tokens=[
        {"prompt": 300, "bucket": 512}]).items() if k != "state_bytes"}]
    assert decode_bytes_floor_share_ssm.read(_ctx(old)) is None
    assert scan_padding_share_ssm.read(_ctx(old)) is None
    assert decode_bytes_floor_share_ssm.read(
        _ctx(steps, model=_model("kimi-linear-48b-a3b-L13"))) is None
    assert decode_bytes_floor_share_ssm.read(
        _ctx(steps, model=_model("internlm2-1.8b"))) is None
    assert scan_padding_share_ssm.read(_ctx([_record()])) is None
    assert decode_bytes_floor_share_ssm.read(_ctx([])) is None


def test_the_new_cell_is_in_the_benchmark_by_name_and_membership():
    bench = spec.load_benchmark()
    spec.validate(bench)
    assert len(bench["workloads"]) >= 11
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, TRAFFIC, 1)
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    assert CELL in metrics["serve_tok_s"]["workloads"]
    for name in (*READERS, "prefill_stall_share.sat", "batch_occupancy.sat",
                 "decode_step_ms.sat", "decode_period_ms.sat",
                 "decode_host_ms.sat", "decode_device_wait_ms.sat",
                 "device_idle_share.serve", "device_starved_share.sat",
                 "ahead_share.sat", "loop_accounted_share.sat",
                 "admission_drain_ms.sat",
                 "device_idle_unaccounted_share.serve",
                 "prefill_chunk_ms.swa"):
        assert CELL in metrics[name]["workloads"], name
    for name in ("decode_bytes_floor_share.kda", "experts_hit_share.moe",
                 "moe_stream_roofline.moe", "paged_decode_roofline.swa",
                 "kv_gather_live_share.swa", "itl_p95_ms"):
        assert CELL not in metrics[name]["workloads"], name
    floor, padding = (metrics[r] for r in READERS)
    assert (floor["layer"], floor["source"], floor["moves"], floor["better"],
            floor["workloads"]) == ("engine loop (host)", "program_counter",
                                    "serve_tok_s", "higher", [CELL])
    assert (padding["layer"], padding["source"], padding["moves"],
            padding["better"], padding["workloads"]) == (
        "engine admission", "program_counter", "serve_tok_s", "lower",
        [CELL])
    tr = spec.load_cell(CELL)["traffic"]
    assert (tr["kind"], tr["clients"], tr["pool"], tr["schedule_seed"],
            tr["shared_prefix"], tr["temperature"]) \
        == ("serve_closed", 256, 128, 0, 0, 0.0)
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 512,
                                "sigma": 1.0, "min": 64, "max": 6144}
    assert tr["output_len"] == {"dist": "lognormal", "median": 1536,
                                "sigma": 0.5, "min": 256, "max": 3072}
    eng = tr["engine"]
    assert (eng["batch_slots"], eng["page_size"], eng["max_prompt_len"],
            eng["max_new_tokens_cap"], eng["prefill_chunk"],
            eng["prefix_cache"], eng["max_queue"]) \
        == (128, 128, 6144, 3072, 2048, False, 256)
    assert tr["max_concurrent_queries"] == 512
    assert tr["check"]["prompt_lens"] == [300, 1500, 3000, 6000]
    assert tr["check"]["new_tokens"] == 16
    assert tr["system_config"] == {"engine_steps_max_records": 16384,
                                   "step_ring_size": 16384,
                                   "peer_call_deadline_s": 240.0}
    from benchmarks.traffic import quantile_lengths
    prompts = quantile_lengths(tr["prompt_len"], 128)
    outputs = quantile_lengths(tr["output_len"], 128)
    assert (sum(p < 128 for p in prompts), sum(p > 2048 for p in prompts),
            sum(p > 4096 for p in prompts), prompts.count(6144)) \
        == (11, 11, 2, 1)
    assert (outputs.count(3072), min(outputs)) == (11, 406)
    # The generator does not run dry: what a ramp and a window can send.
    horizon = tr["ramp_s"] + 51
    assert tr["clients"] + 8 * horizon + 64 > 1000
