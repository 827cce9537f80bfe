"""The kimi_linear family (Kimi-Linear-48B-A3B: gated delta-rule layers whose
state lives in a slot, latent layers without a q latent or position, a
chip's share of the experts) at its tiny configuration on the CPU, float32:
the program against the family's plain reference (the token-by-token
recurrence), the chunk form against the recurrent one, the state through the
engine, the share's arithmetic, the comparison's faults, and the benchmark's
new files (the cell's rehearsals: ``test_benchmark_kimi_linear_cell.py``).
Entries of BENCHMARK.json are looked up by name and membership,
never by a list's tail or whole."""

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_testlib import ROOT

from benchmarks import spec
from benchmarks.families import kimi_linear
from benchmarks.reference import kimi_linear_compare

LOGIT_TOL = 2e-4
CONFIG = "kimi-linear-48b-a3b-L13"
TRAFFIC = "serve-long-decode-doc-tail"
CELL = f"{CONFIG}.{TRAFFIC}"
READERS = ("decode_bytes_floor_share.kda", "expert_pairs_held_share.moe")
#: Chunks of 8 over pages of 4; the chunk form's blocks are 64 tokens, so a
#: test that crosses a block's edge sets ``kda.BLOCK`` to 4.
ENGINE = dict(batch_slots=2, page_size=4, max_prompt_len=48,
              max_new_tokens_cap=48, prefill_chunk=8, prefix_cache=False)


def _model(name="kimi-linear-tiny", **over):
    return {**spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", name + ".json")), **over}


def _weights(cfg, seed=0):
    """Seeded weights whose norm weights are not all ones, so that a norm
    left out (or put in the wrong place) shows."""
    from benchmarks.reference.olmoe_compare import _weights as draw

    return draw(kimi_linear, cfg, seed)


def _tokens(model, shape, seed=2):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), shape, 1, model["vocab_size"]), np.int32)


@pytest.fixture(scope="module")
def tiny():
    """(model file, program configuration, weights, reference)."""
    model = _model()
    cfg = kimi_linear.program_config(model, max_seq=96, remat=False)
    params = _weights(cfg)
    return model, cfg, params, kimi_linear.reference(model, params)


@pytest.fixture
def small_blocks(monkeypatch):
    """The chunk form in blocks of 4 tokens, so that tiny prompts cross
    block edges."""
    from ray_tpu.models import kda

    monkeypatch.setattr(kda, "BLOCK", 4)


# ------------------------------------------------------------- full forward


def test_the_configuration_object_carries_what_the_equations_need(tiny):
    from ray_tpu.models import block, paged

    model, cfg, params, _ = tiny
    assert cfg.attn_layout == ("kda", "kda", "kda", "latent", "kda")
    assert [block.is_kda(cfg, i) for i in range(5)] \
        == [True, True, True, False, True]
    assert [block.is_latent(cfg, i) for i in range(5)] \
        == [False, False, False, True, False]
    assert block.is_latent(cfg) and block.is_kda(cfg)
    assert not any(block.layer_rotary(cfg, i) for i in range(5))
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.head_dim) == (0, 16, 20)
    assert (cfg.n_experts, cfg.router_experts, cfg.first_expert) \
        == (4, 16, 4)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv) == (4, 16, 4)
    assert paged.kv_layers(cfg) == ([3], [])
    assert paged.state_layers(cfg) == [0, 1, 2, 4]
    assert paged.routing_keys(cfg) == paged.ROUTING_KEYS + paged.SHARE_KEYS
    assert "wq" in params["layers"][3]["attn"] \
        and "wq_a" not in params["layers"][3]["attn"]
    assert params["layers"][1]["moe"]["router"].shape == (64, 16)
    assert params["layers"][1]["moe"]["w1"].shape == (4, 64, 32)
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == cfg.param_count() == kimi_linear.param_count(model)


def test_moe_apply_matches_the_reference(tiny, small_blocks):
    """The full forward (the chunk form from a zero state, over several
    blocks) against the token-by-token recurrence."""
    from ray_tpu.models import moe_apply

    model, cfg, params, ref = tiny
    seq = _tokens(model, (37,), seed=3)
    got = np.asarray(moe_apply(cfg, params, jnp.asarray(seq)[None])[0][0])
    want = ref.logits(seq, range(len(seq)))
    assert np.abs(got - want).max() < LOGIT_TOL


# ------------------------------------------------------- the paged programs


def _paged(cfg, params, seq, prompt, *, engine=ENGINE, slot=1, pools=None):
    """The engine's way through the programs, by hand: the prompt in chunks
    of the largest bucket (the first through ``prefill_logits``, the rest
    through ``prefill_prefix_logits``, the state carried in ``slot``), then
    a teacher-forced decode step for every further token of ``seq``.
    Returns (logits [1 + new, V], the pools' final state)."""
    from ray_tpu.models import paged
    from ray_tpu.serve.engine import EngineConfig

    ec = EngineConfig(**engine)
    ps, maxp, b = ec.page_size, ec.pages_per_seq, ec.batch_slots
    buckets = ec.prefill_buckets()
    chunk = buckets[-1]
    if pools is None:
        pools = paged.init_paged_pools(cfg, ec.pool_pages, ps, 0, b)
    adapters = paged.init_adapter_pool(cfg, ec.max_adapters, ec.lora_rank)
    zero = jnp.asarray(ec.max_adapters, jnp.int32)
    need = -(-len(seq) // ps)
    table = np.full((maxp,), ec.pool_pages, np.int32)
    table[:need] = 3 + np.arange(need)
    state = jnp.asarray(slot, jnp.int32)
    for start in range(0, prompt, chunk):
        end = min(start + chunk, prompt)
        bucket = next(x for x in buckets if x >= end - start)
        pad = np.zeros((1, bucket), np.int32)
        pad[0, :end - start] = seq[start:end]
        if start:
            logits, pools, _ = paged.prefill_prefix_logits(
                cfg, params, pools, adapters, jnp.asarray(pad),
                jnp.asarray(start), jnp.asarray(end), jnp.asarray(table),
                zero, None, state)
        else:
            logits, pools, _ = paged.prefill_logits(
                cfg, params, pools, adapters, jnp.asarray(pad),
                jnp.asarray(end), jnp.asarray(table), zero, None, state)
    rows = [np.asarray(logits[0])]
    tables = np.full((b, maxp), ec.pool_pages, np.int32)
    tables[slot] = table
    live = np.arange(b) == slot
    for i in range(prompt, len(seq)):
        logits, pools, _ = paged.decode_logits(
            cfg, params, pools, adapters,
            jnp.asarray(np.where(live, seq[i], 0), jnp.int32),
            jnp.asarray(tables), jnp.asarray(np.where(live, i, 0), jnp.int32),
            jnp.asarray(live), jnp.asarray([ec.max_adapters] * b, jnp.int32))
        rows.append(np.asarray(logits[slot]))
    return np.stack(rows), pools


@pytest.mark.parametrize("prompt,new", [(6, 6), (14, 6), (30, 5), (45, 3)],
                         ids=["one-bucket", "two-chunks", "four-chunks",
                              "six-chunks-a-padded-last"])
def test_prefill_and_decode_through_state_and_pool_match_the_reference(
        tiny, small_blocks, prompt, new):
    """The cell's check prompts at the tiny size: the state from zeros
    through the chunk form, carried across chunk edges in the slot, then
    through the recurrent form; the latent layer's rows in its own pool."""
    model, cfg, params, ref = tiny
    seq = _tokens(model, (prompt + new,), seed=5)
    got, pools = _paged(cfg, params, seq, prompt)
    want = ref.logits(seq, range(prompt - 1, prompt + new))
    assert np.abs(got - want).max() < LOGIT_TOL
    assert set(pools) == {"kv", "S", "conv"}
    assert pools["kv"].shape[0] == 1          # the ONE latent layer
    assert pools["S"].shape == (4, 2, 4, 16, 16)
    assert pools["conv"].shape == (4, 2, 3, 3 * 64)
    assert not np.asarray(pools["S"][:, 0]).any()  # the other slot's


def test_a_chunked_prefill_equals_the_one_shot_program(tiny, small_blocks):
    """The same 29-token prompt through four chunks of 8 and through one
    32-token bucket: across block edges (4) and chunk edges (8), the same
    logits, the same state and convolution rows, the same latent rows."""
    model, cfg, params, _ = tiny
    seq = _tokens(model, (29 + 3,), seed=6)
    chunked, pools_c = _paged(cfg, params, seq, 29)
    whole, pools_w = _paged(cfg, params, seq, 29,
                            engine=dict(ENGINE, prefill_chunk=0))
    assert np.abs(chunked - whole).max() < LOGIT_TOL
    for name in ("S", "conv"):
        np.testing.assert_allclose(np.asarray(pools_c[name][:, 1]),
                                   np.asarray(pools_w[name][:, 1]),
                                   atol=2e-5)
    np.testing.assert_allclose(np.asarray(pools_c["kv"][:, 3:11]),
                               np.asarray(pools_w["kv"][:, 3:11]), atol=1e-5)


def _recurrence(q, k, v, g, beta):
    """The recurrence in float64 numpy: o [T, H, D]."""
    T, H, D = q.shape
    S = np.zeros((H, D, D))
    out = []
    for t in range(T):
        S = np.exp(g[t])[:, :, None] * S
        u = beta[t][:, None] * (v[t] - np.einsum("hk,hkv->hv", k[t], S))
        S = S + k[t][:, :, None] * u[:, None, :]
        out.append(np.einsum("hkv,hk->hv", S, q[t]))
    return np.stack(out), S


def test_the_chunk_form_equals_the_recurrence_where_a_factored_exp_overflows():
    """Log-decays down to -40 a token: over a block of 64 the running sum
    passes -2000, and ``exp(-G)`` alone overflows float32 at 89.  The chunk
    form takes differences first; the recurrence in float64 is the
    reference, and a factored form is shown to fail on the same data."""
    from ray_tpu.models import kda

    rng = np.random.default_rng(7)
    T, H, D = 150, 2, 16
    q, k, v = (rng.standard_normal((T, H, D)) for _ in range(3))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * 4.0
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -np.exp(rng.uniform(np.log(1e-3), np.log(40.0), (T, H, D)))
    beta = rng.uniform(0.05, 0.95, (T, H))
    want, want_S = _recurrence(q, k, v, g, beta)
    f32 = [jnp.asarray(t[None], jnp.float32) for t in (q, k, v, g, beta)]
    got, got_S = kda.chunked(jnp.zeros((1, H, D, D), jnp.float32), *f32)
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got[0]) - want).max() < 2e-5
    assert np.abs(np.asarray(got_S[0]) - want_S).max() < 2e-5
    G = np.cumsum(g[:64], axis=0, dtype=np.float32)
    with np.errstate(over="ignore"):
        assert np.isinf(np.exp(-G)).any()        # the factored form's term
    # And with a state carried in, and padded rows behind the real ones.
    head = kda.chunked(jnp.zeros((1, H, D, D), jnp.float32),
                       *[t[:, :70] for t in f32])[1]
    valid = jnp.arange(96)[None] < 80
    tail = [jnp.pad(t[:, 70:], ((0, 0), (0, 16)) + ((0, 0),) * (t.ndim - 2))
            for t in f32]
    got2, S2 = kda.chunked(head, *tail, valid=valid)
    assert np.abs(np.asarray(got2[0, :80]) - want[70:]).max() < 2e-5
    assert np.abs(np.asarray(S2[0]) - want_S).max() < 2e-5


def test_the_recurrent_form_is_the_recurrence():
    from ray_tpu.models import kda

    rng = np.random.default_rng(8)
    T, H, D = 9, 3, 8
    q, k, v = (rng.standard_normal((T, H, D)) for _ in range(3))
    g = -rng.uniform(0.01, 3.0, (T, H, D))
    beta = rng.uniform(0.05, 0.95, (T, H))
    want, want_S = _recurrence(q, k, v, g, beta)
    S = jnp.zeros((1, H, D, D), jnp.float32)
    for t in range(T):
        o, S = kda.recurrent(S, *[jnp.asarray(x[t][None], jnp.float32)
                                  for x in (q, k, v, g, beta)])
        assert np.abs(np.asarray(o[0]) - want[t]).max() < 1e-4
    assert np.abs(np.asarray(S[0]) - want_S).max() < 1e-4


# ------------------------------------------------------- the experts' share


def test_the_shares_partial_sums_and_the_shared_expert_once_are_the_layer():
    """One routed layer with all 16 experts, and the same weights cut into
    four shares of 4 (the tiny size's eight-of-256): each share's
    ``_moe_ffn`` returns the partial sum of its own experts, the shares sum
    to the uncut layer, the shared expert is added once, and every share
    counts its own pairs beside all the pairs routed."""
    from ray_tpu.models import MoEConfig, moe

    base = dict(vocab_size=64, d_model=32, n_layers=1, n_heads=2,
                n_kv_heads=2, d_ff=16, top_k=3, router_score="sigmoid",
                routed_scaling_factor=2.446, n_shared_experts=1,
                dtype=jnp.float32, remat=False, max_seq=16)
    whole = MoEConfig(n_experts=16, **base)
    m = moe.moe_init(whole, jax.random.PRNGKey(4))["layers"][0]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(5), (11, 32), jnp.float32)
    valid = jnp.arange(11) < 9
    want, _, counts = moe._moe_ffn(whole, m, x, valid)
    assert counts.shape == (16,) and int(counts.sum()) == 9 * 3
    total, held_pairs = 0.0, 0
    for first in range(0, 16, 4):
        cfg = MoEConfig(n_experts=4, router_experts=16, first_expert=first,
                        **base)
        mine = {**m, **{w: m[w][first:first + 4] for w in ("w1", "w3", "w2")}}
        out, _, c = moe._moe_ffn(cfg, mine, x, valid)
        assert c.shape == (5,) and int(c[-1]) == 9 * 3
        np.testing.assert_array_equal(np.asarray(c[:4]),
                                      np.asarray(counts[first:first + 4]))
        assert not np.asarray(out[9:]).any()    # a padded row gets nothing
        total, held_pairs = total + out, held_pairs + int(c[:4].sum())
    assert held_pairs == 9 * 3
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5)
    shared = moe._shared_expert(whole, m["shared"], x, valid)
    assert np.abs(np.asarray(shared[:9])).max() > 0.01


def test_the_stream_is_chosen_by_the_rows_an_expert_gets_here(monkeypatch):
    """64 slots x 8 = 512 pairs over the ROUTER's 256 experts are two rows
    an expert, whatever share of them is held."""
    from ray_tpu.models import moe
    from ray_tpu.ops import grouped_ffn

    cfg = kimi_linear.program_config(_model(CONFIG), max_seq=256,
                                     remat=False)
    monkeypatch.setattr(grouped_ffn, "on_tpu", lambda: True)
    assert moe.grouped_form(cfg, 64) == "stream"
    assert moe.grouped_form(cfg, 2048) == "ragged_dot"
    monkeypatch.setattr(grouped_ffn, "on_tpu", lambda: False)
    assert moe.grouped_form(cfg, 64) == "ragged_dot"


# ----------------------------------------------------- the comparison's faults


@pytest.mark.parametrize("fault", kimi_linear_compare.FAULTS)
def test_the_comparison_catches(tiny, small_blocks, fault):
    """Each of the comparison's faults, through the paged programs at the
    tiny size (called untraced, so that the function a fault swapped in is
    the one that runs), moves a row's logits far past the tolerance."""
    from benchmarks.reference.glm4_moe_lite_compare import _float8

    model, cfg, params, ref = tiny
    seq = _tokens(model, (21 + 4,), seed=9)
    want = ref.logits(seq, range(20, 25))
    sound, _ = _paged(cfg, params, seq, 21)
    assert np.abs(sound - want).max() < LOGIT_TOL
    if fault in ("float8-experts", "float8"):
        params = _float8(jax.tree.map(jnp.array, params), fault == "float8")
    with kimi_linear_compare.faulted(cfg, fault) as fcfg:
        if fault == "layout-shift":
            params = kimi_linear_compare._shift_weights(
                params, kimi_linear_compare._shifted(cfg)[1])
        got, _ = _paged(fcfg, params, seq, 21)
    assert np.abs(got - want).max() > 20 * LOGIT_TOL, fault


# ---------------------------------------- older configurations' programs


OLDER = ["internlm2-1.8b", "olmoe-1b-7b-0125", "smallthinker-21b-a3b-L8",
         "glm-4.7-flash-L6", "trinity-mini-L5"]


@pytest.mark.parametrize("name", OLDER)
def test_an_older_configurations_decode_program_has_none_of_the_branches(
        name):
    """The layout of attention kinds, the held experts and the state are
    trace-time branches: an older configuration's decode program holds no
    KDA scope, no state pool and no fourth routing counter, and lowers to
    the same text with the new fields stated at their defaults."""
    from test_benchmark_afmoe import _decode_text

    from ray_tpu.models import MoEConfig, paged

    model = _model(name)
    fam = spec.family(model)
    build = getattr(fam, "program_config", None) or fam.llama_config
    cfg = build(model, max_seq=64, remat=False)
    text = _decode_text(cfg)
    assert "attn_kda" not in text and "kda_conv" not in text
    assert paged.state_layers(cfg) == [] and paged.state_bytes(cfg) == 0
    assert "expert_pairs_routed" not in paged.counter_keys(cfg)
    if isinstance(cfg, MoEConfig):
        assert cfg.attn_layout == () and cfg.first_expert == 0
        assert cfg.router_width == cfg.n_experts
        off = dataclasses.replace(cfg, attn_layout=(), kda_heads=0,
                                  router_experts=0)
        assert _decode_text(off) == text


def test_the_new_configurations_decode_program_has_the_state_and_the_share():
    from ray_tpu.models import paged

    cfg = kimi_linear.program_config(_model(), max_seq=64, remat=False)
    ec_slots = 4
    pools = jax.eval_shape(lambda: paged.init_paged_pools(
        cfg, 8, 8, 0, ec_slots))
    assert set(pools) == {"kv", "S", "conv"}
    assert paged.state_bytes(cfg, ec_slots) == sum(
        pools[n].size * pools[n].dtype.itemsize for n in ("S", "conv"))
    with pytest.raises(ValueError, match="state_slots"):
        paged.init_paged_pools(cfg, 8, 8)
    assert paged.counter_keys(cfg)[-3:] == (
        "expert_pairs_routed", "kv_rows_read", "kv_rows_live")


# ------------------------------------------------------------------ counts


def test_the_familys_counts_are_pinned_at_the_cells_configuration():
    model = _model(CONFIG)
    assert kimi_linear._kda_params(model) == 39_514_272
    assert kimi_linear._latent_params(model) == 29_114_880
    assert kimi_linear._expert_params(model) == 7_077_888
    assert kimi_linear.param_count(model) == 3_450_547_008
    assert kimi_linear.state_slot_bytes(model) \
        == 10 * (32 * 128 * 128 * 4 + 3 * 12288 * 2) == 21_708_800
    cfg = kimi_linear.program_config(model, max_seq=11264, remat=False)
    assert cfg.param_count() == 3_450_547_008
    assert cfg.attn_layout.count("kda") == 10
    assert [i for i, a in enumerate(cfg.attn_layout) if a == "latent"] \
        == [3, 7, 11]
    # A step at full occupancy, 27.8 of 32 experts a layer, 4000 rows a slot.
    floor = kimi_linear.decode_floor_bytes(
        model, experts_hit=334, kv_rows_distinct=3 * 64 * 4000, occupancy=64)
    assert 9.7e9 < floor < 9.9e9
    state = 64 * 2 * kimi_linear.state_slot_bytes(model)
    assert 0.27 < state / floor < 0.30
    need = kimi_linear.routed_ffn_ops_bytes(model, pairs=768, experts_hit=334)
    assert need["bytes"] == (334 * 7_077_888 + 768 * 2 * 2304) * 2


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 768), ("mla_use_nope", False), ("num_expert_group", 2),
    ("moe_router_activation_func", "softmax"), ("rope_scaling", {"x": 1}),
    ("num_nextn_predict_layers", 1), ("first_expert", 14),
    ("tie_word_embeddings", True)])
def test_the_family_refuses_what_the_program_does_not_compute(key, value):
    with pytest.raises(ValueError):
        kimi_linear.check_supported(_model(**{key: value}))


def test_the_family_refuses_lists_that_do_not_name_every_layer_once():
    lin = _model()["linear_attn_config"]
    for bad in (dict(lin, kda_layers=[1, 2, 3]),
                dict(lin, full_attn_layers=[4, 5])):
        with pytest.raises(ValueError, match="each of its 5 layers once"):
            kimi_linear.check_supported(_model(linear_attn_config=bad))


def test_the_catalogs_numbers_are_in_the_file_under_their_keys():
    """Every number of the catalog entry's ``config`` is in the file under
    the same key, or the key is in ``reduced`` with its published value
    beside it; no reduced key is a width."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    entry = next(e for e in map(json.loads, open(catalog))
                 if e["name"] == "Kimi-Linear-48B-A3B-Instruct")
    model = _model(CONFIG)
    bench = spec.load_benchmark()
    mine = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert mine["source"] == entry["source_url"] == model["source"]
    assert sorted(mine["reduced"]) == sorted(model["reduced"]) == sorted(
        ["num_hidden_layers", "num_experts", "vocab_size",
         "linear_attn_config"])
    for key, value in entry["config"].items():
        if key in model["reduced"]:
            assert model["published"][key] == value
        else:
            assert model[key] == value, key
    lin, pub = model["linear_attn_config"], entry["config"][
        "linear_attn_config"]
    for width in ("head_dim", "num_heads", "short_conv_kernel_size"):
        assert lin[width] == pub[width]
    assert lin["kda_layers"] == [x for x in pub["kda_layers"] if x <= 13]
    assert lin["full_attn_layers"] \
        == [x for x in pub["full_attn_layers"] if x <= 13]
    assert model["router_experts"] == 256 and model["num_experts"] == 32
    assert model["vocab_size"] * 8 == entry["config"]["vocab_size"]


# ------------------------------------------------------ engine and records


def _server(cfg, name, **engine):
    from ray_tpu.serve.engine import LLMServer, register_model

    register_model(name, lambda: cfg)
    return LLMServer(model=name, engine=dict(ENGINE, **engine), seed=3,
                     warmup=True)


def test_the_model_is_served_with_its_state_in_the_slots(tiny, small_blocks):
    """``register_model`` -> ``LLMServer`` -> ``InferenceEngine``, the
    normal path: the state beside a latent pool of the one latent layer,
    the prefix cache off with its reason, the records' new keys, a chunked
    prompt, slots used again (each answer is the reference's greedy one, so
    each started from zero state), no recompile."""
    from test_benchmark_smallthinker import _drain

    from ray_tpu.models.paged import trace_count
    from ray_tpu.util import steprec

    model, cfg, params, _ = tiny
    server = _server(cfg, "kimi-linear-tiny-test", prefix_cache=True)
    try:
        eng = server.engine
        st0 = server.stats()
        assert st0["prefix_cache"] is None
        assert st0["prefix_cache_off"] == "recurrent layers"
        slot_bytes = 4 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
        assert st0["state"] == {"layers": 4, "slot_bytes": slot_bytes,
                                "total_bytes": 2 * slot_bytes}
        assert st0["window_pages"] is None
        assert set(eng.pools) == {"kv", "S", "conv"}
        assert eng.pools["kv"].shape[:2] == (1, 2 * 24 + 1)
        traced = {p: trace_count(p)
                  for p in ("decode", "prefill", "prefill_prefix")}
        steprec.drain_buffered()
        prompts = [_tokens(model, (n,), seed=20 + n).tolist()
                   for n in (6, 41, 13, 22, 5)]
        streams = [eng.submit(p, max_new_tokens=7) for p in prompts]
        outs = [list(s) for s in streams]     # five requests on two slots
        assert [len(o) for o in outs] == [7] * 5
        recs = _drain(eng, 5)
        first = {e["prompt"]: e for r in recs for e in r["first_tokens"]}
        assert {n: first[n]["chunks"] for n in first} \
            == {6: 1, 41: 6, 13: 2, 22: 3, 5: 1}
        # The four ROUTED layers' pairs, all of them and the held ones.
        for n, e in first.items():
            assert e["expert_pairs_routed"] == n * 2 * 4
            assert 0 <= e["expert_pairs"] <= e["expert_pairs_routed"]
        decode = [r for r in recs if r["occupancy"]]
        assert decode
        for r in decode:
            assert r["state_bytes"] == 2 * 2 * slot_bytes
            assert r["expert_pairs_routed"] in (2 * 4, 2 * 2 * 4)
            assert r["experts_hit"] <= r["expert_pairs"] \
                <= r["expert_pairs_routed"]
            # One latent layer: every slot's whole table is gathered here.
            assert r["kv_rows_read"] == 2 * 24 * 4
            assert 0 < r["kv_rows_distinct"] == r["kv_rows_live"] \
                <= r["kv_rows_read"]
        ref = kimi_linear.reference(model, eng.params)
        for prompt, out in zip(prompts, outs):
            seq = np.asarray(prompt + out[:-1], np.int32)
            want = ref.logits(seq, range(len(prompt) - 1, len(seq)))
            assert want.argmax(-1).tolist() == out
        assert {p: trace_count(p) for p in traced} == traced
        st1 = server.stats()
        assert st1["free_pages"] == st1["total_pages"] == 2 * 24
    finally:
        server.engine.shutdown()


def test_the_reset_after_a_failed_step_clears_the_state(tiny, small_blocks,
                                                        monkeypatch):
    """A decode step that raises fails the requests in flight and rebuilds
    the pools: the state is zeros again, and the next request is served
    from them with the reference's tokens."""
    from ray_tpu.models import paged

    model, cfg, params, _ = tiny
    server = _server(cfg, "kimi-linear-tiny-fail")
    try:
        eng = server.engine
        prompt = _tokens(model, (13,), seed=31).tolist()
        assert len(list(eng.submit(prompt, max_new_tokens=3))) == 3
        assert np.asarray(eng.pools["S"]).any()

        real = paged.paged_decode_step

        def boom(*a, **kw):
            raise RuntimeError("forced failure")

        import ray_tpu.models.paged as paged_mod
        monkeypatch.setattr(paged_mod, "paged_decode_step", boom)
        with pytest.raises(Exception, match="forced failure"):
            list(eng.submit(prompt, max_new_tokens=3))
        monkeypatch.setattr(paged_mod, "paged_decode_step", real)
        deadline = time.time() + 10
        while np.asarray(eng.pools["S"]).any() and time.time() < deadline:
            time.sleep(0.05)
        assert not np.asarray(eng.pools["S"]).any()
        assert not np.asarray(eng.pools["conv"]).any()
        out = list(eng.submit(prompt, max_new_tokens=4))
        ref = kimi_linear.reference(model, eng.params)
        seq = np.asarray(prompt + out[:-1], np.int32)
        assert ref.logits(seq, range(12, len(seq))).argmax(-1).tolist() == out
    finally:
        server.engine.shutdown()


# ----------------------------------------------------- the benchmark's files


def _ctx(steps, model=None, **over):
    return {"kind": "serve_closed", "steps": steps, "seconds": 51.0,
            "model": model or _model(CONFIG),
            "device": {"platform": "tpu", "kind": "TPU v5 lite"}, **over}


def _record(**over):
    rec = {"t": 1.0, "wall_s": 0.010, "between_s": 0.005, "idle_s": 0.0,
           "upload_s": 0.0, "dispatch_s": 0.001, "readback_s": 0.008,
           "emit_s": 0.001, "first_tokens": [], "stall_s": 0.0,
           "admitted": 0, "occupancy": 64, "slots": 64, "ahead": 1,
           "experts_hit": 334, "expert_pairs": 768,
           "expert_pairs_routed": 64 * 8 * 12, "expert_load_max": 5,
           "kv_rows_read": 3 * 64 * 4096, "kv_rows_live": 3 * 64 * 4000,
           "kv_rows_distinct": 3 * 64 * 4000,
           "state_bytes": 2 * 64 * 21_708_800}
    return {**rec, **over}


def test_the_readers_read_the_records_and_nothing_from_a_parent():
    from benchmarks.layer_metrics import (decode_bytes_floor_share_kda,
                                          expert_pairs_held_share_moe)

    steps = [_record(), _record(expert_pairs=790), _record(stall_s=0.1)]
    share = decode_bytes_floor_share_kda.read(_ctx(steps))
    floor = kimi_linear.decode_floor_bytes(_model(CONFIG), 334,
                                           3 * 64 * 4000, 64)
    assert share == pytest.approx(100 * floor / 819e9 / 0.015, rel=1e-3)
    assert 75 < share < 85
    held = expert_pairs_held_share_moe.read(_ctx(steps))
    assert held == pytest.approx(100 * (768 + 790) / (2 * 6144))
    # A parent's records have neither key; another family's floor takes no
    # occupancy; a CPU has no peak.
    old = [{k: v for k, v in _record().items()
            if k not in ("state_bytes", "expert_pairs_routed")}]
    assert decode_bytes_floor_share_kda.read(_ctx(old)) is None
    assert expert_pairs_held_share_moe.read(_ctx(old)) is None
    assert decode_bytes_floor_share_kda.read(
        _ctx(steps, model=_model("glm-4.7-flash-L6"))) is None
    assert decode_bytes_floor_share_kda.read(
        _ctx(steps, device={"platform": "cpu", "kind": "cpu"})) is None
    assert decode_bytes_floor_share_kda.read(_ctx([])) is None


def test_the_new_cell_is_in_the_benchmark_by_name_and_membership():
    bench = spec.load_benchmark()
    spec.validate(bench)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, TRAFFIC, 1)
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    assert CELL in metrics["serve_tok_s"]["workloads"]
    assert metrics["serve_tok_s"]["bound"] == 0.03
    for name in (*READERS, "prefill_chunk_ms.swa", "kv_gather_live_share.swa",
                 "latent_decode_roofline.mla", "moe_stream_roofline.moe",
                 "moe_decode_roofline.moe", "experts_hit_share.moe",
                 "prefill_stall_share.sat", "decode_period_ms.sat",
                 "device_idle_share.serve"):
        assert CELL in metrics[name]["workloads"], name
    for name in ("kv_pages_held_share.swa", "decode_bytes_floor_share.swa",
                 "decode_bytes_floor_share.mla", "paged_decode_roofline.swa",
                 "suffix_prefill_ms.mla", "prefix_cached_token_share.prefix"):
        assert CELL not in metrics[name]["workloads"], name
    kda, held = (metrics[r] for r in READERS)
    assert (kda["layer"], kda["source"], kda["moves"], kda["workloads"]) == (
        "engine loop (host)", "program_counter", "serve_tok_s", [CELL])
    assert (held["layer"], held["source"], held["workloads"]) == (
        "compiled decode program", "program_counter", [CELL])
    tr = spec.load_cell(CELL)["traffic"]
    assert (tr["clients"], tr["pool"], tr["schedule_seed"]) == (128, 64, 0)
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 1024,
                                "sigma": 1.0, "min": 128, "max": 8192}
    assert tr["output_len"] == {"dist": "lognormal", "median": 1536,
                                "sigma": 0.5, "min": 256, "max": 3072}
    eng = tr["engine"]
    assert (eng["batch_slots"], eng["page_size"], eng["prefill_chunk"],
            eng["prefix_cache"]) == (64, 128, 2048, False)
    assert eng["max_prompt_len"] + eng["max_new_tokens_cap"] == 11264
    assert tr["check"]["prompt_lens"] == [300, 1500, 3000, 7000]
    assert tr["system_config"]["engine_steps_max_records"] == 16384
    from benchmarks.traffic import quantile_lengths
    prompts = quantile_lengths(tr["prompt_len"], 64)
    assert (sum(p > 2048 for p in prompts), sum(p > 4096 for p in prompts),
            prompts.count(8192)) == (16, 5, 1)
