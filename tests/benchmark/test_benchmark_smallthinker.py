"""The smallthinker family at a tiny size on the CPU (window 8, pages of 4,
chunks of 8, two periods of the layer pattern, float32, heads wider than
hidden / heads): the system's decoder (``ray_tpu/models/moe.py`` over
``block.py``, and ``models/paged.py`` through the two kinds of paged cache)
against the family's plain reference on seeded weights, logits and not
tokens; what the comparison has to catch; the family's counts; the engine
with window layers (a ring a slot, chunked prefill, its records); and the
new cell's rehearsal.

Tolerance.  System and reference both compute in float32 here, in different
orders (pages and rings against a full forward, a grouped product over
sorted pairs against a masked loop over the experts), so they differ by
float32 rounding through eight layers: the largest logit difference seen is
3.6e-6 (logits are of order 1, the largest about 5).  ``LOGIT_TOL`` leaves
that a factor of 25 and is 5000 times under the least any structural fault
below moves a logit (0.6 to 5.4; experts in bfloat16: 1.4e-2): on the chip the configuration IS bfloat16 and the
tolerance written in ``benchmarks/reference/smallthinker_compare.py`` takes
this one's place."""

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
from benchmarks.families import smallthinker as st

LOGIT_TOL = 1e-4
CELL = "smallthinker-21b-a3b-L8.serve-long-mixed"
#: Window 8 and chunks of 8 over pages of 4: a ring of 4 pages, 16 tokens.
ENGINE = dict(batch_slots=2, page_size=4, max_prompt_len=48,
              max_new_tokens_cap=16, prefill_chunk=8, prefix_cache=False)


def _model(**over):
    return {**spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "smallthinker-tiny.json")), **over}


def _weights(cfg, seed=0):
    """Seeded weights whose norm weights are not all ones, so that a norm
    left out (or put in the wrong place) shows."""
    params = st.init(cfg, jax.random.PRNGKey(seed))
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


@pytest.fixture(scope="module")
def tiny():
    """(model file, program configuration, weights, reference)."""
    model = _model()
    cfg = st.program_config(model, max_seq=64, remat=False)
    params = _weights(cfg)
    return model, cfg, params, st.reference(model, params)


def _system_logits(cfg, params, tokens):
    from ray_tpu.models import moe_apply

    return np.asarray(moe_apply(cfg, params, jnp.asarray(tokens)[None])[0][0])


# ------------------------------------------------------------- full forward


def test_the_configuration_object_carries_the_pattern(tiny):
    from ray_tpu.models import block, paged

    _, cfg, params, _ = tiny
    assert cfg.head_dim == 32 != cfg.d_model // cfg.n_heads
    assert params["layers"][0]["attn"]["wq"].shape == (64, 4 * 32)
    assert params["layers"][0]["attn"]["wo"].shape == (4 * 32, 64)
    assert [block.layer_window(cfg, i) for i in range(8)] \
        == [0, 8, 8, 8, 0, 8, 8, 8]
    assert [block.layer_rotary(cfg, i) for i in range(8)] \
        == [False, True, True, True] * 2
    assert paged.kv_layers(cfg) == ([0, 4], [1, 2, 3, 5, 6, 7])
    assert paged.counter_keys(cfg) == paged.ROUTING_KEYS + paged.KV_KEYS
    assert paged.ring_entries(cfg, 4, 8) == 4
    # A configuration without a pattern has neither, whatever its family.
    from ray_tpu.models import LlamaConfig, MoEConfig

    for plain in (LlamaConfig.tiny(), MoEConfig.tiny()):
        assert block.layer_window(plain, 1) == 0
        assert block.layer_rotary(plain, 0) is True
        assert paged.kv_layers(plain)[1] == []
        assert paged.ring_entries(plain, 4, 8) == 0
    assert MoEConfig.tiny().head_dim == 128 // 4
    with pytest.raises(ValueError, match="window_layout"):
        dataclasses.replace(cfg, window_layout=(1, 0))


def test_moe_apply_and_loss_match_the_reference(tiny):
    model, cfg, params, ref = tiny
    toks = _tokens(model, (40,))
    want = ref.logits(toks, range(40))
    assert np.abs(_system_logits(cfg, params, toks) - want).max() < LOGIT_TOL
    batch = _tokens(model, (2, 24), seed=7)
    targets = np.roll(batch, -1, axis=1)
    rcfg = dataclasses.replace(cfg, remat=True)
    loss, grads = jax.value_and_grad(
        lambda p: st.loss(rcfg, p, jnp.asarray(batch),
                          jnp.asarray(targets)))(params)
    norm = float(jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                              for g in jax.tree.leaves(grads))))
    ref_loss, ref_norm = ref.loss_and_grad_norm(batch, targets)
    assert abs(float(loss) - ref_loss) / ref_loss < 1e-5
    assert abs(norm - ref_norm) / ref_norm < 1e-4


# ------------------------------------------------------- the paged programs


def _paged(cfg, params, seq, prompt, *, engine=ENGINE):
    """The engine's way through the programs, by hand: the prompt in
    chunks of the largest bucket (the first through ``prefill_logits``,
    the rest through ``prefill_prefix_logits``), then a teacher-forced
    decode step for every further token of ``seq`` in slot 1 of 2.
    Returns (logits [1 + new, V], the pools' final state)."""
    from ray_tpu.models import paged
    from ray_tpu.serve.engine import EngineConfig

    ec = EngineConfig(**engine)
    ps, maxp, b = ec.page_size, ec.pages_per_seq, ec.batch_slots
    buckets = ec.prefill_buckets()
    chunk = buckets[-1]
    ring = min(maxp, paged.ring_entries(cfg, ps, chunk))
    pools = paged.init_paged_pools(cfg, ec.pool_pages, ps, b * ring)
    adapters = paged.init_adapter_pool(cfg, ec.max_adapters, ec.lora_rank)
    zero = jnp.asarray(ec.max_adapters, jnp.int32)
    need = -(-len(seq) // ps)
    table = np.full((maxp,), ec.pool_pages, np.int32)
    table[:need] = 3 + np.arange(need)
    rt = np.full((ring,), b * ring, np.int32)
    rt[:min(ring, need)] = 1 + np.arange(min(ring, need))
    for start in range(0, prompt, chunk):
        end = min(start + chunk, prompt)
        bucket = next(x for x in buckets if x >= end - start)
        pad = np.zeros((1, bucket), np.int32)
        pad[0, :end - start] = seq[start:end]
        if start:
            logits, pools, _ = paged.prefill_prefix_logits(
                cfg, params, pools, adapters, jnp.asarray(pad),
                jnp.asarray(start), jnp.asarray(end), jnp.asarray(table),
                zero, jnp.asarray(rt))
        else:
            logits, pools, _ = paged.prefill_logits(
                cfg, params, pools, adapters, jnp.asarray(pad),
                jnp.asarray(end), jnp.asarray(table), zero, jnp.asarray(rt))
    rows = [np.asarray(logits[0])]
    tables = np.full((b, maxp), ec.pool_pages, np.int32)
    rings = np.full((b, ring), b * ring, np.int32)
    tables[1], rings[1] = table, rt
    for i in range(prompt, len(seq)):
        logits, pools, _ = paged.decode_logits(
            cfg, params, pools, adapters,
            jnp.asarray([0, seq[i]], jnp.int32), jnp.asarray(tables),
            jnp.asarray([0, i], jnp.int32), jnp.asarray([False, True]),
            jnp.asarray([ec.max_adapters] * b, jnp.int32),
            jnp.asarray(rings))
        rows.append(np.asarray(logits[1]))
    return np.stack(rows), pools


@pytest.mark.parametrize("prompt,new", [(6, 14), (8, 4), (21, 6), (45, 3)],
                         ids=["one-bucket-then-past-the-window",
                              "a-whole-chunk", "chunked", "ring-wrapped-twice"])
def test_prefill_and_decode_through_the_ring_match_the_reference(
        tiny, prompt, new):
    """A prompt inside one bucket decoded until its window layers have
    slid past the window; a prompt in three chunks; one whose 45 tokens lap
    the 16-token ring twice before the decode steps lap it again."""
    model, cfg, params, ref = tiny
    seq = _tokens(model, (prompt + new,), seed=5)
    got, _ = _paged(cfg, params, seq, prompt)
    want = ref.logits(seq, range(prompt - 1, prompt + new))
    assert np.abs(got - want).max() < LOGIT_TOL


def test_a_chunked_prefill_equals_the_one_shot_program(tiny):
    """The same 29-token prompt through four chunks of 8 (the ring wraps)
    and through one 32-token bucket (``prefill_chunk`` 0: the buckets
    double past the prompt cap): the same logits, and the same K/V in the
    whole-length layers' pages."""
    model, cfg, params, _ = tiny
    seq = _tokens(model, (29 + 3,), seed=6)
    chunked, pools_c = _paged(cfg, params, seq, 29)
    whole, pools_w = _paged(cfg, params, seq, 29,
                            engine=dict(ENGINE, prefill_chunk=0))
    assert np.abs(chunked - whole).max() < LOGIT_TOL
    # Pages 3.. hold the sequence in both (the scratch page differs).
    np.testing.assert_allclose(np.asarray(pools_c["k"][:, 3:11]),
                               np.asarray(pools_w["k"][:, 3:11]), atol=1e-5)
    # The ring is 4 pages there; here window + the 64-token bucket would
    # be 18, more than a sequence's 16, so it is a whole table.
    assert pools_c["kw"].shape[1] == 2 * 4 + 1
    assert pools_w["kw"].shape[1] == 2 * 16 + 1


def test_the_reference_computes_with_the_experts_it_is_given(tiny):
    """Top-k routing is discontinuous, so the chip's comparison hands the
    reference the experts the system took: its own choice changes nothing;
    its second expert swapped for its third reaches exactly its margin and
    moves that token's logits; any other set reaches further."""
    model, _, _, ref = tiny
    seq = _tokens(model, (20,), seed=9)
    plain = ref.logits(seq, range(20))
    margins, reach = ref.routing(seq)
    assert margins.shape == reach.shape == (8, 20)
    assert (margins > 0).all() and (reach == 0).all()
    own = ref.top_experts(seq)  # [L, S, k]
    given = np.full((8, 20, 2), -1, np.int32)
    given[:, 12:] = own[:, 12:]
    assert np.array_equal(ref.logits(seq, range(20), given), plain)
    assert (ref.routing(seq, given)[1] == 0).all()
    # Token 15 in layer 3: of the twelve ways to keep one of its two
    # experts and take another of the eight beside it, the one that
    # reaches least is the router's second choice swapped for its third,
    # and it reaches the margin.
    a, b = own[3, 15]
    reaches = {}
    for keep in (a, b):
        for other in set(range(8)) - {a, b}:
            given[3, 15] = [keep, other]
            reaches[int(keep), int(other)] = float(
                ref.routing(seq, given)[1][3, 15])
    swap = min(reaches, key=reaches.get)
    assert reaches[swap] == pytest.approx(float(margins[3, 15]), rel=1e-5)
    assert sorted(reaches.values())[1] > reaches[swap]
    given[3, 15] = swap
    moved = ref.logits(seq, range(20), given)
    assert (ref.routing(seq, given)[1][:3] == 0).all()
    assert np.abs(moved[:15] - plain[:15]).max() == 0  # causal
    assert np.abs(moved[15] - plain[15]).max() > 50 * LOGIT_TOL


def test_the_ring_holds_what_the_arithmetic_says():
    from ray_tpu.models.paged import _ring_positions

    # 4 entries of 4 tokens; the last position written is 21 (page 5,
    # entry 1, offset 1): entry 1 holds page 5, entries 2 and 3 pages 2
    # and 3, entry 0 page 4.
    held = np.asarray(_ring_positions(jnp.asarray(21), 4, 4))
    assert held.tolist() == [16, 17, 18, 19, 20, 21, 22, 23,
                             8, 9, 10, 11, 12, 13, 14, 15]
    # Early in a sequence the entries ahead were never written.
    held = np.asarray(_ring_positions(jnp.asarray([2, 5]), 4, 4))
    assert held[0].tolist()[:4] == [0, 1, 2, 3] and (held[0][4:] < 0).all()
    assert held[1].tolist()[:8] == list(range(8)) and (held[1][8:] < 0).all()


FAULTS = {
    "window-left-out": dict(window=1 << 30),
    "rotary-on-a-global-layer": dict(rope_layout=(1,) * 8),
    "router-fed-from-after-attention": dict(router_before_attn=False),
    "silu-for-relu": dict(expert_act="silu"),
    "top-k-not-renormalised": dict(norm_topk_prob=False),
}


@pytest.mark.parametrize("fault", [*FAULTS, "bfloat16-experts"])
def test_the_comparison_catches(tiny, fault, monkeypatch):
    """Each of these is a different model, or the same one in a lower
    precision, and has to read as incorrect, in the full forward and
    through the pages: at this tolerance here, at bfloat16's on the
    chip."""
    from ray_tpu.models import moe

    model, cfg, params, ref = tiny
    seq = _tokens(model, (30,), seed=8)
    want = ref.logits(seq, range(30))
    floor = 50 * LOGIT_TOL
    if fault == "bfloat16-experts":
        real_ffn = moe._moe_ffn

        def low(config, m, x, valid=None, logits=None):
            bf = dataclasses.replace(config, dtype=jnp.bfloat16)
            m16 = {k: v.astype(jnp.bfloat16) if k != "router" else v
                   for k, v in m.items()}
            out, aux, c = real_ffn(bf, m16, x.astype(jnp.bfloat16), valid,
                                   logits)
            return out.astype(x.dtype), aux, c
        monkeypatch.setattr(moe, "_moe_ffn", low)
        bad = cfg
    else:
        bad = dataclasses.replace(cfg, **FAULTS[fault])
    assert np.abs(_system_logits(bad, params, seq) - want).max() > floor
    got, _ = _paged(bad, params, seq, 25)
    assert np.abs(got - want[24:]).max() > floor


# ------------------------------------------------------------------ counts


def test_the_familys_counts_equal_the_trees():
    model = spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "smallthinker-21b-a3b-L8.json"))
    assert model["reduced"] == ["num_hidden_layers", "rope_layout",
                                "sliding_window_layout"]
    assert model["rope_layout"] == model["sliding_window_layout"] \
        == [0, 1, 1, 1, 0, 1, 1, 1]
    published = {**model, "num_hidden_layers": 52,
                 "rope_layout": [0, 1, 1, 1] * 13,
                 "sliding_window_layout": [0, 1, 1, 1] * 13}
    assert st.param_count(model) == 3_966_937_600
    assert st.param_count(published) == 21_506_562_560  # "21B"
    assert st.matmul_params(published) == 3_328_245_760  # "A3B"
    # The program's own count and the tree's agree with the family's.
    for m in (model, _model()):
        cfg = st.program_config(m, max_seq=256)
        shapes = jax.eval_shape(lambda: st.init(cfg, jax.random.PRNGKey(0)))
        leaves = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
        assert leaves == st.param_count(m) == cfg.param_count()
    # The experts' three matrices of the tree are what the roofline's
    # bytes count: one decode step of 16 slots (96 pairs a layer) that
    # hits 51 of 64 experts in each of 8 layers, by hand.
    one = shapes = jax.eval_shape(lambda: st.init(
        st.program_config(model, max_seq=256), jax.random.PRNGKey(0)))
    moe = one["layers"][0]["moe"]
    expert = sum(int(np.prod(moe[w].shape[1:])) for w in ("w1", "w2", "w3"))
    assert expert == 3 * 2560 * 768
    need = st.routed_ffn_ops_bytes(model, 8 * 96, 8 * 51)
    assert need == {"ops": 8 * 96 * 2.0 * expert,
                    "bytes": (8 * 51 * expert + 8 * 96 * 2 * 2560) * 2}
    assert 4.8e9 < need["bytes"] < 4.9e9
    with pytest.raises(NotImplementedError):
        st.train_step_kernel_ops_bytes(model, 1, 4096, 24)
    # What the harness's readers ask of a configuration file by name.
    assert model["num_experts"] == model["moe_num_primary_experts"] == 64
    assert model["vocab_size"] == 151936 and model["torch_dtype"] == "bfloat16"


@pytest.mark.parametrize("key,value", [
    ("rope_scaling", {"type": "yarn", "factor": 4.0}),
    ("moe_primary_router_apply_softmax", False),
    ("tie_word_embeddings", True), ("norm_topk_prob", False),
    ("rope_layout", [0, 1, 1, 1]), ("sliding_window_layout", [2] * 8),
    ("sliding_window_size", 0), ("num_experts", 32)])
def test_the_family_refuses_what_the_program_does_not_compute(key, value):
    with pytest.raises(ValueError, match=key):
        st.check_supported(_model(**{key: value}))


def test_the_catalogs_numbers_are_in_the_file_under_their_keys():
    """Every number of the published config is in the cell's file under
    the same key, but the three the file lists as reduced."""
    model = spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "smallthinker-21b-a3b-L8.json"))
    published = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384, "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
        "num_attention_heads": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_theta": 1500000,
        "sliding_window_size": 4096, "vocab_size": 151936,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "tie_word_embeddings": False, "rope_scaling": None}
    for key, value in published.items():
        assert model[key] == value, key
    assert model["num_hidden_layers"] == 8
    assert model["published"]["num_hidden_layers"] == 52
    assert set(model["assumed"]) >= {"router", "hidden_act", "window",
                                     "secondary_experts", "torch_dtype"}


# ------------------------------------------------------ engine and records


def _drain(eng, want_first, deadline_s=10):
    from ray_tpu.util import steprec

    deadline, recs = time.time() + deadline_s, []
    while time.time() < deadline:
        recs += [r for r in steprec.drain_buffered()
                 if r.get("engine") == eng.engine_id]
        if sum(len(r["first_tokens"]) for r in recs) >= want_first:
            break
        time.sleep(0.05)
    return recs


def test_a_window_model_is_served_through_two_kinds_of_cache(tiny):
    from ray_tpu.models.paged import KV_KEYS, trace_count
    from ray_tpu.serve.engine import LLMServer, register_model
    from ray_tpu.util import steprec

    model, cfg, params, _ = tiny
    register_model("smallthinker-tiny-test", lambda: cfg)
    server = LLMServer(model="smallthinker-tiny-test",
                       engine=dict(ENGINE, prefix_cache=True), seed=3,
                       warmup=True)
    try:
        eng = server.engine
        st0 = server.stats()
        # The prefix cache cannot share a window layer's pages: off, and
        # the engine says so.
        assert st0["prefix_cache"] is None
        assert st0["prefix_cache_off"] == "window layers"
        assert eng.ring == 4 and eng.maxp == 16
        assert st0["window_pages"] == {"ring_entries": 4, "free": 8,
                                       "total": 8}
        assert st0["total_pages"] == 2 * 16 + 2 * 4 == st0["free_pages"]
        assert set(eng.pools) == {"k", "v", "kw", "vw"}
        assert eng.pools["k"].shape[:2] == (2, 33)
        assert eng.pools["kw"].shape[:2] == (6, 9)
        programs = {(r["program"], r["bucket"]) for r in st0["setup"][
            "programs"]}
        assert {("prefill", 4), ("prefill", 8), ("decode", None),
                ("prefill_prefix", 4), ("prefill_prefix", 8)} <= programs
        traced = {p: trace_count(p)
                  for p in ("decode", "prefill", "prefill_prefix")}
        steprec.drain_buffered()
        # A short prompt and one of 41 tokens (six chunks, the ring lapped
        # twice) at once; pages by kind while both are live.
        short = _tokens(model, (6,), seed=11).tolist()
        long = _tokens(model, (41,), seed=12).tolist()
        a = eng.submit(short, max_new_tokens=14)
        b = eng.submit(long, max_new_tokens=9)
        out_a, out_b = list(a), list(b)
        assert (len(out_a), len(out_b)) == (14, 9)
        recs = _drain(eng, 2)
        first = sorted((e for r in recs for e in r["first_tokens"]),
                       key=lambda e: e["prompt"])
        assert [(e["prompt"], e["chunks"], e["bucket"], e["cached"])
                for e in first] == [(6, 1, 8, 0), (41, 6, 5 * 8 + 4, 0)]
        # The chunks' routing counters are summed on the one entry.
        assert [e["expert_pairs"] for e in first] \
            == [6 * 2 * 8, 41 * 2 * 8]
        both = [r for r in recs if r["occupancy"] == 2]
        assert both, [r["occupancy"] for r in recs]
        for r in both:
            # ceil((6 + 14) / 4) = 5 and ceil((41 + 9) / 4) = 13 pages of
            # whole length; rings of min(4, .) = 4 and 4.
            assert r["pages_global"] == 2 * (5 + 13)
            assert r["pages_window"] == 6 * (4 + 4)
            assert r["pages_uniform"] == 8 * (5 + 13)
            assert r["pages_used"] == 5 + 13
            # Every slot's whole tables are gathered: 2 x (2 x 16 + 6 x 4)
            # pages of 4 rows.
            assert r["kv_rows_read"] == 2 * (2 * 16 + 6 * 4) * 4
            assert 0 < r["kv_rows_live"] <= r["kv_rows_read"]
        decode = [r for r in recs if r["occupancy"]]
        assert all(set(KV_KEYS) <= set(r) for r in decode)
        # One live sequence of length n: 2 (n + 1) + 6 min(n + 1, 8).
        lone = [r for r in decode if r["occupancy"] == 1
                and not r["first_tokens"]]
        assert lone and all(
            (r["kv_rows_live"] - 6 * 8) % 2 == 0
            and r["kv_rows_live"] >= 2 * 9 + 6 * 8 for r in lone)
        # The engine's greedy tokens are the reference's.
        ref = st.reference(model, eng.params)
        for prompt, out in ((short, out_a), (long, out_b)):
            seq = np.asarray(prompt + out[:-1], np.int32)
            want = ref.logits(seq, range(len(prompt) - 1, len(seq)))
            assert want.argmax(-1).tolist() == out
        assert server.reference_logits(long)["argmax"] == out_b[0]
        # Nothing compiled after warm-up, and every page is back.
        assert {p: trace_count(p) for p in traced} == traced
        st1 = server.stats()
        assert st1["free_pages"] == st1["total_pages"] == 40
        assert st1["window_pages"]["free"] == 8
        assert eng.allocator.free_count == 32
    finally:
        server.engine.shutdown()


def test_a_slot_owns_its_ring_and_the_next_request_reads_none_of_the_last(
        tiny):
    """One slot, so that every request inherits the ring its predecessor
    lapped: nothing is allocated, cleared or refused for a ring, a
    sequence shorter than it holds only its own length of it, and a
    request served from a used ring decodes the reference's tokens."""
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    model, cfg, params, ref = tiny
    eng = InferenceEngine(cfg, params, EngineConfig(
        **dict(ENGINE, batch_slots=1)), seed=1)
    try:
        assert eng._ring_tables.tolist() == [[0, 1, 2, 3]]
        assert eng.stats()["window_pages"] == {
            "ring_entries": 4, "free": 4, "total": 4}
        # 41 + 9 tokens lap the ring three times; the 3 + 5 and 6 + 14 that
        # follow are shorter than it and just past it.
        for n, new, held in ((41, 9, 4), (3, 5, 2), (6, 14, 4)):
            prompt = _tokens(model, (n,), seed=20 + n).tolist()
            stream = eng.submit(prompt, max_new_tokens=new)
            first = next(iter(stream))
            assert eng._ring_pages_held() == held
            assert eng.stats()["window_pages"]["free"] == 4 - held
            out = [first, *stream]
            seq = np.asarray(prompt + out[:-1], np.int32)
            want = ref.logits(seq, range(n - 1, len(seq)))
            assert want.argmax(-1).tolist() == out, n
        st1 = eng.stats()
        assert st1["free_pages"] == st1["total_pages"] == 16 + 4
    finally:
        eng.shutdown()


def test_a_dense_model_keeps_one_kind_and_its_record():
    from ray_tpu.models.paged import KV_KEYS
    from ray_tpu.serve.engine import LLMServer
    from ray_tpu.util import steprec

    server = LLMServer(model="tiny", engine=dict(
        batch_slots=2, page_size=8, max_prompt_len=32,
        max_new_tokens_cap=16, prefill_chunk=16), seed=3)
    try:
        eng = server.engine
        assert eng.ring == 0 and set(eng.pools) == {"k", "v"}
        assert eng.config.prefill_buckets() == [8, 16]
        stats = server.stats()
        assert stats["window_pages"] is None
        assert stats["prefix_cache_off"] is None
        assert stats["prefix_cache"] is not None
        steprec.drain_buffered()
        # 29 tokens: two chunks through the suffix program, dense too.
        prompt = list(range(3, 32))
        out = list(server(prompt, 3))
        assert server.reference_logits(prompt)["argmax"] == out[0]
        recs = _drain(eng, 1)
        entry = [e for r in recs for e in r["first_tokens"]][0]
        assert (entry["chunks"], entry["bucket"]) == (2, 32)
        for r in recs:
            assert not set(KV_KEYS) & set(r) and "pages_window" not in r
    finally:
        server.engine.shutdown()


# ----------------------------------------------------- the benchmark's files


def _ctx(steps, **over):
    return {"kind": "serve_closed", "seconds": 51.0, "steps": steps, **over}


def test_the_three_readers_read_the_records_and_nothing_from_a_parent():
    from benchmarks.layer_metrics import (kv_gather_live_share_swa,
                                          kv_pages_held_share_swa,
                                          prefill_chunk_ms_swa)

    phases = {k: 0.0 for k in ("between_s", "idle_s", "upload_s",
                               "dispatch_s", "readback_s", "emit_s")}
    base = dict(phases, stall_s=0.0, admitted=0, occupancy=16, wall_s=0.02)
    chunked = {"prefill_s": 0.6, "chunks": 4, "prompt": 7000}
    steps = [
        dict(base, first_tokens=[], kv_rows_read=1000, kv_rows_live=300,
             pages_window=60, pages_global=40, pages_uniform=160),
        dict(base, first_tokens=[], kv_rows_read=1000, kv_rows_live=500,
             pages_window=30, pages_global=30, pages_uniform=120),
        # A step with an admission: not a pure decode step; its entries
        # count for the chunk metric (one chunked, one not).
        dict(base, stall_s=0.7, admitted=2, kv_rows_read=1000,
             kv_rows_live=999, pages_window=0, pages_global=0,
             pages_uniform=0,
             first_tokens=[chunked, {"prefill_s": 0.05, "chunks": 1,
                                     "prompt": 300}]),
    ]
    ctx = _ctx(steps)
    assert kv_gather_live_share_swa.read(ctx) == pytest.approx(40.0)
    assert kv_pages_held_share_swa.read(ctx) \
        == pytest.approx(100.0 * (100 / 160 + 60 / 120) / 2)
    assert prefill_chunk_ms_swa.read(ctx) == pytest.approx(150.0)
    # The parent's records have none of the keys; a train run no records.
    old = [{k: v for k, v in r.items()
            if not k.startswith(("kv_", "pages_window", "pages_global",
                                 "pages_uniform"))} for r in steps]
    for r in old:
        r["first_tokens"] = [{k: v for k, v in e.items() if k != "chunks"}
                             for e in r["first_tokens"]]
    for reader in (kv_gather_live_share_swa, kv_pages_held_share_swa,
                   prefill_chunk_ms_swa):
        assert reader.read(_ctx(old)) is None
        assert reader.read(_ctx([])) is None
        assert reader.read({"kind": "train", "steps": 3}) is None


def test_the_new_cell_is_in_the_benchmark_as_the_issue_names_it():
    doc = spec.load_benchmark(ROOT)
    spec.validate(doc)
    # By name, not by place: the next cell goes behind this one.
    cell, = [w for w in doc["workloads"] if w["name"] == CELL]
    assert cell == {
        "name": CELL, "config": "smallthinker-21b-a3b-L8",
        "traffic": "serve-long-mixed", "chips": 1, "why": cell["why"]}
    config, = [c for c in doc["configs"] if c["name"] == cell["config"]]
    assert config["reduced"] == [
        "num_hidden_layers", "rope_layout", "sliding_window_layout"]
    assert config["source"].startswith(
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct")
    new = {m["name"]: m for m in doc["per_layer"] if m["name"] in (
        "kv_gather_live_share.swa", "kv_pages_held_share.swa",
        "prefill_chunk_ms.swa")}
    assert len(new) == 3
    assert all(m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
               for m in new.values())
    joined = {m["name"] for m in doc["per_layer"] + doc["end_to_end"]
              if CELL in m.get("workloads", ())}
    assert joined == set(new) | {
        "serve_tok_s", "prefill_stall_share.sat", "batch_occupancy.sat",
        "decode_step_ms.sat", "decode_host_ms.sat",
        "decode_device_wait_ms.sat", "loop_accounted_share.sat",
        "device_idle_share.serve", "experts_hit_share.moe",
        "moe_decode_roofline.moe"}
    tr = spec.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", "serve-long-mixed.json"))
    assert tr["engine"] == {
        "batch_slots": 16, "page_size": 128, "max_prompt_len": 14336,
        "max_new_tokens_cap": 1024, "prefill_chunk": 2048,
        "prefix_cache": False, "max_queue": 32, "ttft_window": 4096}
    assert (tr["clients"], tr["pool"], tr["schedule_seed"]) == (32, 64, 0)
    assert tr["check"]["prompt_lens"] == [300, 3000, 7000, 13000]
    from benchmarks.traffic import quantile_lengths

    prompts = quantile_lengths(tr["prompt_len"], 64)
    assert (sum(p < 512 for p in prompts), sum(p > 4096 for p in prompts),
            sum(p > 8192 for p in prompts), prompts.count(14336)) \
        == (7, 17, 7, 2)


def test_the_cell_rehearses_and_prints_its_three_metrics():
    rc, lines, err = run_bench(
        "--workload", CELL, "--seed", str(2 ** 31 + 34), "--seconds", "3",
        "--trace", "1", "--rehearse")
    assert rc == 0, err[-3000:]
    out = lines[-1]
    assert out["correct"] is True, lines
    assert out["attempted"] > 0 and out["failed"] == 0
    got = out["metrics"]
    live = got["kv_gather_live_share.swa"]
    held = got["kv_pages_held_share.swa"]
    assert live["unit"] == "%" and 0 < live["value"] <= 100
    # Window 8 in a ring of 16 beside tables of 64 tokens: well under one
    # pool for all.
    assert held["unit"] == "%" and 25 < held["value"] < 100
    assert got["prefill_chunk_ms.swa"]["unit"] == "ms"
    assert {"experts_hit_share.moe", "decode_step_ms.sat",
            "batch_occupancy.sat", "prefill_stall_share.sat"} <= set(got)
    assert "moe_decode_roofline.moe" not in got  # no peak for a CPU
    samples = next(l for l in lines if l.get("phase") == "samples")
    assert samples["reference_gap_max"] <= 1e-3


def test_a_program_without_chunked_prefill_fails_before_any_process(
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
        "import dataclasses\n\n@dataclasses.dataclass\n"
        "class EngineConfig:\n    batch_slots: int = 8\n\n"
        "def register_model(name, builder):\n    pass\n")
    t0 = time.time()
    rc, lines, err = run_bench(
        "--workload", CELL, "--seed", "1", "--seconds", "2", "--trace", "0",
        "--rehearse", root=str(root), timeout=60)
    assert rc == 1 and not lines and time.time() - t0 < 30
    assert "no EngineConfig.prefill_chunk" in err


def test_the_chip_comparison_rehearses_and_refuses_each_fault():
    """``benchmarks/reference/smallthinker_compare.py`` at the tiny
    configuration: the decode rows' experts are the reference's own (in
    float32 nothing rounds a choice the other way), logits through pages
    and rings within the float32 tolerance at all four lengths (one
    bucket, chunked, the ring lapped once and twice), and each of its four
    faults read as incorrect."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "benchmarks/reference/smallthinker_compare.py",
         "--rehearse", "--seed", str(2 ** 31 + 3), "--faults"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    assert out["ok"] is True and out["logit_tol"] == LOGIT_TOL
    assert out["prompt_lens"] == [5, 14, 27, 45]
    assert out["swap_margin"] == 0.0
    base, *faults = out["results"]
    assert base["correct"] and base["rows"] == 4 * (1 + 4)
    assert base["rows_judged"] == base["argmax_agree"] == base["rows"]
    assert base["tie_swaps"] == base["routing_violations"] == 0
    assert set(base["by_prompt"]) == {"5", "14", "27", "45"}
    assert [f["fault"] for f in faults] == [
        "no-window", "rotary-on-global", "router-after-attention",
        "float8-experts"]
    assert not any(f["correct"] for f in faults)
    # A router fed from after attention takes other experts: the routing
    # half of the comparison says so by itself.
    assert faults[2]["routing_violations"] > 16
    assert base["cell_check_passes"] and out["cell_logit_tol"] == 1e-3
