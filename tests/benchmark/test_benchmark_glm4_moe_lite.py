"""The glm4_moe_lite family at a tiny size on the CPU (one dense layer before
two routed ones, 8 experts of which 2 a token beside a shared one, heads
whose q/k and v widths differ, pages of 4, chunks of 8, float32): the
system's decoder (``ray_tpu/models/moe.py`` over ``block.py``, and
``models/paged.py`` through the latent pool, absorbed) against the family's
plain reference (expanded) on seeded weights, logits and not tokens; what
the comparison has to catch; the routing's particulars; the family's counts;
the engine with a latent pool (prefix hits over shared latent pages, chunked
prefill, its records); and the new cell's rehearsal.

Tolerance.  System and reference both compute in float32 here, in different
orders and FORMS (absorbed over pages against expanded over the whole
sequence, a grouped product over sorted pairs against a masked loop over the
experts), so they differ by float32 rounding through three layers: the
largest logit difference seen is 3e-6 (logits are of order 1).  ``LOGIT_TOL``
leaves that a factor of 30 and is thousands of times under what any
structural fault below moves a logit (0.29 to 3.5): on the chip the
configuration IS bfloat16 and the tolerance written in
``benchmarks/reference/glm4_moe_lite_compare.py`` takes this one's place."""

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
from benchmarks.families import glm4_moe_lite as glm
from benchmarks.reference.glm4_moe_lite_compare import FAULTS, faulted

LOGIT_TOL = 1e-4
CONFIG = "glm-4.7-flash-L6"
CELL = CONFIG + ".serve-agent-shared-context"
ENGINE = dict(batch_slots=3, page_size=4, max_prompt_len=48,
              max_new_tokens_cap=16, prefill_chunk=8, prefix_cache=True)


def _model(name="glm4-moe-lite-tiny", **over):
    return {**spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", name + ".json")), **over}


def _weights(cfg, seed=0):
    """Seeded weights whose norm weights are not all ones, so that a norm
    left out (or put in the wrong place) shows."""
    params = glm.init(cfg, jax.random.PRNGKey(seed))
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
    cfg = glm.program_config(model, max_seq=64, remat=False)
    params = _weights(cfg)
    return model, cfg, params, glm.reference(model, params)


def _system_logits(cfg, params, tokens):
    """The system's full forward (the EXPANDED form), on fresh traces (a
    fault swaps a module's function)."""
    from ray_tpu.models import moe

    return np.asarray(jax.jit(
        lambda p, t: moe.moe_apply(cfg, p, t)[0])(
            params, jnp.asarray(tokens)[None])[0])


# ------------------------------------------------------------- full forward


def test_the_configuration_object_carries_what_the_equations_need(tiny):
    from ray_tpu.models import LlamaConfig, MoEConfig, block, paged

    _, cfg, params, _ = tiny
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (24, 16, 12, 8, 20)
    assert cfg.head_dim == 12 + 8 and block.rotary_dim(cfg) == 8
    assert block.is_latent(cfg)
    assert [block.is_routed(cfg, i) for i in range(3)] == [False, True, True]
    assert block.is_routed(cfg) and cfg.ffn_layout == (0, 1, 1)
    assert (cfg.router_score, cfg.routed_scaling_factor,
            cfg.n_shared_experts, cfg.dense_d_ff) == ("sigmoid", 1.8, 1, 96)
    attn = params["layers"][1]["attn"]
    assert {k: v.shape for k, v in attn.items()} == {
        "wq_a": (64, 24), "q_norm": (24,), "wq_b": (24, 4 * 20),
        "wkv_a": (64, 16 + 8), "kv_norm": (16,),
        "wkv_b": (16, 4 * (12 + 20)), "wo": (4 * 20, 64)}
    # The dense layer 0 has no router, no expert and no shared expert.
    assert set(params["layers"][0]) == {"attn_norm", "attn", "mlp_norm",
                                        "mlp"}
    assert params["layers"][0]["mlp"]["w1"].shape == (64, 96)
    moe = params["layers"][2]["moe"]
    assert set(moe) == {"router", "router_bias", "shared", "w1", "w2", "w3"}
    assert moe["router_bias"].shape == (8,) \
        and float(jnp.abs(moe["router_bias"]).max()) > 0
    assert moe["shared"]["w1"].shape == (64, 32)
    assert paged.kv_layers(cfg) == ([0, 1, 2], [])
    assert paged.counter_keys(cfg) == paged.ROUTING_KEYS + paged.KV_KEYS
    assert sum(x.size for x in jax.tree.leaves(params)) \
        == cfg.param_count() == glm.param_count(_model())
    # A configuration without the fields is what it was, whatever it is.
    for plain in (LlamaConfig.tiny(), MoEConfig.tiny()):
        assert not block.is_latent(plain)
        assert block.rotary_dim(plain) == plain.head_dim
        assert block.is_routed(plain, 0) == block.is_routed(plain) \
            == isinstance(plain, MoEConfig)
        assert set(paged.counter_keys(plain)) <= set(paged.ROUTING_KEYS)
    with pytest.raises(ValueError, match="five"):
        dataclasses.replace(cfg, v_head_dim=0)
    with pytest.raises(ValueError, match="dense_d_ff"):
        dataclasses.replace(cfg, dense_d_ff=0)
    with pytest.raises(ValueError, match="router_score"):
        dataclasses.replace(cfg, router_score="tanh")


def test_moe_apply_and_loss_match_the_reference(tiny):
    model, cfg, params, ref = tiny
    toks = _tokens(model, (40,))
    want = ref.logits(toks, range(40))
    assert np.abs(_system_logits(cfg, params, toks) - want).max() < LOGIT_TOL
    batch = _tokens(model, (2, 24), seed=7)
    targets = np.roll(batch, -1, axis=1)
    rcfg = dataclasses.replace(cfg, remat=True)
    loss, grads = jax.value_and_grad(
        lambda p: glm.loss(rcfg, p, jnp.asarray(batch),
                           jnp.asarray(targets)))(params)
    norm = float(jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                              for g in jax.tree.leaves(grads))))
    ref_loss, ref_norm = ref.loss_and_grad_norm(batch, targets)
    assert abs(float(loss) - ref_loss) / ref_loss < 1e-5
    assert abs(norm - ref_norm) / ref_norm < 1e-4


# ------------------------------------------------------- the paged programs


def _programs():
    from ray_tpu.models import paged

    return tuple(jax.jit(lambda *a, f=f: f(*a), static_argnums=0)
                 for f in (paged.prefill_logits, paged.prefill_prefix_logits,
                           paged.decode_logits))


def _paged(cfg, params, seq, prompt, *, engine=ENGINE, cached=0, pools=None,
           first_page=3):
    """The engine's way through the programs, by hand: the prompt (from
    ``cached`` on, whose pages ``pools`` already hold) in chunks of the
    largest bucket (a cold first chunk through ``prefill_logits``, the
    rest through ``prefill_prefix_logits``), then a teacher-forced decode
    step for every further token of ``seq`` in slot 1.  The first
    ``cached // page`` entries of the table are pages 3.. (what an earlier
    call wrote there), the others start at ``first_page``.  Returns
    (logits [1 + new, V], the pools' final state)."""
    from ray_tpu.models import paged
    from ray_tpu.serve.engine import EngineConfig

    prefill, suffix, decode = _programs()
    ec = EngineConfig(**engine)
    ps, maxp, b = ec.page_size, ec.pages_per_seq, ec.batch_slots
    buckets = ec.prefill_buckets()
    chunk = buckets[-1]
    if pools is None:
        pools = paged.init_paged_pools(cfg, ec.pool_pages, ps)
    adapters = paged.init_adapter_pool(cfg, ec.max_adapters, ec.lora_rank)
    zero = jnp.asarray(ec.max_adapters, jnp.int32)
    need, shared = -(-len(seq) // ps), cached // ps
    table = np.full((maxp,), ec.pool_pages, np.int32)
    table[:shared] = 3 + np.arange(shared)
    table[shared:need] = first_page + np.arange(need - shared)
    for start in range(cached, prompt, chunk):
        end = min(start + chunk, prompt)
        bucket = next(x for x in buckets if x >= end - start)
        pad = np.zeros((1, bucket), np.int32)
        pad[0, :end - start] = seq[start:end]
        if start:
            logits, pools, _ = suffix(
                cfg, params, pools, adapters, jnp.asarray(pad),
                jnp.asarray(start), jnp.asarray(end), jnp.asarray(table),
                zero)
        else:
            logits, pools, _ = prefill(
                cfg, params, pools, adapters, jnp.asarray(pad),
                jnp.asarray(end), jnp.asarray(table), zero)
    rows = [np.asarray(logits[0])]
    tables = np.full((b, maxp), ec.pool_pages, np.int32)
    tables[1] = table
    active = np.arange(b) == 1
    for i in range(prompt, len(seq)):
        logits, pools, _ = decode(
            cfg, params, pools, adapters,
            jnp.asarray(np.where(active, seq[i], 0), jnp.int32),
            jnp.asarray(tables), jnp.asarray(np.where(active, i, 0),
                                             jnp.int32),
            jnp.asarray(active),
            jnp.asarray([ec.max_adapters] * b, jnp.int32))
        rows.append(np.asarray(logits[1]))
    return np.stack(rows), pools


@pytest.mark.parametrize("prompt,new", [(6, 14), (8, 4), (21, 6), (45, 3)],
                         ids=["one-bucket", "a-whole-chunk", "chunked",
                              "six-chunks"])
def test_prefill_and_absorbed_decode_match_the_expanded_reference(
        tiny, prompt, new):
    """Cold (one bucket, expanded within the chunk) and chunked (every
    further chunk absorbed over the latent rows cached before it), then
    absorbed decode steps: the reference's expanded full forward, logits
    and not tokens."""
    model, cfg, params, ref = tiny
    seq = _tokens(model, (prompt + new,), seed=5)
    got, _ = _paged(cfg, params, seq, prompt)
    want = ref.logits(seq, range(prompt - 1, prompt + new))
    assert np.abs(got - want).max() < LOGIT_TOL
    # The system's own expanded form, its full forward, agrees with both.
    full = _system_logits(cfg, params, seq)[prompt - 1:]
    assert np.abs(got - full).max() < LOGIT_TOL


@pytest.mark.parametrize("cached", [16, 20], ids=["whole-pages",
                                                  "mid-chunk"])
def test_a_prefix_hit_reads_another_sequences_latent_pages(tiny, cached):
    """A second sequence whose first ``cached`` tokens are the first's:
    its table starts with the first's pages, its prefill starts there
    (``prefix_len``), over latent rows it did not write, and its logits are
    the reference's of ITS tokens."""
    model, cfg, params, ref = tiny
    first = _tokens(model, (30,), seed=5)
    _, pools = _paged(cfg, params, first, 30)
    second = np.concatenate([first[:cached],
                             _tokens(model, (17,), seed=6)])
    got, _ = _paged(cfg, params, second, 33, cached=cached, pools=pools,
                    first_page=20)
    want = ref.logits(second, range(32, len(second)))
    assert np.abs(got - want).max() < LOGIT_TOL


def test_a_chunked_prefill_equals_the_one_shot_program(tiny):
    """The same 29-token prompt through four chunks of 8 (absorbed over
    cached rows) and through one 32-token bucket (expanded within the
    chunk): the same logits, and the same latent rows in the pages."""
    from ray_tpu.models import paged

    model, cfg, params, _ = tiny
    seq = _tokens(model, (29 + 3,), seed=6)
    chunked, pools_c = _paged(cfg, params, seq, 29)
    whole, pools_w = _paged(cfg, params, seq, 29,
                            engine=dict(ENGINE, prefill_chunk=0))
    assert np.abs(chunked - whole).max() < LOGIT_TOL
    assert set(pools_c) == {"kv"}
    np.testing.assert_allclose(np.asarray(pools_c["kv"][:, 3:11]),
                               np.asarray(pools_w["kv"][:, 3:11]), atol=1e-5)
    # A row is [c ; rope(k_r)], then zeros up to whole 128-lane tiles.
    rows = np.asarray(pools_c["kv"][:, 3:10])
    assert rows.shape[-1] == paged.latent_row_width(cfg) == 128
    assert np.abs(rows[..., :16 + 8]).min() > 0
    assert (rows[..., 16 + 8:] == 0).all()


def test_the_latent_pool_is_one_array_of_tile_padded_rows():
    """L x (pages + the scratch page) x page x row bytes: 576 numbers a
    token and layer at the published widths (1152 bytes, what the family
    counts), in rows of 640, five whole 128-lane tiles (the bytes the pool
    holds on the chip, ``paged.latent_row_width`` says why)."""
    from ray_tpu.models import paged

    model = _model(CONFIG)
    cfg = glm.program_config(model, max_seq=19200, remat=False)
    assert paged.latent_row_width(cfg) == 640 >= 512 + 64
    pools = jax.eval_shape(lambda: paged.init_paged_pools(cfg, 4800, 128))
    assert set(pools) == {"kv"}
    pool = pools["kv"]
    assert pool.shape == (6, 4801, 128, 640) and pool.dtype == jnp.bfloat16
    assert pool.size * 2 == 6 * 4801 * 128 * 640 * 2
    assert glm.latent_row_bytes(model) == 576 * 2
    assert 6 * 4800 * 128 * glm.latent_row_bytes(model) \
        == pytest.approx(4.25e9, rel=2e-3)


def test_kv_rows_live_equals_read_when_every_table_is_full(tiny):
    """``kv_rows_live <= kv_rows_read``, with equality when every slot's
    last position is its table's last row."""
    from ray_tpu.models import paged

    _, cfg, params, _ = tiny
    b, ps, maxp = 2, 4, 3
    adapters = paged.init_adapter_pool(cfg, 1, 2)
    tables = jnp.arange(b * maxp, dtype=jnp.int32).reshape(b, maxp)

    def step(lens, active):  # the pools are donated: fresh ones a step
        pools = paged.init_paged_pools(cfg, b * maxp, ps)
        out = paged.paged_decode_step(
            cfg, params, pools, adapters, jnp.zeros((b,), jnp.int32),
            tables, jnp.asarray(lens, jnp.int32), jnp.asarray(active),
            jnp.zeros((b,)), jnp.full((b,), 1, jnp.int32),
            jax.random.PRNGKey(0))[0]
        return dict(zip(paged.counter_keys(cfg), np.asarray(out)[b:]))

    full = step([ps * maxp - 1] * b, [True] * b)
    assert full["kv_rows_live"] == full["kv_rows_read"] == 3 * b * ps * maxp
    part = step([5, 0], [True, False])
    assert part["kv_rows_read"] == full["kv_rows_read"]
    assert part["kv_rows_live"] == 3 * (5 + 1)
    # The routed layers' pairs: 2 layers x top-2 x the live rows; the
    # shared expert and the dense layer are in no counter.
    assert full["expert_pairs"] == 2 * 2 * b and part["expert_pairs"] == 4
    assert part["experts_hit"] <= 4 and part["expert_load_max"] == 1


# ------------------------------------------------------------------ routing


def test_the_selection_bias_changes_the_choice_and_no_weight(tiny):
    from ray_tpu.models import moe

    _, cfg, params, _ = tiny
    m = params["layers"][1]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(3), (64, cfg.d_model))
    scores = np.asarray(jax.nn.sigmoid(moe.router_logits(m, x)))
    _, top_p, top_e = moe._route(cfg, m, x)
    unbiased = dict(m, router_bias=jnp.zeros((8,)))
    _, plain_p, plain_e = moe._route(cfg, unbiased, x)
    # The drawn bias already changes some rows' experts (else "in the
    # choice only" would be untested) ...
    changed = np.asarray((jnp.sort(top_e) != jnp.sort(plain_e)).any(-1))
    assert 0 < changed.sum() < 64
    # ... and a large one forces the choice: experts 6 and 1, in that order
    forced = dict(m, router_bias=jnp.zeros((8,)).at[6].set(9.).at[1].set(5.))
    _, p, e = moe._route(cfg, forced, x)
    assert np.asarray(e).tolist() == [[6, 1]] * 64
    # ... while every weight is the bare scores of whatever was chosen,
    # renormalised and scaled by 1.8: the bias is in none of them.
    for probs, experts in ((top_p, top_e), (p, e), (plain_p, plain_e)):
        s = np.take_along_axis(scores, np.asarray(experts), -1)
        np.testing.assert_allclose(
            np.asarray(probs), 1.8 * s / s.sum(-1, keepdims=True), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(probs).sum(-1), 1.8, rtol=1e-6)


def test_the_shared_expert_takes_every_valid_row_and_is_in_no_counter(tiny):
    from ray_tpu.models import block

    _, cfg, params, _ = tiny
    layer = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(4), (10, cfg.d_model))
    valid = jnp.arange(10) < 7
    with_shared, _, counts = block.ffn(cfg, layer, x, valid, routed=True)
    without, _, counts0 = block.ffn(
        dataclasses.replace(cfg, n_shared_experts=0), layer, x, valid,
        routed=True)
    assert np.array_equal(np.asarray(counts), np.asarray(counts0))
    assert int(counts.sum()) == 7 * cfg.top_k  # the routed pairs only
    from ray_tpu.ops.norms import rms_norm

    h = rms_norm(x, layer["moe_norm"], cfg.norm_eps)
    s = layer["moe"]["shared"]
    want = (jax.nn.silu(h @ s["w1"]) * (h @ s["w3"])) @ s["w2"]
    diff = np.asarray(with_shared - without)
    np.testing.assert_allclose(diff[:7], np.asarray(want)[:7], atol=1e-5)
    assert np.abs(diff[7:]).max() == 0  # a row that holds no token
    assert float(jnp.abs(want[:7]).max()) > 0.01
    # The dense layer: no counts, no aux, and ``routed`` decides, not the
    # configuration object.
    out, aux, none = block.ffn(cfg, params["layers"][0], x, valid,
                               routed=False)
    assert aux is None and none is None and out.shape == x.shape


def test_the_reference_computes_with_the_experts_it_is_given(tiny):
    model, _, _, ref = tiny
    seq = _tokens(model, (20,), seed=9)
    plain = ref.logits(seq, range(20))
    margins, reach = ref.routing(seq)
    assert margins.shape == reach.shape == (3, 20)
    assert np.isinf(margins[0]).all() and (margins[1:] > 0).all()
    assert (reach == 0).all()
    own = ref.top_experts(seq)  # [L, S, k]; -1 in the dense layer
    assert (own[0] == -1).all() and (own[1:] >= 0).all()
    given = np.full((3, 20, 2), -1, np.int32)
    given[:, 12:] = own[:, 12:]
    assert np.array_equal(ref.logits(seq, range(20), given), plain)
    # Token 15 in layer 2: its second expert swapped for its third reaches
    # the margin (in score + bias) and moves that token's logits.
    a, b = own[2, 15]
    reaches = {}
    for keep in (a, b):
        for other in set(range(8)) - {a, b}:
            given[2, 15] = [keep, other]
            reaches[int(keep), int(other)] = float(
                ref.routing(seq, given)[1][2, 15])
    swap = min(reaches, key=reaches.get)
    assert reaches[swap] == pytest.approx(float(margins[2, 15]), rel=1e-4)
    given[2, 15] = swap
    moved = ref.logits(seq, range(20), given)
    assert np.abs(moved[:15] - plain[:15]).max() == 0  # causal
    assert np.abs(moved[15] - plain[15]).max() > 50 * LOGIT_TOL


@pytest.mark.parametrize("fault", [*FAULTS, "bfloat16-latent"])
def test_the_comparison_catches(tiny, fault):
    """Each of these is a different model, or the same one in a lower
    precision, and has to read as incorrect through the pages (and, where
    the full forward runs the swapped function, there too): at this
    tolerance here, at bfloat16's on the chip."""
    from ray_tpu.models import paged

    model, cfg, params, ref = tiny
    seq = _tokens(model, (30,), seed=8)
    want = ref.logits(seq, range(30))
    floor = 50 * LOGIT_TOL
    if fault in ("float8-experts", "float8"):
        from benchmarks.reference.glm4_moe_lite_compare import _float8

        params = _float8(jax.tree.map(jnp.copy, params), fault == "float8")
        # The float32 router and the norms stay as they were.
        assert all(np.array_equal(a["moe"][k], b["moe"][k])
                   and np.array_equal(a["attn_norm"], b["attn_norm"])
                   for a, b in zip(params["layers"][1:], tiny[2]["layers"][1:])
                   for k in ("router", "router_bias"))
        assert (fault == "float8") != np.array_equal(params["embed"],
                                                     tiny[2]["embed"])
    if fault == "bfloat16-latent":
        real = paged._latent_row
        paged._latent_row = lambda *a: real(*a).astype(
            jnp.bfloat16).astype(jnp.float32)
        try:
            got, _ = _paged(cfg, params, seq, 25)
        finally:
            paged._latent_row = real
    else:
        with faulted(cfg, fault) as bad:
            got, _ = _paged(bad, params, seq, 25)
            if fault in ("unnormalised-latent", "bias-in-weights",
                         "no-scaling", "no-shared-expert",
                         "float8-experts", "float8"):
                assert np.abs(_system_logits(bad, params, seq)
                              - want).max() > floor
    assert np.abs(got - want[24:]).max() > floor
    # ... and the sound program, traced again after the swap, is sound.
    got, _ = _paged(cfg, tiny[2], seq, 25)
    assert np.abs(got - want[24:]).max() < LOGIT_TOL


# ------------------------------------------------------------------ counts


def test_the_familys_counts_are_pinned_at_the_cells_configuration():
    model = _model(CONFIG)
    glm.check_supported(model)
    assert glm.param_count(model) == 3_895_625_536
    assert glm._attention_params(model) == 21_759_232
    assert glm._expert_params(model) == 9_437_184
    # A routed layer 635,311,424, the dense one 84,677,888, the rest.
    assert glm.param_count({**model, "num_hidden_layers": 1}) \
        == 634_390_528 + 84_677_888
    assert glm.param_count(model) - glm.param_count(
        {**model, "num_hidden_layers": 5}) == 635_311_424
    # What a token multiplies with: four experts and the shared one.
    assert glm.matmul_params(model) == 747_241_472
    need = glm.routed_ffn_ops_bytes(model, pairs=640, experts_hit=278)
    assert need["ops"] == 640 * 3 * 2 * 2048 * 1536
    assert need["bytes"] == (278 * 9_437_184 + 640 * 2 * 2048) * 2
    # The floor: 558,532,416 parameters outside the embedding and the
    # routed experts (routers and biases in float32), then experts and
    # latent rows as counted.
    base = glm.decode_floor_bytes(model, 0, 0)
    assert base == 2 * (558_532_416 - 5 * (2048 * 64 + 64)) \
        + 4 * 5 * (2048 * 64 + 64) == 1_118_376_192
    assert glm.decode_floor_bytes(model, 278, 100_000) \
        == base + 278 * 9_437_184 * 2 + 100_000 * 1152
    with pytest.raises(NotImplementedError, match="no cell trains"):
        glm.train_step_kernel_ops_bytes(model, 1, 1, 1)
    # The program's configuration object agrees, and the tiny one with
    # its tree (``test_the_configuration_object...``).
    assert glm.program_config(model, max_seq=128).param_count() \
        == 3_895_625_536


@pytest.mark.parametrize("key,value", [
    ("n_group", 8), ("topk_group", 4), ("rope_scaling", {"type": "yarn"}),
    ("tie_word_embeddings", True), ("attention_bias", True),
    ("topk_method", "greedy"), ("num_key_value_heads", 2),
    ("num_experts", 32), ("first_k_dense_replace", 9)])
def test_the_family_refuses_what_the_program_does_not_compute(key, value):
    with pytest.raises(ValueError):
        glm.check_supported(_model(**{key: value}))


def test_the_catalogs_numbers_are_in_the_file_under_their_keys():
    """Every key of the catalog row's ``config`` is in the file with the
    row's value, ``num_hidden_layers`` alone reduced and listed."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row, = [r for r in map(json.loads, f) if r["name"] == "GLM-4.7-Flash"]
    model = _model(CONFIG)
    assert model["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if model.get(k) != v]
    assert differs == model["reduced"] == ["num_hidden_layers"]
    assert model["published"] == {"num_hidden_layers": 47}
    assert model["num_hidden_layers"] == 6
    assert {"rope", "e_score_correction_bias", "num_nextn_predict_layers",
            "torch_dtype", "num_experts"} <= set(model["assumed"])


# ------------------------------------------------------------------- engine


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


def test_a_latent_model_is_served_with_prefix_hits_and_chunks(tiny):
    from ray_tpu.models.paged import KV_KEYS, trace_count
    from ray_tpu.serve.engine import LLMServer, register_model
    from ray_tpu.util import steprec

    model, cfg, params, _ = tiny
    register_model("glm4-moe-lite-tiny-test", lambda: cfg)
    server = LLMServer(model="glm4-moe-lite-tiny-test", engine=ENGINE,
                       seed=3, warmup=True)
    try:
        eng = server.engine
        st0 = server.stats()
        # One more kind of cache, and the prefix cache stays ON: a page of
        # latent rows is valid for every layer.
        assert st0["prefix_cache"] is not None
        assert st0["prefix_cache_off"] is None and eng.ring == 0
        assert set(eng.pools) == {"kv"}
        assert eng.pools["kv"].shape == (3, 3 * 16 + 1, 4, 128)
        assert st0["total_pages"] == 48 == st0["free_pages"]
        programs = {(r["program"], r["bucket"]) for r in st0["setup"][
            "programs"]}
        assert {("prefill", 4), ("prefill", 8), ("decode", None),
                ("prefill_prefix", 4), ("prefill_prefix", 8),
                ("copy_page", None)} <= programs
        with pytest.raises(ValueError, match="no LoRA adapter"):
            server.load_adapter("a", 1)
        traced = {p: trace_count(p)
                  for p in ("decode", "prefill", "prefill_prefix",
                            "page_copy")}
        steprec.drain_buffered()
        # A 22-token prompt, cold, in three chunks; while it decodes, a
        # second that shares its first 16 tokens (4 pages) and a third
        # that shares 18 (4 pages and a copied one, diverging mid-page).
        first = _tokens(model, (22,), seed=11).tolist()
        second = first[:16] + _tokens(model, (7,), seed=12).tolist()
        third = first[:18] + _tokens(model, (3,), seed=13).tolist()
        a = eng.submit(first, max_new_tokens=16)
        it = iter(a)
        out_a = [next(it)]
        b = eng.submit(second, max_new_tokens=5)
        c = eng.submit(third, max_new_tokens=4)
        out_b, out_c = list(b), list(c)
        out_a += list(it)
        assert (len(out_a), len(out_b), len(out_c)) == (16, 5, 4)
        recs = _drain(eng, 3)
        entries = {e["prompt"]: e for r in recs for e in r["first_tokens"]}
        assert [(e["prompt"], e["cached"], e["chunks"])
                for e in map(entries.get, (22, 23, 21))] \
            == [(22, 0, 3), (23, 16, 1), (21, 18, 1)]
        # A prefill's routing counters: two routed layers x top-2 x the
        # rows it computed (the cached ones were not computed again).
        assert [entries[n]["expert_pairs"] for n in (22, 23, 21)] \
            == [22 * 4, 7 * 4, 3 * 4]
        decode = [r for r in recs if r["occupancy"] and "kv_rows_live" in r]
        assert decode and all(set(KV_KEYS) <= set(r) for r in decode)
        for r in decode:
            assert r["kv_rows_read"] == 3 * 3 * 16 * 4  # every whole table
            assert 0 < r["kv_rows_distinct"] <= r["kv_rows_live"] \
                <= r["kv_rows_read"]
        # Alone, nothing is shared: distinct is live.  With two and three
        # live, the four shared pages are read once a slot and counted
        # once: 3 layers x 4 rows x 4 pages a further holder.  (A record's
        # ``occupancy`` is taken after the step's evictions, the rows when
        # it was dispatched: the values seen are told, not which step.)
        dups = [r["kv_rows_live"] - r["kv_rows_distinct"] for r in decode]
        assert set(dups) == {0, 48, 96}
        assert dups[0] == dups[-1] == 0  # the first alone again at the end
        assert all(d == 0 for r, d in zip(decode, dups)
                   if r["kv_rows_live"] <= 3 * (22 + 16))
        # The engine's greedy tokens are the reference's.
        ref = glm.reference(model, eng.params)
        for prompt, out in ((first, out_a), (second, out_b),
                            (third, out_c)):
            seq = np.asarray(prompt + out[:-1], np.int32)
            want = ref.logits(seq, range(len(prompt) - 1, len(seq)))
            assert want.argmax(-1).tolist() == out
        assert server.reference_logits(second)["argmax"] == out_b[0]
        # Nothing compiled after warm-up, and every page comes back.
        assert {p: trace_count(p) for p in traced} == traced
        assert eng._shared_dups == 0 and not eng._page_holders
        server.clear_prefix_cache()
        st1 = server.stats()
        assert st1["free_pages"] == st1["total_pages"] == 48
    finally:
        server.engine.shutdown()


def test_a_failed_step_leaves_no_page_held(tiny):
    """The reset after a failed model call stops every slot decoding at
    once, without an eviction each: the holders ``kv_rows_distinct`` is
    counted from go with them, or every later record would read low."""
    from ray_tpu.serve.engine import LLMServer, register_model
    from ray_tpu.util import steprec

    model, cfg, _, _ = tiny
    register_model("glm4-moe-lite-tiny-test", lambda: cfg)
    server = LLMServer(model="glm4-moe-lite-tiny-test", engine=ENGINE,
                       seed=3)
    try:
        eng = server.engine
        first = _tokens(model, (22,), seed=11).tolist()
        second = first[:16] + _tokens(model, (7,), seed=12).tolist()

        def pair():
            a, b = eng.submit(first, max_new_tokens=16), None
            it_a = iter(a)
            next(it_a)
            b = eng.submit(second, max_new_tokens=8)
            it_b = iter(b)
            next(it_b)
            return it_a, it_b

        it_a, it_b = pair()
        assert eng._shared_dups == 4  # the four shared pages, held twice
        real = eng._run_step

        def boom(*a, **kw):
            eng._run_step = real
            raise RuntimeError("the model call failed")

        eng._run_step = boom
        for it in (it_a, it_b):
            with pytest.raises(RuntimeError, match="model call failed"):
                list(it)
        deadline = time.time() + 10.0  # the streams fail before the reset ends
        while eng._page_holders and time.time() < deadline:
            time.sleep(0.01)
        assert eng._shared_dups == 0 and not eng._page_holders
        # The same pair again on the fresh pools: the records count the
        # shared pages once, as on an engine that never failed.
        steprec.drain_buffered()
        it_a, it_b = pair()
        assert (len(list(it_a)), len(list(it_b))) == (15, 7)
        dups = {r["kv_rows_live"] - r["kv_rows_distinct"]
                for r in _drain(eng, 2) if "kv_rows_live" in r}
        assert dups == {0, 48}
        assert eng._shared_dups == 0 and not eng._page_holders
    finally:
        server.engine.shutdown()


def test_the_other_kinds_records_have_no_distinct_rows():
    """A dense model keeps its K and V pools, and its records carry no
    ``kv_rows_*`` (its decode program is the one it always was)."""
    from ray_tpu.serve.engine import LLMServer
    from ray_tpu.util import steprec

    server = LLMServer(model="tiny", engine=dict(
        batch_slots=2, page_size=8, max_prompt_len=32,
        max_new_tokens_cap=16), seed=3)
    try:
        assert set(server.engine.pools) == {"k", "v"}
        steprec.drain_buffered()
        assert len(list(server(list(range(3, 20)), 3))) == 3
        for r in _drain(server.engine, 1):
            assert not [k for k in r if k.startswith("kv_rows")]
        assert server.engine._page_holders == {}
    finally:
        server.engine.shutdown()


# ----------------------------------------------------- the benchmark's files


def _ctx(steps, **over):
    return {"kind": "serve_closed", "seconds": 51.0, "steps": steps,
            "model": _model(CONFIG),
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            **over}


def test_the_two_readers_read_the_records_and_nothing_from_a_parent():
    from benchmarks.layer_metrics import (decode_bytes_floor_share_mla,
                                          suffix_prefill_ms_mla)

    phases = {k: 0.0 for k in ("between_s", "idle_s", "upload_s",
                               "dispatch_s", "readback_s", "emit_s")}
    base = dict(phases, stall_s=0.0, admitted=0, occupancy=32, wall_s=0.018,
                between_s=0.002, ahead=1, first_tokens=[], experts_hit=278,
                expert_pairs=640, kv_rows_live=3_000_000,
                kv_rows_distinct=230_000)
    hit = {"prefill_s": 0.040, "cached": 16384, "prompt": 16900, "chunks": 1}
    steps = [
        dict(base),
        dict(base, wall_s=0.038, experts_hit=300),   # the median's other side
        dict(base, wall_s=0.028),
        dict(base, ahead=0, wall_s=1.0),             # not dispatched ahead
        dict(base, stall_s=0.9, admitted=21, wall_s=0.95, first_tokens=[
            *[dict(hit, prefill_s=0.030 + 0.001 * i) for i in range(20)],
            {"prefill_s": 2.0, "cached": 0, "prompt": 17000, "chunks": 9}]),
    ]
    ctx = _ctx(steps)
    model = ctx["model"]
    floor = glm.decode_floor_bytes(model, 278, 230_000)
    assert floor == 1_118_376_192 + 278 * 18_874_368 + 230_000 * 1152
    assert decode_bytes_floor_share_mla.read(ctx) == pytest.approx(
        100.0 * floor / 819e9 / 0.030)
    assert 25 < decode_bytes_floor_share_mla.read(ctx) < 35
    # The median of the twenty suffix prefills; the cold one is not among
    # them, and nineteen are too few.
    assert suffix_prefill_ms_mla.read(ctx) == pytest.approx(39.5)
    steps[-1]["first_tokens"].pop(0)
    assert suffix_prefill_ms_mla.read(ctx) is None
    # It cannot pass 100%: a step as fast as the floor's bytes allow.
    fast = _ctx([dict(base, wall_s=floor / 819e9, between_s=0.0)])
    assert decode_bytes_floor_share_mla.read(fast) == pytest.approx(100.0)
    # The parent's records have none of the keys; another family no floor;
    # a CPU no peak; a train run no records.
    old = [{k: v for k, v in r.items()
            if k not in ("kv_rows_distinct", "kv_rows_live")} for r in steps]
    for r in old:
        r["first_tokens"] = [{k: v for k, v in e.items() if k != "cached"}
                             for e in r["first_tokens"]]
    for reader in (decode_bytes_floor_share_mla, suffix_prefill_ms_mla):
        assert reader.read(_ctx(old)) is None
        assert reader.read(_ctx([])) is None
        assert reader.read(_ctx(3, kind="train")) is None
    assert decode_bytes_floor_share_mla.read(_ctx(steps, model=_model(
        "olmoe-1b-7b-0125"))) is None
    assert decode_bytes_floor_share_mla.read(_ctx(steps, device={
        "platform": "cpu", "kind": "cpu", "count": 1})) is None


def test_the_new_cell_is_in_the_benchmark_as_the_issue_names_it():
    doc = spec.load_benchmark(ROOT)
    spec.validate(doc)
    # By name, not by place: the next cell goes behind this one.
    cell, = [w for w in doc["workloads"] if w["name"] == CELL]
    assert cell == {
        "name": CELL, "config": CONFIG,
        "traffic": "serve-agent-shared-context", "chips": 1,
        "why": cell["why"]}
    config, = [c for c in doc["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert config["source"] == _model(CONFIG)["source"] \
        == "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json"
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1
    new = {m["name"]: m for m in doc["per_layer"] if m["name"] in (
        "suffix_prefill_ms.mla", "decode_bytes_floor_share.mla")}
    assert len(new) == 2
    assert all(m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
               for m in new.values())
    assert new["suffix_prefill_ms.mla"]["layer"] == "engine admission"
    assert new["decode_bytes_floor_share.mla"]["layer"] \
        == "engine loop (host)"
    joined = {m["name"] for m in doc["per_layer"] + doc["end_to_end"]
              if CELL in m.get("workloads", ())}
    assert joined == set(new) | {
        "serve_tok_s", "prefill_stall_share.sat", "batch_occupancy.sat",
        "decode_step_ms.sat", "decode_host_ms.sat",
        "decode_device_wait_ms.sat", "loop_accounted_share.sat",
        "device_idle_share.serve", "experts_hit_share.moe",
        "moe_decode_roofline.moe", "prefix_cached_token_share.prefix",
        "kv_gather_live_share.swa"}
    tr = spec.load_cell(CELL, ROOT)["traffic"]
    assert tr["engine"]["batch_slots"] in (32, 24)  # the one deviation
    slots = tr["engine"]["batch_slots"]
    assert tr["engine"] == {
        "batch_slots": slots, "page_size": 128, "max_prompt_len": 18432,
        "max_new_tokens_cap": 768, "prefill_chunk": 2048,
        "prefix_cache": True, "max_queue": 2 * slots, "ttft_window": 4096}
    assert (tr["kind"], tr["clients"], tr["pool"], tr["schedule_seed"],
            tr["shared_prefix"], tr["temperature"], tr["ramp_s"]) \
        == ("serve_closed", 2 * slots, 64, 0, 16384, 0.0, 45.0)
    assert tr["max_concurrent_queries"] == 4 * slots
    assert tr["check"]["prompt_lens"] == [300, 3000, 9000, 17000]
    assert tr["check"]["new_tokens"] == 16
    from benchmarks.traffic import quantile_lengths

    own = [p - 16384 for p in quantile_lengths(tr["prompt_len"], 64)]
    outs = quantile_lengths(tr["output_len"], 64)
    assert (min(own), max(own), own.count(32), sum(x > 1024 for x in own)) \
        == (32, 1783, 11, 10)
    assert 550 < sum(own) / 64 < 575 and 290 < sum(outs) / 64 < 305
    assert (max(outs), outs.count(768)) == (768, 2)


def test_the_cell_rehearses_and_prints_its_metrics():
    rc, lines, err = run_bench(
        "--workload", CELL, "--seed", str(2 ** 31 + 36), "--seconds", "8",
        "--trace", "1", "--rehearse")
    assert rc == 0, err[-3000:]
    out = lines[-1]
    assert out["correct"] is True, lines
    assert out["attempted"] > 0 and out["failed"] == 0
    got = out["metrics"]
    live = got["kv_gather_live_share.swa"]
    assert live["unit"] == "%" and 0 < live["value"] <= 100
    # Every request starts with the same 16 tokens, four whole pages.
    cached = got["prefix_cached_token_share.prefix"]
    assert cached["unit"] == "%" and 30 < cached["value"] < 90
    assert got["suffix_prefill_ms.mla"]["unit"] == "ms"
    assert {"experts_hit_share.moe", "decode_step_ms.sat",
            "batch_occupancy.sat", "prefill_stall_share.sat"} <= set(got)
    # No peak for a CPU: no share of one.
    assert "moe_decode_roofline.moe" not in got
    assert "decode_bytes_floor_share.mla" not in got
    samples = next(l for l in lines if l.get("phase") == "samples")
    assert samples["reference_gap_max"] <= 1e-3


def _copy_of_the_benchmark(root):
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)


def test_the_rehearsal_finds_a_broken_program_incorrect(tmp_path):
    """The same run of a program that leaves ``routed_scaling_factor`` out
    (the family of a COPY hands the engine such a configuration; the
    reference reads the file): the tokens come, and the check says they
    are not the model's."""
    root = tmp_path / "broken"
    root.mkdir()
    _copy_of_the_benchmark(root)
    os.symlink(os.path.join(ROOT, "ray_tpu"), root / "ray_tpu")
    with open(root / "benchmarks" / "families" / "glm4_moe_lite.py",
              "a") as f:
        f.write("\n\n_sound = program_config\n\n\n"
                "def program_config(model, **kw):\n"
                "    import dataclasses\n"
                "    return dataclasses.replace(_sound(model, **kw),\n"
                "                               routed_scaling_factor=1.0)\n")
    rc, lines, err = run_bench(
        "--workload", CELL, "--seed", "7", "--seconds", "2", "--trace", "0",
        "--rehearse", root=str(root))
    out = lines[-1]
    assert out["correct"] is False and out["failed"] == 0, (rc, err[-2000:])
    samples = next(l for l in lines if l.get("phase") == "samples")
    assert samples["reference_gap_max"] > 1e-3


def test_a_program_without_a_latent_cache_fails_before_any_process(
        tmp_path):
    """The parent of this PR under this PR's benchmark files: the family
    says why where the harness finds it, exit 1 in about a second, no
    replica started and restarted until the deployment times out."""
    root = tmp_path / "old"
    root.mkdir()
    _copy_of_the_benchmark(root)
    for pkg in ("ray_tpu", "ray_tpu/serve", "ray_tpu/models"):
        os.makedirs(root / pkg)
        (root / pkg / "__init__.py").write_text("")
    (root / "ray_tpu" / "models" / "moe.py").write_text(
        "import dataclasses\n\n@dataclasses.dataclass\n"
        "class MoEConfig:\n    n_experts: int = 8\n")
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
    assert "ray_tpu/models/moe.py has no MoEConfig.kv_lora_rank" in err


def test_the_chip_comparison_rehearses_and_refuses_each_fault():
    """``benchmarks/reference/glm4_moe_lite_compare.py`` at the tiny
    configuration: the decode rows' experts are the reference's own (in
    float32 nothing rounds a choice the other way), logits through the
    latent pool within the float32 tolerance at all four lengths (one
    bucket, two chunks, four, six), and each of its nine faults read as
    incorrect."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "benchmarks/reference/glm4_moe_lite_compare.py",
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
    assert tuple(f["fault"] for f in faults) == FAULTS
    assert not any(f["correct"] for f in faults)
    assert all(f["rows_over"] > 0 for f in faults)
    assert base["cell_check_passes"] and out["cell_logit_tol"] == 1e-3
