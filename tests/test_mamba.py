"""The selective state-space layer (``ray_tpu/models/mamba.py``) in float32 on
the CPU: its two forms against a naive loop over positions written here from
the equations, the state and convolution rows across chunk edges and padded
rows, a prefill call's rows through the chunk kernel against the same
through the scan, and what the paged programs do with a slot's state (an
inactive slot, a slot used again)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import MoEConfig, block, mamba, moe_init, paged


def _config(**kw):
    """One period at toy widths: 3 Mamba layers around 1 attention layer."""
    return MoEConfig(**{**dict(
        vocab_size=128, d_model=32, n_layers=4, n_heads=2, n_kv_heads=1,
        attn_layout=("ssm", "ssm", "kv", "ssm"), ssm_inner=64, ssm_state=16,
        ssm_dt_rank=4, ssm_conv=4, rope_layout=(0,) * 4,
        ffn_layout=(0,) * 4, dense_d_ff=48, d_ff=48, n_experts=1, top_k=1,
        tie_embeddings=True, max_seq=64, dtype=jnp.float32, remat=False),
        **kw})


@pytest.fixture(scope="module")
def layer():
    """(configuration, one Mamba layer's weights with D, the norms and the
    convolution's bias away from their initial ones and zeros)."""
    cfg = _config()
    a = dict(jax.jit(mamba.init, static_argnums=0)(
        cfg, jax.random.PRNGKey(0)))
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    for k, name in zip(ks, ("D", "dt_norm", "b_norm", "c_norm")):
        a[name] = jax.random.uniform(k, a[name].shape, jnp.float32, 0.5, 1.5)
    return cfg, a


def _pre(cfg, rows, seed=2, batch=2):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (batch, rows, cfg.ssm_inner), jnp.float32)


def _naive(cfg, a, pre):
    """The equations, one sequence and one position at a time, in numpy:
    (y with the D skip [S, I], the state [I, N], the last taps - 1 rows)."""
    a = {k: np.asarray(v, np.float64) for k, v in a.items()}
    i, n, r = mamba.widths(cfg)
    taps, eps = cfg.ssm_conv, cfg.norm_eps
    pre = np.asarray(pre, np.float64)
    rows = np.concatenate([np.zeros((taps - 1, i)), pre])

    def rms(x, w):
        return x / np.sqrt(np.mean(x * x) + eps) * w

    def silu(x):
        return x / (1 + np.exp(-x))

    A = -np.exp(a["A_log"]).T                       # [I, N]
    H, ys = np.zeros((i, n)), []
    for t in range(pre.shape[0]):
        xs = silu(sum(rows[t + j] * a["conv_w"][j] for j in range(taps))
                  + a["conv_b"])
        dbc = xs @ a["w_x"]
        d = rms(dbc[:r], a["dt_norm"])
        bm = rms(dbc[r:r + n], a["b_norm"])
        cm = rms(dbc[r + n:], a["c_norm"])
        delta = np.log1p(np.exp(d @ a["w_dt"] + a["dt_bias"]))
        H = np.exp(delta[:, None] * A) * H + (delta * xs)[:, None] * bm[None]
        ys.append(H @ cm + a["D"] * xs)
    return np.stack(ys), H, rows[-(taps - 1):]


@functools.partial(jax.jit, static_argnums=0)
def _chunk(cfg, a, H, rows, pre, length=None):
    """The chunk form on pre [B, S, I] behind (H, rows): y with the D skip,
    the new state, the next rows.  Jitted: one compile a length, where the
    eager form compiles every operation of it."""
    valid = None if length is None \
        else jnp.arange(pre.shape[1])[None] < length[:, None]
    xs, nxt = mamba.conv(cfg, a, pre, rows, length)
    y, H = mamba.chunked(a, H, xs, *mamba.drive(cfg, a, xs), valid)
    return y + a["D"] * xs, H, nxt


def _zeros(cfg, batch):
    st = mamba.state_shapes(cfg, 1, batch)
    return (jnp.zeros(st["S"].shape[1:], jnp.float32),
            jnp.zeros(st["conv"].shape[1:], jnp.float32))


def test_the_two_forms_and_a_naive_loop_agree(layer):
    cfg, a = layer
    pre = _pre(cfg, 21)
    y_c, H_c, rows_c = _chunk(cfg, a, *_zeros(cfg, 2), pre)
    H, rows = _zeros(cfg, 2)
    ys = []
    decode_rows = jax.jit(mamba.decode_rows, static_argnums=0)
    for t in range(pre.shape[1]):  # the recurrent form, a row at a time
        (y, xs), H, rows = decode_rows(cfg, a, H, rows, pre[:, t])
        ys.append(y + a["D"] * xs)
    y_r = jnp.stack(ys, axis=1)
    for b in range(2):
        want_y, want_H, want_rows = _naive(cfg, a, pre[b])
        for got_y, got_H, got_rows in ((y_c, H_c, rows_c), (y_r, H, rows)):
            np.testing.assert_allclose(got_y[b], want_y, atol=2e-5)
            np.testing.assert_allclose(got_H[b].T, want_H, atol=2e-5)
            np.testing.assert_allclose(
                got_rows[b].reshape(cfg.ssm_conv - 1, -1), want_rows,
                atol=1e-6)


@pytest.mark.parametrize("edge", range(1, 13))
def test_a_prompt_split_at_any_edge_equals_one_pass(layer, edge):
    """The state AND the convolution rows carried across the edge: an edge
    under the taps leaves rows of the call before in the next one's."""
    cfg, a = layer
    pre = _pre(cfg, 13, seed=3)
    y, H, rows = _chunk(cfg, a, *_zeros(cfg, 2), pre)
    y1, H1, rows1 = _chunk(cfg, a, *_zeros(cfg, 2), pre[:, :edge])
    y2, H2, rows2 = _chunk(cfg, a, H1, rows1, pre[:, edge:])
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y, atol=1e-5)
    np.testing.assert_allclose(H2, H, atol=1e-5)
    np.testing.assert_array_equal(rows2, rows)


def test_padded_rows_change_nothing(layer):
    """Rows behind a sequence's last real one (each sequence's own count)
    leave the state where the last real row left it, and the convolution
    rows are the last REAL ones."""
    cfg, a = layer
    pre = _pre(cfg, 16, seed=4)
    length = jnp.asarray([9, 2], jnp.int32)  # the second under the taps
    y, H, rows = _chunk(cfg, a, *_zeros(cfg, 2), pre, length)
    for b, n in enumerate(length.tolist()):
        y1, H1, rows1 = _chunk(cfg, a, *_zeros(cfg, 1), pre[b:b + 1, :n])
        np.testing.assert_allclose(y[b, :n], y1[0], atol=1e-6)
        np.testing.assert_allclose(H[b], H1[0], atol=1e-6)
        np.testing.assert_array_equal(rows[b], rows1[0])
    # And to the bit, whatever the padded rows hold.
    real = jnp.arange(16)[None, :, None] < length[:, None, None]
    _, H2, rows2 = _chunk(cfg, a, *_zeros(cfg, 2),
                          jnp.where(real, pre, 1e3 * pre + 7.0), length)
    np.testing.assert_array_equal(H2, H)
    np.testing.assert_array_equal(rows2, rows)


@pytest.mark.parametrize("rows, real, behind", [
    (16, 16, False), (16, 5, False), (32, 9, True), (32, 32, True),
    (8, 1, True)])
def test_prefill_rows_through_the_kernel_is_prefill_rows_through_the_scan(
        monkeypatch, rows, real, behind):
    """``prefill_rows`` with ``_scans_on_chip`` steered on (the kernel,
    interpreted) against the same with it off: the layer's (y, xs) on the
    real rows, the state and the convolution rows, from zeros and behind a
    state; the kernel's y of a row that holds no token is zero."""
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.ops import ssm_scan

    cfg = _config(ssm_inner=128)  # a state of whole tiles
    a = jax.jit(mamba.init, static_argnums=0)(cfg, jax.random.PRNGKey(0))
    H, conv_rows = _zeros(cfg, 1)
    if behind:
        k = jax.random.split(jax.random.PRNGKey(7))
        H = jax.random.normal(k[0], H.shape, jnp.float32)
        conv_rows = jax.random.normal(k[1], conv_rows.shape, jnp.float32)
    pre = _pre(cfg, rows, seed=6, batch=1)[0]
    valid = jnp.arange(rows) < real
    monkeypatch.setattr(ssm_scan, "POSITIONS_BLOCK", 8)

    def run():  # a function a form: jit keeps a trace by its function
        return jax.jit(lambda *rest: mamba.prefill_rows(cfg, *rest))(
            a, H, conv_rows, valid, pre)

    assert not mamba._scans_on_chip(cfg, rows)
    (want_y, want_xs), want, want_nxt = run()
    monkeypatch.setattr(ssm_scan, "on_tpu", lambda: True)
    assert mamba._scans_on_chip(cfg, rows)
    with pltpu.force_tpu_interpret_mode():
        (y, xs), new, nxt = run()
    assert y.shape == want_y.shape == (1, rows, 128)
    np.testing.assert_allclose(np.asarray(y)[0, :real],
                               np.asarray(want_y)[0, :real],
                               rtol=1e-5, atol=1e-6)
    assert not np.asarray(y)[0, real:].any()
    np.testing.assert_array_equal(xs, want_xs)
    np.testing.assert_array_equal(nxt, want_nxt)
    np.testing.assert_allclose(new, want, rtol=1e-6, atol=1e-6)


# ------------------------------------------------------ through the programs


ENGINE = dict(page=4, maxp=8, slots=3)
_prefill_logits = jax.jit(paged.prefill_logits, static_argnums=0)
_decode_logits = jax.jit(paged.decode_logits, static_argnums=0)


def _programs(cfg, params, pools, slot, prompt):
    """A cold prefill of ``prompt`` into ``slot``; returns the pools."""
    adapters = paged.init_adapter_pool(cfg, 1, 2)
    table = jnp.asarray(slot * ENGINE["maxp"] + np.arange(ENGINE["maxp"]),
                        jnp.int32)
    pad = np.zeros((1, 16), np.int32)
    pad[0, :len(prompt)] = prompt
    _, pools, _ = _prefill_logits(
        cfg, params, pools, adapters, jnp.asarray(pad),
        jnp.asarray(len(prompt), jnp.int32), table,
        jnp.asarray(1, jnp.int32), None, jnp.asarray(slot, jnp.int32))
    return pools


@pytest.fixture(scope="module")
def model():
    cfg = _config()
    params = jax.jit(moe_init, static_argnums=0)(
        cfg, jax.random.PRNGKey(5))
    return cfg, params


def _pools(cfg):
    return paged.init_paged_pools(
        cfg, ENGINE["slots"] * ENGINE["maxp"], ENGINE["page"], 0,
        ENGINE["slots"])


def test_the_layout_and_the_pools(model):
    cfg, params = model
    assert [block.is_ssm(cfg, i) for i in range(4)] \
        == [True, True, False, True]
    assert block.recurrent(cfg) is mamba and block.recurrent(cfg, 2) is None
    assert not block.is_routed(cfg) and not block.is_latent(cfg)
    assert paged.kv_layers(cfg) == ([2], [])
    assert paged.state_layers(cfg) == [0, 1, 3]
    pools = _pools(cfg)
    assert set(pools) == {"k", "v", "S", "conv"}
    assert pools["k"].shape == (1, 25, 4, 1, 16)
    assert pools["S"].shape == (3, 3, 16, 64)        # transposed: [N, I]
    assert pools["conv"].shape == (3, 3, 3 * 64)
    assert "lm_head" not in params                   # the tied head
    assert sum(x.size for x in jax.tree.leaves(params)) == cfg.param_count()
    assert paged.counter_keys(cfg) == paged.KV_KEYS
    with pytest.raises(ValueError, match="two kinds of recurrent"):
        _config(attn_layout=("ssm", "kda", "kv", "ssm"), kda_heads=2,
                kda_head_dim=8, kda_conv=4)
    with pytest.raises(ValueError, match="ssm_inner"):
        _config(ssm_inner=0)


def test_an_inactive_slots_state_is_bit_identical_after_a_decode_step(model):
    cfg, params = model
    rng = np.random.default_rng(0)
    pools = _pools(cfg)
    for slot in (0, 2):
        pools = _programs(cfg, params, pools, slot,
                          rng.integers(1, 128, size=7))
    before = {n: np.asarray(pools[n]) for n in ("S", "conv")}
    assert before["S"][:, 2].any() and before["conv"][:, 2].any()
    b, maxp = ENGINE["slots"], ENGINE["maxp"]
    tables = np.arange(b * maxp, dtype=np.int32).reshape(b, maxp)
    active = np.asarray([True, False, False])
    _, pools, _ = _decode_logits(
        cfg, params, pools, paged.init_adapter_pool(cfg, 1, 2),
        jnp.asarray([5, 6, 7], jnp.int32), jnp.asarray(tables),
        jnp.asarray([7, 0, 7], jnp.int32), jnp.asarray(active),
        jnp.full((b,), 1, jnp.int32))
    for name in ("S", "conv"):
        after = np.asarray(pools[name])
        np.testing.assert_array_equal(after[:, 1:], before[name][:, 1:])
        assert (after[:, 0] != before[name][:, 0]).any()


def test_a_slot_used_again_starts_from_zeros(model):
    """A cold prefill into a slot that holds another sequence's state leaves
    what the same prefill leaves in a slot never used."""
    cfg, params = model
    rng = np.random.default_rng(1)
    first, second = rng.integers(1, 128, size=11), rng.integers(1, 128,
                                                                size=6)
    used = _programs(cfg, params, _pools(cfg), 1, first)
    assert np.asarray(used["S"][:, 1]).any()
    used = _programs(cfg, params, used, 1, second)
    fresh = _programs(cfg, params, _pools(cfg), 1, second)
    for name in ("S", "conv"):
        np.testing.assert_array_equal(np.asarray(used[name][:, 1]),
                                      np.asarray(fresh[name][:, 1]))
