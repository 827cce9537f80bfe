"""The state-space decode kernel (``ray_tpu/ops/ssm_decode.py``) on the CPU,
interpreted: against ``mamba.recurrent`` (its reference) at the tiny and at
whole-tile geometries, what it leaves of an inactive slot and of the other
layers of the pool, what a dead slot's NaN reaches, the geometries it
refuses, who chooses it (``mamba._steps_in_place``), and a Mamba layer's
and the decode program's work through it against the same through
``recurrent``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu
from walk_ref import ssm_toy as _config

from ray_tpu.models import mamba, moe_init, paged
from ray_tpu.ops import ssm_decode, ssm_decode_step


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _operands(layers, slots, n, i, seed=0):
    """(the pool, A_log as Mamba-1 draws it, delta, xs, bm, cm): a step
    between 0.001 and 0.1 as ``dt_bias`` is drawn, so that the decay of the
    sixteen entries of a channel runs from exp(-0.001) to exp(-1.6)."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    S = jax.random.normal(k[0], (layers, slots, n, i), jnp.float32)
    A_log = jnp.broadcast_to(
        jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None], (n, i))
    delta = jnp.exp(jax.random.uniform(k[1], (slots, i), jnp.float32,
                                       jnp.log(0.001), jnp.log(0.1)))
    xs = jax.random.normal(k[2], (slots, i), jnp.float32)
    bm = jax.random.normal(k[3], (slots, n), jnp.float32)
    cm = jax.random.normal(k[4], (slots, n), jnp.float32)
    return S, A_log, delta, xs, bm, cm


def _active(slots):
    """Every third slot dead (the only slot of one: live)."""
    return jnp.asarray(np.arange(slots) % 3 != 1)


@pytest.mark.parametrize("width, slots, layers, layer", [
    *((256, slots, layers, layer) for slots in (1, 5, 16)
      for layers, layer in ((1, 0), (3, 0), (3, 2))),
    (5120, 1, 1, 0), (5120, 5, 3, 2), (5120, 16, 2, 0)])
def test_the_kernel_is_the_recurrent_form(width, slots, layers, layer):
    """At the tiny configuration's channels and at the published ones:
    ``y`` and the state of the live slots to float32 round-off (the sum over
    N in another order); a dead slot's state TO THE BIT and its y zero; the
    pool's other layers untouched."""
    S, A_log, delta, xs, bm, cm = _operands(layers, slots, 16, width)
    active = np.asarray(_active(slots))
    y, new = ssm_decode_step(S, layer, A_log, delta, xs, bm, cm,
                             jnp.asarray(active), interpret=True)
    want_y, want = jax.jit(mamba.recurrent)({"A_log": A_log}, S[layer], xs,
                                            delta, bm, cm)
    assert y.shape == (slots, width) and y.dtype == jnp.float32
    assert new.shape == S.shape and new.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(y)[active],
                               np.asarray(want_y)[active],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new[layer])[active],
                               np.asarray(want)[active],
                               rtol=1e-6, atol=1e-6)
    assert np.array_equal(np.asarray(new[layer])[~active],
                          np.asarray(S[layer])[~active])
    assert not np.asarray(y)[~active].any()
    others = [i for i in range(layers) if i != layer]
    assert np.array_equal(np.asarray(new)[others], np.asarray(S)[others])


@pytest.mark.parametrize("block, strip", [(1, 128), (2, 512), (4, 256),
                                          (8, 384), (16, 5120)])
def test_every_cut_of_the_blocks_computes_the_same(monkeypatch, block,
                                                   strip):
    """Blocks of fewer slots than a sublane tile (the rows then whole in
    VMEM), of a tile, of all the slots; strips that divide the channels and
    one that does not."""
    S, A_log, delta, xs, bm, cm = _operands(2, 16, 16, 640, seed=1)
    active = _active(16)
    want_y, want = ssm_decode_step(S, 1, A_log, delta, xs, bm, cm, active,
                                   interpret=True)
    monkeypatch.setattr(ssm_decode, "SLOTS_BLOCK", block)
    monkeypatch.setattr(ssm_decode, "STRIP", strip)
    y, new = ssm_decode_step(S, 1, A_log, delta, xs, bm, cm, active,
                             interpret=True)
    assert np.array_equal(np.asarray(y), np.asarray(want_y))
    assert np.array_equal(np.asarray(new), np.asarray(want))


@pytest.mark.parametrize("where", ["state", "delta", "both"])
def test_what_a_dead_slot_holds_stays_there(where):
    """A NaN in an inactive slot's state or in its step reaches no other
    slot's y and no other slot's state, and the state it sits in comes back
    as it was."""
    S, A_log, delta, xs, bm, cm = _operands(2, 5, 16, 256, seed=2)
    active = jnp.asarray([True, False, True, True, False])
    clean_y, clean = ssm_decode_step(S, 1, A_log, delta, xs, bm, cm, active,
                                     interpret=True)
    if where in ("state", "both"):
        S = S.at[1, 1].set(jnp.nan).at[1, 4, 3, 7].set(jnp.inf)
    if where in ("delta", "both"):
        delta = delta.at[1].set(jnp.nan)
        xs = xs.at[4].set(jnp.nan)
    y, new = ssm_decode_step(S, 1, A_log, delta, xs, bm, cm, active,
                             interpret=True)
    assert np.array_equal(np.asarray(y), np.asarray(clean_y))
    live = np.asarray(active)
    assert np.array_equal(np.asarray(new[1])[live], np.asarray(clean[1])[live])
    assert np.array_equal(np.asarray(new[1])[~live], np.asarray(S[1])[~live],
                          equal_nan=True)
    assert np.array_equal(np.asarray(new[0]), np.asarray(S[0]))


@pytest.mark.parametrize("what, n, i, dtype, says", [
    ("channels that are not whole lane tiles", 16, 192, jnp.float32,
     "whole lane tiles: I 192"),
    ("a state that is not whole sublane tiles", 12, 256, jnp.float32,
     "whole sublane tiles a channel: N 12"),
    ("a state that is not float32", 16, 256, jnp.bfloat16,
     "float32 state: got bfloat16")],
    ids=lambda x: x.replace(" ", "-") if isinstance(x, str) and " " in x
    else "")
def test_a_geometry_the_kernel_cannot_take_is_refused(what, n, i, dtype,
                                                      says):
    """Before anything is traced, in a sentence; ``takes`` answers the same
    without raising (what ``mamba._steps_in_place`` asks)."""
    S, A_log, delta, xs, bm, cm = _operands(1, 4, n, i)
    with pytest.raises(ValueError, match=says):
        ssm_decode_step(S.astype(dtype), 0, A_log, delta, xs, bm, cm,
                        jnp.ones((4,), bool), interpret=True)
    assert not ssm_decode.takes(n, i, dtype), what
    assert ssm_decode.takes(16, 256, jnp.float32)
    with pytest.raises(ValueError, match=r"\[layers, slots, N, I\]"):
        ssm_decode.check_geometry(S[0], delta)


# -------------------------------------------------------- who chooses it


@pytest.fixture
def on_the_chip(monkeypatch):
    """The answer only a TPU gives; jit keeps a trace by its arguments, not
    by that answer."""
    monkeypatch.setattr(ssm_decode, "on_tpu", lambda: True)
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_off_a_tpu_the_recurrent_form_stays():
    cfg = _config()
    assert not ssm_decode.on_tpu()
    assert not mamba._steps_in_place(cfg)
    assert not paged._steps_in_place(cfg, 0)
    assert paged.recurrent_decode_form(cfg) == "jnp"


@pytest.mark.parametrize("what, over, steps", [
    ("whole tiles", {}, True),
    ("the published widths", dict(ssm_inner=5120), True),
    ("channels that are not whole lane tiles", dict(ssm_inner=64), False),
    ("a state that is not whole sublane tiles", dict(ssm_state=12), False)],
    ids=lambda x: x.replace(" ", "-") if isinstance(x, str) else "")
def test_on_a_tpu_the_one_predicate_chooses_by_the_tiles(on_the_chip, what,
                                                         over, steps):
    cfg = _config(**over)
    assert mamba._steps_in_place(cfg) is steps, what
    assert paged._steps_in_place(cfg) is steps
    assert paged._steps_in_place(cfg, 0) is steps
    assert not paged._steps_in_place(cfg, 2)  # the attention layer
    assert paged.recurrent_decode_form(cfg) == ("kernel" if steps else "jnp")


def test_a_model_without_recurrent_layers_has_no_form(on_the_chip):
    from ray_tpu.models import LlamaConfig

    cfg = LlamaConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=2,
                      n_kv_heads=1, d_ff=48, max_seq=32)
    assert paged.recurrent_decode_form(cfg) is None
    assert not paged._steps_in_place(cfg)


# ------------------------------------------- a layer and the program through it


def test_a_layers_decode_rows_through_the_kernel(monkeypatch):
    """``decode_rows`` handed the pool and the layer's place, through the
    kernel, against ``decode_rows`` on that layer's slice through
    ``recurrent``: y, xs, the state, the convolution rows."""
    cfg = _config()
    a = jax.jit(mamba.init, static_argnums=0)(cfg, jax.random.PRNGKey(0))
    shapes = mamba.state_shapes(cfg, 3, 5)
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    S = jax.random.normal(k[0], shapes["S"].shape, jnp.float32)
    rows = jax.random.normal(k[1], shapes["conv"].shape[1:], jnp.float32)
    pre = jax.random.normal(k[2], (5, cfg.ssm_inner), jnp.float32)
    active = jnp.asarray([True, True, False, True, True])
    # One program each, where the eager form compiles every operation.
    (want_y, want_xs), want, want_nxt = jax.jit(
        lambda *rest: mamba.decode_rows(cfg, *rest))(a, S[2], rows, pre)
    monkeypatch.setattr(ssm_decode, "on_tpu", lambda: True)
    with pltpu.force_tpu_interpret_mode():
        (y, xs), new, nxt = jax.jit(lambda *rest: mamba.decode_rows(
            cfg, *rest, layer=2, active=active))(a, S, rows, pre)
    live = np.asarray(active)
    np.testing.assert_allclose(np.asarray(y)[live], np.asarray(want_y)[live],
                               rtol=1e-5, atol=1e-6)
    assert np.array_equal(np.asarray(xs), np.asarray(want_xs))
    assert np.array_equal(np.asarray(nxt), np.asarray(want_nxt))
    np.testing.assert_allclose(np.asarray(new[2])[live],
                               np.asarray(want)[live], rtol=1e-6, atol=1e-6)
    assert np.array_equal(np.asarray(new[2])[~live], np.asarray(S[2])[~live])
    assert np.array_equal(np.asarray(new[:2]), np.asarray(S[:2]))


def _decode(cfg, params, pools, steps=3):
    """``steps`` decode steps of four slots, the third dead: (the logits a
    step, the pools at the end).  Jitted, a function a call: jit keeps a
    trace by its function and arguments, not by what ``on_tpu`` answered."""
    decode_logits = jax.jit(lambda *args: paged.decode_logits(cfg, *args))
    b, maxp, ps = 4, 4, 8
    adapters = paged.init_adapter_pool(cfg, 1, 4)
    tables = np.arange(b * maxp, dtype=np.int32).reshape(b, maxp)
    tables[2] = 16  # the dead slot's writes land on the scratch page
    tables = jnp.asarray(tables)
    active = jnp.asarray([True, True, False, True])
    ids = jnp.ones((b,), jnp.int32)
    rows = []
    for step in range(steps):
        toks = jnp.asarray((np.arange(b) * 7 + step * 3) % cfg.vocab_size,
                           jnp.int32)
        lens = jnp.where(active, 5 + step, 0).astype(jnp.int32)
        logits, pools, _ = decode_logits(
            params, pools, adapters, toks, tables, lens, active, ids)
        rows.append(np.asarray(logits))
    return np.stack(rows), pools


def test_the_decode_program_through_the_kernel(monkeypatch):
    """Three decode steps of the toy model, a slot dead throughout, with
    the three Mamba layers through the kernel (the pool handed whole)
    against the same through ``recurrent``: every live row's logits, the
    state pool, the convolution pool and the K/V pools' own pages."""
    cfg = _config()
    params = jax.jit(moe_init, static_argnums=0)(cfg, jax.random.PRNGKey(0))
    k = jax.random.PRNGKey(5)

    def pools():
        p = dict(paged.init_paged_pools(cfg, 16, 8, 0, 4))
        p["S"] = jax.random.normal(k, p["S"].shape, jnp.float32)
        return p

    want, want_pools = _decode(cfg, params, pools())
    monkeypatch.setattr(ssm_decode, "on_tpu", lambda: True)
    jax.clear_caches()
    handed = []  # as the step is traced: the three steps run one program
    real = ssm_decode.ssm_decode_step
    monkeypatch.setattr(
        ssm_decode, "ssm_decode_step",
        lambda S, layer, *rest: handed.append((S.shape, layer))
        or real(S, layer, *rest))
    with pltpu.force_tpu_interpret_mode():
        got, got_pools = _decode(cfg, params, pools())
    jax.clear_caches()
    assert handed == [((3, 4, 16, 128), i) for i in (0, 1, 2)]
    live = [0, 1, 3]
    np.testing.assert_allclose(got[:, live], want[:, live], rtol=2e-5,
                               atol=2e-5)
    for name in ("k", "v"):  # but the scratch page, behind the others
        np.testing.assert_allclose(
            np.asarray(got_pools[name][:, :16]),
            np.asarray(want_pools[name][:, :16]), rtol=1e-5, atol=1e-5,
            err_msg=name)
    for name in ("S", "conv"):
        np.testing.assert_allclose(
            np.asarray(got_pools[name]), np.asarray(want_pools[name]),
            rtol=1e-5, atol=1e-5, err_msg=name)
    assert np.array_equal(np.asarray(got_pools["S"][:, 2]),
                          np.asarray(want_pools["S"][:, 2]))
