"""The state-space chunk kernel (``ray_tpu/ops/ssm_scan.py``) on the CPU,
interpreted: against ``mamba.chunked`` (its reference) in float32 at small
stand-ins of the engine's chunk lengths, at every kind of real-row count,
a prompt as two chunks with the state carried, what the rows past the count
hold and reach (NaN in, zero out), the geometries it refuses, and who
chooses it (``mamba._scans_on_chip``).  The position block is cut to a
sublane tile here, so that a chunk of 8 / 32 / 128 rows is one block, four
and sixteen: what 128 / 512 / 2048 rows are to the kernel as it ships."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from walk_ref import ssm_toy as _config

from ray_tpu.models import mamba, paged
from ray_tpu.ops import ssm_scan, ssm_scan_chunk

BLOCK = 8  # the position block of these tests (``small_blocks``)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(ssm_scan, "POSITIONS_BLOCK", BLOCK)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _operands(rows, n, i, seed=0):
    """(A_log as Mamba-1 draws it, the state, delta, xs, bm, cm): a step
    between 0.001 and 0.1 as ``dt_bias`` is drawn."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    A_log = jnp.broadcast_to(
        jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None], (n, i))
    H = jax.random.normal(k[0], (n, i), jnp.float32)
    delta = jnp.exp(jax.random.uniform(k[1], (rows, i), jnp.float32,
                                       jnp.log(0.001), jnp.log(0.1)))
    xs = jax.random.normal(k[2], (rows, i), jnp.float32)
    bm = jax.random.normal(k[3], (rows, n), jnp.float32)
    cm = jax.random.normal(k[4], (rows, n), jnp.float32)
    return A_log, H, delta, xs, bm, cm


@jax.jit
def _chunked(A_log, H, delta, xs, bm, cm, count):
    """``mamba.chunked`` of one sequence: (y [T, I], the state)."""
    valid = jnp.arange(delta.shape[0]) < count
    y, H = mamba.chunked({"A_log": A_log}, H[None], xs[None], delta[None],
                         bm[None], cm[None], valid[None])
    return y[0], H[0]


def _counts(rows):
    """One row, a block's edge, the edge + 1, the last block's edge + 1
    and the whole chunk (the same where the chunk is one block)."""
    return sorted(c for c in {1, BLOCK, BLOCK + 1, rows - BLOCK + 1, rows}
                  if 0 < c <= rows)


@pytest.mark.parametrize("rows, count", [
    (rows, count) for rows in (8, 32, 128) for count in _counts(rows)])
def test_the_kernel_is_the_chunk_form(rows, count):
    """The real rows' ``y`` and the state behind the last of them to
    float32 round-off (the sum over N in another order); the rows past the
    count exactly zero."""
    args = _operands(rows, 16, 256)
    y, H = ssm_scan_chunk(*args, count, interpret=True)
    want_y, want = _chunked(*args, count)
    assert y.shape == (rows, 256) and y.dtype == jnp.float32
    assert H.shape == (16, 256) and H.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(y)[:count],
                               np.asarray(want_y)[:count],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(H), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    assert not np.asarray(y)[count:].any()


@pytest.mark.parametrize("width, strip, channels", [
    (640, 256, 5120), (640, 512, 256), (5120, 512, 5120), (384, 1024, 128)])
def test_every_cut_of_the_channels_computes_the_same(monkeypatch, width,
                                                     strip, channels):
    """Strips that divide the channels and one that does not, one channel
    block and several (the grid's outer dimension), the published width."""
    args = _operands(16, 16, width, seed=1)
    monkeypatch.setattr(ssm_scan, "STRIP", strip)
    monkeypatch.setattr(ssm_scan, "CHANNELS_BLOCK", channels)
    y, H = ssm_scan_chunk(*args, 11, interpret=True)
    want_y, want = _chunked(*args, 11)
    np.testing.assert_allclose(np.asarray(y)[:11], np.asarray(want_y)[:11],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(H), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    assert not np.asarray(y)[11:].any()


@pytest.mark.parametrize("first, second", [(32, 32), (32, 13), (8, 1),
                                           (16, 9)])
def test_a_prompt_as_two_chunks_is_one_call(first, second):
    """The state carried from a whole chunk into the next, whose rows end
    where the prompt does, against one call over all the rows: to the bit
    (the same arithmetic in the same order)."""
    rows = first + 32
    A_log, H, *streams = _operands(rows, 16, 256, seed=2)
    whole_y, whole = ssm_scan_chunk(A_log, H, *streams, first + second,
                                    interpret=True)
    y1, mid = ssm_scan_chunk(A_log, H, *(t[:first] for t in streams), first,
                             interpret=True)
    y2, end = ssm_scan_chunk(A_log, mid, *(t[first:] for t in streams),
                             second, interpret=True)
    assert np.array_equal(np.asarray(end), np.asarray(whole))
    assert np.array_equal(np.concatenate([y1, y2]), np.asarray(whole_y))


@pytest.mark.parametrize("count", [0, 1, 8, 9, 21, 24])
def test_what_the_rows_past_the_count_hold_stays_there(count):
    """NaN in every stream of the padded rows (and what a fresh output
    buffer may hold is never read): the state behind the last real row is
    the clean call's TO THE BIT, the real rows' y too, the padded rows' y
    exactly zero; a count of 0 hands the state back as it came."""
    A_log, H, *streams = _operands(32, 16, 256, seed=3)
    clean_y, clean = ssm_scan_chunk(A_log, H, *streams, count,
                                    interpret=True)
    dirty = [t.at[count:].set(jnp.nan) for t in streams]
    y, new = ssm_scan_chunk(A_log, H, *dirty, count, interpret=True)
    assert np.array_equal(np.asarray(new), np.asarray(clean))
    assert np.array_equal(np.asarray(y), np.asarray(clean_y))
    assert not np.asarray(y)[count:].any()
    if count == 0:
        assert np.array_equal(np.asarray(new), np.asarray(H))
    # A padded row is a row of ``chunked`` with Delta 0: the state as it is.
    assert np.allclose(np.asarray(new),
                       np.asarray(_chunked(A_log, H, *streams, count)[1]),
                       rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("what, n, i, rows, dtype, says", [
    ("channels that are not whole lane tiles", 16, 192, 16, jnp.float32,
     "whole lane tiles: I 192"),
    ("a state that is not whole sublane tiles", 12, 256, 16, jnp.float32,
     "whole sublane tiles a channel: N 12"),
    ("a state that is not float32", 16, 256, 16, jnp.bfloat16,
     "float32 state: got bfloat16"),
    ("a chunk that is not whole position blocks", 16, 256, 12, jnp.float32,
     "whole position blocks: T 12")],
    ids=lambda x: x.replace(" ", "-") if isinstance(x, str) and " " in x
    else "")
def test_a_geometry_the_kernel_cannot_take_is_refused(what, n, i, rows,
                                                      dtype, says):
    """Before anything is traced, in a sentence; ``takes`` and
    ``takes_rows`` answer the same without raising (what
    ``mamba._scans_on_chip`` asks)."""
    A_log, H, *streams = _operands(rows, n, i)
    with pytest.raises(ValueError, match=says):
        ssm_scan_chunk(A_log, H.astype(dtype), *streams, 3, interpret=True)
    assert not (ssm_scan.takes(n, i, dtype) and ssm_scan.takes_rows(rows)), \
        what
    assert ssm_scan.takes(16, 256, jnp.float32) and ssm_scan.takes_rows(16)
    assert not ssm_scan.takes_rows(0)
    with pytest.raises(ValueError, match=r"\[N, I\] and delta \[T, I\]"):
        ssm_scan.check_geometry(H[None], streams[0])


# -------------------------------------------------------- who chooses it


@pytest.fixture
def on_the_chip(monkeypatch):
    """The answer only a TPU gives; jit keeps a trace by its arguments, not
    by that answer."""
    monkeypatch.setattr(ssm_scan, "on_tpu", lambda: True)
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_off_a_tpu_the_chunk_form_stays():
    cfg = _config()
    assert not ssm_scan.on_tpu()
    assert not mamba._scans_on_chip(cfg) and not mamba._scans_on_chip(cfg, 16)
    assert mamba.rows_walked(cfg, 16, 5) == 16
    assert paged.recurrent_prefill_form(cfg) == "scan"
    assert paged.recurrent_rows_walked(cfg, 16, 5) == 16


@pytest.mark.parametrize("what, over, rows, scans", [
    ("whole tiles", {}, 16, True),
    ("the published widths", dict(ssm_inner=5120), 2048, True),
    ("a chunk that is not whole position blocks", {}, 12, False),
    ("channels that are not whole lane tiles", dict(ssm_inner=64), 16,
     False),
    ("a state that is not whole sublane tiles", dict(ssm_state=12), 16,
     False)],
    ids=lambda x: x.replace(" ", "-") if isinstance(x, str) else "")
def test_on_a_tpu_the_one_predicate_chooses_by_the_tiles_and_the_length(
        on_the_chip, what, over, rows, scans):
    cfg = _config(**over)
    assert mamba._scans_on_chip(cfg, rows) is scans, what
    # What it walks of a call with five real rows: those, or the bucket.
    assert mamba.rows_walked(cfg, rows, 5) == (5 if scans else rows)
    assert paged.recurrent_rows_walked(cfg, rows, 5) == (5 if scans
                                                         else rows)
    tiles = "ssm_inner" not in over or over["ssm_inner"] % 128 == 0
    assert paged.recurrent_prefill_form(cfg) == (
        "kernel" if tiles and "ssm_state" not in over else "scan")


def test_other_recurrent_layers_and_none_keep_their_form(on_the_chip):
    """Kimi-Linear's gated delta-rule layers walk the bucket on every
    backend; a model without recurrent layers has no form."""
    import walk_ref

    from ray_tpu.models import LlamaConfig

    cfg = walk_ref.tiny("kimi-linear-tiny", kv_lora_rank=128)
    assert paged.recurrent_prefill_form(cfg) == "scan"
    assert paged.recurrent_rows_walked(cfg, 16, 5) == 16
    llama = LlamaConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=2,
                        n_kv_heads=1, d_ff=48, max_seq=32)
    assert paged.recurrent_prefill_form(llama) is None
