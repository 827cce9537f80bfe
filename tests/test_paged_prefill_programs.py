"""The prefill programs of a model with window layers through
``ops/paged_prefill.py`` (``paged._walks_live_pages`` steered, the one thing
a CPU cannot see; the kernel interpreted), against the gather form they take
everywhere else; the engine's ``attn_pairs`` arithmetic; and who walks.  The
kernel alone is in ``tests/test_paged_prefill.py``."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.models import paged
from ray_tpu.ops import paged_decode

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DIM = 128

TINY_PAGE, TINY_SEQ = 8, 64


def _tiny(name, heads):
    """The benchmark's tiny configuration of that name with heads of 128
    (what the kernel's DMAs move whole) and ``heads`` query heads over its
    two KV heads: its layer pattern, window (8) and everything else as the
    rehearsal runs them, in float32."""
    from benchmarks import spec

    model = spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", name + ".json"))
    model = {**model, "head_dim": DIM, "num_attention_heads": heads}
    return spec.family(model).program_config(model, remat=False,
                                             max_seq=TINY_SEQ)


@functools.lru_cache(maxsize=None)
def _program(logits, walk):
    """jit keeps a trace by its arguments, not by what ``on_tpu`` answered:
    one jitted program a form."""
    return jax.jit(logits, static_argnums=0)


def _prefill(cfg, walk, monkeypatch, prompt, chunk):
    """A prompt of ``prompt`` tokens through the cold program and then the
    suffix program, ``chunk`` rows a call, into pools of seeded rows: (the
    logits of each call, the pools' real pages after the last).  ``walk``:
    as on a TPU, the kernel interpreted, and every call the suffix
    program's, the first at ``prefix_len`` 0 (the engine's rule)."""
    from ray_tpu.models import init_and_apply

    monkeypatch.setattr(paged_decode, "on_tpu", lambda: walk)
    maxp = TINY_SEQ // TINY_PAGE
    ring = paged.ring_entries(cfg, TINY_PAGE, chunk)
    params = init_and_apply(cfg)[0](cfg, jax.random.PRNGKey(0))
    pools = paged.init_paged_pools(cfg, 2 * maxp, TINY_PAGE, 2 * ring)
    pools = {name: jax.random.normal(jax.random.PRNGKey(i), x.shape, x.dtype)
             for i, (name, x) in enumerate(sorted(pools.items()))}
    adapters = paged.init_adapter_pool(cfg, 1, 2)
    table = jnp.arange(maxp, dtype=jnp.int32)[::-1] + maxp
    rings = jnp.arange(ring, dtype=jnp.int32)[::-1] + ring
    tokens = np.random.default_rng(3).integers(1, 500, prompt)
    logits = []
    with pltpu.force_tpu_interpret_mode():
        for start in range(0, prompt, chunk):
            end = min(start + chunk, prompt)
            toks = np.zeros((1, chunk), np.int32)
            toks[0, :end - start] = tokens[start:end]
            args = (cfg, params, pools, adapters, jnp.asarray(toks))
            tail = (jnp.int32(end), table, jnp.int32(1), rings)
            # As the engine calls them: where the prefills walk, a
            # prompt's first rows are a suffix behind nothing.
            out, pools, _ = _program(paged.prefill_prefix_logits, walk)(
                *args, jnp.int32(start), *tail) if start or walk \
                else _program(paged.prefill_logits, walk)(*args, *tail)
            logits.append(np.asarray(out))
    # The real rows the calls left: every position of the whole-length
    # pools, of the rings what the last call's ring still holds.
    first = max(0, (prompt - 1) // TINY_PAGE - ring + 1) * TINY_PAGE
    held = {}
    for name, x in pools.items():
        kind = name[-1] == "w"
        at = np.arange(first if kind else 0, prompt)
        pages = np.asarray(rings)[(at // TINY_PAGE) % ring] if kind \
            else np.asarray(table)[at // TINY_PAGE]
        held[name] = np.asarray(x)[:, pages, at % TINY_PAGE]
    return logits, held


@pytest.mark.parametrize("name, heads", [("smallthinker-tiny", 14),
                                         ("trinity-mini-tiny", 16)])
@pytest.mark.parametrize("prompt, chunk", [(13, 16), (37, 16), (61, 8)],
                         ids=["inside-one-bucket", "three-chunks-a-ring-"
                              "wrapped", "eight-chunks-of-a-page"])
def test_the_prefill_programs_through_the_kernel(monkeypatch, name, heads,
                                                 prompt, chunk):
    """The prefill calls of the tiny SmallThinker (7:1, a whole-length
    layer before three rings) and Trinity-Mini (8:1, three rings to a
    whole-length layer, gate and norms around them) as a TPU takes them
    (the suffix program through the kernel, a prompt's first rows too),
    against the cold program and the suffix program in the gather form: the same logits after every
    call of a prompt inside one bucket (its tail padding), chunked past the
    window of 8 with the ring of three pages wrapping, and chunked a page at
    a time to the table's last page; the rows the calls leave in the pools
    are the same."""
    cfg = _tiny(name, heads)
    whole, window = paged.kv_layers(cfg)
    assert whole and window and cfg.window == TINY_PAGE
    walked, pools = _prefill(cfg, True, monkeypatch, prompt, chunk)
    assert paged.prefill_attention_form(cfg) == "walk"
    gathered, ref_pools = _prefill(cfg, False, monkeypatch, prompt, chunk)
    assert paged.prefill_attention_form(cfg) == "gather"
    assert len(walked) == -(-prompt // chunk)
    for out, ref in zip(walked, gathered):
        scale = float(np.abs(ref).max())
        np.testing.assert_allclose(out, ref, atol=3e-5 * scale, rtol=0)
    for pool in ref_pools:
        scale = float(np.abs(ref_pools[pool]).max())
        np.testing.assert_allclose(pools[pool], ref_pools[pool],
                                   atol=3e-5 * scale, rtol=0, err_msg=pool)


def test_off_the_tpu_the_prefills_are_the_gather_form():
    """Nothing steers it here: on this backend a model with window layers
    lowers without the kernel, and one without them everywhere, whatever the
    backend answers; a latent model gathers here and walks where
    ``latent_decode.on_tpu`` answers true (``ops/latent_prefill.py``), as
    its decode step does."""
    import dataclasses

    from ray_tpu.ops import latent_decode

    cfg = _tiny("smallthinker-tiny", 14)
    assert not paged._walks_live_pages(cfg)
    assert paged.prefill_attention_form(cfg) == "gather"
    plain = dataclasses.replace(cfg, window=0, window_layout=(),
                                rope_layout=())
    was = paged_decode.on_tpu, latent_decode.on_tpu
    paged_decode.on_tpu = latent_decode.on_tpu = lambda: True
    try:
        assert paged.prefill_attention_form(cfg) == "walk"
        assert paged.prefill_attention_form(plain) == "gather"
        from benchmarks import spec
        model = spec.load_json(os.path.join(
            ROOT, "benchmarks", "configs", "glm4-moe-lite-tiny.json"))
        latent = spec.family(model).program_config(model, remat=False,
                                                   max_seq=TINY_SEQ)
        assert paged._walks_live_pages(latent)
        assert paged.prefill_attention_form(latent) == "walk"
    finally:
        paged_decode.on_tpu, latent_decode.on_tpu = was
    assert paged.prefill_attention_form(latent) == "gather"


@pytest.mark.parametrize("start, end", [(0, 5), (0, 8), (0, 30), (8, 24),
                                        (3, 9), (16, 17), (40, 64)])
def test_attn_pairs_counts_what_the_real_rows_see(start, end):
    """By the formula, a tiny SmallThinker's two whole-length layers and
    six rings of window 8, against the count row by row."""
    cfg = _tiny("smallthinker-tiny", 14)
    whole, window = paged.kv_layers(cfg)
    by_row = sum(len(whole) * (p + 1) + len(window) * min(p + 1, cfg.window)
                 for p in range(start, end))
    assert paged.attn_pairs(cfg, start, end) == by_row
