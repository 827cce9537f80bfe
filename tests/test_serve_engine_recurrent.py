"""The engine beside ``test_serve_engine.py``, on a model with state-space
layers: which form its prefill programs carry the state over a call's rows
in (``stats()["recurrent_prefill"]``), what that form walked beyond the
real rows (``first_tokens[].scan_rows_padded``, asked of the model code),
and that the two forms serve the same greedy tokens.  A file of its own:
steering a form clears jit's caches, which the other file's compile counts
would see."""

import time

import jax
import numpy as np
import pytest

from walk_ref import ssm_toy

from ray_tpu.models import moe_init
from ray_tpu.ops import ssm_scan
from ray_tpu.serve.engine import EngineConfig, InferenceEngine
from ray_tpu.util import steprec

#: Buckets of 8 and 16 rows, a chunk of 16: a prompt of 27 is two calls.
ENGINE = dict(batch_slots=2, page_size=8, max_prompt_len=32,
              max_new_tokens_cap=8, prefill_chunk=16, prefix_cache=False)
PROMPTS = (5, 13, 27, 16)
NEW = 5


@pytest.fixture(scope="module")
def model():
    """One period at toy widths whose state is whole tiles: 3 Mamba layers
    around 1 attention layer."""
    cfg = ssm_toy()
    return cfg, jax.jit(moe_init, static_argnums=0)(cfg,
                                                    jax.random.PRNGKey(0))


def _serve(model):
    """The prompts through a fresh engine: (its stats, each prompt's greedy
    tokens, each prompt's ``first_tokens`` entry by its length)."""
    cfg, params = model
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in PROMPTS]
    steprec.drain_buffered()
    eng = InferenceEngine(cfg, params, EngineConfig(**ENGINE), seed=0)
    try:
        outs = [list(s) for s in
                [eng.submit(p, max_new_tokens=NEW) for p in prompts]]
        recs, deadline = [], time.time() + 10
        while time.time() < deadline and sum(
                len(r["first_tokens"]) for r in recs) < len(prompts):
            recs += [r for r in steprec.drain_buffered()
                     if r.get("engine") == eng.engine_id]
            time.sleep(0.05)
        first = {e["prompt"]: e for r in recs for e in r["first_tokens"]}
        return eng.stats(), outs, first
    finally:
        eng.shutdown()


def test_off_a_tpu_the_scan_walks_the_bucket(model):
    assert not ssm_scan.on_tpu()
    stats, outs, first = _serve(model)
    assert stats["recurrent_prefill"] == "scan"
    assert stats["recurrent_decode"] == "jnp"
    assert [len(o) for o in outs] == [NEW] * len(PROMPTS)
    assert {n: e["chunks"] for n, e in first.items()} \
        == {5: 1, 13: 1, 27: 2, 16: 1}
    # The calls' buckets less the real rows: 8 - 5, 16 - 13, 32 - 27, 0.
    assert {n: (e["scan_rows"], e["scan_rows_padded"])
            for n, e in first.items()} \
        == {5: (5, 3), 13: (13, 3), 27: (27, 5), 16: (16, 0)}


def test_steered_on_the_kernel_walks_the_real_rows_and_serves_the_same(
        model, monkeypatch):
    """``_scans_on_chip`` steered on (the kernel interpreted, its position
    block cut to these buckets): the form is named, nothing is walked
    beyond the real rows, and every prompt's greedy tokens are the scan
    engine's."""
    _, want, _ = _serve(model)
    monkeypatch.setattr(ssm_scan, "on_tpu", lambda: True)
    monkeypatch.setattr(ssm_scan, "POSITIONS_BLOCK", 8)
    called = []
    real = ssm_scan.ssm_scan_chunk
    monkeypatch.setattr(
        ssm_scan, "ssm_scan_chunk",
        lambda *args: called.append(args[2].shape) or real(*args,
                                                          interpret=True))
    jax.clear_caches()  # jit keeps a trace by its arguments, not the form
    try:
        stats, outs, first = _serve(model)
    finally:
        jax.clear_caches()
    assert stats["recurrent_prefill"] == "kernel"
    assert stats["recurrent_decode"] == "jnp"  # steered apart
    # As the programs were traced: three layers a bucket's program.
    assert sorted(set(called)) == [(8, 128), (16, 128)] \
        and len(called) % 3 == 0
    assert {n: (e["scan_rows"], e["scan_rows_padded"])
            for n, e in first.items()} \
        == {5: (5, 0), 13: (13, 0), 27: (27, 0), 16: (16, 0)}
    assert outs == want
