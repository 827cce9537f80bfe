"""The plain reference (``benchmarks/reference/llama_ref.py``) against the
program at the tiny configuration on the CPU, over five seeds: the forward
pass against ``llama_apply``, loss and gradient norm against ``llama_loss``,
and the serving comparison's form (the emitted token's reference logit
against the reference's best) on the program's own greedy tokens.

Tolerances: everything here is float32 with the same mathematics in another
order, so 1e-4 on logits of a few units and 1e-5 relative on the loss are
several hundred float32 roundings; a bf16 forward pass is off by ~1e-2 and
would fail both."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_testlib import ROOT

from benchmarks import spec
from benchmarks.modelcfg import llama_config
from benchmarks.reference.llama_ref import Reference, teacher_forced_gaps

SEEDS = [0, 1, 7, 2 ** 31 + 11, 3_000_000_019]


@pytest.fixture(scope="module")
def tiny():
    model = spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "rehearsal-tiny.json"))
    return model, llama_config(model, max_seq=64, remat=False)


def _weights(cfg, seed):
    from ray_tpu.models import llama_init

    return llama_init(cfg, jax.random.PRNGKey(seed % (2 ** 31 - 1)))


@pytest.mark.parametrize("seed", SEEDS)
def test_forward_pass_matches_llama_apply(tiny, seed):
    from ray_tpu.models import llama_apply

    model, cfg = tiny
    params = _weights(cfg, seed)
    toks = np.random.default_rng(seed).integers(0, 512, (40,), np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(llama_apply(cfg, params, jnp.asarray(toks[None]))[0])
    got = Reference(model, params).logits(toks, range(40))
    assert np.abs(got - want).max() < 1e-4
    # And a lower precision would not pass: bf16 weights move the logits.
    rough = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    off = Reference(model, rough).logits(toks, range(40))
    assert np.abs(off - want).max() > 1e-3


@pytest.mark.parametrize("seed", SEEDS)
def test_loss_and_gradient_norm_match_llama_loss(tiny, seed):
    import optax

    from ray_tpu.models import llama_loss

    model, cfg = tiny
    params = _weights(cfg, seed)
    toks = np.random.default_rng(seed).integers(0, 512, (3, 64), np.int32)
    tgts = np.roll(toks, -1, axis=1)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(lambda p: llama_loss(
            cfg, p, jnp.asarray(toks), jnp.asarray(tgts)))(params)
    ref_loss, ref_norm = Reference(model, params).loss_and_grad_norm(
        toks, tgts)
    assert ref_loss == pytest.approx(float(loss), rel=1e-5)
    assert ref_norm == pytest.approx(float(optax.global_norm(grads)),
                                     rel=1e-4)


@pytest.mark.parametrize("seed", SEEDS)
def test_greedy_tokens_are_the_references_best(tiny, seed):
    """Greedy continuation by the program's own full forward pass, then the
    comparison the serving cells make: zero gap at every position."""
    from ray_tpu.models import llama_apply

    model, cfg = tiny
    params = _weights(cfg, seed)
    prompt = np.random.default_rng(seed).integers(1, 512, (9,)).tolist()
    seq, out = list(prompt), []
    with jax.default_matmul_precision("highest"):
        for _ in range(4):
            logits = llama_apply(cfg, params, jnp.asarray([seq], jnp.int32))
            out.append(int(np.asarray(logits[0, -1]).argmax()))
            seq.append(out[-1])
    ref = Reference(model, params)
    gaps = teacher_forced_gaps(ref, prompt, out)
    assert len(gaps) == 4 and max(gaps) < 1e-4
    wrong = [(t + 1) % 512 for t in out]
    assert max(teacher_forced_gaps(ref, prompt, wrong)) > 1e-3
