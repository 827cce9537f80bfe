"""Greedy decoding by the plain full forward pass: the tokens the serving
tests hold the paged engine to.  ``llama_apply`` shares nothing with the
paged programs but the decoder layer itself (no cache, no pages, no
buckets); the sequence is padded to one fixed length, so a configuration
compiles once however long the prompts are (causal attention: a real
position sees no padding behind it)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import llama_apply

#: Every prompt plus its new tokens in the serving tests fits.
PAD_TO = 64


@functools.partial(jax.jit, static_argnums=(0,))
def _logits_at(cfg, params, padded, last):
    return llama_apply(cfg, params, padded)[0, last]


def greedy_tokens(cfg, params, prompt, n_new, stream=None):
    """The ``n_new`` tokens greedy decoding emits after ``prompt`` (a list
    of ints); ``stream(token)`` is called with each as it is chosen."""
    n = len(prompt)
    assert n + n_new <= PAD_TO, (n, n_new)
    toks = np.zeros((1, PAD_TO), np.int32)
    toks[0, :n] = prompt
    for i in range(n, n + n_new):
        toks[0, i] = int(jnp.argmax(
            _logits_at(cfg, params, jnp.asarray(toks), i - 1)))
        if stream is not None:
            stream(int(toks[0, i]))
    return toks[0, n:n + n_new].tolist()
