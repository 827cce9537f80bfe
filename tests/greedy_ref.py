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


def logits_after(cfg, params, context):
    """The full forward pass's logits [V] for the token after ``context`` (a
    list of ints)."""
    assert len(context) <= PAD_TO, len(context)
    toks = np.zeros((1, PAD_TO), np.int32)
    toks[0, :len(context)] = context
    return _logits_at(cfg, params, jnp.asarray(toks), len(context) - 1)


def greedy_tokens(cfg, params, prompt, n_new, stream=None):
    """The ``n_new`` tokens greedy decoding emits after ``prompt`` (a list
    of ints); ``stream(token)`` is called with each as it is chosen."""
    assert len(prompt) + n_new <= PAD_TO, (len(prompt), n_new)
    toks = list(prompt)
    for _ in range(n_new):
        toks.append(int(jnp.argmax(logits_after(cfg, params, toks))))
        if stream is not None:
            stream(toks[-1])
    return toks[len(prompt):]
