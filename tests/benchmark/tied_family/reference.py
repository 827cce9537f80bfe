"""The toy family's plain reference, added to a temporary copy as
``benchmarks/reference/tied_ref.py``: float32 at ``highest`` precision, the
whole batch at once (it only ever sees the tiny configuration).  The block
is llama's, so it takes the block from the llama reference; the head is the
embedding, whatever else the tree holds."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .llama_ref import _layer, _rms


class Reference:
    def __init__(self, model, params, device=None):
        self.device = device or jax.devices()[0]
        self.params = jax.tree.map(
            lambda t: jax.device_put(t, self.device).astype(jnp.float32),
            {k: params[k] for k in ("embed", "layers", "final_norm")})
        self.eps = float(model["rms_norm_eps"])
        self.kw = dict(n_heads=model["num_attention_heads"],
                       n_kv=model["num_key_value_heads"], eps=self.eps,
                       theta=float(model["rope_theta"]))

    def _logits(self, params, tokens):
        x = params["embed"][tokens]
        for layer in params["layers"]:
            x = _layer(x, layer, **self.kw)
        return _rms(x, params["final_norm"], self.eps) @ params["embed"].T

    def logits(self, tokens, positions):
        with jax.default_matmul_precision("highest"):
            out = self._logits(self.params, jnp.asarray(tokens))
        return np.asarray(out[jnp.asarray(list(positions))])

    def loss_and_grad_norm(self, tokens, targets):
        def mean_nll(params):
            total = 0.0
            for seq, gold in zip(jnp.asarray(tokens), jnp.asarray(targets)):
                logits = self._logits(params, seq)
                logz = jax.scipy.special.logsumexp(logits, axis=-1)
                total += (logz - jnp.take_along_axis(
                    logits, gold[:, None], axis=-1)[:, 0]).sum()
            return total / tokens.size

        with jax.default_matmul_precision("highest"):
            value, grads = jax.value_and_grad(mean_nll)(self.params)
        return float(value), math.sqrt(sum(
            float(jnp.sum(g * g)) for g in jax.tree.leaves(grads)))
