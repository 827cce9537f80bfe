"""The llama family: the dense decoder of ``ray_tpu/models/llama.py`` (GQA,
SwiGLU, RoPE, RMSNorm, untied head, no bias, no window), and the family of
every configuration file without a ``family`` key.

A family module is the one place of the harness that knows an architecture.
The harness finds it by name (``spec.family``) and asks it for exactly the
names below; it holds no state and no class of its own.  No JAX is imported
here at the top: the parent process reads the counts, and only the process
that holds the chip calls what builds a program or a reference.
"""

from __future__ import annotations

from typing import Any, Dict

# The counts, with no JAX.  ``matmul_params`` is what a token multiplies
# with (a sparse family gives its active parameters).
from ..arith import (flash_train_step_ops_bytes,  # noqa: F401
                     matmul_params, train_flops_per_token)
# From the file's published keys to the program's configuration object:
# ``program_config(model, *, max_seq, **overrides)``.
from ..modelcfg import (check_supported,  # noqa: F401
                        llama_config as program_config, param_count)

#: ``configs/<this>.json`` is what a ``--rehearse`` run of any cell of this
#: family runs in place of the cell's configuration.
REHEARSAL_CONFIG = "rehearsal-tiny"


def train_step_kernel_ops_bytes(model: Dict[str, Any], batch: int, seq: int,
                                tpu_custom_calls: int) -> Dict[str, float]:
    """Operations and bytes the family's kernels need in one train step,
    given how many ``tpu_custom_call`` the compiled step holds.  Flash is
    this program's one kernel: a layer holds the two backward kernels and
    one forward, or two where remat runs the forward again."""
    forwards = max(1, tpu_custom_calls // model["num_hidden_layers"] - 2)
    return flash_train_step_ops_bytes(model, batch, seq, forwards)


# ---------------------------------------------------------------- training


def init(cfg, key):
    from ray_tpu.models import llama_init

    return llama_init(cfg, key)


def loss(cfg, params, tokens, targets):
    from ray_tpu.models import llama_loss

    return llama_loss(cfg, params, tokens, targets)


def sharding_rules(cfg):
    from ray_tpu.models import llama_sharding_rules

    return llama_sharding_rules()


# ----------------------------------------------------------------- serving


def register(model: Dict[str, Any], *, max_seq: int) -> str:
    """Make the engine serve ``model`` under the name this returns.  Called
    inside the replica, before ``LLMServer.__init__``.  The write to the
    engine's private table stands until the program has a public
    ``register_model`` (PERF.md, open questions)."""
    from ray_tpu.serve import engine

    cfg = program_config(model, max_seq=max_seq, remat=False)
    engine._MODEL_BUILDERS[model["name"]] = lambda: cfg
    return model["name"]


# --------------------------------------------------------------- reference


def reference(model: Dict[str, Any], params, device=None):
    """The plain reference over the system's own parameter tree: an object
    with ``logits(tokens, positions)`` and
    ``loss_and_grad_norm(tokens, targets)``."""
    from ..reference.llama_ref import Reference

    return Reference(model, params, device)
