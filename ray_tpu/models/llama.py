"""Llama-family decoder-only transformer, TPU-first.

Pure functional: `llama_init` builds a param pytree, `llama_apply` runs the
forward pass.  Attention goes through the Pallas flash kernel (TPU) or the
jnp reference (CPU), and through ring attention when the sequence is sharded
on the `sp` mesh axis.  Sharding is declared in `llama_sharding_rules`
(megatron TP + FSDP) for the parameters and, under an ambient mesh, by the
layer for its activations (`sharding.constrain`), and applied by pjit — no
wrapper classes.

LoRA: `lora_init` creates low-rank adapters for the attention projections;
the base params stay frozen (the Llama-2-7B LoRA fine-tune target in
BASELINE.md).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.attention import flash_attention
from ..ops.norms import rms_norm
from ..ops.ring_attention import ring_attention
from ..ops.rotary import apply_rotary, rope_frequencies
from ..parallel.mesh import AXIS_DP, AXIS_FSDP, AXIS_SP, AXIS_TP
from ..parallel.sharding import (SPLIT, ShardingRules, auto_mesh, constrain,
                                 fit_spec)

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    d_ff: int = 11008
    max_seq: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    # QK-norm (OLMo-2 / OLMoE): RMSNorm with a learned weight over the whole
    # q and k projection widths, before the split into heads and RoPE.
    qk_norm: bool = False
    dtype: Any = jnp.bfloat16
    # remat: rematerialize each block in backward (HBM <-> FLOPs trade)
    remat: bool = True
    # Remat policy: "full" recomputes everything (lowest memory);
    # "save_attn" asks the policy to keep flash-attention residuals
    # (q/k/v/out/lse, tagged "flash_res"); "xla_cse" disables the CSE
    # barrier so XLA itself chooses which activations to keep — the highest
    # MFU when it fits in HBM (a caller tries it first and falls back to
    # "full").  Note: custom_vjp residual saving is best-effort — measure.
    remat_policy: str = "full"
    # sp_axis set -> use ring attention over that mesh axis inside shard_map
    sp_ring: bool = False
    # Flash-attention tile shapes.  The kernel auto-shrinks when a block
    # exceeds (or doesn't divide) the sequence, so these are CAPS, not
    # exact tiles.  block_q=1024 measured ~+1pp MFU at seq=2048 on v5e
    # (fewer grid launches per head, same VMEM residency); 512 is the
    # safe default across shapes.
    flash_block_q: int = 512
    flash_block_k: int = 512
    # Sequence-chunk size for the vocab-projection loss scan (see
    # llama_loss): larger chunks feed the [B*chunk, d]@[d, vocab] matmul
    # more rows per launch, at (B * chunk * vocab * 4B) logits memory.
    loss_chunk: int = 256

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def param_count(self) -> int:
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        per_layer = (
            d * d  # wq
            + 2 * d * self.n_kv_heads * self.head_dim  # wk, wv
            + d * d  # wo
            + 3 * d * f  # w1, w2, w3 (w2 transposed)
            + 2 * d  # norms
        )
        if self.qk_norm:
            per_layer += d + self.n_kv_heads * self.head_dim
        return v * d + self.n_layers * per_layer + d + d * v

    # ---- stock sizes ------------------------------------------------------

    @staticmethod
    def llama2_7b(**kw) -> "LlamaConfig":
        return LlamaConfig(**kw)

    @staticmethod
    def llama2_13b(**kw) -> "LlamaConfig":
        return LlamaConfig(d_model=5120, n_layers=40, n_heads=40,
                           n_kv_heads=40, d_ff=13824, **kw)

    @staticmethod
    def llama3_8b(**kw) -> "LlamaConfig":
        return LlamaConfig(vocab_size=128256, d_model=4096, n_layers=32,
                           n_heads=32, n_kv_heads=8, d_ff=14336,
                           rope_theta=500000.0, **kw)

    @staticmethod
    def b1(**kw) -> "LlamaConfig":
        """~1.2B bench config (fits one v5e chip with activations)."""
        return LlamaConfig(d_model=2048, n_layers=20, n_heads=16,
                           n_kv_heads=16, d_ff=5632, **kw)

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        kw.setdefault("vocab_size", 512)
        return LlamaConfig(d_model=128, n_layers=2, n_heads=4,
                           n_kv_heads=2, d_ff=256, max_seq=256, **kw)


def llama_init(config: LlamaConfig, key: jax.Array) -> Params:
    d, f = config.d_model, config.d_ff
    hd = config.head_dim
    kv_out = config.n_kv_heads * hd
    std = d ** -0.5
    n_keys = 2 + config.n_layers
    keys = jax.random.split(key, n_keys)

    def dense(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(
            config.dtype
        )

    params: Params = {
        "embed": dense(keys[0], (config.vocab_size, d), 1.0),
        "final_norm": jnp.ones((d,), config.dtype),
        "lm_head": dense(keys[1], (d, config.vocab_size), std),
        "layers": [],
    }
    for i in range(config.n_layers):
        ks = jax.random.split(keys[2 + i], 7)
        params["layers"].append({
            "attn_norm": jnp.ones((d,), config.dtype),
            "attn": {
                "wq": dense(ks[0], (d, d), std),
                "wk": dense(ks[1], (d, kv_out), std),
                "wv": dense(ks[2], (d, kv_out), std),
                "wo": dense(ks[3], (d, d), std),
            },
            "mlp_norm": jnp.ones((d,), config.dtype),
            "mlp": {
                "w1": dense(ks[4], (d, f), std),   # gate
                "w3": dense(ks[5], (d, f), std),   # up
                "w2": dense(ks[6], (f, d), f ** -0.5),  # down
            },
        })
        if config.qk_norm:
            params["layers"][-1]["attn"].update(qk_norm_init(config))
    return params


def qk_norm_init(config) -> Params:
    """The two QK-norm weights of one layer's ``attn`` (see ``_qk_norm``):
    over the projections' widths, or one head's where ``qk_norm`` is
    ``"head"``."""
    q_heads, k_heads = (1, 1) if config.qk_norm == "head" \
        else (config.n_heads, config.n_kv_heads)
    return {"q_norm": jnp.ones((q_heads * config.head_dim,), config.dtype),
            "k_norm": jnp.ones((k_heads * config.head_dim,), config.dtype)}


def _qk_norm(config, a: Params, q: jax.Array, k: jax.Array):
    """QK-norm where the configuration has it: q [..., H*D] and
    k [..., H_kv*D] are the flat projections, normalised before they are
    split into heads and rotated: over their whole width, or
    (``qk_norm`` ``"head"``) each head over its own ``head_dim`` with one
    weight that the heads share.  A trace-time branch: a model without it
    compiles to the program it always did."""
    if not config.qk_norm:
        return q, k
    if config.qk_norm == "head":
        def a_head(x, w):
            heads = x.reshape(*x.shape[:-1], -1, config.head_dim)
            return rms_norm(heads, w, config.norm_eps).reshape(x.shape)
        return a_head(q, a["q_norm"]), a_head(k, a["k_norm"])
    return (rms_norm(q, a["q_norm"], config.norm_eps),
            rms_norm(k, a["k_norm"], config.norm_eps))


def llama_sharding_rules() -> ShardingRules:
    """Megatron TP x FSDP rules (2D); norms replicated.  A matrix is split
    over tp the way its product is (``sharding.SPLIT``) and over fsdp in
    its other dimension, to be gathered where it is used; the embedding's
    rows over both (``sharding.VOCAB_ROWS`` where it is looked up).
    Reference behavior replaced: train_loop_utils.py prepare_model wrappers."""
    return ShardingRules([
        (r"embed", P((AXIS_TP, AXIS_FSDP), None)),
        (r"lm_head", P(AXIS_FSDP, AXIS_TP)),
        (r"attn/(wq|wk|wv|wg)", P(AXIS_FSDP, AXIS_TP)),
        (r"attn/wo", P(AXIS_TP, AXIS_FSDP)),
        (r"mlp/(w1|w3)", P(AXIS_FSDP, AXIS_TP)),
        (r"mlp/w2", P(AXIS_TP, AXIS_FSDP)),
        (r"norm", P()),
        (r"lora_(a|b)", P()),  # adapters are tiny: replicate
    ])


def _option(config, name: str):
    """A training option of ``LlamaConfig`` (``sp_ring``, the flash tiles,
    ``remat_policy``); a configuration object without the field, a
    ``MoEConfig``, takes ``LlamaConfig``'s default."""
    return getattr(config, name, getattr(LlamaConfig, name))


def _flash_per_shard(config, q, k, v):
    """Flash attention under pjit.  XLA cannot partition a Pallas kernel
    ("Mosaic kernels cannot be automatically partitioned"), so under an
    ambient mesh (``jax.set_mesh``) the kernel runs per shard: batch over
    dp/fsdp and heads over tp, both independent in attention.  A dim its
    axes do not divide stays replicated."""
    flash = functools.partial(
        flash_attention, causal=True,
        block_q=_option(config, "flash_block_q"),
        block_k=_option(config, "flash_block_k"))
    mesh = auto_mesh()
    if mesh is None:  # no mesh / already per-shard
        return flash(q, k, v)
    spec = fit_spec(P((AXIS_DP, AXIS_FSDP), AXIS_TP, None, None), k.shape,
                    mesh.shape)
    return jax.shard_map(flash, in_specs=(spec,) * 3, out_specs=spec,
                         check_vma=False)(q, k, v)


def _attend_window(q, k, v, window: int):
    """Plain masked attention of q [B, H, S, D] over k, v [B, H_kv, S, D]:
    position i sees j with ``0 <= i - j < window``.  What a window layer's
    full forward computes; there is no window flash kernel, so no cell
    trains such a layer on a chip (ROADMAP)."""
    B, H, S, D = q.shape
    g = k.shape[1]
    qg = q.reshape(B, g, H // g, S, D)
    scores = jnp.einsum("bgrqd,bgkd->bgrqk", qg, k,
                        preferred_element_type=jnp.float32) * (D ** -0.5)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    scores = jnp.where((j <= i) & (i - j < window), scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bgrqk,bgkd->bgrqd", probs, v).reshape(B, H, S, D)


def _attend(config, cos, sin, q, k, v, kind=(0, True)):
    """The training programs' ``attend`` (see ``block``): causal
    self-attention of q [B, S, H, D] over k, v [B, S, H_kv, D] with RoPE,
    through the flash kernel or, sequence-sharded, the ring.  Returns
    [B, S, H*D].  ``kind`` is the layer's ``block.layer_kind``: its window
    (0: none) and whether it has rotary, for a configuration with a layer
    pattern."""
    B, S = q.shape[:2]
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    window, rotary = kind[:2]
    if window or not rotary:
        if rotary:
            q, k = apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)
        out = _attend_window(q, k, v, window) if window \
            else _flash_per_shard(config, q, k, v)
        return out.transpose(0, 2, 1, 3).reshape(B, S, -1)
    # Ring attention engages only when tracing inside shard_map over `sp`
    # (local-chunk view).  Under plain pjit the tensors are the global view:
    # positions start at 0 and XLA partitions full attention itself.
    ring_mode = False
    if _option(config, "sp_ring"):
        from ..collective.xla_ops import axis_size

        try:
            axis_size(AXIS_SP)  # probes whether the sp axis is bound
            ring_mode = True
        except (NameError, KeyError, TypeError):
            ring_mode = False
    if ring_mode:
        # Local chunk at global offset rank * S_local: RoPE must use global
        # positions or cross-chunk relative positions are wrong.
        offset = jax.lax.axis_index(AXIS_SP) * S
        q = apply_rotary(q, cos, sin, position_offset=offset)
        k = apply_rotary(k, cos, sin, position_offset=offset)
        out = ring_attention(q, k, v, axis_name=AXIS_SP, causal=True)
    else:
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)
        out = _flash_per_shard(config, q, k, v)
    return out.transpose(0, 2, 1, 3).reshape(B, S, -1)


def _attend_latent(config, cos, sin, q, c, k_r, wkv_b, rotary=True):
    """The full forward's ``attend`` of a latent model (``block.is_latent``),
    the EXPANDED form: every position's keys and values are made out of its
    latent c [B, S, rank] by ``wkv_b``, the one rotary key k_r [B, S, rope]
    is rotated and shared by the heads, q [B, S, H, nope + rope] rotates its
    last ``rope`` dimensions, and the scores are scaled by
    ``(nope + rope) ** -0.5``; ``rotary`` false (the layer's
    ``block.layer_rotary``): neither rotates.  Plain masked attention
    (there is no flash kernel for keys and values of two widths: ROADMAP
    M3).  Returns [B, S, H * v]."""
    from . import block

    B, S, H, _ = q.shape
    nope = config.qk_nope_head_dim
    w_uk, w_uv = block.latent_up(config, wkv_b)
    k_n = jnp.einsum("bsc,chd->bshd", c, w_uk)
    v = jnp.einsum("bsc,chd->bshd", c, w_uv)
    q_r, k_r = q[..., nope:].transpose(0, 2, 1, 3), k_r[:, None]
    if rotary:
        q_r = apply_rotary(q_r, cos, sin)
        k_r = apply_rotary(k_r, cos, sin)                  # [B, 1, S, rope]
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], k_n,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhqd,bkd->bhqk", q_r, k_r[:, 0],
                           preferred_element_type=jnp.float32)) \
        * (config.head_dim ** -0.5)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    scores = jnp.where(j <= i, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, S, -1)


def _lora(lora_layer: Optional[Params]):
    """``block``'s ``lora`` closure over one layer of a ``lora_init``
    tree: delta = h @ A @ B * (alpha/r)."""
    if lora_layer is None:
        return None
    return lambda name, h: (
        (h @ lora_layer[name + "_lora_a"]) @ lora_layer[name + "_lora_b"]
    ) * lora_layer["scale"]


def _attention(config, x, layer, cos, sin, lora_layer=None):
    """A layer's attention of normalised x [B, S, d], through ``wo``.  No
    program calls it (``_layer`` runs the whole layer);
    ``benchmarks/reference/olmoe_compare.py`` does."""
    from . import block

    return block.attention(
        config, layer["attn"], x,
        functools.partial(_attend, config, cos, sin), _lora(lora_layer))


def _mlp(layer, x):
    m = layer["mlp"]
    gate = jax.nn.silu(constrain(x @ m["w1"], SPLIT))
    return (gate * constrain(x @ m["w3"], SPLIT)) @ m["w2"]


def _layer(config, x, layer, cos, sin, lora_layer, kind, routed):
    """One decoder layer of a training program, dense or routed, with its
    arrays as arguments (what ``jax.checkpoint`` wraps; ``kind``, the
    layer's ``block.layer_kind``, and ``routed``, its ``block.is_routed``,
    are static, so layers of one kind and shape share a trace): (x, the
    routed FFN's aux loss or None)."""
    from . import block

    attn = kind[2] if len(kind) > 2 else ""
    if attn in ("kda", "ssm"):
        attend = block.recurrent(config).full_attend(config)
    elif block.is_latent(config):
        attend = functools.partial(_attend_latent, config, cos, sin,
                                   rotary=kind[1])
    else:
        attend = functools.partial(_attend, config, cos, sin, kind=kind)
    x, aux, _ = block.decoder_layer(config, layer, x, attend,
                                    lora=_lora(lora_layer), routed=routed,
                                    attn=attn)
    return x, aux


def _block(config: LlamaConfig, x, layer, cos, sin, lora_layer=None):
    """A layer of a stack whose layers are all alike (the pipeline stage
    scans over them, so no layer has a number to ask about)."""
    from . import block

    return _layer(config, x, layer, cos, sin, lora_layer, (0, True),
                  block.is_routed(config))[0]


def llama_apply(
    config: LlamaConfig,
    params: Params,
    tokens: jax.Array,                       # [B, S] int32
    lora_params: Optional[Params] = None,
) -> jax.Array:
    """Returns logits [B, S, vocab]."""
    x, _ = hidden_and_aux(config, params, tokens, lora_params)
    return (x @ params["lm_head"]).astype(jnp.float32)


def hidden_and_aux(config, params: Params, tokens: jax.Array,
                   lora_params: Optional[Params] = None):
    """The training forward pass of either architecture: final-norm hidden
    states [B, S, d] (logits = hidden @ lm_head) and the layers' aux losses
    (None where the FFN is dense)."""
    from . import block

    cos, sin = rope_frequencies(
        block.rotary_dim(config), config.max_seq, config.rope_theta
    )
    layer_fn = _layer
    if config.remat:
        # Two independent axes compose here:
        # - prevent_cse: True keeps forward/backward recompute separate
        #   (true remat; the default — under plain jit, CSE merging the
        #   two silently keeps every layer's activations live, observed as
        #   19 simultaneous [8,2048,5632] mlp temps).  False ("xla_cse")
        #   lets XLA choose which activations to keep — highest MFU when
        #   it fits.
        # - policy: which values the backward may keep instead of
        #   recomputing.  "flash_res" skips the attention recompute (the
        #   dominant cost at long sequence); checkpoint_dots keeps matmul
        #   outputs (the classic TPU selective-checkpointing sweet spot).
        from jax.ad_checkpoint import checkpoint_policies as cps

        save_attn = cps.save_only_these_names("flash_res")
        policy, prevent_cse = {
            "full": (None, True),
            "xla_cse": (None, False),
            "save_attn": (save_attn, True),
            "cse_save_attn": (save_attn, False),
            "save_dots": (cps.checkpoint_dots, True),
            "save_dots_no_batch":
                (cps.checkpoint_dots_with_no_batch_dims, True),
        }[_option(config, "remat_policy")]
        layer_fn = jax.checkpoint(
            _layer, static_argnums=(0, 6, 7), policy=policy,
            prevent_cse=prevent_cse,
        )

    def run(i, layer, x):
        ll = lora_params["layers"][i] if lora_params is not None else None
        return (*layer_fn(config, x, layer, cos, sin, ll,
                          block.layer_kind(config, i),
                          block.is_routed(config, i)), None)

    hidden, auxes, _ = block.decoder_stack(config, params, tokens, run)
    return hidden, auxes


def llama_loss(
    config: LlamaConfig,
    params: Params,
    tokens: jax.Array,
    targets: jax.Array,
    lora_params: Optional[Params] = None,
    ignore_index: int = -100,
) -> jax.Array:
    """Causal-LM cross entropy with a seq-chunked vocab projection: the
    full fp32 logits tensor ([B, S, vocab] — 2 GiB at 8x2048x32k, plus its
    gradient) never materializes; each chunk's logits are rematerialized in
    the backward pass (jax.checkpoint over the chunk loss)."""
    hidden, _ = hidden_and_aux(config, params, tokens, lora_params)
    B, S, d = hidden.shape
    w = params["lm_head"]

    from ..ops.losses import masked_nll

    def chunk_nll(h_c, tgt_c):
        # The vocabulary over tp (the head's rule): the loss reduces over
        # tp on [B, chunk], never on the logits.
        logits = constrain((h_c @ w).astype(jnp.float32), SPLIT)
        return masked_nll(logits, tgt_c, ignore_index)

    chunk = config.loss_chunk
    if S % chunk != 0:
        total, count = chunk_nll(hidden, targets)
        return total / jnp.maximum(count, 1)
    n_chunks = S // chunk
    h = hidden.reshape(B, n_chunks, chunk, d).transpose(1, 0, 2, 3)
    t = targets.reshape(B, n_chunks, chunk).transpose(1, 0, 2)

    def scan_body(carry, xs):
        total, count = carry
        nll, cnt = jax.checkpoint(chunk_nll)(xs[0], xs[1])
        return (total + nll, count + cnt), None

    (total, count), _ = jax.lax.scan(
        scan_body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        (h, t),
    )
    return total / jnp.maximum(count, 1)


# --------------------------------------------------------------------- LoRA


def lora_init(config: LlamaConfig, key: jax.Array, rank: int = 16,
              alpha: float = 32.0) -> Params:
    """Adapters for wq/wv in every layer (frozen-base fine-tuning)."""
    d = config.d_model
    kv_out = config.n_kv_heads * config.head_dim
    layers = []
    keys = jax.random.split(key, config.n_layers)
    for i in range(config.n_layers):
        k1, k2 = jax.random.split(keys[i])
        layers.append({
            "wq_lora_a": (jax.random.normal(k1, (d, rank), jnp.float32)
                          * (d ** -0.5)).astype(config.dtype),
            "wq_lora_b": jnp.zeros(
                (rank, config.n_heads * config.head_dim), config.dtype),
            "wv_lora_a": (jax.random.normal(k2, (d, rank), jnp.float32)
                          * (d ** -0.5)).astype(config.dtype),
            "wv_lora_b": jnp.zeros((rank, kv_out), config.dtype),
            "scale": jnp.asarray(alpha / rank, config.dtype),
        })
    return {"layers": layers}


def lora_sharding_rules() -> ShardingRules:
    return ShardingRules([(r"lora", P())])


def lora_merge(config: LlamaConfig, params: Params, lora: Params) -> Params:
    """Fold adapters into base weights (for export/serving)."""
    out = jax.tree.map(lambda x: x, params)  # shallow-ish copy
    for i, ll in enumerate(lora["layers"]):
        a = out["layers"][i]["attn"]
        scale = ll["scale"].astype(jnp.float32)
        a["wq"] = (a["wq"].astype(jnp.float32)
                   + ll["wq_lora_a"].astype(jnp.float32)
                   @ ll["wq_lora_b"].astype(jnp.float32) * scale
                   ).astype(config.dtype)
        a["wv"] = (a["wv"].astype(jnp.float32)
                   + ll["wv_lora_a"].astype(jnp.float32)
                   @ ll["wv_lora_b"].astype(jnp.float32) * scale
                   ).astype(config.dtype)
    return out
