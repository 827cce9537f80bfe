"""Mixture-of-Experts decoder (Mixtral, OLMoE, SmallThinker, GLM-4.7-Flash,
Trinity-Mini, Kimi-Linear), TPU-first with expert parallelism.

The reference framework has no MoE/EP feature (SURVEY §2.4: expert parallel
"absent as a framework feature") — this is a net-new, first-class TPU
capability, like sequence parallelism: the `ep` mesh axis shards the expert
dimension.

Design (token-choice top-k, no capacity and no drops):
- router: logits [.., E] in float32; softmax over all experts, the top-k
  probabilities kept as they are (OLMoE) or renormalised (Mixtral); or
  sigmoid scores, the top-k chosen by score plus a bias that enters the
  choice only, renormalised and scaled (GLM-4.7-Flash, Trinity-Mini)
- the tokens x top_k (token, expert) pairs are sorted by expert, so each
  expert's rows are one contiguous group of a [tokens*k, d] array
- experts: three grouped products over those groups: memory and operations
  are proportional to tokens x top_k whatever the load; an expert with no
  token costs nothing and one with every token is just a long group.  Three
  forms of one arithmetic, all in ``ops.grouped_ffn`` (``_streams_experts``
  chooses by what it can see): ``jax.lax.ragged_dot`` three times, which
  sweeps the weights once a call and sends ``h`` through HBM between (every
  backend that is not a TPU, widths the kernels cannot cut, a program under
  a mesh or holding a share of the router's experts, and the gradient); and
  on a TPU one Pallas kernel that reads each hit expert's weights once
  through all three: ``"stream"`` where an expert gets a handful of rows (a
  decode step: the step's rows whole in VMEM), ``"rows"`` where it gets a
  prompt's (every prefill, the full forward, a training batch: the rows
  pass in MXU-sized blocks)
- combine: the pairs are put back in token order and summed with their
  router weights in float32
- aux loss: Switch load-balancing loss (mean expert fraction x mean router
  probability x E), returned separately so the trainer can weight it.

`n_experts=1, top_k=1` reduces exactly to the dense SwiGLU MLP — the
correctness anchor used in tests.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops import grouped_ffn
from ..parallel.mesh import AXIS_EP, AXIS_FSDP, AXIS_TP
from ..parallel.sharding import ShardingRules, auto_mesh
from .llama import (LlamaConfig, _mlp, hidden_and_aux, llama_sharding_rules,
                    qk_norm_init)

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Llama attention + a routed FFN in every layer.  The defaults are
    Mixtral's; OLMoE keeps its top-k probabilities as the softmax gave them
    (``norm_topk_prob=False``) and normalises q and k (``qk_norm=True``).
    ``d_ff`` is the width of ONE expert.

    SmallThinker's fields: ``head_dim`` where the heads are not
    ``d_model // n_heads`` wide (0: they are); a layer pattern, one entry a
    layer, read at trace time through ``block.layer_window`` /
    ``block.layer_rotary`` (``window_layout[i]`` true: layer ``i`` attends
    only the last ``window`` positions, ``i - j < window``;
    ``rope_layout[i]`` false: it has no positional term; empty tuples: no
    window anywhere, rotary everywhere); ``expert_act`` (the gate's
    non-linearity: ``silu``, or ``relu`` for a gated ReLU); and
    ``router_before_attn`` (the router reads the layer's normalised INPUT,
    the activations attention reads, not the FFN's).

    GLM-4.7-Flash's fields.  Latent attention (``kv_lora_rank`` > 0; 0:
    ordinary attention, and the other four are 0 too): q through a latent
    of ``q_lora_rank`` and its norm, K and V through one of
    ``kv_lora_rank`` and its norm, beside ONE rotary key of
    ``qk_rope_head_dim`` that every head shares; a head's q and k are
    ``qk_nope_head_dim`` (no position) + ``qk_rope_head_dim`` wide
    (``head_dim`` is their sum), its v ``v_head_dim``.  ``ffn_layout[i]``
    false: layer ``i`` has a dense SwiGLU of ``dense_d_ff`` in place of the
    routed FFN (read through ``block.is_routed``; empty: every layer is
    routed).  ``n_shared_experts`` experts, ``d_ff`` wide each, take every
    token beside the routed ones.  ``router_score`` ``sigmoid``: the
    experts are scored each on its own, the ``top_k`` largest of score plus
    the layer's ``router_bias`` are taken, and their weights are the bare
    scores (renormalised where ``norm_topk_prob``); every routed weight is
    multiplied by ``routed_scaling_factor``.

    Trinity-Mini's fields (``afmoe``; the first configuration whose
    ``ffn_layout`` and ``window_layout`` are both set).  ``qk_norm``
    ``"head"``: q and k are RMS-normalised a head, one weight of
    ``head_dim`` each (``True`` is OLMoE's: one weight over the
    projection's whole width).  ``attn_gate``: attention's heads are
    multiplied by ``sigmoid(h Wg)``, ``Wg`` [d, H * D] on the layer's
    normalised input, before the output projection.  ``post_norm``: a
    sandwich-normed block, ``x + N2(Attn(N1 x))`` then
    ``x + N4(FFN(N3 x))`` (``attn_post_norm``, ``ffn_post_norm``).
    ``embed_scale``: the embedding's rows are multiplied by it
    (``sqrt(d_model)`` under muP; 1: not at all).

    Kimi-Linear's fields (``kimi_linear``; the first configuration whose
    layers differ in the KIND of their attention, and whose chip holds a
    share of the experts).  ``attn_layout[i]`` ``"kda"``: layer ``i`` is a
    gated delta-rule layer (``models/kda.py``: ``kda_heads`` heads whose
    keys and values are ``kda_head_dim`` wide behind a causal depthwise
    convolution over the last ``kda_conv`` positions; it keeps a recurrent
    state a sequence, no cache rows); ``"latent"``: latent attention as
    above (read through ``block.is_kda`` / ``block.is_latent``; empty: every
    layer is of the configuration's one kind).  ``q_lora_rank`` 0 beside
    ``kv_lora_rank`` > 0: q is one projection, no latent and no norm.  A
    latent layer obeys ``rope_layout`` (false: its 64 "rotary" dimensions
    enter the score unrotated).  ``n_experts`` is the experts this program
    HOLDS (the width of ``w1``/``w3``/``w2``): experts ``first_expert ..
    first_expert + n_experts`` of the ``router_experts`` the router scores
    (0: it scores the held ones, all of them).  A token's ``top_k`` are
    chosen among all the router's experts; the pairs of experts held
    elsewhere are dropped before the grouped products, and the layer
    returns the partial sum of its own.

    Jamba's fields (``jamba``; the first configuration with an
    ``attn_layout`` and NO routed layer: ``ffn_layout`` all false, every FFN
    the dense SwiGLU of ``dense_d_ff``, and ``n_experts`` / ``top_k`` say
    nothing).  ``attn_layout[i]`` ``"ssm"``: layer ``i`` is a selective
    state-space layer (``models/mamba.py``: ``ssm_inner`` channels with a
    state of ``ssm_state`` each behind a causal depthwise convolution over
    the last ``ssm_conv`` positions, the step projected through
    ``ssm_dt_rank``; a recurrent state a sequence, no cache rows);
    ``"kv"``: ordinary attention (K and V rows, whole length) BESIDE
    recurrent layers.  A configuration has one kind of recurrent layer
    (``"kda"`` or ``"ssm"``: the state pools are of one shape) and one kind
    of cache (``"latent"`` or ``"kv"``).  ``tie_embeddings``: there is no
    ``lm_head``; the logits multiply by the embedding where it lies
    (``block.head``)."""

    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    n_experts: int = 8
    top_k: int = 2
    norm_topk_prob: bool = True
    qk_norm: Any = False          # False | True (the width) | "head"
    max_seq: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    aux_loss_coeff: float = 0.01
    dtype: Any = jnp.bfloat16
    remat: bool = True
    head_dim: int = 0             # 0 -> d_model // n_heads
    window: int = 0
    window_layout: Tuple[int, ...] = ()
    rope_layout: Tuple[int, ...] = ()
    expert_act: str = "silu"
    router_before_attn: bool = False
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    ffn_layout: Tuple[int, ...] = ()
    dense_d_ff: int = 0
    n_shared_experts: int = 0
    router_score: str = "softmax"
    routed_scaling_factor: float = 1.0
    attn_gate: bool = False
    post_norm: bool = False
    embed_scale: float = 1.0
    attn_layout: Tuple[str, ...] = ()
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 0
    router_experts: int = 0       # 0 -> n_experts
    first_expert: int = 0
    ssm_inner: int = 0
    ssm_state: int = 0
    ssm_dt_rank: int = 0
    ssm_conv: int = 0
    tie_embeddings: bool = False

    def __post_init__(self):
        latent = (self.kv_lora_rank, self.qk_nope_head_dim,
                  self.qk_rope_head_dim, self.v_head_dim)
        if any(latent) or self.q_lora_rank:
            if not all(x > 0 for x in latent) or self.qk_rope_head_dim % 2 \
                    or self.q_lora_rank < 0:
                raise ValueError(
                    f"latent attention needs all five of its widths (the "
                    f"q latent alone may be 0: one projection), got "
                    f"{(self.q_lora_rank, *latent)}")
            if self.n_kv_heads != self.n_heads or self.qk_norm \
                    or self.attn_gate:
                raise ValueError("latent attention has a key for every head, "
                                 "no QK-norm and no gate")
            object.__setattr__(
                self, "head_dim",
                self.qk_nope_head_dim + self.qk_rope_head_dim)
        if not self.head_dim:
            object.__setattr__(self, "head_dim",
                               self.d_model // self.n_heads)
        for name in ("window_layout", "rope_layout", "ffn_layout"):
            layout = tuple(int(x) for x in getattr(self, name))
            if layout and len(layout) != self.n_layers:
                raise ValueError(f"{name} has {len(layout)} entries for "
                                 f"{self.n_layers} layers")
            object.__setattr__(self, name, layout)
        layout = tuple(str(x) for x in self.attn_layout)
        object.__setattr__(self, "attn_layout", layout)
        if layout:
            if len(layout) != self.n_layers or any(
                    a not in ("kda", "latent", "ssm", "kv") for a in layout):
                raise ValueError(
                    f"attn_layout is not \"kda\", \"latent\", \"ssm\" or "
                    f"\"kv\" for each of the {self.n_layers} layers: "
                    f"{layout}")
            if "latent" in layout and not self.kv_lora_rank:
                raise ValueError("attn_layout names latent layers and "
                                 "kv_lora_rank is 0")
            if "kv" in layout and self.kv_lora_rank:
                raise ValueError("attn_layout names K/V layers beside a "
                                 "latent pool: one kind of cache")
            if "kda" in layout and "ssm" in layout:
                raise ValueError("attn_layout names two kinds of recurrent "
                                 "layer: the state pools are of one shape")
            if "ssm" in layout and not (
                    self.ssm_inner > 0 and self.ssm_state > 0
                    and self.ssm_dt_rank > 0 and self.ssm_conv > 1):
                raise ValueError(
                    "attn_layout names ssm layers: ssm_inner, ssm_state, "
                    "ssm_dt_rank and ssm_conv (at least 2) are needed")
            if "kda" in layout and not (self.kda_heads > 0
                                        and self.kda_head_dim > 0
                                        and self.kda_conv > 1):
                raise ValueError(
                    "attn_layout names kda layers: kda_heads, kda_head_dim "
                    "and kda_conv (at least 2) are needed")
            if any(self.window_layout) or self.attn_gate or self.post_norm:
                raise ValueError("attn_layout goes with no window layer, "
                                 "gate or sandwich norm")
        if not 0 <= self.first_expert \
                <= self.router_width - self.n_experts:
            raise ValueError(
                f"experts {self.first_expert}..+{self.n_experts} are not "
                f"among the router's {self.router_width}")
        if self.top_k > self.router_width:
            raise ValueError("top_k is more than the router's experts")
        if any(self.window_layout) and self.window <= 0:
            raise ValueError("window_layout names window layers and window "
                             "is not positive")
        if self.qk_norm not in (False, True, "head"):
            raise ValueError(f"qk_norm {self.qk_norm!r}")
        if self.expert_act not in ("silu", "relu"):
            raise ValueError(f"expert_act {self.expert_act!r}")
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(f"router_score {self.router_score!r}")
        if self.ffn_layout and not all(self.ffn_layout) \
                and self.dense_d_ff <= 0:
            raise ValueError("ffn_layout names dense layers and dense_d_ff "
                             "is not positive")

    @property
    def router_width(self) -> int:
        """The experts the router scores: ``router_experts``, or the held
        ones where that is 0."""
        return self.router_experts or self.n_experts

    def param_count(self) -> int:
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        q, kv = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        attn = 2 * d * q + 2 * d * kv
        if self.qk_norm:
            attn += 2 * self.head_dim if self.qk_norm == "head" else q + kv
        if self.attn_gate:
            attn += d * q
        if self.kv_lora_rank:
            rq, rkv, rope = (self.q_lora_rank, self.kv_lora_rank,
                             self.qk_rope_head_dim)
            attn = ((d * rq + rq + rq * q if rq else d * q)
                    + d * (rkv + rope) + rkv
                    + rkv * self.n_heads * (self.qk_nope_head_dim
                                            + self.v_head_dim)
                    + self.n_heads * self.v_head_dim * d)
        n_rec = self.attn_layout.count("kda") + self.attn_layout.count("ssm")
        attn *= self.n_layers - n_rec
        if n_rec:  # of one kind (``__post_init__``): its module counts
            from . import block
            attn += n_rec * block.recurrent(self).param_count(self)
        routed = (d * self.router_width              # router
                  + self.n_experts * 3 * d * f       # experts (held)
                  + self.n_shared_experts * 3 * d * f)
        if self.router_score == "sigmoid":
            routed += self.router_width              # the selection bias
        n_routed = sum(self.ffn_layout) if self.ffn_layout else self.n_layers
        norms = 4 * d if self.post_norm else 2 * d
        return (v * d + attn + self.n_layers * norms + n_routed * routed
                + (self.n_layers - n_routed) * 3 * d * self.dense_d_ff
                + d + (0 if self.tie_embeddings else d * v))

    def as_llama(self) -> LlamaConfig:
        """The attention fields as a ``LlamaConfig``.  No program needs it
        (``block`` reads either configuration object);
        ``benchmarks/reference/olmoe_compare.py`` still calls it."""
        return LlamaConfig(
            vocab_size=self.vocab_size, d_model=self.d_model,
            n_layers=self.n_layers, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, d_ff=self.d_ff,
            max_seq=self.max_seq, rope_theta=self.rope_theta,
            norm_eps=self.norm_eps, qk_norm=self.qk_norm, dtype=self.dtype,
        )

    # ---- stock sizes ------------------------------------------------------

    @staticmethod
    def mixtral_8x7b(**kw) -> "MoEConfig":
        return MoEConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "MoEConfig":
        kw.setdefault("vocab_size", 512)
        return MoEConfig(d_model=128, n_layers=2, n_heads=4, n_kv_heads=2,
                         d_ff=256, n_experts=4, top_k=2, max_seq=256, **kw)


def moe_init(config: MoEConfig, key: jax.Array) -> Params:
    d, f, E = config.d_model, config.d_ff, config.n_experts
    routed_E = config.router_width
    hd = config.head_dim
    q_out, kv_out = config.n_heads * hd, config.n_kv_heads * hd
    std = d ** -0.5
    keys = jax.random.split(key, 2 + config.n_layers)

    def dense(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(
            config.dtype
        )

    def swiglu(ks, width):
        return {"w1": dense(ks[0], (d, width), std),
                "w3": dense(ks[1], (d, width), std),
                "w2": dense(ks[2], (width, d), width ** -0.5)}

    params: Params = {
        # Tied, the embedding is the head too and is drawn as a head is:
        # at 1.0 a token's own row (norm sqrt(d)) would stand in the
        # residual stream it is scored against, every logit but its own
        # would be noise beside that one, and no comparison of logits
        # would see a fault (each row's best token the one it was fed).
        "embed": dense(keys[0], (config.vocab_size, d),
                       std if config.tie_embeddings else 1.0),
        "final_norm": jnp.ones((d,), config.dtype),
        "layers": [],
    }
    if not config.tie_embeddings:  # tied: the head is the embedding
        params["lm_head"] = dense(keys[1], (d, config.vocab_size), std)
    for i in range(config.n_layers):
        ks = jax.random.split(keys[2 + i], 8)
        # What only GLM-4.7-Flash's and Trinity-Mini's lines have draws
        # from keys of its own, so that the older architectures' weights
        # are what they were.
        more = jax.random.split(jax.random.fold_in(keys[2 + i], 1), 8)
        layer = {"attn_norm": jnp.ones((d,), config.dtype)}
        if config.attn_layout and config.attn_layout[i] in ("kda", "ssm"):
            from . import block
            layer["attn"] = block.recurrent(config, i).init(
                config, keys[2 + i])
        elif config.kv_lora_rank:
            rq, rkv = config.q_lora_rank, config.kv_lora_rank
            o_in = config.n_heads * config.v_head_dim
            layer["attn"] = {
                **({"wq_a": dense(ks[0], (d, rq), std),
                    "q_norm": jnp.ones((rq,), config.dtype),
                    "wq_b": dense(ks[1], (rq, q_out), rq ** -0.5)} if rq
                   else {"wq": dense(ks[0], (d, q_out), std)}),
                "wkv_a": dense(ks[2], (d, rkv + config.qk_rope_head_dim),
                               std),
                "kv_norm": jnp.ones((rkv,), config.dtype),
                "wkv_b": dense(more[0], (rkv, config.n_heads * (
                    config.qk_nope_head_dim + config.v_head_dim)),
                    rkv ** -0.5),
                "wo": dense(ks[3], (o_in, d), o_in ** -0.5),
            }
        else:
            layer["attn"] = {
                "wq": dense(ks[0], (d, q_out), std),
                "wk": dense(ks[1], (d, kv_out), std),
                "wv": dense(ks[2], (d, kv_out), std),
                "wo": dense(ks[3], (q_out, d), q_out ** -0.5),
            }
        if config.qk_norm:
            layer["attn"].update(qk_norm_init(config))
        if config.attn_gate:
            layer["attn"]["wg"] = dense(more[5], (d, q_out), std)
        if config.post_norm:
            # At the embedding's scale: a half-block joins a stream whose
            # embedding was multiplied by ``embed_scale``, and with ones
            # here the layers of a seeded model would be a fiftieth of it
            # (no comparison with a reference would see a fault in one).
            for name in ("attn_post_norm", "ffn_post_norm"):
                layer[name] = jnp.full((d,), config.embed_scale,
                                       config.dtype)
        params["layers"].append(layer)
        if config.ffn_layout and not config.ffn_layout[i]:  # a dense layer
            layer["mlp_norm"] = jnp.ones((d,), config.dtype)
            layer["mlp"] = swiglu(ks[5:], config.dense_d_ff)
            continue
        layer["moe_norm"] = jnp.ones((d,), config.dtype)
        layer["moe"] = {
            # Router in fp32: tiny, and top-k boundaries are precision
            # sensitive.
            "router": jax.random.normal(ks[4], (d, routed_E),
                                        jnp.float32) * std,
            "w1": dense(ks[5], (E, d, f), std),
            "w3": dense(ks[6], (E, d, f), std),
            "w2": dense(ks[7], (E, f, d), f ** -0.5),
        }
        if config.router_score == "sigmoid":
            # Not zero (that would leave "the choice only" untested) and
            # small against the scores' spread: the k-th and next score
            # of E lie about 1 / E apart, so a bias of that size flips
            # near ties and leaves the experts' load as the router has it
            # (the published bias exists to even that load, not skew it).
            layer["moe"]["router_bias"] = jax.random.normal(
                more[1], (routed_E,), jnp.float32) / (2 * routed_E)
        if config.n_shared_experts:
            layer["moe"]["shared"] = swiglu(
                more[2:], config.n_shared_experts * f)
    return params


def moe_sharding_rules() -> ShardingRules:
    """Llama rules + expert weights sharded over (ep, fsdp, tp): each ep
    shard owns E/ep experts; within an expert the FFN shards like megatron.
    The router is tiny and replicated."""
    base = llama_sharding_rules().rules
    return ShardingRules([
        (r"moe/router", P()),
        (r"moe/(w1|w3)", P(AXIS_EP, AXIS_FSDP, AXIS_TP)),
        (r"moe/w2", P(AXIS_EP, AXIS_TP, AXIS_FSDP)),
        *base,
    ])


def router_logits(moe: Params, xf: jax.Array) -> jax.Array:
    """The router's logits [..., E] on tokens xf [..., d], in float32 (a
    float32 product too: the TPU's default would round the router's weights
    to bfloat16, and which experts are the top k turns on differences that
    small)."""
    return jnp.matmul(xf.astype(jnp.float32), moe["router"],
                      precision=jax.lax.Precision.HIGHEST)


def _route(config: MoEConfig, moe: Params, xf: jax.Array,
           logits: Optional[jax.Array] = None
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The router on tokens xf [G, d] (or on ``logits`` [G, E] taken
    earlier in the layer, ``router_before_attn``): each expert's share of
    the router's mass [G, E] (the softmax over all experts; of sigmoid
    scores, each over their sum), and each token's top-k weights and
    experts [G, k] (renormalised to sum to 1 only where the architecture
    does, then scaled by ``routed_scaling_factor``).  Sigmoid scores are
    ranked with the layer's ``router_bias`` added; the weights are the
    scores without it."""
    if logits is None:
        logits = router_logits(moe, xf)
    if config.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, top_e = jax.lax.top_k(scores + moe["router_bias"], config.top_k)
        top_p = jnp.take_along_axis(scores, top_e, axis=-1)
        probs = scores / scores.sum(-1, keepdims=True)
        if config.norm_topk_prob:
            top_p = top_p / (top_p.sum(-1, keepdims=True) + 1e-20)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, config.top_k)
        if config.norm_topk_prob:
            top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    if config.routed_scaling_factor != 1.0:
        top_p = top_p * config.routed_scaling_factor
    return probs, top_p, top_e


def _shared_expert(config: MoEConfig, shared: Params, x: jax.Array,
                   valid: Optional[jax.Array] = None) -> jax.Array:
    """The experts every token visits, on x [..., d]: ``n_shared_experts``
    SwiGLUs of ``d_ff``, which is one of their widths together.  No router
    sees them and no counter counts them; a row that holds no token
    (``valid`` false) gets zero, as from the routed experts."""
    with jax.named_scope("moe_shared"):
        out = _mlp({"mlp": shared}, x)
        if valid is not None:
            out = jnp.where(valid[..., None], out, 0)
    return out.astype(config.dtype)


#: The most rows an expert gets, on average, where its weights are streamed
#: past a step's rows held whole in VMEM.
STREAM_ROWS_AN_EXPERT = 4


def _streams_experts(config: MoEConfig, pairs: int) -> Optional[str]:
    """Which of ``ops.grouped_ffn``'s kernels does the grouped products over
    ``pairs`` (token, expert) rows, each reading every hit expert's weights
    once; None: three ``ragged_dot`` calls.  The one place that chooses, by
    what it can see.  ``pairs`` is the program's static count, so one
    program holds one form.

    A kernel needs a TPU, widths its DMAs can cut (whole lane tiles) and a
    program of one device (XLA does not partition a Mosaic call: under a
    mesh the products stay ``ragged_dot``).  Then ``"stream"`` where an
    expert gets a few rows (the work is reading the hit experts' weights:
    every decode program, 96-128 pairs over 64 experts); where the program
    holds a share of the router's experts, ``pairs`` are the pairs routed,
    of which its share lands here, so the rows an expert gets are ``pairs``
    over the ROUTER's width.  ``"rows"`` for more (a prefill bucket, a
    chunk, the full forward, a training batch: the rows pass each expert in
    MXU-sized blocks), unless an expert does not fit VMEM twice
    (``grouped_ffn.holds_an_expert``) or the program holds a SHARE of its
    router's experts: seven eighths of Kimi-Linear's sorted rows are other
    chips' pairs, and compacting them belongs with the exchange between the
    chips (ROADMAP M2); its prefills keep ``ragged_dot``."""
    if not (grouped_ffn.on_tpu() and auto_mesh() is None
            and config.d_model % grouped_ffn.LANES == 0
            and config.d_ff % grouped_ffn.LANES == 0):
        return None
    if pairs <= STREAM_ROWS_AN_EXPERT * config.router_width:
        return "stream"
    if config.router_width > config.n_experts or not \
            grouped_ffn.holds_an_expert(config.d_model, config.d_ff,
                                        config.dtype):
        return None
    return "rows"


#: The forms of the grouped products, by ``grouped_form``'s names.
GROUPED = {"stream": grouped_ffn.grouped_ffn_stream,
           "rows": grouped_ffn.grouped_ffn_rows,
           "ragged_dot": grouped_ffn.grouped_ffn_ragged}


def grouped_form(config: MoEConfig, tokens: int) -> str:
    """The form the grouped products of a program over ``tokens`` rows
    take, by name: ``"stream"``, ``"rows"`` or ``"ragged_dot"``
    (``LLMServer.stats()["grouped_ffn"]`` of the decode program,
    ``["grouped_ffn_prefill"]`` of the prefills)."""
    return _streams_experts(config, tokens * config.top_k) or "ragged_dot"


def _moe_ffn(config: MoEConfig, moe: Params, x: jax.Array,
             valid: Optional[jax.Array] = None,
             logits: Optional[jax.Array] = None
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k expert FFN over x [..., d]: every token reaches all ``top_k``
    of its experts at any load.  Returns (out, aux_loss, counts), counts
    [E] int32 = the tokens each expert got.  Where the program holds a
    share of the router's experts (``router_width`` > ``n_experts``) the
    pairs of the others are dropped as a padded row's are, ``out`` is the
    partial sum over the held ones, and ``counts`` has one more entry
    behind the held experts': the pairs routed, here or elsewhere.

    ``valid`` [...] bool marks the real tokens of a statically shaped batch
    (the serving programs' padded prompt rows and empty slots): the others
    are sorted behind every group, so no expert multiplies them, they are
    in no count, and their output is zero.  ``logits`` [..., E] are the
    router's where the layer took them before attention."""
    lead, d = x.shape[:-1], x.shape[-1]
    E, k = config.n_experts, config.top_k
    routed_E = config.router_width
    share = routed_E > E
    xf = x.reshape(-1, d)
    G = xf.shape[0]
    with jax.named_scope("moe_ffn"):
        probs, top_p, top_e = _route(
            config, moe, xf,
            None if logits is None else logits.reshape(G, routed_E))
        if share:  # the held experts' own numbers; the others' as padding
            first = top_e[:, 0]
            routed = jnp.asarray(G * k, jnp.int32)
            if valid is not None:
                first = jnp.where(valid.reshape(G), first, routed_E)
                routed = k * jnp.sum(valid, dtype=jnp.int32)
            top_e = top_e - config.first_expert
            top_e = jnp.where((top_e >= 0) & (top_e < E), top_e, E)
        if valid is not None:
            top_e = jnp.where(valid.reshape(G, 1), top_e, E)
        # Sort the G*k pairs by expert (stable: token order inside a
        # group); ``rank`` is where each pair went.
        pair_e = top_e.reshape(G * k)
        order = jnp.argsort(pair_e, stable=True)
        rank = jnp.zeros_like(order).at[order].set(jnp.arange(G * k))
        counts = jnp.sum(pair_e[:, None] == jnp.arange(E)[None, :], axis=0,
                         dtype=jnp.int32)
        xs = xf[order // k]                                    # [G*k, d]

        ys = GROUPED[grouped_form(config, G)](
            xs, moe["w1"], moe["w3"], moe["w2"], counts,
            act=config.expert_act)
        ys = ys[rank].reshape(G, k, d)                         # float32
        if valid is not None or share:  # rows behind the last group are
            ys = jnp.where((top_e < E)[..., None], ys, 0.0)  # not written
        out = jnp.einsum("gk,gkd->gd", top_p, ys).astype(config.dtype)

        # Switch load-balancing loss: E * sum_e f_e * P_e, where f_e is the
        # fraction of tokens whose TOP-1 choice is e and P_e the mean router
        # probability for e.
        top1 = jax.nn.one_hot(first if share else top_e[:, 0], routed_E,
                              dtype=jnp.float32)
        aux = routed_E * jnp.sum(top1.mean(0) * probs.mean(0))
        if share:
            counts = jnp.concatenate([counts, routed[None]])
    return out.reshape(*lead, d), aux, counts


def moe_apply(config: MoEConfig, params: Params, tokens: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    """Returns (logits [B, S, vocab] fp32, aux_loss scalar)."""
    from . import block

    x, auxes = hidden_and_aux(config, params, tokens)
    logits = block.head(config, params, x)
    auxes = [a for a in auxes if a is not None]  # a dense layer has none
    return logits, sum(auxes, jnp.zeros((), jnp.float32)) \
        / max(len(auxes), 1)


def moe_loss(config: MoEConfig, params: Params, tokens: jax.Array,
             targets: jax.Array, ignore_index: int = -100) -> jax.Array:
    """LM cross entropy + weighted load-balancing aux loss."""
    from ..ops.losses import masked_cross_entropy

    logits, aux = moe_apply(config, params, tokens)
    nll = masked_cross_entropy(logits, targets, ignore_index)
    return nll + config.aux_loss_coeff * aux
