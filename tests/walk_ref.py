"""What the tests of the one page walk (``ray_tpu/ops/page_walk.py``) share,
written once: seeded pools and shuffled tables, the gather form the four
fronts are held to (``paged._attend_pages`` on the same pool, table and
``visible``), the float64 arithmetic both approximate, a front's kernel
jitted once a setting of its blocks, the poisoning of the pages a walk
leaves out, and the tiny configurations with their decode step and prefill
calls through the programs.  A LATENT is the K/V pair with one pool and one
head (``tests/test_page_walk.py``), so every helper takes ``v=None`` for it.
The files keep their names, and their scenarios: a file is what pytest-xdist
deals out."""

import contextlib
import functools
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.models import init_and_apply, paged

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYER = 1  # of the two layers every seeded pool has: the other is poisoned


def seeded(shape, dtype, seed, used=None, scale=1.0):
    """Unit draws (times ``scale``) from ``seed`` (or from a generator, which
    goes on), zero past the ``used`` first numbers of a row: a latent row's
    padding, zero on both sides of the product."""
    x = scale * np.random.default_rng(seed).standard_normal(shape, np.float32)
    if used is not None:
        x[..., used:] = 0
    return jnp.asarray(x, dtype)


@functools.lru_cache(maxsize=None)
def pool_and_table(dtype, page, entries, row, used=None, pairs=1, seed=0):
    """``pairs`` seeded pools of two layers with rows of shape ``row`` (K and
    V, or the one latent pool) and a table of ``entries`` that names its
    pages out of order; five pages to spare, the last the scratch page."""
    rng = np.random.default_rng(seed)  # one stream: the pools, the table
    pools = [seeded((2, entries + 5, page, *row), dtype, rng, used)
             for _ in range(pairs)]
    return (*pools, rng.permutation(entries + 4)[:entries].astype(np.int32))


def _as_pair(k, v, rank):
    """(K, V) with a head axis: a latent pool is one head whose first
    ``rank`` columns are its values."""
    return (k, v) if v is not None else (k[..., None, :], k[..., None, :rank])


@functools.lru_cache(maxsize=None)
def _gathered(attend, n_heads, n_kv_heads, head_dim, rank):
    """``attend`` on layer ``LAYER`` as one program a shape (eagerly, every
    operation of it is compiled a shape)."""
    cfg = types.SimpleNamespace(n_heads=n_heads, n_kv_heads=n_kv_heads,
                                head_dim=head_dim, kv_lora_rank=rank)
    return jax.jit(lambda q, k, v, tables, visible: attend(
        cfg, q, k, v, LAYER, tables, visible))


def gather_form(q, k, v, tables, visible, head_dim, rank=None,
                attend=paged._attend_pages):
    """``q`` [B, Q, H, W] through the gather form on layer ``LAYER``:
    float32 [B, Q, H, the values' width]."""
    B, Q, H, _ = q.shape
    out = _gathered(attend, H, H if v is None else k.shape[3], head_dim,
                    rank)(q, k, v, jnp.asarray(tables), visible)
    return np.asarray(out.reshape(B, Q, H, -1), np.float32)


def float64_form(q, k, v, tables, visible, head_dim, rank=None):
    """The arithmetic itself on the operands as they are rounded, in
    float64: what both forms approximate.  ``q`` [B, Q, H, W]."""
    k, v = _as_pair(k, v, rank)
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    B, Q, H, _ = q.shape
    n_rep = H // k.shape[3]
    out = np.zeros((B, Q, H, v.shape[-1]))
    for b in range(B):
        ks = k[LAYER, tables[b]].reshape(-1, *k.shape[3:])
        vs = v[LAYER, tables[b]].reshape(-1, *v.shape[3:])
        for h in range(H):
            s = q[b, :, h] @ ks[:, h // n_rep].T * head_dim ** -0.5
            s[~np.asarray(visible[b])] = -np.inf
            p = np.exp(s - s.max(axis=1, keepdims=True))
            out[b, :, h] = (p / p.sum(axis=1, keepdims=True)) \
                @ vs[:, h // n_rep]
    return out


def attend_in(acc):
    """``_attend_pages`` with both products accumulated in ``acc``."""
    def attend(cfg, q, k_pool, v_pool, layer, tables, visible):
        B, Q, H, W = q.shape
        k_pool, v_pool = _as_pair(k_pool, v_pool, cfg.kv_lora_rank)
        n_kv = k_pool.shape[3]
        k_seq = k_pool[layer, tables].reshape(B, -1, n_kv, W)
        v_seq = v_pool[layer, tables].reshape(B, -1, n_kv, v_pool.shape[-1])
        qg = q.reshape(B, Q, n_kv, H // n_kv, W)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k_seq,
                            preferred_element_type=acc).astype(jnp.float32) \
            * (cfg.head_dim ** -0.5)
        scores = jnp.where(visible[:, None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(v_seq.dtype)
        return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v_seq,
                          preferred_element_type=acc)
    return attend


def accumulates_in_float32(kernel, q, k, v, tables, visible, head_dim, tol,
                           rank=None):
    """Against float64 arithmetic on the same bfloat16 operands the kernel's
    output is as close as the gather form (the rounding of the probabilities
    and of the output); a form that accumulates its products in bfloat16 is
    not, by the tolerance the kernel passes."""
    exact = float64_form(q, k, v, tables, visible, head_dim, rank)

    def off(out):
        return float(np.abs(out.reshape(exact.shape) - exact).max())

    def gathered(**kw):
        return gather_form(q, k, v, tables, visible, head_dim, rank, **kw)

    assert off(kernel) < tol
    assert off(gathered()) < tol
    assert off(gathered(attend=attend_in(jnp.float32))) < tol
    assert off(gathered(attend=attend_in(jnp.bfloat16))) > 3 * tol


def poisoned(pool, live):
    """``pool`` with NaN in every page of every layer except the pages
    ``live`` of ``LAYER``."""
    pool = np.array(pool)
    pool[:, [p for p in range(pool.shape[1]) if p not in live]] = np.nan
    pool[1 - LAYER] = np.nan
    return jnp.asarray(pool)


@functools.lru_cache(maxsize=None)
def blocked(front, attention, blocks=(), **static):
    """``front``'s ``attention`` on layer ``LAYER``, jitted once a setting
    ``blocks`` ((name, value) pairs) of the front's block sizes and a
    ``static`` geometry: called with (the queries and the pools), then
    tables, positions and lengths, which are data, so a later case of the
    same shapes is not traced again."""
    def call(operands, *walk):
        was = {name: getattr(front, name) for name, _ in blocks}
        for name, value in blocks:
            setattr(front, name, value)
        try:
            return attention(*operands, LAYER, *walk, **static)
        finally:
            for name, value in was.items():
                setattr(front, name, value)
    return jax.jit(call)


# ----------------------------------------------------- through the programs

TINY_PAGE, TINY_SEQ = 8, 64


@functools.lru_cache(maxsize=None)
def tiny(name, max_seq=TINY_SEQ, layers=None, **over):
    """The benchmark's tiny configuration of that name, in float32, with
    ``over`` (heads of 128, or a latent of 128: what the kernels' DMAs move
    whole) and, where ``layers`` is given, that many of its layers: its
    pattern, window (8) and everything else as the rehearsal runs them."""
    from benchmarks import spec

    model = {**spec.load_json(os.path.join(
        ROOT, "benchmarks", "configs", name + ".json")), **over}
    if layers:
        model = {**model, "num_hidden_layers": layers, **{
            k: model[k][:layers] for k in (
                "rope_layout", "sliding_window_layout", "layer_types")
            if k in model}}
    return spec.family(model).program_config(model, remat=False,
                                             max_seq=max_seq)


#: The K/V-pair families with window layers: (query heads over the two KV
#: heads, layers).  One period of SmallThinker's pattern (a whole-length
#: layer before three rings) of its two, the whole of Trinity-Mini's five
#: layers (three rings to a whole-length layer, and one more).
PAIR_TINY = {"smallthinker-tiny": (14, 4), "trinity-mini-tiny": (16, None)}


def tiny_pair(name, max_seq=TINY_SEQ, whole=False):
    """``tiny`` with heads of 128 (what the kernels' DMAs move whole);
    ``whole``: every layer of the configuration."""
    heads, layers = PAIR_TINY[name]
    return tiny(name, max_seq, None if whole else layers, head_dim=128,
                num_attention_heads=heads)


def ssm_toy(**over):
    """One period of a state-space model at toy widths whose state is whole
    (8, 128) tiles, what the two state-space kernels can cut: 3 Mamba
    layers around 1 attention layer, in float32."""
    from ray_tpu.models import MoEConfig

    return MoEConfig(**{**dict(
        vocab_size=128, d_model=32, n_layers=4, n_heads=2, n_kv_heads=1,
        attn_layout=("ssm", "ssm", "kv", "ssm"), ssm_inner=128, ssm_state=16,
        ssm_dt_rank=4, ssm_conv=4, rope_layout=(0,) * 4,
        ffn_layout=(0,) * 4, dense_d_ff=48, d_ff=48, n_experts=1, top_k=1,
        tie_embeddings=True, max_seq=64, dtype=jnp.float32, remat=False),
        **over})


@functools.lru_cache(maxsize=None)
def model_of(cfg, pages, page, ring_pages=0, state_slots=0):
    """(parameters, pools of seeded rows, adapters) of a configuration, made
    once: a latent pool's rows are zero past what a row uses."""
    params = jax.jit(init_and_apply(cfg)[0], static_argnums=0)(
        cfg, jax.random.PRNGKey(0))
    pools = paged.init_paged_pools(cfg, pages, page, ring_pages,
                                   state_slots=state_slots)
    used = {"kv": cfg.kv_lora_rank + cfg.qk_rope_head_dim} \
        if "kv" in pools else {}
    pools = {name: seeded(x.shape, x.dtype, i, used.get(name))
             for i, (name, x) in enumerate(sorted(pools.items()))}
    return params, pools, paged.init_adapter_pool(cfg, 1, 2)


@contextlib.contextmanager
def as_on_a_tpu(steer, walk):
    """``steer.on_tpu`` answers ``walk`` (the one thing a CPU cannot see),
    the kernels interpreted."""
    was, steer.on_tpu = steer.on_tpu, lambda: walk
    try:
        with pltpu.force_tpu_interpret_mode():
            yield
    finally:
        steer.on_tpu = was


@functools.lru_cache(maxsize=None)
def program(fn, walk):
    """jit keeps a trace by its function and arguments, not by what
    ``on_tpu`` answered while it was traced: one function a form, jitted
    once."""
    return jax.jit(lambda *args: fn(*args), static_argnums=0)


def _step_and_logits(cfg, params, pools, adapters, tokens, tables, lens,
                     active, rings, shared=False):
    """``paged_decode_step`` (``shared``: in the shared form) at temperature
    0 with the logits it sampled from, looked at on their way."""
    seen, real = [], paged.decode_logits

    def decode_logits(*args, **kwargs):
        seen.append(real(*args, **kwargs))
        return seen[-1]

    paged.decode_logits = decode_logits
    try:
        b = len(lens)
        out, *_ = paged.paged_decode_step.__wrapped__(
            cfg, params, dict(pools), adapters, tokens, tables, lens, active,
            jnp.zeros((b,), jnp.float32), jnp.ones((b,), jnp.int32),
            jax.random.PRNGKey(2), rings, shared=shared)
    finally:
        paged.decode_logits = real
    return out, seen[0][0]


def decode(cfg, steer, walk, model, tables, lens, rings=None, shared=False):
    """One decode step over slots of ``lens`` cached rows on the ``model``
    (``model_of``): (tokens and counters, logits).  ``walk``: as on a TPU
    (``steer``'s ``on_tpu`` answers true), the kernel interpreted;
    ``shared``: the program in the shared form."""
    lens = np.asarray(lens, np.int32)
    step = functools.partial(_step_and_logits, shared=shared)
    with as_on_a_tpu(steer, walk):
        out, logits = program(step, walk)(
            cfg, *model, jnp.arange(len(lens), dtype=jnp.int32) + 7,
            jnp.asarray(tables), jnp.asarray(lens), jnp.asarray(lens > 0),
            None if rings is None else jnp.asarray(rings))
        assert paged.decode_attention_form(cfg, shared) == \
            (("walk+shared" if shared else "walk") if walk else "gather")
    return np.asarray(out), np.asarray(logits)


def prefill(cfg, steer, walk, prompt, chunk, first=0):
    """A prompt's rows from ``first`` on through the prefill programs,
    ``chunk`` rows a call, into pools of seeded rows (what lies before
    ``first`` is a prefix hit's cached pages): (the logits of each call, the
    attention pools' real rows after the last, by pool).  ``walk``: as on a
    TPU (``steer``'s ``on_tpu`` answers true), the kernel interpreted, and
    every call the suffix program's, a prompt's first rows at ``prefix_len``
    0 (the engine's rule); else the cold program and then the suffix program
    in the gather form."""
    maxp = TINY_SEQ // TINY_PAGE
    ring = paged.ring_entries(cfg, TINY_PAGE, chunk) \
        if paged.kv_layers(cfg)[1] else 0
    state = 2 if paged.state_layers(cfg) else 0
    params, pools, adapters = model_of(cfg, 2 * maxp, TINY_PAGE, 2 * ring,
                                       state)
    table = jnp.arange(maxp, dtype=jnp.int32)[::-1] + maxp
    rings = jnp.arange(ring, dtype=jnp.int32)[::-1] + ring if ring else None
    tokens = np.random.default_rng(3).integers(1, 500, prompt)
    slot = jnp.int32(1) if state else None
    logits = []
    with as_on_a_tpu(steer, walk):
        for start in range(first, prompt, chunk):
            end = min(start + chunk, prompt)
            toks = np.zeros((1, chunk), np.int32)
            toks[0, :end - start] = tokens[start:end]
            args = (cfg, params, pools, adapters, jnp.asarray(toks))
            tail = (jnp.int32(end), table, jnp.int32(1), rings, slot)
            out, pools, _ = program(paged.prefill_prefix_logits, walk)(
                *args, jnp.int32(start), *tail) if start or walk \
                else program(paged.prefill_logits, walk)(*args, *tail)
            logits.append(np.asarray(out))
        assert paged.prefill_attention_form(cfg) == \
            ("walk" if walk else "gather")
    # The real rows the calls left: every position of the whole-length
    # pools, of the rings what the last call's ring still holds.
    kept = max(0, (prompt - 1) // TINY_PAGE - ring + 1) * TINY_PAGE
    held = {}
    for name, x in pools.items():
        if name in ("S", "conv"):
            continue
        at = np.arange(kept if name[-1] == "w" else 0, prompt)
        pages = np.asarray(rings)[(at // TINY_PAGE) % ring] \
            if name[-1] == "w" else np.asarray(table)[at // TINY_PAGE]
        held[name] = np.asarray(x)[:, pages, at % TINY_PAGE]
    return logits, held


def same_prefills(cfg, steer, prompt, chunk, first=0):
    """The prefill calls as a TPU takes them against the gather form: the
    same logits after every call and the same rows left in the pools, within
    3e-5 of each one's scale."""
    walked, pools = prefill(cfg, steer, True, prompt, chunk, first)
    gathered, ref_pools = prefill(cfg, steer, False, prompt, chunk, first)
    assert len(walked) == -(-(prompt - first) // chunk)
    for out, ref in zip(walked, gathered):
        scale = float(np.abs(ref).max())
        np.testing.assert_allclose(out, ref, atol=3e-5 * scale, rtol=0)
    assert set(pools) == set(ref_pools) and ref_pools
    for name, ref in ref_pools.items():
        scale = float(np.abs(ref).max())
        np.testing.assert_allclose(pools[name], ref, atol=3e-5 * scale,
                                   rtol=0, err_msg=name)
