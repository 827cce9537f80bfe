"""Kernel correctness tests (CPU: pallas interpret mode + jnp references;
ring attention on the virtual 8-device mesh)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.ops import (
    apply_rotary,
    flash_attention,
    mha_reference,
    ring_attention,
    rms_norm,
    rope_frequencies,
)
from ray_tpu.ops.attention import _flash
from ray_tpu.ops.norms import rms_norm_pallas
from ray_tpu.parallel import MeshConfig, make_mesh


def _qkv(B=2, H=4, Hkv=None, S=256, D=64, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    Hkv = Hkv or H
    q = jax.random.normal(ks[0], (B, H, S, D), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), dtype)
    return q, k, v


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_forward_matches_reference(self, causal):
        q, k, v = _qkv()
        ref = mha_reference(q, k, v, causal=causal)
        out = _flash(q, k, v, q.shape[-1] ** -0.5, causal, 0, 128, 128, True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_gqa_forward(self):
        q, k, v = _qkv(H=8, Hkv=2)
        ref = mha_reference(q, k, v, causal=True)
        out = _flash(q, k, v, q.shape[-1] ** -0.5, True, 0, 128, 128, True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_q_offset(self):
        """Q block at a global offset vs K (sequence-parallel caller)."""
        q, k, v = _qkv(S=128)
        qh = q[:, :, :64]
        ref = mha_reference(qh, k, v, causal=True, q_offset=64)
        out = _flash(qh, k, v, q.shape[-1] ** -0.5, True, 64, 64, 64, True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_backward_matches_reference(self):
        q, k, v = _qkv(B=1, H=2, S=128, D=64)

        def loss_flash(q, k, v):
            out = _flash(q, k, v, q.shape[-1] ** -0.5, True, 0, 64, 64, True)
            return jnp.sum(out * jnp.cos(out))

        def loss_ref(q, k, v):
            out = mha_reference(q, k, v, causal=True)
            return jnp.sum(out * jnp.cos(out))

        g1 = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
        g2 = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)

    def test_gqa_backward(self):
        q, k, v = _qkv(B=1, H=4, Hkv=2, S=128, D=64)

        def loss(fn):
            def f(q, k, v):
                return jnp.sum(fn(q, k, v) ** 2)
            return f

        flash_fn = lambda q, k, v: _flash(
            q, k, v, q.shape[-1] ** -0.5, True, 0, 64, 64, True
        )
        ref_fn = lambda q, k, v: mha_reference(q, k, v, causal=True)
        g1 = jax.jit(jax.grad(loss(flash_fn), argnums=(0, 1, 2)))(q, k, v)
        g2 = jax.jit(jax.grad(loss(ref_fn), argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)

    @pytest.mark.parametrize("q_offset", [0, 64])
    def test_gqa_backward_in_q_chunks(self, monkeypatch, q_offset):
        """dK/dV accumulate over q chunks and over the GQA group's heads
        (the two innermost grid dims), as they do past DKV_CHUNK_ROWS at
        long sequences: 32-row chunks forced onto 128 tokens."""
        from ray_tpu.ops import attention as att

        monkeypatch.setattr(att, "DKV_CHUNK_ROWS", 32)
        assert att._dkv_chunk(128, 16) == 32
        q, k, v = _qkv(B=1, H=4, Hkv=2, S=128, D=32, seed=9)

        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

        flash_fn = lambda q, k, v: _flash(
            q, k, v, q.shape[-1] ** -0.5, True, q_offset, 16, 32, True)
        ref_fn = lambda q, k, v: mha_reference(
            q, k, v, causal=True, q_offset=q_offset)
        g1 = jax.jit(jax.grad(loss(flash_fn), argnums=(0, 1, 2)))(q, k, v)
        g2 = jax.jit(jax.grad(loss(ref_fn), argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)

    def test_dkv_chunk_divides_the_sequence(self):
        from ray_tpu.ops.attention import _dkv_chunk

        assert _dkv_chunk(2048, 512) == 2048   # b1: the whole sequence
        assert _dkv_chunk(8192, 512) == 2048
        assert _dkv_chunk(3072, 512) == 1536   # largest divisor <= 2048
        assert _dkv_chunk(128, 128) == 128

    def test_dispatch_cpu_fallback(self):
        q, k, v = _qkv(S=64)
        out = flash_attention(q, k, v)  # CPU -> reference path
        ref = mha_reference(q, k, v)
        np.testing.assert_allclose(out, ref, atol=1e-6)


class TestRingAttention:
    @pytest.mark.slow  # full-attention sweep: ~10s on a loaded CPU host
    def test_matches_full_attention(self):
        mesh = make_mesh(MeshConfig(fsdp=1, sp=8, dp=1, tp=1))
        B, H, S, D = 2, 4, 256, 32
        q, k, v = _qkv(B=B, H=H, S=S, D=D, seed=3)
        ref = mha_reference(q, k, v, causal=True)

        from ray_tpu.parallel.pipeline import shard_map

        ring = shard_map(
            functools.partial(ring_attention, axis_name="sp", causal=True),
            mesh=mesh,
            in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None),
            check_vma=False,
        )
        out = ring(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    @pytest.mark.slow  # ring-attention grad: ~30s on a loaded CPU host
    def test_grad_flows(self):
        mesh = make_mesh(MeshConfig(fsdp=1, sp=8))
        q, k, v = _qkv(B=1, H=2, S=128, D=32)
        from ray_tpu.parallel.pipeline import shard_map

        ring = shard_map(
            functools.partial(ring_attention, axis_name="sp", causal=True),
            mesh=mesh,
            in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None),
            check_vma=False,
        )

        def loss_ring(q, k, v):
            return jnp.sum(ring(q, k, v) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

        g1 = jax.grad(loss_ring)(q, k, v)
        g2 = jax.grad(loss_ref)(q, k, v)
        np.testing.assert_allclose(g1, g2, atol=1e-4, rtol=1e-4)

    def test_fused_kernel_forward_matches(self):
        """The fused ring+flash path (Pallas kernels under the joint custom
        VJP), forced on CPU via interpret mode."""
        import numpy as _np
        from jax.sharding import Mesh as _Mesh

        B, H, S, D = 1, 2, 256, 32
        q, k, v = _qkv(B=B, H=H, S=S, D=D, seed=5)
        ref = mha_reference(q, k, v, causal=True)
        from ray_tpu.parallel.pipeline import shard_map

        mesh4 = _Mesh(_np.array(jax.devices()[:4]).reshape(1, 1, 1, 4),
                      ("dp", "fsdp", "tp", "sp"))
        ring = shard_map(
            functools.partial(ring_attention, axis_name="sp", causal=True,
                              force_kernel=True, interpret=True),
            mesh=mesh4,
            in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None),
            check_vma=False,
        )
        out = jax.jit(ring)(q, k, v)  # one program, not one an operation
        np.testing.assert_allclose(out, ref, atol=2e-2, rtol=2e-2)

    @pytest.mark.slow  # fused-kernel grad check: ~20s on a loaded CPU host
    def test_fused_kernel_grad_matches(self):
        import numpy as _np
        from jax.sharding import Mesh as _Mesh

        mesh4 = _Mesh(_np.array(jax.devices()[:4]).reshape(1, 1, 1, 4),
                      ("dp", "fsdp", "tp", "sp"))
        q, k, v = _qkv(B=1, H=2, S=256, D=32, seed=6)
        from ray_tpu.parallel.pipeline import shard_map

        ring = shard_map(
            functools.partial(ring_attention, axis_name="sp", causal=True,
                              force_kernel=True, interpret=True),
            mesh=mesh4,
            in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None),
            check_vma=False,
        )

        def loss_ring(q, k, v):
            return jnp.sum(ring(q, k, v) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

        g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=5e-2, rtol=5e-2)

    @pytest.mark.slow  # fused-kernel GQA grad: ~20s on a loaded CPU host
    def test_fused_kernel_gqa_grad(self):
        """GQA (fewer KV heads) through the fused ring kernels."""
        import numpy as _np
        from jax.sharding import Mesh as _Mesh

        mesh4 = _Mesh(_np.array(jax.devices()[:4]).reshape(1, 1, 1, 4),
                      ("dp", "fsdp", "tp", "sp"))
        key = jax.random.PRNGKey(7)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (1, 4, 256, 32), jnp.float32)
        k = jax.random.normal(kk, (1, 2, 256, 32), jnp.float32)
        v = jax.random.normal(kv, (1, 2, 256, 32), jnp.float32)
        from ray_tpu.parallel.pipeline import shard_map

        ring = shard_map(
            functools.partial(ring_attention, axis_name="sp", causal=True,
                              force_kernel=True, interpret=True),
            mesh=mesh4,
            in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None),
            check_vma=False,
        )

        def loss_ring(q, k, v):
            return jnp.sum(ring(q, k, v) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

        g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=5e-2, rtol=5e-2)


class TestNormsRotary:
    def test_rms_norm_pallas_matches(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (64, 128))
        w = jax.random.normal(jax.random.PRNGKey(1), (128,)) + 1.0
        np.testing.assert_allclose(
            rms_norm_pallas(x, w, interpret=True), rms_norm(x, w),
            atol=1e-6, rtol=1e-6,
        )

    def test_rotary_norm_preserving(self):
        cos, sin = rope_frequencies(64, 128)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 128, 64))
        y = apply_rotary(x, cos, sin)
        # Rotation preserves the norm of each (x1[i], x2[i]) pair.
        np.testing.assert_allclose(
            jnp.linalg.norm(y, axis=-1), jnp.linalg.norm(x, axis=-1),
            atol=1e-4, rtol=1e-4,
        )

    def test_rotary_relative_property(self):
        """q·k after RoPE depends only on relative positions."""
        cos, sin = rope_frequencies(32, 64)
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 1, 32))
        k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 32))
        def dot_at(p_q, p_k):
            qq = apply_rotary(q, cos, sin, position_offset=p_q)
            kk = apply_rotary(k, cos, sin, position_offset=p_k)
            return float(jnp.sum(qq * kk))
        assert abs(dot_at(5, 3) - dot_at(10, 8)) < 1e-4


class TestMeshSharding:
    def test_mesh_resolve(self):
        assert MeshConfig(fsdp=-1).resolve(8) == {
            "dp": 1, "fsdp": 8, "tp": 1, "sp": 1, "ep": 1, "pp": 1
        }
        assert MeshConfig(dp=2, fsdp=-1, tp=2).resolve(8) == {
            "dp": 2, "fsdp": 2, "tp": 2, "sp": 1, "ep": 1, "pp": 1
        }
        with pytest.raises(ValueError):
            MeshConfig(dp=3).resolve(8)

    def test_make_mesh(self):
        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
        assert mesh.devices.shape == (2, 2, 2, 1, 1, 1)
        assert mesh.axis_names == ("dp", "fsdp", "tp", "sp", "ep", "pp")

    def test_sharding_rules(self):
        from ray_tpu.parallel import ShardingRules

        rules = ShardingRules([
            (r"attn/(wq|wk|wv)", P("fsdp", "tp")),
            (r"attn/wo", P("tp", "fsdp")),
            (r"embed", P("tp", "fsdp")),
        ])
        params = {
            "layers_0": {"attn": {"wq": jnp.zeros((8, 8)),
                                  "wo": jnp.zeros((8, 8))}},
            "embed": jnp.zeros((16, 8)),
            "norm": jnp.zeros((8,)),
        }
        specs = rules.tree_specs(params)
        assert specs["layers_0"]["attn"]["wq"] == P("fsdp", "tp")
        assert specs["layers_0"]["attn"]["wo"] == P("tp", "fsdp")
        assert specs["embed"] == P("tp", "fsdp")
        assert specs["norm"] == P()  # replicated default, clipped to ndim

    def test_shard_pytree_places_on_mesh(self):
        from ray_tpu.parallel import ShardingRules, shard_pytree

        mesh = make_mesh(MeshConfig(fsdp=8))
        rules = ShardingRules([(r"w", P("fsdp"))])
        tree = {"w": jnp.arange(16.0)}
        sharded = shard_pytree(tree, mesh, rules)
        assert sharded["w"].sharding.spec == P("fsdp")


def test_rotary_chunk_offset_equivalence():
    """Per-chunk RoPE with position_offset must equal global RoPE sliced —
    the invariant ring attention relies on (sp sharding)."""
    from ray_tpu.ops import apply_rotary, rope_frequencies

    cos, sin = rope_frequencies(32, 256)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 256, 32))
    full = apply_rotary(x, cos, sin)
    for i in range(4):
        chunk = apply_rotary(
            x[:, :, i * 64:(i + 1) * 64], cos, sin,
            position_offset=jnp.asarray(i * 64),
        )
        np.testing.assert_allclose(
            chunk, full[:, :, i * 64:(i + 1) * 64], atol=1e-6
        )
