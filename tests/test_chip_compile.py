"""What only the chip's compiler, or only a run, can show — without the chip.

1. Compiles for a *described* TPU v5e (``jax.experimental.topologies``): the
   TPU compiler is installed here and compiles for a chip that is not
   attached, raising what the chip's compiler would raise.  Interpret-mode
   tests cannot see a kernel that runs out of VMEM or a Mosaic kernel that
   XLA refuses to partition; these can.  Kernel-level cases (about a second
   each) run in tier-1; whole-step compiles (10-90 s each) are ``slow`` and
   are run before a chip call.  Nothing executes, so nothing here is a
   measurement.
2. A CPU rehearsal of ``chip_smoke.py``: its phase functions at
   ``model="tiny"`` on the CPU backend, through this test-only entry (the
   script itself has no such option and refuses to run off the chip).

Code that asks ``jax.default_backend()`` sees the CPU here, so the tests that
compile a whole model step steer ``ops.attention._on_tpu`` themselves.
"""

import functools
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from ray_tpu.ops import attention as att
from ray_tpu.ops import grouped_ffn
from ray_tpu.ops.norms import rms_norm_pallas
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.parallel.mesh import MESH_AXES, batch_spec
from ray_tpu.parallel.sharding import count_collectives

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402 — the script at the root; it imports no jax

HBM_BYTES = 16909336064  # bytes_limit of one v5e chip, as memory_stats() gave it


@pytest.fixture(scope="module")
def v5e():
    """A described 2x2 host of TPU v5e, with the persistent compilation
    cache off around the module: a compile for a described chip is written
    to the cache but cannot be read back without one, and warns."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or it cannot describe
        pytest.skip(f"cannot describe a TPU v5e here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _on(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _mesh(topo, **sizes) -> Mesh:
    shape = tuple(sizes.get(a, 1) for a in MESH_AXES)
    return Mesh(np.array(topo.devices).reshape(shape), MESH_AXES)


# ------------------------------------------------------------------ kernels

#: (q heads, kv heads, sequence): LlamaConfig.b1's attention, then
#: LlamaConfig.llama3_8b's (GQA 32/8) at the lengths the preset is used at.
#: 32/8 at 4096 and 8192 is what the backward used to be refused at: the
#: dK/dV kernel held Q, dO, lse and delta of the whole GQA group in VMEM.
FLASH_SHAPES = [(16, 16, 2048), (32, 8, 2048), (32, 8, 4096), (32, 8, 8192)]


def _flash_args(v5e, heads, kv_heads, seq, dtype=jnp.bfloat16, d=128):
    one = SingleDeviceSharding(v5e.devices[0])
    return (_on(one, (1, heads, seq, d), dtype),
            _on(one, (1, kv_heads, seq, d), dtype),
            _on(one, (1, kv_heads, seq, d), dtype))


@pytest.mark.parametrize("heads,kv_heads,seq", FLASH_SHAPES)
def test_flash_forward_compiles(v5e, heads, kv_heads, seq):
    fwd = functools.partial(att.flash_attention, force_pallas=True)
    text = jax.jit(fwd).lower(
        *_flash_args(v5e, heads, kv_heads, seq)).compile().as_text()
    assert text.count("tpu_custom_call") == 1


@pytest.mark.parametrize("heads,kv_heads,seq", FLASH_SHAPES)
def test_flash_backward_compiles(v5e, heads, kv_heads, seq):
    def loss(q, k, v):
        out = att.flash_attention(q, k, v, force_pallas=True)
        return out.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_flash_args(v5e, heads, kv_heads, seq)).compile().as_text()
    assert text.count("tpu_custom_call") == 3  # forward, dq, dk+dv


@pytest.mark.parametrize("seq,dtype,fits", [
    (12288, jnp.bfloat16, True), (16384, jnp.bfloat16, False),
    (6144, jnp.float32, True), (8192, jnp.float32, False),
])
def test_flash_refuses_what_the_compiler_would(v5e, seq, dtype, fits):
    """K and V of one head stay whole in VMEM for the forward and dq
    kernels: at the edge of KV_RESIDENT_BYTES the kernels still compile,
    past it flash_attention raises its own error before tracing one."""
    args = _flash_args(v5e, 4, 4, seq, dtype)
    fwd = jax.jit(functools.partial(att.flash_attention, force_pallas=True))
    if fits:
        assert "tpu_custom_call" in fwd.lower(*args).compile().as_text()
    else:
        with pytest.raises(ValueError, match="keeps K and V of one head"):
            fwd.lower(*args)


def test_flash_refuses_a_length_no_block_divides():
    q = jnp.zeros((1, 2, 1000, 64))  # 1000 = 8 * 125: no block down to 16
    with pytest.raises(ValueError, match="blocks divide"):
        att.flash_attention(q, q, q, force_pallas=True)
    # Off the chip, and not forced, the same call is the XLA reference.
    assert att.flash_attention(q, q, q).shape == q.shape


def test_rms_norm_kernel_compiles(v5e):
    one = SingleDeviceSharding(v5e.devices[0])
    text = jax.jit(rms_norm_pallas).lower(
        _on(one, (8192, 2048)), _on(one, (2048,))).compile().as_text()
    assert text.count("tpu_custom_call") == 1


#: A prefill call's rows in the state-space cell's buckets (Jamba2-3B's
#: chunk, its smallest bucket): the state is [16, 5120] at each.
SCAN_ROWS = [2048, 128]


@pytest.mark.parametrize("rows", SCAN_ROWS)
def test_ssm_scan_compiles_and_fits_fast_memory(v5e, rows):
    """``ops/ssm_scan.py`` at Jamba2-3B's widths: one Mosaic call under its
    name, which the compiler refuses where what it keeps in VMEM (both
    halves of a position block of every stream, and the state) does not
    fit, and nothing of the chunk left in HBM beside the operands (``B``
    and ``C`` laid N on the sublanes: 2 x 16 MB)."""
    from ray_tpu.ops import ssm_scan

    one = SingleDeviceSharding(v5e.devices[0])
    f32 = functools.partial(_on, one, dtype=jnp.float32)
    compiled = jax.jit(ssm_scan.ssm_scan_chunk).lower(
        f32((16, 5120)), f32((16, 5120)), f32((rows, 5120)),
        f32((rows, 5120)), f32((rows, 16)), f32((rows, 16)),
        _on(one, (), jnp.int32)).compile()
    calls = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln]
    assert len(calls) == 1 and "ssm_scan" in calls[0], calls
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= 2 * rows * 16 * 128 * 4


#: The decode step's grouped products of the three routed cells: (rows =
#: slots x top_k, d, f, the gate's non-linearity); 64 experts each.
STREAM_SHAPES = {"olmoe": (128, 2048, 1024, "silu"),
                 "smallthinker": (96, 2560, 768, "relu"),
                 "glm": (128, 2048, 1536, "silu")}


def _stream_args(v5e, rows, d, f, experts=64):
    one = SingleDeviceSharding(v5e.devices[0])
    return (_on(one, (rows, d)), _on(one, (experts, d, f)),
            _on(one, (experts, d, f)), _on(one, (experts, f, d)),
            _on(one, (experts,), jnp.int32))


@pytest.mark.parametrize("cell", STREAM_SHAPES)
def test_grouped_ffn_stream_compiles_at_the_cells_decode_geometry(v5e, cell):
    """One Mosaic call within the VMEM it asks for (both halves of its
    slabs, 19-25 MB: over the compiler's default, so the kernel states its
    own limit), and no temporary beside its arguments: the experts'
    weights are read where they lie, none a second time in HBM."""
    rows, d, f, act = STREAM_SHAPES[cell]
    width = grouped_ffn.slab_width(d, f, jnp.bfloat16)
    assert 2 * 3 * d * width * 2 > 16 << 20
    compiled = jax.jit(functools.partial(
        grouped_ffn.grouped_ffn_stream, act=act)).lower(
        *_stream_args(v5e, rows, d, f)).compile()
    calls = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln]
    assert len(calls) == 1 and "%ragged-dot-stream" in calls[0], calls
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 20
    assert mem.output_size_in_bytes == rows * d * 4


#: A prefill call's grouped products: (rows = tokens x top_k, d, f, experts,
#: the gate's non-linearity) of SmallThinker's 2048-row chunk, GLM's bucket
#: 1024 and Trinity-Mini's chunk.
ROWS_SHAPES = {"smallthinker-chunk": (12288, 2560, 768, 64, "relu"),
               "glm-bucket-1024": (4096, 2048, 1536, 64, "silu"),
               "trinity-mini-chunk": (16384, 2048, 1024, 128, "silu")}


@pytest.mark.parametrize("cell", ROWS_SHAPES)
def test_grouped_ffn_rows_compiles_at_the_cells_prefill_geometry(v5e, cell):
    """One Mosaic call within the VMEM it asks for (two experts whole,
    GLM's 19 MB each, beside two blocks of rows and of output: 42-52 MB of
    a v5e's 128), its rows and its float32 output left in HBM, and no
    temporary beside them: nothing the size of ``h`` (rows x f) or of the
    output is made a second time."""
    rows, d, f, experts, act = ROWS_SHAPES[cell]
    tile, block = grouped_ffn.rows_blocks(rows, experts, jnp.bfloat16)
    assert (tile, block) == (64, 256)
    assert grouped_ffn.holds_an_expert(d, f, jnp.bfloat16)
    compiled = jax.jit(functools.partial(
        grouped_ffn.grouped_ffn_rows, act=act)).lower(
        *_stream_args(v5e, rows, d, f, experts)).compile()
    calls = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln]
    assert len(calls) == 1 and "%ragged-dot-rows" in calls[0], calls
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 20 < rows * f * 2
    assert mem.output_size_in_bytes == rows * d * 4


@pytest.mark.parametrize("tokens,form", [(16, "stream"), (128, "rows")],
                         ids=["decode-step", "smallest-prefill-bucket"])
def test_the_routed_ffn_streams_where_an_expert_gets_a_handful_of_rows(
        v5e, monkeypatch, tokens, form):
    """``moe._moe_ffn`` at OLMoE's widths as a TPU takes it: a decode
    step's sixteen rows go through the one streaming kernel, a prefill
    bucket's through the one row-block kernel, and neither holds a
    ``ragged_dot``."""
    from ray_tpu.models import MoEConfig, moe

    monkeypatch.setattr(grouped_ffn, "on_tpu", lambda: True)
    cfg = MoEConfig(vocab_size=64, d_model=2048, n_layers=1, n_heads=16,
                    n_kv_heads=16, d_ff=1024, n_experts=64, top_k=8,
                    norm_topk_prob=False, max_seq=128, remat=False)
    one = SingleDeviceSharding(v5e.devices[0])
    layer = jax.tree.map(
        lambda x: _on(one, x.shape, x.dtype),
        jax.eval_shape(lambda: moe.moe_init(
            cfg, jax.random.PRNGKey(0))["layers"][0]["moe"]))
    text = jax.jit(lambda m, x: moe._moe_ffn(cfg, m, x)).lower(
        layer, _on(one, (tokens, 2048))).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert sum("%ragged-dot-stream" in ln for ln in calls) \
        == int(form == "stream")
    assert sum("%ragged-dot-rows" in ln for ln in calls) \
        == int(form == "rows")
    assert not any("%ragged-dot-none" in ln for ln in calls)
    assert len(calls) == 1


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dq",
                                    "flash_bwd_dkv", "rms_norm",
                                    "ragged-dot-stream", "ragged-dot-rows"])
def test_kernels_carry_their_names_into_the_compiled_program(v5e, kernel):
    """``pallas_call(name=...)``: what a device trace (and a reduction of
    it) can tell the Mosaic calls apart by."""
    one = SingleDeviceSharding(v5e.devices[0])
    if kernel == "rms_norm":
        fn, args = rms_norm_pallas, (_on(one, (8192, 2048)),
                                     _on(one, (2048,)))
    elif kernel.startswith("ragged-dot"):
        fn = {"ragged-dot-stream": grouped_ffn.grouped_ffn_stream,
              "ragged-dot-rows": grouped_ffn.grouped_ffn_rows}[kernel]
        args = _stream_args(v5e, 32, 256, 256, experts=8)
    else:
        def loss(q, k, v):
            out = att.flash_attention(q, k, v, force_pallas=True)
            return out.astype(jnp.float32).sum()

        fn, args = (jax.grad(loss, argnums=(0, 1, 2)),
                    _flash_args(v5e, 16, 8, 2048))
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert any(kernel in ln for ln in calls), calls


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "backward"])
def test_ring_flash_compiles_on_four_devices(v5e, grad):
    """The fused ring+flash pair over sp=4: b1's heads, 2048 tokens a shard."""
    mesh = _mesh(v5e, sp=4)
    spec = P(None, None, "sp", None)
    ring = jax.shard_map(
        functools.partial(ring_attention, axis_name="sp", force_kernel=True),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False)
    fn = ring
    if grad:
        fn = jax.grad(lambda q, k, v: ring(q, k, v).astype(jnp.float32).sum(),
                      argnums=(0, 1, 2))
    arg = _on(NamedSharding(mesh, spec), (1, 16, 4 * 2048, 128))
    text = jax.jit(fn).lower(arg, arg, arg).compile().as_text()
    # One kernel per ring step forward; forward + 2 per step with the grad.
    assert text.count("tpu_custom_call") == (12 if grad else 4)
    assert count_collectives(text)["collective-permute"] > 0


# ------------------------------------------- the sharded train step's layout


def _lower_train_step(v5e, cfg, tx, shape, **axes):
    """``make_train_step`` of ``cfg``'s loss on a batch of ``shape``, lowered
    for the described host: over a mesh of ``axes`` with the rule table,
    under it as the ambient mesh; or, with no axes, on one device."""
    from ray_tpu.models import (TrainState, llama_init, llama_loss,
                                llama_sharding_rules)
    from ray_tpu.models.train_state import make_train_step
    from ray_tpu.parallel.sharding import named_sharding

    state = jax.eval_shape(lambda: TrainState.create(
        llama_init(cfg, jax.random.PRNGKey(0)), tx))
    loss = lambda p, b: llama_loss(cfg, p, b["tokens"], b["targets"])
    if not axes:
        one = SingleDeviceSharding(v5e.devices[0])
        state = jax.tree.map(lambda x: _on(one, x.shape, x.dtype), state)
        batch = {k: _on(one, shape, jnp.int32) for k in ("tokens", "targets")}
        return make_train_step(loss, tx).lower(state, batch)
    mesh, rules = _mesh(v5e, **axes), llama_sharding_rules()
    state = jax.tree.map(
        lambda x, s: _on(s, x.shape, x.dtype), state,
        named_sharding(mesh, rules.tree_specs(state)))
    data = NamedSharding(mesh, batch_spec())
    batch = {k: _on(data, shape, jnp.int32) for k in ("tokens", "targets")}
    with jax.set_mesh(mesh):
        return make_train_step(loss, tx, mesh, rules).lower(state, batch)


def _cell_train_step(v5e, layers, **axes):
    """The four-chip training cell's step (8 x 2048 tokens, remat
    ``save_attn``, one loss chunk) at ``layers`` layers and HALF
    internlm2-1.8b's widths (its 2:1 grouping over heads of 128, an FFN of
    4 x hidden, 4 KV heads so that tp=4 still parts them): what is asserted
    is where the activations of the batch's shape live, which the widths do
    not decide, and the TPU compiler takes half the time."""
    from ray_tpu.models import LlamaConfig
    from ray_tpu.models.train_state import default_optimizer

    cfg = LlamaConfig(vocab_size=8192, d_model=1024, n_layers=layers,
                      n_heads=8, n_kv_heads=4, d_ff=4096, max_seq=2048,
                      rope_theta=1e6, remat=True, remat_policy="save_attn",
                      loss_chunk=2048)
    return _lower_train_step(
        v5e, cfg, default_optimizer(lr=3e-4, grad_clip=1.0), (8, 2048),
        **axes)


def _collective_shapes(text, kind):
    """Result shapes of the ``kind`` collectives in a compiled text."""
    import re

    return re.findall(r"= \(?(\w+\[[\d,]*\])[^=]*? " + kind
                      + r"(?:-start)?\(", text)


@pytest.mark.parametrize("layers,axes,permutes", [
    (2, dict(fsdp=2, tp=2), 4), (1, dict(fsdp=4), 3), (1, dict(tp=4), 0)],
    ids=["fsdp2-tp2", "fsdp4", "tp4"])
def test_sharded_train_step_keeps_its_activations_in_place(
        v5e, kernels_as_on_chip, layers, axes, permutes):
    """The step says where its activations live (``sharding.constrain``),
    so the partitioner gathers weights over fsdp and reduces over tp and
    moves no activation between the two: no all-to-all, and nothing of the
    batch's shape gathered.  Before, two layers held 30 all-to-alls, 19
    collective-permutes and the head's logits for the whole batch gathered
    19 times.  ~20 s a case."""
    text = _cell_train_step(v5e, layers, **axes).compile().as_text()
    counts = count_collectives(text)
    assert counts["all-to-all"] == 0, counts
    assert text.count("tpu_custom_call") == 4 * layers
    gathered = _collective_shapes(text, "all-gather")
    assert (len(gathered) > 0) == ("fsdp" in axes)  # the pattern still reads
    # Token ids gathered for the embedding's scatter-add are no activation.
    assert not [s for s in gathered
                if s.startswith(("bf16[8,2048,", "f32[8,2048,"))], gathered
    # What is left is the compiler's own: boundary rows of the combined
    # gradient buffers, exchanged inside an fsdp group (0.3 MB each).
    assert counts["collective-permute"] <= permutes, counts


def test_train_step_without_a_mesh_lowers_as_it_did(v5e, kernels_as_on_chip):
    """With no ambient mesh ``constrain`` returns its argument: the
    one-chip step lowers to the text it had before the layer said anything
    (2211 lines at two layers, counted on the parent commit)."""
    text = _cell_train_step(v5e, 2).as_text()
    assert "sharding_constraint" not in text
    assert len(text.splitlines()) == 2211


# ------------------------------------------------------- chip_smoke, on CPU


@pytest.fixture
def smoke(monkeypatch):
    """chip_smoke.py as a module, and a cluster whose workers get four
    virtual CPU devices (the sharded phase's fsdp=2 x tp=2)."""
    import ray_tpu

    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    monkeypatch.setenv("RT_DEBUG_JIT", "1")
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    yield chip_smoke
    ray_tpu.shutdown()


TINY_ENGINE = dict(batch_slots=4, page_size=8, max_prompt_len=16,
                   max_new_tokens_cap=16)
TINY_TRAIN = dict(model="tiny", batch=4, seq=64, steps=3, lr=1e-2)


def test_chip_smoke_serve_phase_rehearsed_on_cpu(smoke):
    """The serve phase end to end at model="tiny": same entry point, same
    checks, the CPU backend.  (With the train phase below it was one test,
    two worker starts and two models' compiles long.)"""
    serve = smoke.serve_phase(
        model="tiny", engine=TINY_ENGINE, prompt_lens=(3, 12, 16),
        new_tokens=6, platform="cpu", num_tpus=0)
    assert serve["platform"] == "cpu" and serve["greedy_repeats"]
    assert serve["decode_traces"] == 1 and serve["sentinel_armed"]
    assert serve["free_pages"] == serve["total_pages"]
    assert serve["tokens_returned"] == 5 * 6
    assert max(serve["reference_logit_gap"].values()) < 1e-4  # float32
    assert serve["replica_dead_after_s"] < 60  # gone (or a zombie) before


def test_chip_smoke_train_phase_rehearsed_on_cpu(smoke):
    """The train phase likewise."""
    train = smoke.train_phase(name="train", platform="cpu", chips=0,
                              **TINY_TRAIN)
    assert train["platform"] == "cpu" and train["last_loss"] < train["first_loss"]
    # The same result held to the chip's contract is a failure.
    with pytest.raises(smoke.SmokeFailure, match="expected 1 x 'tpu'"):
        smoke.check_device(train, "tpu", 1)


def test_chip_smoke_failed_phase_is_an_exit_code(monkeypatch, capsys):
    """With no chip, or JAX held to the CPU, the script refuses at once; a
    phase that fails on a host with one goes up through main() as an
    exception (a non-zero exit).  Neither prints ``"ok": true``."""
    import ray_tpu

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    assert chip_smoke.main([]) == 2  # conftest: JAX_PLATFORMS=cpu
    monkeypatch.delenv("JAX_PLATFORMS")
    assert chip_smoke.main([]) == 2  # and no chip either (RT_TPU_CHIPS=0)
    assert chip_smoke.main(["--chips", "4"]) == 2

    def failing_phase(**kwargs):
        raise chip_smoke.SmokeFailure("forced")

    monkeypatch.setenv("RT_TPU_CHIPS", "1")  # pretend: main() gets to a phase
    monkeypatch.setenv("RT_DEBUG_JIT", "")  # main() sets both: restored after
    monkeypatch.setenv("RT_LOG_TO_DRIVER", "0")
    monkeypatch.setattr(chip_smoke, "serve_phase", failing_phase)
    with pytest.raises(chip_smoke.SmokeFailure, match="forced"):
        chip_smoke.main([])
    assert not ray_tpu.is_initialized()
    out = capsys.readouterr().out
    assert '"phase": "start"' in out and '"ok"' not in out


def test_chip_smoke_parent_stays_off_jax():
    """Everything the script's parent imports, in a fresh interpreter."""
    import subprocess

    code = ("import sys, chip_smoke, ray_tpu.serve.engine, ray_tpu.train, "
            "ray_tpu.cluster_utils\n"
            "from ray_tpu.parallel import MeshConfig\n"
            "MeshConfig(fsdp=2, tp=2)\n"
            "print('jax' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, check=True,
                         capture_output=True, text=True, timeout=60)
    assert out.stdout.strip() == "False"


@pytest.mark.slow  # two gangs, two compiles of the sharded step: ~60 s
def test_chip_smoke_sharded_phases_rehearsed_on_cpu(smoke):
    sharded, single = smoke.sharded_phases(platform="cpu", chips=0,
                                           **TINY_TRAIN)
    assert sharded["mesh"]["fsdp"] == 2 and sharded["mesh"]["tp"] == 2
    assert sharded["count"] == 4 and single["mesh"] is None
    assert sharded["max_loss_diff_vs_one_device"] < 1e-3


# ------------------------------------------------- whole steps, before a call


@pytest.fixture
def kernels_as_on_chip(monkeypatch):
    """Whole model steps ask the backend which attention to take."""
    monkeypatch.setattr(att, "_on_tpu", lambda: True)


def _smoke_train_step(v5e, **axes):
    """chip_smoke.py's train step, compiled for the described host."""
    from ray_tpu.models.train_state import default_optimizer

    t = chip_smoke.TRAIN
    cfg = chip_smoke.train_model_config(t["model"], t["seq"])
    return _lower_train_step(
        v5e, cfg, default_optimizer(lr=t["lr"], grad_clip=1.0),
        (t["batch"], t["seq"]), **axes).compile()


@pytest.mark.slow  # ~30 s
def test_b1_train_step_fits_one_chip(v5e, kernels_as_on_chip):
    compiled = _smoke_train_step(v5e)
    assert compiled.as_text().count("tpu_custom_call") == 60  # 20 x 3
    ma = compiled.memory_analysis()
    # Weights and both Adam moments (donated, so counted once) plus the
    # step's temporaries: within the chip, and not by much.
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM_BYTES


@pytest.mark.slow  # ~90 s
def test_b1_train_step_partitions_over_four_chips(v5e, kernels_as_on_chip):
    """fsdp=2 x tp=2: XLA cannot partition a Mosaic kernel by itself, so
    the model runs the flash kernel per shard (llama._flash_per_shard)."""
    compiled = _smoke_train_step(v5e, fsdp=2, tp=2)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 60
    counts = count_collectives(text)
    # PR 21 counted 246 all-to-alls and 103 collective-permutes here.
    assert counts["all-gather"] > 0 and counts["all-to-all"] == 0, counts
    ma = compiled.memory_analysis()  # bytes on EACH device
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM_BYTES / 2


def _engine_args(v5e, cfg, ec):
    """Shapes of everything the paged programs take, on one described chip."""
    from ray_tpu.models import init_and_apply
    from ray_tpu.models.paged import (init_adapter_pool, init_paged_pools,
                                      state_layers)

    init = init_and_apply(cfg)[0]  # the model's own

    one = SingleDeviceSharding(v5e.devices[0])
    place = functools.partial(
        jax.tree.map, lambda x: _on(one, x.shape, x.dtype))
    params = place(jax.eval_shape(
        lambda: init(cfg, jax.random.PRNGKey(0))))
    pools = place(jax.eval_shape(lambda: init_paged_pools(
        cfg, ec.pool_pages, ec.page_size,
        state_slots=ec.batch_slots if state_layers(cfg) else 0)))
    adapters = place(jax.eval_shape(
        lambda: init_adapter_pool(cfg, ec.max_adapters, ec.lora_rank)))
    key = place(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    return cfg, ec, params, pools, adapters, key, functools.partial(_on, one)


def _lower_paged(program, args, bucket=None):
    """One program of ``models/paged.py`` lowered on ``_engine_args``'s
    shapes (``bucket``: the padded prompt length of the two prefills)."""
    from ray_tpu.models import paged

    cfg, ec, params, pools, adapters, key, on = args
    b, i32 = ec.batch_slots, jnp.int32
    n_tok = b + paged.routing_width(cfg)  # a routed model's counters ride
    scalar, temp = on((), i32), on((), jnp.float32)
    toks, table = on((1, bucket or 1), i32), on((ec.pages_per_seq,), i32)
    if program == "paged_decode_step":
        return paged.paged_decode_step.lower(
            cfg, params, pools, adapters, on((n_tok,), i32),
            on((b, ec.pages_per_seq), i32), on((b,), i32), on((b,), bool),
            on((b,), jnp.float32), on((b,), i32), key)
    if program == "paged_prefill":
        return paged.paged_prefill.lower(
            cfg, params, pools, adapters, toks, scalar, table, scalar, temp,
            key)
    if program == "paged_prefill_prefix":
        return paged.paged_prefill_prefix.lower(
            cfg, params, pools, adapters, toks, scalar, scalar, table,
            scalar, temp, key, None,
            scalar if paged.state_layers(cfg) else None)
    assert program == "copy_page"
    return paged.copy_page.lower(pools, scalar, scalar)


def _smoke_engine_args(v5e):
    from ray_tpu.serve.engine import EngineConfig, _b1_config

    return _engine_args(v5e, _b1_config(),
                        EngineConfig(**chip_smoke.SERVE_ENGINE))


@pytest.mark.slow  # ~10 s
def test_b1_decode_step_fits_one_chip(v5e):
    ma = _lower_paged("paged_decode_step", _smoke_engine_args(v5e)
                      ).compile().memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM_BYTES


@pytest.mark.slow  # ~15 s each
@pytest.mark.parametrize("program", ["paged_prefill", "paged_prefill_prefix"],
                         ids=["cold", "suffix"])
def test_b1_largest_prefill_bucket_fits_one_chip(v5e, program):
    args = _smoke_engine_args(v5e)
    ma = _lower_paged(program, args, bucket=args[1].prefill_buckets()[-1]
                      ).compile().memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM_BYTES


@pytest.mark.parametrize("model", ["llama", "olmoe"])
@pytest.mark.parametrize("program", [
    "paged_decode_step", "paged_prefill", "paged_prefill_prefix",
    "copy_page"])
def test_paged_programs_never_copy_the_pool(v5e, program, model):
    """The donated pools keep the layout they are declared in from argument
    to aliased result, and attention holds no second copy of a table's K/V:
    no instruction of the optimized HLO with a pool-shaped result is a
    ``copy`` (a layout the write or the read does not want costs a
    transpose of the whole pool in and one out, every call), and the
    temporaries stay under a quarter of one pool (the gathered pages,
    upcast, transposed and GQA-repeated, were three pools' worth), beside
    one layer's gathered pair where that is too large for fast memory.  A
    2-layer model at internlm2-1.8b's widths (GQA 16/8, heads of 128), and
    one at OLMoE-1B-7B's (MHA 16, QK-norm, 64 experts of 1024, 8 a token:
    the same block with the routed FFN), on the serving cells' engine
    geometry, bucket 128: ~3 s a program."""
    import re

    from ray_tpu.models import LlamaConfig, MoEConfig
    from ray_tpu.serve.engine import EngineConfig

    ec = EngineConfig(batch_slots=16, page_size=128, max_prompt_len=1024,
                      max_new_tokens_cap=256)
    if model == "llama":
        cfg = LlamaConfig(vocab_size=92544, d_model=2048, n_layers=2,
                          n_heads=16, n_kv_heads=8, d_ff=8192, remat=False,
                          max_seq=ec.pages_per_seq * ec.page_size)
    else:
        cfg = MoEConfig(vocab_size=50304, d_model=2048, n_layers=2,
                        n_heads=16, n_kv_heads=16, d_ff=1024, n_experts=64,
                        top_k=8, norm_topk_prob=False, qk_norm=True,
                        remat=False, max_seq=ec.pages_per_seq * ec.page_size)
    args = _engine_args(v5e, cfg, ec)
    pool = args[3]["k"]
    compiled = _lower_paged(program, args, bucket=128).compile()
    dims = ",".join(str(d) for d in pool.shape)
    pool_shaped = re.findall(
        r"^\s*(?:ROOT\s+)?\S+ = \w+\[" + re.escape(dims) + r"\]\S* "
        r"([\w-]+)\(", compiled.as_text(), re.M)
    assert "parameter" in pool_shaped  # the pattern still reads this HLO
    assert "copy" not in pool_shaped, pool_shaped
    pool_bytes = int(np.prod(pool.shape)) * pool.dtype.itemsize
    allowed = pool_bytes / 4
    if model == "olmoe" and program in ("paged_decode_step",
                                        "paged_prefill_prefix"):
        # MHA's gathered K and V of one layer's 16 tables (2 x 84 MB) do not
        # fit the fast memory GQA 16/8's pair stays in, so the compiler
        # keeps that one pair in HBM (178 MB of temporaries in the decode
        # step): allowed once, and no upcast or transposed view of it.
        allowed += 2 * ec.batch_slots * ec.pages_per_seq \
            * int(np.prod(pool.shape[2:])) * pool.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < allowed


@pytest.mark.slow  # ~25 s
def test_the_state_space_cells_chunk_program_scans_on_the_chip(
        v5e, monkeypatch):
    """Jamba2-3B's 2048-token chunk of the suffix prefill at the serving
    cell's geometry, with the answers only a TPU gives steered on: one
    ``ssm_scan`` call a Mamba layer, no loop left under the recurrence's
    scope (``chunked``'s scan was a ``while`` a layer), and the 1.09 GB
    state pool written where it lies (a slot's state in, a slot's state
    out: no copy of the pool)."""
    import re

    from benchmarks import spec
    from ray_tpu.models import mamba, paged
    from ray_tpu.ops import paged_decode, ssm_scan
    from ray_tpu.serve.engine import EngineConfig

    load = lambda *p: spec.load_json(  # noqa: E731
        os.path.join(ROOT, "benchmarks", *p))
    model = load("configs", "jamba2-3b.json")
    ec = EngineConfig(**load(
        "traffic", "serve-reasoning-wide-batch.json")["engine"])
    cfg = spec.family(model).program_config(
        model, remat=False, max_seq=ec.pages_per_seq * ec.page_size)
    args = _engine_args(v5e, cfg, ec)
    pools, bucket = args[3], ec.prefill_buckets()[-1]
    assert bucket == 2048 and not mamba._scans_on_chip(cfg, bucket)
    monkeypatch.setattr(paged_decode, "on_tpu", lambda: True)
    monkeypatch.setattr(ssm_scan, "on_tpu", lambda: True)
    jax.clear_caches()
    try:
        assert mamba._scans_on_chip(cfg, bucket)
        assert paged.recurrent_prefill_form(cfg) == "kernel"
        text = _lower_paged("paged_prefill_prefix", args,
                            bucket).compile().as_text()
    finally:
        jax.clear_caches()
    calls = re.findall(r"^\s*%?(\S+) = .*? custom-call\(", text, re.M)
    assert sum(x.startswith("ssm_scan") for x in calls) == 26, calls
    assert sum(x.startswith("paged_prefill") for x in calls) == 2, calls
    assert "attn_ssm" in text
    assert not [ln for ln in text.splitlines()
                if " while(" in ln and "attn_ssm" in ln]
    shape = "[" + ",".join(map(str, pools["S"].shape)) + "]"
    made = [op for kind, op in re.findall(
        r"^\s*(?:ROOT\s+)?\S+ = (.*?) ([\w-]+)\(", text, re.M)
        if shape in kind]
    assert "parameter" in made and "copy" not in made, made
