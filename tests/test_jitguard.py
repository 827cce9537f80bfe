"""Recompile sentinel (devtools.jitguard): registry semantics, the
post-warmup RecompileError with the argument shape/dtype delta and call
site, the disabled identity path (RT_DEBUG_JIT unset keeps bump a plain
counter), and the engine wiring — warmup arms the sentinel and a
steady-state decode never retraces — exercised in a subprocess with
RT_DEBUG_JIT=1.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from ray_tpu.devtools import jitguard

REPO_ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _clean_registry(monkeypatch):
    """Run each test on an empty registry, then RESTORE the prior state:
    trace counts are global and back real jax compile caches — a later
    engine warmup in this process would cache-hit without re-bumping, so
    wiping them would break other files' trace-count assertions."""
    monkeypatch.delenv(jitguard.ENV_FLAG, raising=False)
    with jitguard._lock:
        saved = (dict(jitguard._counts), dict(jitguard._sigs),
                 dict(jitguard._baseline))
    jitguard.reset_sentinel_state()
    yield
    with jitguard._lock:
        for store, snap in zip(
                (jitguard._counts, jitguard._sigs, jitguard._baseline),
                saved):
            store.clear()
            store.update(snap)


class TestRegistry:
    def test_register_count_and_counts(self):
        jitguard.register_program("p")
        assert jitguard.count("p") == 0
        assert jitguard.counts() == {"p": 0}
        jitguard.bump("p", jitguard.signature_of(
            {"x": np.zeros((2, 3), np.float32)}))
        jitguard.bump("p")
        assert jitguard.count("p") == 2
        # Unregistered names join on first bump (late learners).
        jitguard.bump("q")
        assert jitguard.counts() == {"p": 2, "q": 1}

    def test_signature_of_arrays_and_statics(self):
        sig = jitguard.signature_of(
            {"x": np.zeros((2, 3), np.float32), "n": 7})
        assert sig["x"] == ((2, 3), "float32")
        assert sig["n"].startswith("int:")


class TestSentinel:
    def test_post_warmup_recompile_raises_with_arg_delta(self):
        jitguard.register_program("step")
        jitguard.bump("step", jitguard.signature_of(
            {"x": np.zeros((4, 8), np.float32)}))
        assert jitguard.arm(force=True)
        assert jitguard.armed()

        def traced_body():  # stand-in for the jitted body's trace frame
            jitguard.bump("step", jitguard.signature_of(
                {"x": np.zeros((4, 16), np.float32)}))

        with pytest.raises(jitguard.RecompileError) as ei:
            traced_body()
        msg = str(ei.value)
        assert "'step'" in msg
        assert "(4, 8)" in msg and "(4, 16)" in msg  # the arg delta
        assert "test_jitguard" in msg                # the call site

    def test_identical_signature_recompile_names_static_drift(self):
        jitguard.bump("step", {"x": ((2,), "int32")})
        jitguard.arm(force=True)
        with pytest.raises(jitguard.RecompileError) as ei:
            jitguard.bump("step", {"x": ((2,), "int32")})
        assert "static arg or closure constant" in str(ei.value)

    def test_late_registered_program_is_unarmed(self):
        jitguard.bump("early")
        jitguard.arm(force=True)
        # First traced after arm(): no baseline yet, free to compile.
        jitguard.bump("late")
        jitguard.bump("late")
        assert jitguard.count("late") == 2

    def test_reregistration_stands_baseline_down(self):
        """Building a new component (engine/pool/learner) re-registers
        its programs: their cold traces are a compile phase, enforced
        again only after the next arm()."""
        jitguard.register_program("p")
        jitguard.bump("p")
        jitguard.arm(force=True)
        jitguard.register_program("p")
        jitguard.bump("p")  # fresh component's cold trace: no raise
        assert jitguard.count("p") == 2
        jitguard.arm(force=True)
        with pytest.raises(jitguard.RecompileError):
            jitguard.bump("p")

    def test_disarm_stops_enforcement(self):
        jitguard.bump("p")
        jitguard.arm(force=True)
        jitguard.disarm()
        assert not jitguard.armed()
        jitguard.bump("p")  # growth after disarm must not raise
        assert jitguard.count("p") == 2


class TestDisabledPath:
    def test_arm_is_identity_when_off(self):
        """RT_DEBUG_JIT unset: arm() is a no-op and bump stays the plain
        trace counter — zero behavior change on the production path."""
        jitguard.bump("p")
        assert jitguard.arm() is False
        assert not jitguard.armed()
        jitguard.bump("p")  # would raise if a baseline had been frozen
        assert jitguard.count("p") == 2

    def test_env_flag_turns_arm_on(self, monkeypatch):
        monkeypatch.setenv(jitguard.ENV_FLAG, "1")
        jitguard.bump("p")
        assert jitguard.arm() is True
        with pytest.raises(jitguard.RecompileError):
            jitguard.bump("p")


def test_engine_warmup_arms_and_steady_state_never_retraces(tmp_path):
    """The integration contract, in a fresh process with RT_DEBUG_JIT=1:
    InferenceEngine.warmup() arms the sentinel after compiling every
    bucket, and a full submit afterwards completes WITHOUT tripping it —
    one decode trace serves the steady state.  Any stray post-warmup
    specialization raises RecompileError and fails this test."""
    script = tmp_path / "engine_under_sentinel.py"
    script.write_text(textwrap.dedent("""
        import jax
        import jax.numpy as jnp

        from ray_tpu.devtools import jitguard
        from ray_tpu.models import LlamaConfig, llama_init
        from ray_tpu.serve.engine import EngineConfig, InferenceEngine

        cfg = LlamaConfig.tiny(remat=False, dtype=jnp.float32)
        params = jax.jit(llama_init, static_argnums=0)(
            cfg, jax.random.PRNGKey(0))
        eng = InferenceEngine(  # one bucket (8) of each prefill program
            cfg, params,
            EngineConfig(batch_slots=4, page_size=8, max_prompt_len=8,
                         max_new_tokens_cap=16, max_queue=16),
            seed=0)
        eng.warmup()
        assert jitguard.armed(), "warmup must arm under RT_DEBUG_JIT=1"
        toks = list(eng.submit([5, 7, 11], max_new_tokens=6))
        assert len(toks) == 6, toks
        assert jitguard.count("decode") == 1, jitguard.counts()
        eng.shutdown()
        print("SENTINEL_OK", jitguard.counts())
    """))
    out = subprocess.run(
        [sys.executable, str(script)],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=540,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "RT_DEBUG_JIT": "1",
             "PYTHONPATH": str(REPO_ROOT)},
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "SENTINEL_OK" in out.stdout
