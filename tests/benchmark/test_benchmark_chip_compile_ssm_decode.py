"""PR 50's compile-only checks, beside ``test_benchmark_chip_compile_jamba.py``
(whose cell, described v5e and lowering they borrow): with the answer only a
TPU gives steered on, the Jamba2-3B decode program at the cell's geometry
holds one ``ssm_decode`` call a Mamba layer on the state pool where it lies,
and the programs of the configurations that share ``paged._state_decode`` or
stand beside it lower to the parent's text.  Nothing executes, so nothing
here is a measurement."""

import re

import jax
import pytest

# The cell, the described topology and the steered walk are fixtures of this
# file too.
from test_benchmark_chip_compile_afmoe import HBM_BYTES, v5e  # noqa: F401
from test_benchmark_chip_compile_jamba import (  # noqa: F401
    _lower, _report, cell, on_the_chip)

from benchmarks import spec


@pytest.fixture
def stepping_in_place(monkeypatch, on_the_chip):
    """Both answers only a TPU gives: the attention layers' walk and the
    Mamba layers' one-pass kernel (``ops/ssm_decode.py``)."""
    from ray_tpu.ops import ssm_decode

    monkeypatch.setattr(ssm_decode, "on_tpu", lambda: True)
    jax.clear_caches()


def test_the_decode_program_steps_the_state_pool_where_it_lies(
        cell, capsys, stepping_in_place):
    """One ``ssm_decode`` call a Mamba layer, each on the whole pool
    (aliased to its output), and no copy of the 1.09 GB pool between them:
    a copy a layer would be 26 x 2.7 ms."""
    from ray_tpu.models import paged

    assert paged.recurrent_decode_form(cell["cfg"]) == "kernel"
    compiled = _lower(cell, "decode").compile()
    total, ma = _report(capsys, "decode through ssm_decode", compiled)
    assert 0.5 * 16e9 < total < HBM_BYTES - 3e9
    assert ma.argument_size_in_bytes < 8.6e9
    assert ma.temp_size_in_bytes < 1.0e9
    text = compiled.as_text()
    # The kernel returns (y, the pool): a tuple's type has spaces in it.
    calls = re.findall(r"^\s*%?(\S+) = .*? custom-call\(", text, re.M)
    assert sum(x.startswith("ssm_decode") for x in calls) == 26, calls
    assert sum(x.startswith("paged_decode") for x in calls) == 2, calls
    assert "attn_ssm" in text and "ssm_conv" in text
    # What makes a value of the pool's shape, alone or in a tuple: the
    # parameter, the 26 calls and their elements, and no copy.
    shape = "[" + ",".join(map(str, cell["pools"]["S"].shape)) + "]"
    made = [op for kind, op in re.findall(
        r"^\s*(?:ROOT\s+)?\S+ = (.*?) ([\w-]+)\(", text, re.M)
        if shape in kind]
    assert made.count("custom-call") == 26 and "parameter" in made \
        and "copy" not in made and "fusion" not in made, made


#: sha256 of the decode step's and the suffix prefill's lowered texts at
#: ``test_benchmark_jamba._program_texts``' small geometry, at the parent
#: commit (e29cea4): the configurations whose programs share
#: ``paged._state_decode`` or stand beside it.
PARENT_TEXTS = {
    "kimi-linear-48b-a3b-L13": (
        "493eedf79842fd1f718302566e437c051876815376ffb4f7ceba25284658e99f",
        "179ad0e26b558ac67941dec81f85f853f0e1c3a131130f8a3ca29d4a2285e8ae"),
    "olmoe-1b-7b-0125": (
        "a0d03c5b1665008724e9385c9beee4caf3b433349ff8a54cdffa9384b88d3c24",
        "41c82f536d21c10138f59136cc7c2bbd05dd85ae59a6280eb528c9168dbe382a"),
    "internlm2-1.8b": (
        "6a6f7cbcebfa28b26e9a8d3a15f6da934327bef26854ea5d32cfb383910cf387",
        "a518d4178d0097eb79a0e6b716ba96f75ea7e505d540fac8c93a311c761408d5"),
    # Off a TPU the new configuration's own keep ``mamba.recurrent``.
    "jamba-tiny": (
        "0d1364edbe81f60fd518e4d63cd04da5e8202c2d4c37931f117c0bbbec9ce261",
        "4503fe0e390f2546b09d3f3e341e996013955393ccc3f942fc25254bf1ddfe04"),
}


@pytest.mark.parametrize("name", list(PARENT_TEXTS))
def test_the_other_programs_lower_to_the_parents_text(name):
    """``_state_decode``'s other caller (Kimi-Linear's KDA layers), a routed
    and a dense model without recurrent layers, and this family off a TPU:
    the decode step and the suffix prefill lower to the parent's text."""
    import hashlib

    from test_benchmark_jamba import _model, _program_texts

    model = _model(name)
    cfg = spec.family(model).program_config(model, max_seq=64, remat=False)
    assert tuple(hashlib.sha256(t.encode()).hexdigest()
                 for t in _program_texts(cfg)) == PARENT_TEXTS[name]
