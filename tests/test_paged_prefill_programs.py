"""The prefill programs of a model with window layers through
``ops/paged_prefill.py`` (``paged._walks_live_pages`` steered, the one thing
a CPU cannot see; the kernel interpreted), against the gather form they take
everywhere else; the engine's ``attn_pairs`` arithmetic; and who walks.  The
kernel alone is in ``tests/test_paged_prefill.py``."""

import pytest

import walk_ref
from ray_tpu.models import paged
from ray_tpu.ops import paged_decode


@pytest.mark.parametrize("name, heads", [("smallthinker-tiny", 14),
                                         ("trinity-mini-tiny", 16)])
@pytest.mark.parametrize("prompt, chunk", [(13, 16), (37, 16), (61, 8)],
                         ids=["inside-one-bucket", "three-chunks-a-ring-"
                              "wrapped", "eight-chunks-of-a-page"])
def test_the_prefill_programs_through_the_kernel(name, heads, prompt, chunk):
    """The prefill calls of the tiny SmallThinker (7:1, a whole-length
    layer before three rings) and Trinity-Mini (8:1, three rings to a
    whole-length layer, gate and norms around them) as a TPU takes them
    (the suffix program through the kernel, a prompt's first rows too),
    against the cold program and the suffix program in the gather form: the
    same logits after every call of a prompt inside one bucket (its tail
    padding), chunked past the window of 8 with the ring of three pages
    wrapping, and chunked a page at a time to the table's last page; the
    rows the calls leave in the pools are the same
    (``walk_ref.same_prefills``)."""
    cfg = walk_ref.tiny_pair(name)
    whole, window = paged.kv_layers(cfg)
    assert whole and window and cfg.window == walk_ref.TINY_PAGE
    assert cfg.n_heads == heads
    walk_ref.same_prefills(cfg, paged_decode, prompt, chunk)


def test_off_the_tpu_the_prefills_are_the_gather_form():
    """Nothing steers it here: on this backend a model with window layers
    lowers without the kernel, and one without them everywhere, whatever the
    backend answers; a latent model gathers here and walks where
    ``latent_decode.on_tpu`` answers true (``ops/latent_prefill.py``), as
    its decode step does."""
    import dataclasses

    from ray_tpu.ops import latent_decode

    cfg = walk_ref.tiny_pair("smallthinker-tiny")
    assert not paged._walks_live_pages(cfg)
    assert paged.prefill_attention_form(cfg) == "gather"
    plain = dataclasses.replace(cfg, window=0, window_layout=(),
                                rope_layout=())
    was = paged_decode.on_tpu, latent_decode.on_tpu
    paged_decode.on_tpu = latent_decode.on_tpu = lambda: True
    try:
        assert paged.prefill_attention_form(cfg) == "walk"
        assert paged.prefill_attention_form(plain) == "gather"
        latent = walk_ref.tiny("glm4-moe-lite-tiny")
        assert paged._walks_live_pages(latent)
        assert paged.prefill_attention_form(latent) == "walk"
    finally:
        paged_decode.on_tpu, latent_decode.on_tpu = was
    assert paged.prefill_attention_form(latent) == "gather"


@pytest.mark.parametrize("start, end", [(0, 5), (0, 8), (0, 30), (8, 24),
                                        (3, 9), (16, 17), (40, 64)])
def test_attn_pairs_counts_what_the_real_rows_see(start, end):
    """By the formula, a tiny SmallThinker's two whole-length layers and
    six rings of window 8, against the count row by row."""
    cfg = walk_ref.tiny_pair("smallthinker-tiny", whole=True)
    whole, window = paged.kv_layers(cfg)
    assert (len(whole), len(window)) == (2, 6)
    by_row = sum(len(whole) * (p + 1) + len(window) * min(p + 1, cfg.window)
                 for p in range(start, end))
    assert paged.attn_pairs(cfg, start, end) == by_row
