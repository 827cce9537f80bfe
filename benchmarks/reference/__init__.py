"""The plain references, one module to a family (``llama_ref.py`` is the
llama family's), and what is generic over them."""

from __future__ import annotations

from typing import List, Sequence

import numpy as np


def teacher_forced_gaps(ref, prompt: Sequence[int],
                        output: Sequence[int]) -> List[float]:
    """For each generated token: the reference's best logit at that
    position minus the reference's logit of the token the system emitted
    (0 when the system's token is the reference's argmax).  ``ref`` is any
    family's reference: ``logits(tokens, positions)`` is all it is asked."""
    seq = np.asarray(list(prompt) + list(output[:-1]), np.int32)
    positions = range(len(prompt) - 1, len(seq))
    logits = ref.logits(seq, positions)
    return [float(row.max() - row[tok]) for row, tok in zip(logits, output)]
