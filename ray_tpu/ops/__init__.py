"""TPU compute kernels (Pallas) with portable jnp fallbacks.

Net-new relative to the reference, which delegates all device compute to
torch/CUDA (SURVEY.md §5.7): flash attention, ring attention (sequence
parallelism), decode attention over a latent paged pool and over paged K/V
pairs, prefill attention over a latent paged pool and over paged K/V
pairs (four fronts of the one walk in ``page_walk.py``), the routed FFN
streamed expert by expert (a step's rows, a prompt's rows), a state-space
layer's decode step in one pass over the slots' state and its recurrence over
a prompt's chunk with the state kept on the chip, fused RMSNorm, rotary
embeddings.
"""

from .attention import flash_attention, mha_reference
from .grouped_ffn import grouped_ffn_rows, grouped_ffn_stream
from .latent_decode import latent_decode_attention
from .latent_prefill import latent_prefill_attention
from .norms import rms_norm
from .paged_decode import paged_decode_attention
from .paged_prefill import paged_prefill_attention
from .rotary import apply_rotary, rope_frequencies
from .ring_attention import ring_attention
from .ssm_decode import ssm_decode_step
from .ssm_scan import ssm_scan_chunk

__all__ = [
    "flash_attention", "mha_reference", "latent_decode_attention",
    "latent_prefill_attention", "paged_decode_attention", "paged_prefill_attention", "grouped_ffn_rows", "grouped_ffn_stream",
    "rms_norm", "ssm_decode_step", "ssm_scan_chunk",
    "apply_rotary", "rope_frequencies", "ring_attention",
]
