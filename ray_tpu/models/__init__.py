"""Model zoo: pure-functional JAX models with first-class sharding rules.

Unlike the reference (which wraps torch modules in DDP/FSDP/DeepSpeed —
SURVEY.md §2.4), models here are parameter pytrees + apply functions, and
parallelism is a ShardingRules table consumed by pjit: DP/FSDP/TP/SP are
configurations, not code paths.
"""

from .llama import (
    LlamaConfig,
    llama_apply,
    llama_init,
    llama_loss,
    llama_sharding_rules,
    lora_init,
    lora_merge,
    lora_sharding_rules,
)
from .mlp import MLPConfig, mlp_apply, mlp_init
from .paged import (
    PageAllocator,
    init_paged_pools,
    paged_decode_step,
    paged_prefill,
)
from .moe import MoEConfig, moe_apply, moe_init, moe_loss, moe_sharding_rules
from .block import init_and_apply
from .train_state import TrainState, make_train_step

__all__ = [
    "LlamaConfig", "llama_init", "llama_apply", "llama_loss",
    "PageAllocator", "init_paged_pools", "paged_prefill",
    "paged_decode_step",
    "llama_sharding_rules", "lora_init", "lora_merge", "lora_sharding_rules",
    "MLPConfig", "mlp_init", "mlp_apply",
    "MoEConfig", "moe_init", "moe_apply", "moe_loss", "moe_sharding_rules",
    "init_and_apply",
    "TrainState", "make_train_step",
]
