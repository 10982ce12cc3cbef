"""TPU kernel / collective ops layer.

Hot ops the zoo models call into: Pallas TPU kernels where a hand
schedule beats XLA fusion, pure-XLA blockwise formulations everywhere
else, and both context-parallel schedules — ring (ppermute K/V
rotation) and Ulysses (all-to-all head re-sharding) — for sequence
parallelism over the ``sp`` mesh axis (SURVEY.md §5 — absent upstream,
first-class here).
"""

from .attention import (batch_sharded_flash_attention,
                        blockwise_attention, default_attention,
                        flash_attention,
                        naive_attention, ring_attention,
                        sequence_sharded_attention, ulysses_attention)
from .moe import held_experts_swiglu, sigmoid_topk_gates, switch_moe
from .pipeline import pipeline_apply, pipelined
from .short_conv import gated_short_conv

__all__ = [
    "batch_sharded_flash_attention", "blockwise_attention",
    "default_attention", "flash_attention", "gated_short_conv",
    "held_experts_swiglu",
    "naive_attention",
    "pipeline_apply", "pipelined", "ring_attention",
    "sequence_sharded_attention", "sigmoid_topk_gates", "switch_moe",
    "ulysses_attention",
]
