"""Pallas TPU kernels for the paper's O(n) attention hot spots.

``ss_attention.py`` holds the forward pl.pallas_call kernels (BlockSpec VMEM
tiling, segment-causal masks, online-softmax stats, dynamic key-validity
bounds), ``ss_attention_bwd.py`` the flash-style backward kernels,
``ops.py`` the jitted custom-VJP wrappers, ``sharded.py`` the shard_map
context-parallel driver (per-shard kernels + landmark-sized collectives),
``paged_decode.py`` the gather-free serving decode kernel (chunks of
pool pages copied by DMA through the scalar-prefetched block table),
``dispatch.py`` the impl/block-size registry with measured autotune, and
``ref.py`` the pure-jnp oracles. Validated in interpret mode on CPU; TPU
v5e is the compile target.
"""

from repro.kernels.dispatch import (
    Plan,
    PlanKey,
    autotune,
    autotune_decode,
    dispatch_ss_attention,
    get_plan,
    load_cache,
    make_key,
    register_plan,
    save_cache,
)
from repro.kernels.paged_decode import paged_row_stats, paged_row_stats_lanes
from repro.kernels.ops import (
    flash_merge,
    flash_rescale,
    landmark_summary_op,
    nystrom_attention_fused,
    query_side_op,
    ss_attention_fused,
    ss_core_factors,
)
from repro.kernels.sharded import ss_attention_fused_sharded
from repro.kernels.ss_attention import landmark_summary, query_side
from repro.kernels.ss_attention_bwd import landmark_summary_bwd, query_side_bwd

__all__ = [
    "Plan",
    "PlanKey",
    "autotune",
    "autotune_decode",
    "dispatch_ss_attention",
    "flash_merge",
    "flash_rescale",
    "get_plan",
    "landmark_summary",
    "landmark_summary_bwd",
    "landmark_summary_op",
    "load_cache",
    "make_key",
    "nystrom_attention_fused",
    "paged_row_stats",
    "paged_row_stats_lanes",
    "query_side",
    "query_side_bwd",
    "query_side_op",
    "register_plan",
    "save_cache",
    "ss_attention_fused",
    "ss_attention_fused_sharded",
    "ss_core_factors",
]
