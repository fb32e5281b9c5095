"""Core multi-striding library (the paper's contribution, adapted to TPU).

Public API:
  StridingConfig          — (stride_unroll D, portion_unroll P, lookahead)
  plan / rank_configs     — auto-configuration (paper §6.3 search, modeled)
  plan_transform          — paper §5.1 critical-access selection
  TpuDmaModel             — latency-hiding analytical DMA model
"""
from repro.core.dma_model import TPU_V5E, TpuDmaModel
from repro.core.planner import (Plan, Traffic, plan, rank_configs,
                                traffic_bytes)
from repro.core.striding import (SINGLE_STRIDED, StridingConfig, divisors,
                                 factorizations, partition_rows,
                                 stream_offsets, stream_spacing_bytes,
                                 valid_stride_unrolls)
from repro.core.transform import (ArrayAccess, LoopNest, TransformPlan,
                                  plan_transform)

__all__ = [
    "StridingConfig", "SINGLE_STRIDED", "divisors", "factorizations",
    "stream_offsets", "stream_spacing_bytes", "partition_rows",
    "valid_stride_unrolls",
    "Traffic", "Plan", "plan", "rank_configs", "traffic_bytes",
    "ArrayAccess", "LoopNest", "TransformPlan", "plan_transform",
    "TpuDmaModel", "TPU_V5E",
]
