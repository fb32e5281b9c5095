"""Analytical memory-stream concurrency model.

``TpuDmaModel`` is a latency-hiding (Little's law:
``throughput = min(peak, outstanding_bytes / latency)``) model of D
concurrent HBM→VMEM DMA streams, each a ring of ``lookahead`` block
buffers. This is what the planner scores candidate ``StridingConfig``s
with, and what the roofline memory term refines. It treats the paper's
§4.5 collision effect as a multiplicative efficiency loss when concurrent
streams alias (see ``layout.collides``).
"""
from __future__ import annotations

import dataclasses
import os

from repro.core import layout
from repro.core.striding import StridingConfig

__all__ = ["TpuDmaModel", "TPU_V5E", "seeded_descriptor_overhead",
           "default_tpu_model"]


@dataclasses.dataclass(frozen=True)
class TpuDmaModel:
    """Little's-law model of the TPU HBM↔VMEM DMA subsystem."""

    hbm_bw: float = 819e9          # bytes/s — v5e HBM bandwidth (per brief)
    dma_latency: float = 2e-6      # s — issue→first-byte latency per transfer
    engine_bw: float = 205e9       # bytes/s — single DMA stream ceiling (~hbm/4)
    n_engines: int = 16            # concurrent DMA queues usefully engaged
    descriptor_overhead: float = 0.3e-6  # s per descriptor (strided blocks)

    def stream_bandwidth(self, block_bytes: int, lookahead: int) -> float:
        """Sustained bytes/s of ONE stream with a `lookahead`-deep ring.

        Each block transfer pays a fixed issue cost: the DMA latency plus
        one descriptor (``descriptor_overhead`` — the §5.1.1 term bigger
        ``block_rows`` tiles amortize; seed it from a measured sweep via
        ``REPRO_DMA_DESCRIPTOR_NS`` / ``default_tpu_model``)."""
        in_flight = max(lookahead - 1, 0) * block_bytes + block_bytes
        issue = self.dma_latency + self.descriptor_overhead
        latency_bound = in_flight / (issue + block_bytes / self.engine_bw)
        return min(latency_bound, self.engine_bw)

    def throughput(self, config: StridingConfig, block_bytes: int,
                   spacing_bytes: int | None = None,
                   n_write_streams: int = 0) -> float:
        """Predicted aggregate bytes/s for a multi-strided traversal."""
        d = config.stride_unroll
        per_stream = self.stream_bandwidth(block_bytes * config.portion_unroll,
                                           config.lookahead)
        engines = min(d, self.n_engines)
        agg = engines * per_stream
        # paper §4.5: aliased spacing → streams thrash the same banks
        if spacing_bytes is not None and d > 1 and layout.collides(spacing_bytes):
            agg *= 1.0 / (1.0 + 0.25 * d)
        # paper §4.4: too many concurrent write streams contend on the
        # copy-out queue; soft cap mirrored from the write-buffer effect.
        if n_write_streams > self.n_engines // 2:
            agg *= (self.n_engines // 2) / n_write_streams
        return min(agg, self.hbm_bw)


TPU_V5E = TpuDmaModel()


def seeded_descriptor_overhead(default: float = 0.3e-6) -> float:
    """Per-descriptor issue cost, seedable from a measurement.

    ``REPRO_DMA_DESCRIPTOR_NS`` (nanoseconds) overrides the static
    default, so the ranked ``block_rows`` ordering is testable and can
    take a value fitted on the chip; no such fit exists yet."""
    env = os.environ.get("REPRO_DMA_DESCRIPTOR_NS")
    return float(env) * 1e-9 if env else default


def default_tpu_model() -> TpuDmaModel:
    """The planner's scoring model with the seeded descriptor term (an
    un-seeded environment reproduces ``TPU_V5E`` exactly)."""
    return TpuDmaModel(descriptor_overhead=seeded_descriptor_overhead())
