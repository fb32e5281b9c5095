"""Configuration planner: pick (stride_unroll, portion_unroll) per workload.

The paper explores the (D, P) space exhaustively per kernel (§6.3); the
planner encodes the paper's empirical findings as a scoring model so the
framework can auto-configure:

  * best D is usually 2–10, never past the engine count (Fig 6);
  * D must divide the traversal extent (§5.1.2 divisibility);
  * aliased (power-of-two) stream spacing must be avoided or padded (§4.5);
  * concurrent *write* streams are capped (write-buffer effect, §4.4);
  * the buffer budget bounds D*P (register file → VMEM here).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax.numpy as jnp

from repro import obs
from repro.core import layout
from repro.core.dma_model import TpuDmaModel, default_tpu_model
from repro.core.striding import StridingConfig, valid_stride_unrolls

__all__ = ["Traffic", "Plan", "plan", "rank_configs", "traffic_bytes"]

# Default per-core VMEM working budget (bytes). v5e VMEM ≈ 16 MiB/core; we
# leave half for compute operands/accumulators.
DEFAULT_VMEM_BUDGET = 8 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class Traffic:
    """Memory signature of a kernel traversal (paper Table 1 columns)."""

    rows: int                  # stride-unrollable extent
    cols: int                  # contiguous-axis extent (elements)
    dtype: object = jnp.float32
    read_arrays: int = 1       # load streams per stride (Table 1 "L")
    write_arrays: int = 0      # store streams per stride (Table 1 "S")
    rw_arrays: int = 0         # load/store streams per stride ("L/S")
    resident_bytes: int = 0    # always-in-VMEM operands (vectors, weights)

    @property
    def arrays_per_stride(self) -> int:
        return self.read_arrays + self.write_arrays + 2 * self.rw_arrays


@dataclasses.dataclass(frozen=True)
class Plan:
    config: StridingConfig
    padded_cols: int           # collision-free lane-aligned column count
    predicted_bw: float        # bytes/s from the DMA model
    vmem_bytes: int
    ranked: tuple = ()         # [(config, bw), ...] best-first (for sweeps)


def traffic_bytes(traffic: Traffic) -> int:
    """Total bytes one traversal moves (the denominator of effective
    bandwidth): every read/write stream touches rows × cols elements
    once, load/store streams twice, plus the resident operands.  Pairs a
    measured wall-clock with the paper's GiB/s unit (§4)."""
    body = traffic.rows * traffic.cols * jnp.dtype(traffic.dtype).itemsize
    return body * traffic.arrays_per_stride + traffic.resident_bytes


def _block_bytes(traffic: Traffic, portion: int, block_rows: int = 0) -> int:
    sub, lane = layout.sublane_tile(traffic.dtype)
    rows = block_rows or sub   # §5.1.1 cache block; default one sublane tile
    return rows * lane * portion * jnp.dtype(traffic.dtype).itemsize


def _vmem(traffic: Traffic, cfg: StridingConfig) -> int:
    per_stream = _block_bytes(traffic, cfg.portion_unroll,
                              cfg.block_rows) * cfg.lookahead
    return (cfg.stride_unroll * traffic.arrays_per_stride * per_stream
            + traffic.resident_bytes)


def rank_configs(traffic: Traffic,
                 model: Optional[TpuDmaModel] = None,
                 vmem_budget: int = DEFAULT_VMEM_BUDGET,
                 max_streams: int = 16,
                 max_unrolls: int = 32,
                 pad_layout: bool = True,
                 lookahead: int = 2,
                 block_rows_candidates: Sequence[int] = (0,),
                 spec=None,
                 ) -> list[tuple[StridingConfig, float, int]]:
    """All feasible configs scored best-first: [(config, bw, padded_cols)].

    ``block_rows_candidates`` adds the §5.1.1 cache-blocking dimension to
    the sweep: each entry is a per-stream block-row tile (0 = emitter
    default).  Larger blocks amortize DMA descriptors (bigger transfers)
    but cost ``D · arrays · block · lookahead`` VMEM, so infeasible
    (block, D, P) points are pruned against ``vmem_budget`` exactly like
    plain (D, P) points.

    ``spec`` (a ``TraversalSpec`` or tuple of them) additionally gates
    every candidate through the static verifier (``repro.analysis``):
    a config the checker rejects — a write race, a pad-contract
    violation, an emitter-geometry VMEM overflow the coarse ``_vmem``
    signature model missed — never reaches the autotune sweep.  Each
    drop ticks the ``analysis.rejected_candidates`` counter; if every
    candidate is rejected this raises the same ``ValueError`` as an
    infeasible Traffic.

    ``model=None`` scores with :func:`~repro.core.dma_model.
    default_tpu_model`, whose descriptor term is seedable via
    ``REPRO_DMA_DESCRIPTOR_NS``.
    """
    if model is None:
        model = default_tpu_model()
    rejects = None
    if spec is not None:
        from repro.analysis import checker as _checker   # deferred: heavy
        static_bad = any(f.severity == "error"
                         for f in _checker.check(spec))

        def rejects(cfg):
            if static_bad:
                return True
            fs = _checker.check(spec, cfg, vmem_budget=vmem_budget,
                                static=False)
            return any(f.severity == "error" for f in fs)
    itemsize = jnp.dtype(traffic.dtype).itemsize
    out = []
    for d in valid_stride_unrolls(traffic.rows, max_d=max_streams):
        if pad_layout:
            cols, aliased = layout.conflict_free_cols(
                traffic.rows, traffic.cols, d, traffic.dtype)
        else:
            cols = layout.pad_to_lane(traffic.cols)
            aliased = False
        spacing = (traffic.rows // d) * cols * itemsize
        if aliased:
            # kernel will apply a column stagger; spacing is de-aliased by
            # one block per stream (see layout.stream_stagger).
            sub, lane = layout.sublane_tile(traffic.dtype)
            spacing += lane * itemsize
        for p in (1, 2, 4, 8):
            if d * p > max_unrolls:
                continue
            for bm in block_rows_candidates:
                if bm and bm > max(traffic.rows // d, 1):
                    continue     # tile taller than a stream's segment
                cfg = StridingConfig(d, p, lookahead=lookahead,
                                     block_rows=bm)
                vmem = _vmem(traffic, cfg)
                if vmem > vmem_budget:
                    continue
                if rejects is not None and rejects(cfg):
                    obs.counter("analysis.rejected_candidates")
                    continue
                n_write = d * (traffic.write_arrays + traffic.rw_arrays)
                bw = model.throughput(cfg, _block_bytes(traffic, 1, bm),
                                      spacing_bytes=spacing,
                                      n_write_streams=n_write)
                out.append((cfg, bw, cols))
    if not out:
        raise ValueError(f"no feasible striding config for {traffic}")
    # best bandwidth first; tie-break toward smaller D, P, then block
    out.sort(key=lambda t: (-t[1], t[0].stride_unroll, t[0].portion_unroll,
                            t[0].block_rows))
    return out


def plan(traffic: Traffic, **kw) -> Plan:
    ranked = rank_configs(traffic, **kw)
    cfg, bw, cols = ranked[0]
    return Plan(config=cfg, padded_cols=cols, predicted_bw=bw,
                vmem_bytes=_vmem(traffic, cfg),
                ranked=tuple((c, b) for c, b, _ in ranked))
