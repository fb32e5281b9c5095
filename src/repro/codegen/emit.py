"""Emitter: lower a scheduled loop nest to a Pallas kernel.

The scheduled nest's STREAM part becomes D operand refs per traversed
array — D independent HBM→VMEM DMA pipelines, the TPU rendering of the
paper's D concurrent strides (same machinery as ``core.pipeline``).  The
GRID parts become the ``pallas_call`` grid (batch axes lead), UNROLL the
block rows, VECTOR the lane dimension, and BLOCK tiles (free axes, the
§5.1.1 cache blocks) ride whole inside every kernel block.  Four
lowering strategies:

  * ``_emit_streaming`` — elementwise/stencil nests: D (or D × taps, for
    row stencils) input operands, a ``[batch…, D, bm, …]``-blocked
    output, body applied per stream in grouped or interleaved
    arrangement (§4.1/§4.4).  Covers free-axis outputs (e.g. doitgen's
    ``[q, p]`` tiles with the reduction contracted inside the body).
  * ``_emit_reduction`` — vector-axis reductions written per stride row:
    f32 VMEM accumulator per stream, written on the last reduction step
    (the mxv pattern).
  * ``_emit_stream_reduction`` — the stride axis itself is reduced (the
    mxv_t / flash-decode pattern): every stream's partial state merges
    across streams and row-grid steps with the ``spec.reduce``
    combinator — "sum" / "max", or any paired-state ``codegen.Combine``
    (e.g. ``OnlineSoftmax``: running max + rescaled sums, the
    single-pass flash-decode algebra) — into one f32 accumulator per
    state component, finalized into the output ref(s) at the end.
  * ``_emit_manual`` — explicit ``lookahead``-deep DMA rings (the
    ``copy_manual`` pattern), one *fused* ring per operand: each step's
    D stream copies issue back-to-back onto a single per-slot
    semaphore, and stores drain through a double-buffered staging ring
    instead of blocking each stream's compute.  Selected when
    ``config.lookahead != 2`` (lookahead=1 = prefetch off).

Specs with multiple ``writes`` lower to multiple Pallas output refs —
one store stream (or manual staging ring) per output, no stacked free
axis and no unstack copies; the body returns one block per write.  Each
write carries its OWN access map (``_plan_writes``): a rank-1 row
statistic lowers to a ``(d, bm, 1)`` column block next to a matrix
write's ``(d, bm, bn)``, a free-axis side output to its own whole-extent tile,
and stream reductions finalize one block per write through a
*finalizing* combinator (``OnlineSoftmax(with_lse=True)`` emits the
attention row and its log-sum-exp from one accumulated state).
Writes-only specs (no reads) broadcast the body's value into the store
stream (the ``init`` fill pattern).

1-D nests take the §5.1.1 loop-blocking path first (``classify`` flags
them): the single axis is tiled into a ``[rows, 128·P]`` grid — the
``transforms.block`` shape — and the blocked 2-D spec then runs the
standard multi-striding pipeline.

``evaluate`` (in ``loopir``) is the ref-mode fallback; ``make_kernel_op``
wraps the whole pipeline as a public op with the same mode dispatch,
tune-cache/planner config resolution, and padding conventions as the
hand-written ``ops.py`` wrappers.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.codegen import loopir, transforms
from repro.codegen.combine import resolve_combine
from repro.core.striding import StridingConfig

__all__ = ["emit_spec", "emit_scheduled", "run_spec", "make_kernel_op"]


def _fit(x, shape: tuple[int, ...], broadcast: bool = False):
    """Reshape a body result to its output block.  Only writes-only
    (fill) bodies may *broadcast* a scalar value into the block —
    read-ful bodies must produce the block's exact element count, so a
    dimension accidentally collapsed in the body still errors instead
    of being silently replicated."""
    x = jnp.asarray(x)
    size = 1
    for s in shape:
        size *= s
    if x.size == size:
        return x.reshape(shape)
    if broadcast:
        return jnp.broadcast_to(x, shape)
    raise ValueError(f"body result shape {x.shape} does not fill the "
                     f"output block {shape}")


def _as_blocks(res, spec: loopir.TraversalSpec) -> tuple:
    """Normalize a body result to one block per write access."""
    outs = res if isinstance(res, tuple) else (res,)
    if len(outs) != len(spec.writes):
        raise ValueError(f"{spec.name}: body returned {len(outs)} blocks "
                         f"for {len(spec.writes)} writes")
    return outs


# ------------------------------------------------------------ operands

@dataclasses.dataclass
class _Operand:
    """One read access lowered to pallas operands (possibly one per
    stream × stencil tap)."""

    access: loopir.Access
    arrays: list           # operand arrays, in_specs order
    specs: list            # matching pl.BlockSpec list
    kind: str              # "stream2d" | "stream1d" | "resident"
    taps: int = 1          # row-tap operands per stream
    squeeze: bool = False  # drop the artificial leading dim of a 1-D read
    env_shape: Optional[tuple] = None   # body block shape of a lifted read

    def load(self, refs: Sequence, base: int, k: int, lanes=None):
        """Build this access's env block for stream ``k`` (optionally a
        lane sub-slice, for the interleaved arrangement)."""
        if self.kind == "resident":
            blk = refs[base][...]
            if self.squeeze:
                blk = blk[0]
            elif self.env_shape is not None:
                blk = blk.reshape(self.env_shape)
            return blk if lanes is None else blk[lanes]
        if self.kind == "stream1d":
            blk = refs[base + k][...]
            # drop the artificial leading dim of an unbatched 1-D read;
            # batched row streams get back their (1,)*nb batch dims
            if self.squeeze:
                return blk[0]
            return blk.reshape(self.env_shape)
        if self.taps == 1:
            blk = refs[base + k][...]
            return blk if lanes is None else blk[:, lanes]
        rows = [refs[base + k * self.taps + t][...] for t in range(self.taps)]
        return jnp.concatenate(rows, axis=0)   # halo-widened block


def _lift(x, nb: int):
    """Mosaic tiles the last two dims of every block at (8, 128) or their
    full extent, so a batch dim may not be one of them.  Give a batched
    operand with fewer than two non-batch dims unit dims after its batch
    prefix (``[b, F]`` → ``[b, 1, F]``); returns the array and the count
    of unit dims added.  Its batch block dims are then squeezed."""
    k = max(0, 2 - (x.ndim - nb)) if nb else 0
    if k:
        x = x.reshape(x.shape[:nb] + (1,) * k + x.shape[nb:])
    return x, k


def _lower_reads(sched: transforms.Schedule, bp: transforms.BlockPlan,
                 arrays: Sequence, pos: dict) -> list[_Operand]:
    """Lower every read access against the grid-position map ``pos``
    (axis name → pallas grid dimension).

    Streamed forms (stride axis in the index): ``[batch…, stride,
    vector]`` (D operands × row taps) and ``[batch…, stride]`` (D
    rank-1 row streams, e.g. gemver's u vectors, mxv_t's x, or decode
    attention's per-batch validity mask).  Everything else is resident:
    whole-extent blocks on the non-batch dims, one batch element per
    grid step on the batch dims.
    """
    spec, info = sched.spec, bp.info
    stream = sched.find(info.stride_axis, transforms.STREAM)
    d, seg_rows = stream.extent, stream.stride
    segb = seg_rows // bp.bm
    full = info.col_halo != (0, 0) or spec.full_width
    row_pos, col_pos = pos[info.stride_axis], pos[info.vector_axis]

    ops = []
    for acc, x in zip(spec.reads, arrays):
        bvars = tuple(v for v in acc.index if v in info.batch_axes)
        rest = tuple(v for v in acc.index if v not in info.batch_axes)
        nb = len(bvars)
        bpos = tuple(pos[v] for v in bvars)
        if info.stride_axis not in rest:
            # resident: whole extents, except a vector-indexed dim which
            # follows the column grid at bn lanes (unless full-width)
            squeeze = False
            dim_vars = acc.index
            if nb == 0 and x.ndim == 1:
                x, squeeze = x.reshape(1, -1), True
                dim_vars = (None,) + dim_vars
            x, lifted = _lift(x, nb)
            dim_vars = dim_vars[:nb] + (None,) * lifted + dim_vars[nb:]
            block, codes = [], []
            for dv, size in zip(dim_vars, x.shape):
                if dv in info.batch_axes:
                    block.append(pl.squeezed if lifted else 1)
                    codes.append(pos[dv])
                elif (dv == info.vector_axis and not full
                        and acc.halo_of(dv) == (0, 0)):
                    block.append(bp.bn)
                    codes.append(col_pos)
                else:
                    block.append(size)
                    codes.append(-1)
            env_shape = ((1,) * nb + tuple(block[nb + lifted:])
                         if lifted else None)

            def imap(*g, _codes=tuple(codes)):
                return tuple(0 if c < 0 else g[c] for c in _codes)
            ops.append(_Operand(acc, [x], [pl.BlockSpec(tuple(block), imap)],
                                "resident", squeeze=squeeze,
                                env_shape=env_shape))
        elif (len(rest) == 2 and rest[0] == info.stride_axis
                and (rest[1] == info.vector_axis
                     or rest[1] in info.free_axes)):
            lo, hi = acc.halo_of(info.stride_axis)
            taps = 1 + lo + hi
            if taps > 1 and bp.bm != 1:
                raise NotImplementedError(
                    f"{spec.name}: row-haloed access {acc.array!r} needs "
                    "single-row blocks")
            if taps > 1 and nb:
                raise NotImplementedError(
                    f"{spec.name}: row halo on a batched access")
            if rest[1] != info.vector_axis:       # free axis: whole dim
                width, full_width = x.shape[-1], True
            else:
                width = (x.shape[-1] if (full or acc.halo_of(
                    info.vector_axis) != (0, 0)) else bp.bn)
                full_width = width != bp.bn or full
            specs, operands = [], []
            for k in range(d):
                for t in range(taps):
                    def imap(*g, _k=k, _t=t, _taps=taps, _fw=full_width,
                             _bpos=bpos):
                        i = g[row_pos]
                        if _taps > 1:      # bm == 1: block idx == row idx
                            i = i + _k * seg_rows + _t
                        else:
                            i = i + _k * segb
                        j = 0 if _fw else g[col_pos]
                        return tuple(g[p] for p in _bpos) + (i, j)
                    specs.append(
                        pl.BlockSpec((1,) * nb + (bp.bm, width), imap))
                    operands.append(x)
            ops.append(_Operand(acc, operands, specs, "stream2d", taps=taps))
        elif rest == (info.stride_axis,):
            if acc.has_halo:
                raise NotImplementedError(
                    f"{spec.name}: halo on rank-1 streamed {acc.array!r}")
            # [batch…, stride]: D rank-1 row streams (one batch element
            # per grid step), e.g. decode_attn's kv_len validity mask.
            # Rows ride the lane axis: unbatched operands get a leading
            # unit dim (squeezed back at load), batched ones a unit dim
            # after their squeezed batch prefix (see ``_lift``).
            x2 = _lift(x, nb)[0] if nb else x.reshape(1, -1)
            lead_block = (pl.squeezed,) * nb + (1,)
            specs, operands = [], []
            for k in range(d):
                def imap(*g, _k=k, _bpos=bpos):
                    lead = tuple(g[p] for p in _bpos) + (0,)
                    return lead + (g[row_pos] + _k * segb,)
                specs.append(pl.BlockSpec(lead_block + (bp.bm,), imap))
                operands.append(x2)
            ops.append(_Operand(acc, operands, specs, "stream1d",
                                squeeze=not nb,
                                env_shape=(1,) * nb + (bp.bm,)))
        else:
            raise NotImplementedError(
                f"{spec.name}: access {acc.array!r}{acc.index} not "
                "lowerable (supported: [batch…, stride, vector], "
                "[batch…, stride], and stride-free resident reads; "
                "interchange the nest or transpose the operand)")
    return ops


def _scalar_specs(scalars: Sequence) -> tuple[list, list]:
    arrays = [jnp.asarray(s).reshape(1, 1) for s in scalars]
    specs = [pl.BlockSpec((1, 1), lambda *g: (0, 0)) for _ in scalars]
    return arrays, specs


def _env_builder(spec: loopir.TraversalSpec, ops: list[_Operand],
                 n_reads_ops: int):
    """Returns env(refs, k, lanes) mapping array/scalar names → blocks."""
    bases, base = [], 0
    for op in ops:
        bases.append(base)
        base += len(op.arrays)

    def env(refs, k, lanes=None):
        e = {}
        for op, b in zip(ops, bases):
            e[op.access.array] = op.load(refs, b, k, lanes)
        for s, name in enumerate(spec.scalars):
            e[name] = refs[n_reads_ops + s][0, 0]
        return e
    return env


# ------------------------------------------------------------ lowering

def _geometry(sched: transforms.Schedule, bp: transforms.BlockPlan,
              row_innermost: bool = False):
    """Pallas grid tuple + axis→dimension map.  Batch axes lead; the
    stride row grid and vector col grid follow (row innermost for
    stride-axis reductions so partials accumulate per output block)."""
    extents = {l.axis: l.extent for l in sched.grid_loops()}
    inner = ([bp.info.vector_axis, bp.info.stride_axis] if row_innermost
             else [bp.info.stride_axis, bp.info.vector_axis])
    order = list(bp.info.batch_axes) + inner
    grid = tuple(extents[a] for a in order)
    return grid, {a: i for i, a in enumerate(order)}


def _write_rest(acc: loopir.Access, info: loopir.NestInfo) -> tuple:
    """A write's non-batch index vars, in declared order."""
    return tuple(v for v in acc.index if v not in info.batch_axes)


@dataclasses.dataclass
class _WritePlan:
    """One write access lowered to its OWN output geometry: block shape,
    grid index map, and padded/final array shapes — heterogeneous maps
    (a rank-1 row statistic next to a matrix write) each get their own
    split instead of sharing writes[0]'s."""

    access: loopir.Access
    nb: int                    # leading batch dims
    bpos: tuple                # batch grid positions
    batch_ext: tuple           # batch extents (natural, unpadded)
    tail: tuple                # non-batch vars after the stride axis
    block_tail: tuple          # block dims for the tail vars
    shape_tail: tuple          # padded array dims for the tail vars
    imap_tail: tuple           # grid position per tail dim (None = whole)
    plain: bool                # == (stride, vector) map, lane-slicable
    transposed: bool = False   # == (vector, stride) map: permuted store


def _plan_writes(spec: loopir.TraversalSpec, bp: transforms.BlockPlan,
                 pos: dict) -> list[_WritePlan]:
    """Per-write geometry for the streaming path.  Every write must lead
    with the stride axis (after its batch prefix); the tail may be any
    order/subset of the vector axis and free axes — a write that OMITS
    the vector axis is a reduced-rank side output whose row statistic
    needs whole rows (``full_width``), since a lane-split body could only
    produce per-sub-row values."""
    info = bp.info
    full = info.col_halo != (0, 0) or spec.full_width
    plans = []
    for acc in spec.writes:
        bvars = tuple(v for v in acc.index if v in info.batch_axes)
        rest = _write_rest(acc, info)
        if rest == (info.vector_axis, info.stride_axis):
            # transposed store: the stride axis lands AFTER the vector
            # axis in the output, so each stream's (bm, bn) compute block
            # stores into a (bn, bm) column slab of a [cols, d, seg_rows]
            # buffer (merged to [cols, rows] after the call).  The body
            # returns the block already permuted to the write's index
            # order (vector leading) — same contract as every other
            # write: blocks match the write map.
            plans.append(_WritePlan(
                access=acc, nb=len(bvars),
                bpos=tuple(pos[v] for v in bvars),
                batch_ext=tuple(spec.axis(v).extent for v in bvars),
                tail=(info.vector_axis,),
                block_tail=(bp.cols if full else bp.bn,),
                shape_tail=(bp.cols,),
                imap_tail=(None if full else pos[info.vector_axis],),
                plain=False, transposed=True,
            ))
            continue
        if not rest or rest[0] != info.stride_axis:
            raise NotImplementedError(
                f"{spec.name}: streaming write {acc.array!r}{acc.index} "
                "must lead with the stride axis (after any batch axes) "
                "or be the transposed (vector, stride) pair")
        tail = rest[1:]
        if (info.vector_axis not in tail
                and not (full or bp.bn == bp.cols)):
            raise NotImplementedError(
                f"{spec.name}: write {acc.array!r}{acc.index} omits the "
                f"vector axis {info.vector_axis!r}; a reduced-rank side "
                "output needs full_width=True (its row statistic must "
                "see whole rows)")
        block_tail, shape_tail, imap_tail = [], [], []
        for v in tail:
            if v == info.vector_axis:
                shape_tail.append(bp.cols)
                block_tail.append(bp.cols if full else bp.bn)
                imap_tail.append(None if full else pos[v])
            else:                               # free axis: whole extent
                shape_tail.append(spec.axis(v).extent)
                block_tail.append(spec.axis(v).extent)
                imap_tail.append(None)
        if not tail:
            # a rank-1 row statistic is a [rows, 1] column: its rows
            # take the sublane axis, so the block needs bm % 8 == 0
            # instead of bm % 128 (lane-major rows)
            block_tail, shape_tail, imap_tail = [1], [1], [None]
        plans.append(_WritePlan(
            access=acc, nb=len(bvars),
            bpos=tuple(pos[v] for v in bvars),
            batch_ext=tuple(spec.axis(v).extent for v in bvars),
            tail=tail, block_tail=tuple(block_tail),
            shape_tail=tuple(shape_tail), imap_tail=tuple(imap_tail),
            plain=(not bvars and tail == (info.vector_axis,) and not full),
        ))
    return plans


def _lane_slices(cfg: StridingConfig, bn: int) -> list:
    """Interleaved arrangement (§4.4): round-robin streams at 128-lane
    sub-portion granularity; grouped keeps each stream's accesses
    consecutive (§4.1 default)."""
    if cfg.arrangement != "interleaved" or bn <= 128:
        return [None]
    sub = bn // 128
    step = bn // sub
    return [slice(s * step, (s + 1) * step) for s in range(sub)]


def _grouped_fold_env(spec: loopir.TraversalSpec, ops: list[_Operand],
                      env, lanes: list):
    """env(refs, k) for reduction bodies under the interleaved
    arrangement: each lane-affected access's sub-portion loads are
    issued round-robin (§4.4) but REASSEMBLED into one full-width block,
    so the body folds every row in the same grouped bracketing as the
    grouped arrangement.  Folding each sub-portion's partial into the
    accumulator separately reassociated the f32 sum — the regression
    that forced the grouped-vs-interleaved tolerance to 1e-5 in PR 4;
    tests pin the restored 1e-6 parity."""
    if len(lanes) == 1:
        return lambda refs, k: env(refs, k, lanes[0])
    laned = {op.access.array for op in ops if op.kind != "stream1d"}

    def env_full(refs, k):
        parts = [env(refs, k, sl) for sl in lanes]   # round-robin issue
        return {name: (jnp.concatenate([p[name] for p in parts], axis=-1)
                       if name in laned else parts[0][name])
                for name in parts[0]}
    return env_full


def _emit_streaming(sched, bp, arrays, scalars, interpret: bool):
    spec, info = sched.spec, bp.info
    stream = sched.find(info.stride_axis, transforms.STREAM)
    d, seg_rows = stream.extent, stream.stride
    grid, pos = _geometry(sched, bp)
    row_pos = pos[info.stride_axis]
    ops = _lower_reads(sched, bp, arrays, pos)
    scal_arrays, scal_specs = _scalar_specs(scalars)
    in_specs = [s for op in ops for s in op.specs] + scal_specs
    operands = [a for op in ops for a in op.arrays] + scal_arrays
    env = _env_builder(spec, ops, sum(len(op.arrays) for op in ops))

    wplans = _plan_writes(spec, bp, pos)
    plain = (all(wp.plain for wp in wplans) and not info.free_axes
             and all(op.taps == 1 for op in ops))
    lanes = _lane_slices(sched.config, bp.bn) if plain else [None]
    out_dtypes = spec.out_dtypes(arrays)
    n_out = len(spec.writes)

    fill = not spec.reads               # writes-only: broadcast the value

    def kernel(*refs):
        o_refs = refs[len(operands):len(operands) + n_out]
        for sl in lanes:
            for k in range(d):
                blocks = _as_blocks(spec.body(env(refs, k, sl)), spec)
                for o_ref, res, wp in zip(o_refs, blocks, wplans):
                    if wp.transposed:   # plain=False ⇒ sl is None here
                        o_ref[(0,) * wp.nb + (slice(None), k)] = _fit(
                            res, (*wp.block_tail, bp.bm),
                            broadcast=fill).astype(o_ref.dtype)
                        continue
                    idx = (0,) * wp.nb + (k,)
                    if sl is None:
                        o_ref[idx] = _fit(res, (bp.bm, *wp.block_tail),
                                          broadcast=fill
                                          ).astype(o_ref.dtype)
                    else:               # lane sub-portion: static shape
                        o_ref[idx + (slice(None), sl)] = _fit(
                            res, (bp.bm, sl.stop - sl.start),
                            broadcast=fill).astype(o_ref.dtype)

    def out_spec(wp):
        if wp.transposed:
            def out_imap_t(*g):
                return (tuple(g[p] for p in wp.bpos)
                        + tuple(0 if p is None else g[p]
                                for p in wp.imap_tail)
                        + (0, g[row_pos]))
            return pl.BlockSpec(
                (1,) * wp.nb + (*wp.block_tail, d, bp.bm), out_imap_t)

        def out_imap(*g):
            return (tuple(g[p] for p in wp.bpos) + (0, g[row_pos])
                    + tuple(0 if p is None else g[p]
                            for p in wp.imap_tail))
        return pl.BlockSpec((1,) * wp.nb + (d, bp.bm, *wp.block_tail),
                            out_imap)

    def out_buf_shape(wp):
        if wp.transposed:   # stride dims trail; merged after the call
            return wp.batch_ext + (*wp.shape_tail, d, seg_rows)
        return wp.batch_ext + (d, seg_rows, *wp.shape_tail)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[out_spec(wp) for wp in wplans],
        out_shape=[jax.ShapeDtypeStruct(out_buf_shape(wp), jnp.dtype(dt))
                   for wp, dt in zip(wplans, out_dtypes)],
        interpret=interpret,
        name=spec.name,
    )(*operands)
    res = tuple(
        o.reshape(*wp.batch_ext, *wp.shape_tail, d * seg_rows)
        if wp.transposed
        else o.reshape(*wp.batch_ext, d * seg_rows,
                       *(wp.shape_tail if wp.tail else ()))   # [rows, 1]
        for o, wp in zip(out, wplans))
    return res[0] if n_out == 1 else res


def _emit_reduction(sched, bp, arrays, scalars, interpret: bool):
    """Vector-axis reductions written per stride row (the mxv pattern):
    one f32 VMEM accumulator PER WRITE, written on the last reduction
    step.  Multi-output specs accumulate each write's partial block into
    its own accumulator with its OWN single-state combinator from
    ``spec.combines()`` (a row-max next to a row-sum in one sweep); a
    scalar ``reduce`` keeps the historical all-sum vecred contract."""
    spec, info = sched.spec, bp.info
    if info.batch_axes:
        raise NotImplementedError(
            f"{spec.name}: batched vector-axis reduction")
    combs = spec.combines()
    for comb in combs:
        if comb.n_state > 1 or comb.finalizing:
            raise NotImplementedError(
                f"{spec.name}: vector-axis reduction accumulators are "
                f"per-write single-state; combine {comb.name!r} is "
                "stateful/finalizing (stride-reduction only)")
    stream = sched.find(info.stride_axis, transforms.STREAM)
    d, seg_rows = stream.extent, stream.stride
    grid, pos = _geometry(sched, bp)
    row_pos, col_pos = pos[info.stride_axis], pos[info.vector_axis]
    ops = _lower_reads(sched, bp, arrays, pos)
    scal_arrays, scal_specs = _scalar_specs(scalars)
    in_specs = [s for op in ops for s in op.specs] + scal_specs
    operands = [a for op in ops for a in op.arrays] + scal_arrays
    env = _env_builder(spec, ops, sum(len(op.arrays) for op in ops))
    has_taps = any(op.taps > 1 for op in ops)
    lanes = ([None] if has_taps
             else _lane_slices(sched.config, bp.bn))
    env_full = _grouped_fold_env(spec, ops, env, lanes)
    out_dtypes = spec.out_dtypes(arrays)
    n_out = len(spec.writes)

    def kernel(*refs):
        o_refs = refs[len(operands):len(operands) + n_out]
        accs = refs[len(operands) + n_out:]
        j = pl.program_id(col_pos)

        @pl.when(j == 0)
        def _():
            for acc, comb in zip(accs, combs):
                (v,) = comb.init([acc.shape])
                acc[...] = v

        for k in range(d):
            blocks = _as_blocks(spec.body(env_full(refs, k)), spec)
            for acc, res, comb in zip(accs, blocks, combs):
                part = _fit(res, (bp.bm, 1)).astype(jnp.float32)
                (v,) = comb.merge((acc[k],), (part,))
                acc[k] = v

        @pl.when(j == pl.num_programs(col_pos) - 1)
        def _():
            for o_ref, acc in zip(o_refs, accs):
                o_ref[...] = acc[...].astype(o_ref.dtype)

    # per-row results are [rows, 1] columns (rows on sublanes)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((d, bp.bm, 1),
                                lambda *g: (0, g[row_pos], 0))
                   for _ in range(n_out)],
        out_shape=[jax.ShapeDtypeStruct((d, seg_rows, 1), jnp.dtype(dt))
                   for dt in out_dtypes],
        scratch_shapes=[pltpu.VMEM((d, bp.bm, 1), jnp.float32)
                        for _ in range(n_out)],
        interpret=interpret,
        name=spec.name,
    )(*operands)
    res = tuple(o.reshape(d * seg_rows) for o in out)
    return res[0] if n_out == 1 else res


def _emit_stream_reduction(sched, bp, arrays, scalars, interpret: bool):
    """Stride axis is the reduction (mxv_t / flash-decode partials): all
    D streams' partial states merge with ``spec.combine`` — one f32 VMEM
    accumulator per state component — across streams and the row grid,
    finalized into the output ref(s) on the last row step.  Single-state
    combinators ("sum" / "max") keep the historical body contract (one
    partial block); paired-state combinators (e.g. ``OnlineSoftmax``)
    take the body's state tuple.  Each write gets its OWN geometry —
    the vector axis or one free axis (plus the batch prefix) — and a
    multi-output spec needs a *finalizing* combinator whose finalize
    produces one block per write (e.g. ``OnlineSoftmax(with_lse=True)``:
    the attention row next to the ``groups``-wide log-sum-exp)."""
    spec, info = sched.spec, bp.info
    if isinstance(spec.reduce, tuple):
        raise NotImplementedError(
            f"{spec.name}: per-write combinators on a stride-axis "
            "reduction (all D streams merge ONE shared state); use a "
            "scalar or finalizing combinator")
    comb = resolve_combine(spec.reduce)
    stream = sched.find(info.stride_axis, transforms.STREAM)
    d = stream.extent
    grid, pos = _geometry(sched, bp, row_innermost=True)
    row_pos, col_pos = pos[info.stride_axis], pos[info.vector_axis]
    ops = _lower_reads(sched, bp, arrays, pos)
    scal_arrays, scal_specs = _scalar_specs(scalars)
    in_specs = [s for op in ops for s in op.specs] + scal_specs
    operands = [a for op in ops for a in op.arrays] + scal_arrays
    env = _env_builder(spec, ops, sum(len(op.arrays) for op in ops))
    out_dtypes = spec.out_dtypes(arrays)
    n_out = len(spec.writes)
    if n_out > 1 and not (comb.n_state > 1 or comb.finalizing):
        raise NotImplementedError(
            f"{spec.name}: a multi-output stride reduction needs a "
            f"finalizing combinator producing one block per write; "
            f"{comb.name!r} finalizes the accumulated state identically")

    plans, widths_per = [], []
    for acc_w in spec.writes:
        bvars = tuple(v for v in acc_w.index if v in info.batch_axes)
        rest = _write_rest(acc_w, info)
        batch_ext = tuple(spec.axis(v).extent for v in bvars)
        if rest == (info.vector_axis,):
            w = bp.bn                      # per-col-block partial outputs
            if comb.n_state > 1 and bp.bn != bp.cols:
                raise NotImplementedError(
                    f"{spec.name}: a paired-state combinator cannot split "
                    "the vector axis across grid steps (state widths are "
                    "derived from the whole output row); set "
                    "full_width=True")
        elif rest and all(v in info.free_axes for v in rest):
            if bp.bn != bp.cols:
                raise NotImplementedError(
                    f"{spec.name}: free-axis reduction output "
                    f"{acc_w.array!r} needs full_width=True (vector axis "
                    "consumed in the body)")
            w = 1
            for v in rest:
                w *= spec.axis(v).extent
        else:
            raise NotImplementedError(
                f"{spec.name}: stride-reduction write {acc_w.array!r}"
                f"{acc_w.index} must be the vector axis or free axes "
                "(plus batch)")
        plans.append((rest, tuple(pos[v] for v in bvars), batch_ext))
        widths_per.append(w)
    # accumulator geometry follows the PRIMARY (first) write: its width
    # is what the body's partial state covers; side writes are derived
    # by finalize from the same state
    state_shapes = comb.state_shapes(widths_per[0])
    res_shapes = [r.shape for r in _as_blocks(jax.eval_shape(
        comb.finalize, tuple(jax.ShapeDtypeStruct(s, jnp.float32)
                             for s in state_shapes)), spec)]

    out_specs, out_shapes, finals = [], [], []
    for (rest, bpos, batch_ext), w, res_shape in zip(plans, widths_per,
                                                     res_shapes):
        nb = len(bpos)
        if rest == (info.vector_axis,):
            def out_imap(*g, _bpos=bpos):
                return tuple(g[p] for p in _bpos) + (0, g[col_pos])
            block = (1,) * nb + (1, w)
            out_shapes.append(batch_ext + (1, bp.cols))
            finals.append(batch_ext + (bp.cols,))
        else:
            # free-axis outputs take the finalized block's own layout
            # (a lane row, or [groups, …] for the online softmax), so
            # the batch dims stay out of the tiled last two
            def out_imap(*g, _bpos=bpos, _n=len(res_shape)):
                return tuple(g[p] for p in _bpos) + (0,) * _n
            block = (1,) * nb + res_shape
            out_shapes.append(batch_ext + res_shape)
            finals.append(batch_ext + tuple(spec.axis(v).extent
                                            for v in rest))
        out_specs.append(pl.BlockSpec(block, out_imap))

    def kernel(*refs):
        o_refs = refs[len(operands):len(operands) + n_out]
        accs = refs[len(operands) + n_out:]
        i = pl.program_id(row_pos)

        @pl.when(i == 0)
        def _():
            for acc, v in zip(accs, comb.init([a.shape for a in accs])):
                acc[...] = v

        for k in range(d):
            part = spec.body(env(refs, k))
            part = part if isinstance(part, tuple) else (part,)
            if len(part) != comb.n_state:
                raise ValueError(
                    f"{spec.name}: body returned {len(part)} state "
                    f"components for combine {comb.name!r} "
                    f"(n_state={comb.n_state})")
            part = tuple(_fit(p, acc.shape).astype(jnp.float32)
                         for p, acc in zip(part, accs))
            state = comb.merge(tuple(acc[...] for acc in accs), part)
            for acc, v in zip(accs, state):
                acc[...] = v

        @pl.when(i == pl.num_programs(row_pos) - 1)
        def _():
            res = comb.finalize(tuple(acc[...] for acc in accs))
            for o_ref, r in zip(o_refs, _as_blocks(res, spec)):
                o_ref[...] = _fit(r, o_ref.shape).astype(o_ref.dtype)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct(shape, jnp.dtype(dt))
                   for shape, dt in zip(out_shapes, out_dtypes)],
        scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in state_shapes],
        interpret=interpret,
        name=spec.name,
    )(*operands)
    res = tuple(o.reshape(f) for o, f in zip(out, finals))
    return res[0] if n_out == 1 else res


def _manual_eligible(spec: loopir.TraversalSpec,
                     bp: transforms.BlockPlan) -> bool:
    """Reads must be plain ``(stride, vector)`` streams; writes may also
    be rank-1 ``(stride,)`` side outputs (the manual ring streams whole
    rows, so a row statistic is computable without ``full_width``).  A
    ``full_width`` spec is eligible for the same reason — every block
    the ring stages IS a full row."""
    info = bp.info
    if (info.reduction or info.stride_reduction
            or info.batch_axes or info.free_axes
            or info.row_halo != (0, 0) or info.col_halo != (0, 0)):
        return False
    sv = (info.stride_axis, info.vector_axis)
    if not all(a.index == sv and not a.has_halo for a in spec.reads):
        return False
    return all(w.index in (sv, (info.stride_axis,)) for w in spec.writes)


def _emit_manual(sched, bp, arrays, scalars, interpret: bool):
    """Explicit D-stream, ``lookahead``-deep DMA rings with the spec body
    fused between loads and stores (the ``stream.copy_manual`` pattern).

    One fused ring per *operand*: each step's D stream copies issue
    back-to-back onto a single per-slot semaphore (no interleaved
    per-stream wait/start serializing the issue slots), and stores drain
    through a double-buffered staging ring so a stream's store never
    blocks the next stream's compute.  Per-output geometry: a rank-1
    ``(stride,)`` side write stages/stores 1-lane blocks next to its
    full-row siblings.
    """
    spec = sched.spec
    stream = sched.find(bp.info.stride_axis, transforms.STREAM)
    d, seg_rows = stream.extent, stream.stride
    la = sched.config.lookahead
    bm = bp.bm
    cols = bp.cols                      # manual path streams full rows
    n_steps = seg_rows // bm
    n_in = len(arrays)
    n_scal = len(scalars)
    n_out = len(spec.writes)
    scal_arrays = [jnp.asarray(s).reshape(1, 1) for s in scalars]
    out_dtypes = spec.out_dtypes(arrays)
    # per-write store width: full rows, or one lane for (stride,) side
    # outputs (their HBM buffer is a [rows, 1] column, squeezed after)
    w_cols = [cols if len(w.index) == 2 else 1 for w in spec.writes]
    ost = 2                             # output staging ring depth

    def kernel(*refs):
        in_hbm = refs[:n_in]
        scal_refs = refs[n_in:n_in + n_scal]
        o_hbms = refs[n_in + n_scal:n_in + n_scal + n_out]
        scratch = refs[n_in + n_scal + n_out:]
        bufs = scratch[:n_in]                        # (la, d, bm, cols)
        obufs = scratch[n_in:n_in + n_out]           # (ost, d, bm, cols)
        insems = scratch[n_in + n_out:2 * n_in + n_out]  # (la,) per opnd
        outsems = scratch[2 * n_in + n_out:]         # (ost, d) per output

        def in_copy(r, k, t, slot):
            return pltpu.make_async_copy(
                in_hbm[r].at[pl.ds(k * seg_rows + t * bm, bm), :],
                bufs[r].at[slot, k], insems[r].at[slot])

        def out_copy(o, k, t, oslot):
            return pltpu.make_async_copy(
                obufs[o].at[oslot, k],
                o_hbms[o].at[pl.ds(k * seg_rows + t * bm, bm), :],
                outsems[o].at[oslot, k])

        def env(k, slot):
            e = {acc.array: bufs[r][slot, k]
                 for r, acc in enumerate(spec.reads)}
            for s, name in enumerate(spec.scalars):
                e[name] = scal_refs[s][0, 0]
            return e

        # prologue: prime `lookahead` steps per operand ring — all D
        # stream copies of a step issue back-to-back on one shared slot
        # semaphore (lookahead=1 = prefetch off)
        for r in range(n_in):
            for t in range(min(la, n_steps)):
                for k in range(d):
                    in_copy(r, k, t, t % la).start()

        def body(t, _):
            slot = t % la
            oslot = t % ost

            @pl.when(t >= ost)         # drain the store last on this slot
            def _():
                for o in range(n_out):
                    for k in range(d):
                        out_copy(o, k, t - ost, oslot).wait()
            for r in range(n_in):      # one wait per copy; shared sem
                for k in range(d):
                    in_copy(r, k, t, slot).wait()
            for k in range(d):
                blocks = _as_blocks(spec.body(env(k, slot)), spec)
                for o, res in enumerate(blocks):
                    obufs[o][oslot, k] = _fit(
                        res, (bm, w_cols[o]), broadcast=not spec.reads
                        ).astype(obufs[o].dtype)
            for o in range(n_out):
                for k in range(d):
                    out_copy(o, k, t, oslot).start()
            nxt = t + la

            @pl.when(nxt < n_steps)    # refill the rings, again fused
            def _():
                for r in range(n_in):
                    for k in range(d):
                        in_copy(r, k, nxt, slot).start()
            return ()

        jax.lax.fori_loop(0, n_steps, body, ())
        for tail in range(min(ost, n_steps)):      # drain pending stores
            t = n_steps - 1 - tail
            for o in range(n_out):
                for k in range(d):
                    out_copy(o, k, t, t % ost).wait()

    out = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n_in
        + [pl.BlockSpec(memory_space=pltpu.VMEM)] * n_scal,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n_out,
        out_shape=[jax.ShapeDtypeStruct((d * seg_rows, wc),
                                        jnp.dtype(dt))
                   for wc, dt in zip(w_cols, out_dtypes)],
        scratch_shapes=(
            [pltpu.VMEM((la, d, bm, cols), x.dtype) for x in arrays]
            + [pltpu.VMEM((ost, d, bm, wc), jnp.dtype(dt))
               for wc, dt in zip(w_cols, out_dtypes)]
            + [pltpu.SemaphoreType.DMA((la,)) for _ in arrays]
            + [pltpu.SemaphoreType.DMA((ost, d)) for _ in range(n_out)]
        ),
        interpret=interpret,
        name=spec.name,
    )(*arrays, *scal_arrays)
    res = tuple(o.reshape(-1) if len(w.index) == 1 else o
                for o, w in zip(out, spec.writes))
    return res[0] if n_out == 1 else res


def emit_scheduled(sched: transforms.Schedule, bp: transforms.BlockPlan,
                   arrays: Sequence, scalars: Sequence,
                   interpret: bool):
    """Dispatch a scheduled nest to the right lowering.  A non-default
    lookahead selects the manual ring when the nest supports it; nests
    the ring cannot express (stencils, reductions, batched/free nests)
    keep the Pallas auto-pipeline, whose ring depth is fixed at 2."""
    spec, info = sched.spec, bp.info
    if info.stride_reduction:
        return _emit_stream_reduction(sched, bp, arrays, scalars, interpret)
    if info.reduction and all(_write_rest(w, info) == (info.stride_axis,)
                              for w in spec.writes):
        return _emit_reduction(sched, bp, arrays, scalars, interpret)
    if isinstance(spec.reduce, tuple):
        raise NotImplementedError(
            f"{spec.name}: per-write combinators only apply to vector-"
            "axis reductions whose writes are all per-row (stride,) "
            "outputs — this nest lowers to the streaming/manual path, "
            "where no cross-block merge happens")
    if info.reduction and bp.bn != bp.cols:
        raise NotImplementedError(
            f"{spec.name}: a body-contracted reduction axis needs "
            "full_width=True")
    if sched.config.lookahead != 2 and _manual_eligible(spec, bp):
        return _emit_manual(sched, bp, arrays, scalars, interpret)
    return _emit_streaming(sched, bp, arrays, scalars, interpret)


# ------------------------------------------------- pad / crop / driver

def _pad_dim(x, dim: int, target: int):
    if x.shape[dim] == target:
        return x
    pads = [(0, 0)] * x.ndim
    pads[dim] = (0, target - x.shape[dim])
    return jnp.pad(x, pads)


def _pad_arrays(spec: loopir.TraversalSpec, bp: transforms.BlockPlan,
                arrays: Sequence) -> list:
    """Zero-pad every operand to the BlockPlan's extents (§5.1.2
    divisibility — pad+crop instead of leftover loops).  Batch and free
    dims keep their natural extents.  Reduction bodies see zeros in the
    padded vector region, which contributes nothing to dot-like
    reductions."""
    info = bp.info
    targets = {info.stride_axis: bp.rows, info.vector_axis: bp.cols}
    padded = []
    for acc, x in zip(spec.reads, arrays):
        for dim, (var, (lo, hi)) in enumerate(zip(acc.index, acc.halo)):
            target = targets.get(var, spec.axis(var).extent) + lo + hi
            x = _pad_dim(x, dim, target)
        padded.append(x)
    return padded


def _emit_blocked(spec: loopir.TraversalSpec, info: loopir.NestInfo,
                  arrays: Sequence, scalars: Sequence,
                  config: StridingConfig, interpret: bool):
    """§5.1.1 loop blocking for 1-D nests: tile the single axis into a
    ``[rows, 128·P]`` 2-D grid (the shape ``transforms.block`` gives the
    schedule) and run the standard multi-striding pipeline on the
    blocked spec — exactly the paper's gemversum/init recipe."""
    ax = spec.axis(info.stride_axis)
    n = ax.extent
    cols = transforms.LANE * config.portion_unroll
    rows = max(-(-n // cols), 1)
    total = rows * cols
    row_ax, lane_ax = ax.name + "__blk", ax.name + "__lane"

    def remap(acc):
        return dataclasses.replace(acc, index=(row_ax, lane_ax), halo=None)

    spec2 = dataclasses.replace(
        spec,
        axes=(loopir.Axis(row_ax, rows), loopir.Axis(lane_ax, cols)),
        reads=tuple(remap(a) for a in spec.reads),
        writes=tuple(remap(a) for a in spec.writes),
    )

    def to2d(x):
        return _pad_dim(x, 0, total).reshape(rows, cols)

    out = emit_spec(spec2, [to2d(x) for x in arrays] + list(scalars),
                    config, interpret=interpret)
    outs = out if isinstance(out, tuple) else (out,)
    res = tuple(o.reshape(-1)[:n] for o in outs)
    return res[0] if len(res) == 1 else res


def emit_spec(spec: loopir.TraversalSpec, inputs: Sequence,
              config: StridingConfig, *, interpret: bool):
    """The whole pipeline for one call: plan blocks → pad operands →
    rebuild the spec at padded extents → §5.1 default schedule →
    emit → crop to the original domain.  1-D nests are loop-blocked
    into a 2-D tile grid first (§5.1.1)."""
    n = len(spec.reads)
    if len(inputs) != n + len(spec.scalars):
        raise ValueError(f"{spec.name}: expected {n} arrays + "
                         f"{len(spec.scalars)} scalars")
    arrays, scalars = list(inputs[:n]), list(inputs[n:])
    info = loopir.classify(spec)
    if info.blocked:
        return _emit_blocked(spec, info, arrays, scalars, config, interpret)
    bp = transforms.plan_blocks(spec, config)
    rows = spec.axis(bp.info.stride_axis).extent
    if bp.info.stride_reduction and bp.rows != rows:
        # zero-padded rows would have to contribute the combine identity
        # through the body, which no generic body guarantees (and max /
        # online_softmax structurally cannot) — refuse rather than
        # silently corrupt, for EVERY combinator
        raise ValueError(
            f"{spec.name}: a stride-axis reduction cannot pad the stride "
            f"axis ({rows} rows, D={bp.d}); pick a D dividing the extent")
    cols = spec.axis(bp.info.vector_axis).extent
    if (bp.info.reduction and bp.cols != cols
            and any(c.name != "sum" for c in spec.combines())):
        # zero-padded vector lanes feed the body's reduction: harmless
        # for sums, but they poison any non-'sum' combinator (a padded
        # zero beats every negative row max) — refuse loudly
        raise ValueError(
            f"{spec.name}: padding the reduced vector axis ({cols} -> "
            f"{bp.cols}) feeds zeros into a non-'sum' per-write "
            "combinator; use a lane-multiple extent or full_width=True")
    arrays = _pad_arrays(spec, bp, arrays)
    targets = {bp.info.stride_axis: bp.rows, bp.info.vector_axis: bp.cols}
    padded_axes = tuple(
        dataclasses.replace(ax, extent=targets.get(ax.name, ax.extent))
        for ax in spec.axes)
    spec_p = dataclasses.replace(spec, axes=padded_axes)
    sched = transforms.default_schedule(spec_p, config, blocks=bp)
    out = emit_scheduled(sched, bp, arrays, scalars, interpret)
    outs = out if isinstance(out, tuple) else (out,)
    res = tuple(o[tuple(slice(0, s) for s in shape)]
                for o, shape in zip(outs, spec.out_shapes()))
    return res[0] if len(res) == 1 else res


# ------------------------------------------------------------- op glue

def run_spec(build_spec: Callable[..., loopir.TraversalSpec],
             inputs: Sequence, config: StridingConfig, mode: str):
    """Mode-dispatched spec execution (jit-traceable): the building block
    composite gen ops fuse into one jitted program so multi-spec kernels
    (bicg's two passes, adamw's triple write) cost one dispatch, like
    their hand-written fused counterparts."""
    spec = build_spec(*inputs)
    if mode == "ref":
        return loopir.evaluate(spec, inputs)
    return emit_spec(spec, inputs, config, interpret=(mode == "interpret"))


def _shape_key(inputs: Sequence) -> tuple:
    # dtype objects hash/compare fast; str(dtype) costs ~15µs per call
    return tuple((getattr(x, "shape", None), getattr(x, "dtype", None))
                 for x in inputs)


def make_kernel_op(name: str,
                   build_spec: Callable[..., loopir.TraversalSpec],
                   default: StridingConfig = StridingConfig(4, 1),
                   ) -> Callable:
    """Wrap a spec builder as a public kernel op with the house
    conventions: ``op(*arrays, *scalars, config=None, mode=None)``,
    mode dispatch (ref = spec interpreter / interpret / pallas), and
    config resolution (explicit > tune-cache > planner > default) run
    outside jit — identical plumbing to the hand-written ``ops.py``
    wrappers, but the kernel itself is derived from the spec.

    Execution is *guarded* (``common.guarded_run``): a config that fails
    to lower or execute is classified, quarantined in the tune cache,
    and the call degrades alt-config → interpret → ref, emitting a
    ``kernel.fallback`` event instead of taking the caller down.  Before
    any non-ref dispatch the static verifier (``repro.analysis``) must
    pass the (spec, config) pair: a rejected plan raises
    ``AnalysisError`` *outside* jit with zero ``pallas_call``
    construction — ``guarded_run`` quarantines it under failure class
    ``analysis`` and degrades to the ref oracle (the ref tier serves
    every statically-rejected config, so results still flow).

    Classification and the Traffic signature are pure in the input
    shapes/dtypes and memoized (checker verdicts per (shapes, config)
    likewise), so a hot-loop call costs the same Python-side work as a
    hand ops wrapper."""
    from repro.kernels import common   # deferred: avoids import cycle

    facts: dict[tuple, tuple] = {}     # shape key → (rows, traffic, spec)
    verdicts: dict[tuple, Optional[Exception]] = {}

    @functools.partial(jax.jit, static_argnames=("config", "mode"))
    def _run(inputs: tuple, config: StridingConfig, mode: str):
        return run_spec(build_spec, inputs, config, mode)

    def op(*inputs, config: Optional[StridingConfig] = None,
           mode: Optional[str] = None):
        mode = mode or common.kernel_mode()
        key = _shape_key(inputs)
        if key not in facts:
            obs.counter("codegen.spec_memo.miss", kernel=name)
            spec = build_spec(*inputs)
            info = loopir.classify(spec)
            # blocked 1-D nests derive their tile grid from the config —
            # pad+crop makes any D valid, so no divisibility clamp
            rows = (None if info.blocked
                    else spec.axis(info.stride_axis).extent)
            facts[key] = (rows, loopir.traffic_of(spec, inputs[0].dtype,
                                                  info=info), spec)
        else:
            obs.counter("codegen.spec_memo.hit", kernel=name)
        rows, traffic, spec = facts[key]
        lead = inputs[0]
        cfg = common.resolve_config(
            name, lead.shape, lead.dtype, config, rows, default,
            traffic=(None if config is not None else traffic), mode=mode,
            spec=spec)

        def run(c: StridingConfig, m: str):
            if m != "ref":
                # checker gate, outside jit (a jit-cached trace would
                # skip it) and memoized per (shapes, config); ref mode
                # skips it so the oracle tier serves rejected configs
                vkey = (key, c)
                if vkey not in verdicts:
                    from repro import analysis
                    try:
                        analysis.ensure_valid(name, spec, c)
                        verdicts[vkey] = None
                    except analysis.AnalysisError as err:
                        verdicts[vkey] = err
                if verdicts[vkey] is not None:
                    raise verdicts[vkey]
            return _run(tuple(inputs), c, m)

        return common.guarded_run(
            name, run, cfg, mode,
            shape=lead.shape, dtype=lead.dtype, rows=rows, traffic=traffic,
            spec=spec)

    op.__name__ = name
    op.__qualname__ = name
    op.__doc__ = (f"Generated multi-strided kernel {name!r} "
                  "(repro.codegen: spec → schedule → Pallas).")
    return op
