"""Codegen-derived kernel family: hand-written families re-expressed as
``TraversalSpec``s and lowered by ``repro.codegen`` — no Pallas by hand.

Every hand family is fully *retired*: the spec builders live with their
families (``kernels/<family>/specs.py``) and are shared verbatim by the
public ``ops.py`` wrappers and the ``*_gen`` registry variants alike —
one definition, two registry rows (hand-named and ``_gen``), zero hand
Pallas anywhere outside ``repro.codegen``.

This module holds the first ported archetypes plus two spec-only
kernels that exist to exercise dedicated emitter features:

  * ``stream_copy_gen``  — streaming elementwise
  * ``stream_triad_gen`` — STREAM triad a = b + αc (paper Table 1 class)
  * ``mxv_gen``          — vector-axis reduction
  * ``jacobi2d_gen``     — 5-point stencil
  * ``rowstat_gen``      — row max AND row sum in ONE sweep: two writes
    with *per-write combinators* (``reduce=("max", "sum")``), each
    output merging its vector-axis partials under its own combine.
  * ``transpose_gen``    — y = xᵀ via *transposed stores*: the write's
    access map is the (vector, stride) pair, so each stream's block
    stores through a transposed BlockSpec instead of a copy-out pass.

The remaining families live in sibling modules:

  * ``polybench``  — bicg, the four gemver steps, conv3x3, doitgen
    (stride-axis reductions, rank-1 row streams, §5.1.1 loop blocking,
    batch axes);
  * ``framework``  — decode_attn, rmsnorm, adamw (online-softmax
    stream reductions, full-width rows, blocked 1-D optimizer nests).

Each ``*_gen`` variant registers with the hand family's problem sizes and
oracle, so the generated kernels are conformance-tested on exactly the
same (D, P) × sizes matrix as their hand-written counterparts.
"""
import jax
import jax.numpy as jnp

from repro.codegen import (Access, Axis, TraversalSpec, make_kernel_op,
                           traffic_of)
from repro.core.striding import StridingConfig
from repro.kernels.common import example_input as _rand
from repro.kernels.jacobi2d import ref as _jac_ref
from repro.kernels.jacobi2d.specs import jacobi_spec
from repro.kernels.mxv import ref as _mxv_ref
from repro.kernels.mxv.specs import mxv_spec
from repro.kernels.stream import ref as _stream_ref
from repro.kernels.stream.specs import copy_spec, triad_spec
from repro.registry.base import KernelSpec, register

__all__ = [
    "stream_copy_gen", "stream_triad_gen", "mxv_gen", "jacobi2d_gen",
    "rowstat_gen", "transpose_gen",
    "bicg_gen", "gemver_outer_gen", "gemver_sum_gen", "gemver_mxv1_gen",
    "gemver_mxv1_sum_gen", "gemver_mxv2_gen", "conv3x3_gen",
    "doitgen_gen", "decode_attn_gen", "rmsnorm_gen", "adamw_update_gen",
]


# ------------------------------------------------------------- specs
# family specs live with their families (stream/specs.py, mxv/specs.py,
# jacobi2d/specs.py, ...) — shared verbatim by the retired families'
# ops wrappers.  Only the two emitter-feature kernels are defined here.

def rowstat_spec(x) -> TraversalSpec:
    """Row max AND row sum in ONE sweep of x: two rank-1 writes off the
    same vector-axis reduction, each with its own combinator
    (``reduce=("max", "sum")``) merging that output's partials across
    the column grid.  Extents stay lane multiples: zero-padded lanes
    would poison the max accumulator, and the emitter refuses them."""
    m, n = x.shape
    return TraversalSpec(
        name="rowstat",
        axes=(Axis("i", m), Axis("j", n, kind="reduction")),
        reads=(Access("x", ("i", "j")),),
        writes=(Access("mx", ("i",)), Access("sm", ("i",))),
        body=lambda env: (env["x"].astype(jnp.float32).max(axis=-1),
                          env["x"].astype(jnp.float32).sum(axis=-1)),
        out_dtype=(jnp.float32, jnp.float32),
        reduce=("max", "sum"),
    )


def transpose_spec(x) -> TraversalSpec:
    """y = xᵀ: the write's access map is the (vector, stride) pair, so
    each of the D streams stores its block through a *transposed*
    BlockSpec — no separate transpose copy after the sweep."""
    m, n = x.shape
    return TraversalSpec(
        name="transpose",
        axes=(Axis("i", m), Axis("j", n)),
        reads=(Access("x", ("i", "j")),),
        writes=(Access("xt", ("j", "i")),),
        body=lambda env: jnp.swapaxes(env["x"], -1, -2),
    )


# --------------------------------------------------------------- ops

stream_copy_gen = make_kernel_op("stream_copy_gen", copy_spec,
                                 default=StridingConfig(4, 2))
stream_triad_gen = make_kernel_op("stream_triad_gen", triad_spec,
                                  default=StridingConfig(4, 2))
mxv_gen = make_kernel_op("mxv_gen", mxv_spec,
                         default=StridingConfig(4, 2))
jacobi2d_gen = make_kernel_op("jacobi2d_gen", jacobi_spec,
                              default=StridingConfig(4, 1))
rowstat_gen = make_kernel_op("rowstat_gen", rowstat_spec,
                             default=StridingConfig(4, 2))
transpose_gen = make_kernel_op("transpose_gen", transpose_spec,
                               default=StridingConfig(4, 1))


# ---------------------------------------------------------- registry

def _ir(build, shapes_fn):
    """``traversal`` adapter: build the variant's TraversalSpec on
    ``ShapeDtypeStruct`` placeholders (no arrays) — the IR the static
    verifier (``repro.analysis``) and the planner screen against."""
    def t(sizes, dtype):
        structs = tuple(jax.ShapeDtypeStruct(s, dtype)
                        for s in shapes_fn(sizes))
        return build(*structs)
    return t


def _traffic(build, shapes_fn):
    """Planner signature derived from the IR's access maps."""
    ir = _ir(build, shapes_fn)

    def t(sizes, dtype):
        return traffic_of(ir(sizes, dtype), dtype)
    return t


# problem sizes mirror the hand families so the conformance matrix
# exercises identical (sizes × (D,P)) points for hand and generated
_STREAM_SIZES = {"rows": 32, "cols": 256}
_STREAM_ALIASED = {"rows": 32, "cols": 128}
_MXV_SIZES = {"m": 48, "n": 256}
_MXV_ALIASED = {"m": 32, "n": 128}
_JAC_SIZES = {"h": 34, "w": 130}
_JAC_ALIASED = {"h": 34, "w": 128}


def _rc(s):
    return (s["rows"], s["cols"])


def _mn(s):
    return (s["m"], s["n"])


register(KernelSpec(
    name="stream_copy_gen", family="gen", fn=stream_copy_gen,
    make_inputs=lambda s, dt: (_rand(_rc(s), 0, dt),),
    run=lambda inp, cfg, mode: stream_copy_gen(inp[0], config=cfg,
                                               mode=mode),
    ref=lambda inp, cfg: _stream_ref.copy_ref(inp[0]),
    default_sizes=_STREAM_SIZES, aliased_sizes=_STREAM_ALIASED,
    traffic=_traffic(copy_spec, lambda s: (_rc(s),)),
    traversal=_ir(copy_spec, lambda s: (_rc(s),)),
    cache_shape=_rc))

register(KernelSpec(
    name="stream_triad_gen", family="gen", fn=stream_triad_gen,
    make_inputs=lambda s, dt: (_rand(_rc(s), 0, dt), _rand(_rc(s), 1, dt),
                               jnp.asarray(1.5, dt)),
    run=lambda inp, cfg, mode: stream_triad_gen(inp[0], inp[1], inp[2],
                                                config=cfg, mode=mode),
    ref=lambda inp, cfg: (inp[0] + inp[2] * inp[1]).astype(inp[0].dtype),
    default_sizes=_STREAM_SIZES, aliased_sizes=_STREAM_ALIASED,
    traffic=_traffic(triad_spec, lambda s: (_rc(s), _rc(s))),
    traversal=_ir(triad_spec, lambda s: (_rc(s), _rc(s))),
    cache_shape=_rc))

register(KernelSpec(
    name="mxv_gen", family="gen", fn=mxv_gen,
    make_inputs=lambda s, dt: (_rand(_mn(s), 0, dt),
                               _rand((s["n"],), 1, dt)),
    run=lambda inp, cfg, mode: mxv_gen(inp[0], inp[1], config=cfg,
                                       mode=mode),
    ref=lambda inp, cfg: _mxv_ref.mxv_ref(inp[0], inp[1]),
    default_sizes=_MXV_SIZES, aliased_sizes=_MXV_ALIASED,
    traffic=_traffic(mxv_spec,
                     lambda s: ((s["m"], s["n"]), (s["n"],))),
    traversal=_ir(mxv_spec, lambda s: ((s["m"], s["n"]), (s["n"],))),
    cache_shape=_mn))

register(KernelSpec(
    name="jacobi2d_gen", family="gen", fn=jacobi2d_gen,
    make_inputs=lambda s, dt: (_rand((s["h"], s["w"]), 0, dt),),
    run=lambda inp, cfg, mode: jacobi2d_gen(inp[0], config=cfg, mode=mode),
    ref=lambda inp, cfg: _jac_ref.jacobi2d_ref(inp[0]),
    default_sizes=_JAC_SIZES, aliased_sizes=_JAC_ALIASED,
    traffic=_traffic(jacobi_spec, lambda s: ((s["h"], s["w"]),)),
    traversal=_ir(jacobi_spec, lambda s: ((s["h"], s["w"]),)),
    cache_shape=lambda s: (s["h"], s["w"]),
    rtol=1e-5, atol=1e-5))

# lane-multiple extents: the padded-lanes refusal under a non-'sum'
# per-write combinator never triggers at these sizes
register(KernelSpec(
    name="rowstat_gen", family="gen", fn=rowstat_gen,
    make_inputs=lambda s, dt: (_rand(_mn(s), 0, dt),),
    run=lambda inp, cfg, mode: rowstat_gen(inp[0], config=cfg, mode=mode),
    ref=lambda inp, cfg: (inp[0].astype(jnp.float32).max(axis=-1),
                          inp[0].astype(jnp.float32).sum(axis=-1)),
    default_sizes=_MXV_SIZES, aliased_sizes=_MXV_ALIASED,
    traffic=_traffic(rowstat_spec, lambda s: (_mn(s),)),
    traversal=_ir(rowstat_spec, lambda s: (_mn(s),)),
    cache_shape=_mn,
    rtol=1e-5, atol=1e-5))

register(KernelSpec(
    name="transpose_gen", family="gen", fn=transpose_gen,
    make_inputs=lambda s, dt: (_rand(_mn(s), 0, dt),),
    run=lambda inp, cfg, mode: transpose_gen(inp[0], config=cfg,
                                             mode=mode),
    ref=lambda inp, cfg: inp[0].T,
    default_sizes=_MXV_SIZES, aliased_sizes=_MXV_ALIASED,
    traffic=_traffic(transpose_spec, lambda s: (_mn(s),)),
    traversal=_ir(transpose_spec, lambda s: (_mn(s),)),
    cache_shape=_mn))


# the remaining ported families register on import (they self-register
# exactly like the family packages do)
from repro.kernels.gen.polybench import (bicg_gen, conv3x3_gen,   # noqa: E402
                                         doitgen_gen, gemver_mxv1_gen,
                                         gemver_mxv1_sum_gen,
                                         gemver_mxv2_gen, gemver_outer_gen,
                                         gemver_sum_gen)
from repro.kernels.gen.framework import (adamw_update_gen,        # noqa: E402
                                         decode_attn_gen, rmsnorm_gen)
