"""Codegen variants of the PolyBench paper families (§5.1.1 blocking
wave): bicg, the four gemver steps, conv3x3 and doitgen.

The spec builders live with their families (``kernels/bicg/specs.py``,
``kernels/gemver/specs.py``, ``kernels/conv3x3/specs.py``,
``kernels/doitgen/specs.py``) and are shared verbatim by the public
``ops.py`` wrappers and the ``*_gen`` registry rows here — one
definition, two registry rows (hand-named and ``_gen``), zero hand
Pallas.  Each variant registers with its hand family's problem sizes
and oracle so it runs on the identical conformance matrix.

Archetypes exercised here (all emitter paths):

  * ``bicg_s`` / ``gemver_mxv1`` — *stride-axis* reduction: the streamed
    axis itself is reduced, D partial rows merge into one full-width
    accumulator (the mxv_t pattern).
  * ``gemver_outer``            — rank-1 row streams (u vectors ride the
    same D-stream split as the matrix).
  * ``gemver_sum``              — 1-D nest, loop-blocked into a
    ``[rows, 128·P]`` tile grid before striding (paper gemversum).
  * ``conv3x3``                 — row+column stencil halo with the nine
    weights lowered as scalars.
  * ``doitgen``                 — batched 3-D nest: ``r`` is a batch
    grid dimension, ``q`` the stride axis, ``s`` contracted inside the
    body against the VMEM-resident ``C4`` (vectorize ``p``, the paper's
    own critical-access analysis).
"""
import functools

import jax
import jax.numpy as jnp

from repro.codegen import make_kernel_op, run_spec, traffic_of
from repro.core import Traffic
from repro.core.striding import StridingConfig
from repro.kernels.bicg import ref as _bicg_ref
from repro.kernels.bicg.specs import bicg_q_spec, bicg_s_spec
from repro.kernels.common import example_input as _rand
from repro.kernels.conv3x3 import ref as _conv_ref
from repro.kernels.conv3x3.specs import conv3x3_spec
from repro.kernels.doitgen import ref as _doit_ref
from repro.kernels.doitgen.specs import doitgen_spec
from repro.kernels.gemver import ref as _gem_ref
from repro.kernels.gemver.specs import (SumWithTotal, gemver_mxv1_spec,
                                        gemver_mxv1_sum_spec,
                                        gemver_mxv2_spec, gemver_outer_spec,
                                        gemver_sum_spec)
from repro.registry.base import KernelSpec, register

__all__ = ["bicg_gen", "gemver_outer_gen", "gemver_sum_gen",
           "gemver_mxv1_gen", "gemver_mxv1_sum_gen", "gemver_mxv2_gen",
           "conv3x3_gen", "doitgen_gen",
           # family specs re-exported for spec-level consumers
           "bicg_q_spec", "bicg_s_spec", "gemver_outer_spec",
           "gemver_sum_spec", "gemver_mxv1_spec", "gemver_mxv1_sum_spec",
           "gemver_mxv2_spec", "SumWithTotal", "conv3x3_spec",
           "doitgen_spec"]


def _resolve(kernel: str, lead, config, mode, rows: int,
             default: StridingConfig, traffic, spec=None):
    """Composite ops resolve one config under their own name (explicit >
    tune-cache > planner > default) and fuse every inner generated spec
    into a single jitted program — one dispatch, like the hand-written
    fused kernels.  ``spec`` is the inner spec, or a tuple of them."""
    from repro.kernels import common
    return common.resolve_config(
        kernel, lead.shape, lead.dtype, config, rows, default,
        traffic=(None if config is not None else traffic), mode=mode,
        spec=spec)


def _mode(mode):
    if mode is None:
        from repro.kernels import common
        return common.kernel_mode()
    return mode


def _guarded(kernel: str, run, lead, cfg, mode, rows, traffic, spec=None):
    """Composite wrappers dispatch through the same guarded fallback
    chain as ``make_kernel_op`` kernels: a failed lowering degrades
    alt-config → interpret → ref and quarantines the failing config
    (see ``common.guarded_run``)."""
    from repro.kernels import common
    return common.guarded_run(kernel, run, cfg, mode, shape=lead.shape,
                              dtype=lead.dtype, rows=rows, traffic=traffic,
                              spec=spec)


# ---------------------------------------------------------------- bicg

@functools.partial(jax.jit, static_argnames=("config", "mode"))
def _bicg_run(a, r, p, config, mode):
    return (run_spec(bicg_q_spec, (a, p), config, mode),
            run_spec(bicg_s_spec, (a, r), config, mode))


def bicg_gen(a, r, p, config=None, mode=None):
    """q = A p ; s = Aᵀ r (generated; two specs fused in one program)."""
    mode = _mode(mode)
    m, n = a.shape
    traffic = Traffic(rows=m, cols=n, dtype=a.dtype, read_arrays=2)
    spec = (bicg_q_spec(a, p), bicg_s_spec(a, r))
    cfg = _resolve("bicg_gen", a, config, mode, m, StridingConfig(4, 2),
                   traffic, spec)
    return _guarded("bicg_gen",
                    lambda c, km: _bicg_run(a, r, p, config=c, mode=km),
                    a, cfg, mode, m, traffic, spec)


# -------------------------------------------------------------- gemver

gemver_outer_gen = make_kernel_op("gemver_outer_gen", gemver_outer_spec,
                                  default=StridingConfig(4, 2))
gemver_sum_gen = make_kernel_op("gemver_sum_gen", gemver_sum_spec,
                                default=StridingConfig(4, 2))
gemver_mxv2_gen = make_kernel_op("gemver_mxv2_gen", gemver_mxv2_spec,
                                 default=StridingConfig(4, 2))


@functools.partial(jax.jit, static_argnames=("config", "mode"))
def _mxv1_run(a, y, x, beta, config, mode):
    return x + run_spec(gemver_mxv1_spec, (a, y, beta), config, mode)


def gemver_mxv1_gen(a, y, x, beta, config=None, mode=None):
    """x = x + β Aᵀ y (generated core + affine update, one program)."""
    mode = _mode(mode)
    m, n = a.shape
    traffic = Traffic(rows=m, cols=n, dtype=a.dtype, read_arrays=2)
    spec = gemver_mxv1_spec(a, y)
    cfg = _resolve("gemver_mxv1_gen", a, config, mode, m,
                   StridingConfig(4, 2), traffic, spec)
    return _guarded(
        "gemver_mxv1_gen",
        lambda c, km: _mxv1_run(a, y, x, beta, config=c, mode=km),
        a, cfg, mode, m, traffic, spec)


@functools.partial(jax.jit, static_argnames=("config", "mode"))
def _mxv1_sum_run(a, y, x, z, beta, config, mode):
    s, total = run_spec(gemver_mxv1_sum_spec, (a, y, beta), config, mode)
    return x + s.astype(x.dtype) + z, total.reshape(())


def gemver_mxv1_sum_gen(a, y, x, z, beta, config=None, mode=None):
    """Fused gemver mxv1 + sum steps: x' = x + β Aᵀ y + z, with the
    sweep's own reduction Σⱼ(βAᵀy)ⱼ emitted as a native scalar side
    output (per-output access maps) — one sweep of A where the separate
    mxv1 and sum steps traversed x twice.  Returns (x', ssum)."""
    mode = _mode(mode)
    m, n = a.shape
    traffic = Traffic(rows=m, cols=n, dtype=a.dtype, read_arrays=2)
    spec = gemver_mxv1_sum_spec(a, y)
    cfg = _resolve("gemver_mxv1_sum_gen", a, config, mode, m,
                   StridingConfig(4, 2), traffic, spec)
    return _guarded(
        "gemver_mxv1_sum_gen",
        lambda c, km: _mxv1_sum_run(a, y, x, z, beta, config=c, mode=km),
        a, cfg, mode, m, traffic, spec)


# ------------------------------------------------------------- conv3x3

@functools.partial(jax.jit, static_argnames=("config", "mode"))
def _conv_run(x, w, config, mode):
    w9 = [w[r, c] for r in range(3) for c in range(3)]
    return run_spec(conv3x3_spec, (x, *w9), config, mode)


def conv3x3_gen(x, w, config=None, mode=None):
    """3x3 correlation stencil (generated; weights lowered as scalars)."""
    mode = _mode(mode)
    h_out = max(x.shape[0] - 2, 1)
    traffic = Traffic(rows=h_out, cols=max(x.shape[1] - 2, 1),
                      dtype=x.dtype, read_arrays=3, write_arrays=1)
    spec = conv3x3_spec(x)
    cfg = _resolve("conv3x3_gen", x, config, mode, h_out,
                   StridingConfig(4, 1), traffic, spec)
    return _guarded("conv3x3_gen",
                    lambda c, km: _conv_run(x, w, config=c, mode=km),
                    x, cfg, mode, h_out, traffic, spec)


# ------------------------------------------------------------- doitgen

doitgen_gen = make_kernel_op("doitgen_gen", doitgen_spec,
                             default=StridingConfig(4, 1))


# ---------------------------------------------------------- registry

# problem sizes/oracles mirror the hand families: identical conformance
# (sizes × (D,P)) coverage for hand and generated variants
_S = jax.ShapeDtypeStruct   # traversal rows build IR on placeholders

_MN_SIZES = {"m": 48, "n": 256}
_MN_ALIASED = {"m": 32, "n": 128}
_MN_BENCH = {"m": 4096, "n": 4096}


def _mn(s):
    return (s["m"], s["n"])


register(KernelSpec(
    name="bicg_gen", family="gen", fn=bicg_gen,
    make_inputs=lambda s, dt: (_rand(_mn(s), 0, dt),
                               _rand((s["m"],), 1, dt),
                               _rand((s["n"],), 2, dt)),
    run=lambda inp, cfg, mode: bicg_gen(inp[0], inp[1], inp[2], config=cfg,
                                        mode=mode),
    ref=lambda inp, cfg: _bicg_ref.bicg_ref(inp[0], inp[1], inp[2]),
    default_sizes=_MN_SIZES, aliased_sizes=_MN_ALIASED,
    traffic=lambda s, dt: Traffic(rows=s["m"], cols=s["n"], dtype=dt,
                                  read_arrays=2),
    # composite: both fused specs screen as one plan (shared config)
    traversal=lambda s, dt: (
        bicg_q_spec(_S(_mn(s), dt), _S((s["n"],), dt)),
        bicg_s_spec(_S(_mn(s), dt), _S((s["m"],), dt))),
    cache_shape=_mn, bench_sizes=_MN_BENCH, tags=("paper", "gen")))

register(KernelSpec(
    name="gemver_outer_gen", family="gen", fn=gemver_outer_gen,
    make_inputs=lambda s, dt: (
        _rand(_mn(s), 0, dt), _rand((s["m"],), 1, dt),
        _rand((s["n"],), 2, dt), _rand((s["m"],), 3, dt),
        _rand((s["n"],), 4, dt)),
    run=lambda inp, cfg, mode: gemver_outer_gen(*inp, config=cfg,
                                                mode=mode),
    ref=lambda inp, cfg: _gem_ref.outer_ref(*inp),
    default_sizes=_MN_SIZES, aliased_sizes=_MN_ALIASED,
    traffic=lambda s, dt: traffic_of(
        gemver_outer_spec(jnp.zeros(_mn(s), dt), *(None,) * 4), dt),
    traversal=lambda s, dt: gemver_outer_spec(_S(_mn(s), dt),
                                              *(None,) * 4),
    cache_shape=_mn, bench_sizes=_MN_BENCH, tags=("paper", "gen")))

register(KernelSpec(
    name="gemver_sum_gen", family="gen", fn=gemver_sum_gen,
    make_inputs=lambda s, dt: (_rand((s["vn"],), 0, dt),
                               _rand((s["vn"],), 1, dt)),
    run=lambda inp, cfg, mode: gemver_sum_gen(inp[0], inp[1], config=cfg,
                                              mode=mode),
    ref=lambda inp, cfg: _gem_ref.sum_ref(inp[0], inp[1]),
    default_sizes={"vn": 1000}, aliased_sizes={"vn": 2048},
    traffic=lambda s, dt: traffic_of(
        gemver_sum_spec(jnp.zeros((s["vn"],), dt), None), dt),
    traversal=lambda s, dt: gemver_sum_spec(_S((s["vn"],), dt), None),
    cache_shape=lambda s: (s["vn"],),
    bench_sizes={"vn": 4 * 2**20}, tags=("paper", "gen")))

register(KernelSpec(
    name="gemver_mxv1_gen", family="gen", fn=gemver_mxv1_gen,
    make_inputs=lambda s, dt: (_rand(_mn(s), 0, dt),
                               _rand((s["m"],), 1, dt),
                               _rand((s["n"],), 2, dt), 1.2),
    run=lambda inp, cfg, mode: gemver_mxv1_gen(inp[0], inp[1], inp[2],
                                               inp[3], config=cfg,
                                               mode=mode),
    ref=lambda inp, cfg: _gem_ref.mxv1_ref(inp[0], inp[1], inp[2], inp[3]),
    default_sizes=_MN_SIZES, aliased_sizes=_MN_ALIASED,
    traffic=lambda s, dt: Traffic(rows=s["m"], cols=s["n"], dtype=dt,
                                  read_arrays=2),
    traversal=lambda s, dt: gemver_mxv1_spec(_S(_mn(s), dt), None),
    cache_shape=_mn, bench_sizes=_MN_BENCH, tags=("paper", "gen")))

register(KernelSpec(
    name="gemver_mxv1_sum_gen", family="gen", fn=gemver_mxv1_sum_gen,
    make_inputs=lambda s, dt: (_rand(_mn(s), 0, dt),
                               _rand((s["m"],), 1, dt),
                               _rand((s["n"],), 2, dt),
                               _rand((s["n"],), 3, dt), 1.2),
    run=lambda inp, cfg, mode: gemver_mxv1_sum_gen(*inp, config=cfg,
                                                   mode=mode),
    ref=lambda inp, cfg: _gem_ref.mxv1_sum_ref(*inp),
    default_sizes=_MN_SIZES, aliased_sizes=_MN_ALIASED,
    traffic=lambda s, dt: Traffic(rows=s["m"], cols=s["n"], dtype=dt,
                                  read_arrays=2),
    traversal=lambda s, dt: gemver_mxv1_sum_spec(_S(_mn(s), dt), None),
    cache_shape=_mn, bench_sizes=_MN_BENCH, tags=("paper", "gen")))

register(KernelSpec(
    name="gemver_mxv2_gen", family="gen", fn=gemver_mxv2_gen,
    make_inputs=lambda s, dt: (_rand(_mn(s), 0, dt),
                               _rand((s["n"],), 1, dt), 1.5),
    run=lambda inp, cfg, mode: gemver_mxv2_gen(inp[0], inp[1], inp[2],
                                               config=cfg, mode=mode),
    ref=lambda inp, cfg: _gem_ref.mxv2_ref(inp[0], inp[1], inp[2]),
    default_sizes=_MN_SIZES, aliased_sizes=_MN_ALIASED,
    traffic=lambda s, dt: Traffic(rows=s["m"], cols=s["n"], dtype=dt,
                                  read_arrays=1),
    traversal=lambda s, dt: gemver_mxv2_spec(_S(_mn(s), dt),
                                             _S((s["n"],), dt)),
    cache_shape=_mn, bench_sizes=_MN_BENCH, tags=("paper", "gen")))

register(KernelSpec(
    name="conv3x3_gen", family="gen", fn=conv3x3_gen,
    make_inputs=lambda s, dt: (_rand((s["h"], s["w"]), 0, dt),
                               _rand((3, 3), 1, dt)),
    run=lambda inp, cfg, mode: conv3x3_gen(inp[0], inp[1], config=cfg,
                                           mode=mode),
    ref=lambda inp, cfg: _conv_ref.conv3x3_ref(inp[0], inp[1]),
    default_sizes={"h": 34, "w": 130}, aliased_sizes={"h": 34, "w": 128},
    traffic=lambda s, dt: traffic_of(
        conv3x3_spec(jnp.zeros((s["h"], s["w"]), dt)), dt),
    traversal=lambda s, dt: conv3x3_spec(_S((s["h"], s["w"]), dt)),
    cache_shape=lambda s: (s["h"], s["w"]),
    bench_sizes={"h": 2050, "w": 2048}, tags=("paper", "gen")))

register(KernelSpec(
    name="doitgen_gen", family="gen", fn=doitgen_gen,
    make_inputs=lambda s, dt: (_rand((s["r"], s["q"], s["s"]), 0, dt),
                               _rand((s["s"], s["s"]), 1, dt)),
    run=lambda inp, cfg, mode: doitgen_gen(inp[0], inp[1], config=cfg,
                                           mode=mode),
    ref=lambda inp, cfg: _doit_ref.doitgen_ref(inp[0], inp[1]),
    default_sizes={"r": 4, "q": 8, "s": 32},
    aliased_sizes={"r": 8, "q": 16, "s": 32},
    traffic=lambda s, dt: traffic_of(
        doitgen_spec(jnp.zeros((s["r"], s["q"], s["s"]), dt),
                     jnp.zeros((s["s"], s["s"]), dt)), dt),
    traversal=lambda s, dt: doitgen_spec(_S((s["r"], s["q"], s["s"]), dt),
                                         _S((s["s"], s["s"]), dt)),
    cache_shape=lambda s: (s["r"], s["q"], s["s"]),
    bench_sizes={"r": 16, "q": 256, "s": 256}, tags=("paper", "gen")))
