"""Jit'd wrappers for mxv / mxv_t.

The hand-written Pallas bodies are retired (ROADMAP retirement plan):
both wrappers resolve through the family's ``TraversalSpec`` builders
in ``specs.py``, lowered by ``repro.codegen`` (padding + cropping
happens inside the emitter; ``mxv_t``'s stride-axis reduction clamps D
to divide the row count instead of padding — the combine identity
cannot be guaranteed through an arbitrary body).  Config resolution
(tune-cache → planner → default) runs outside jit so autotune results
take effect immediately (see common.resolve_config).
"""
from __future__ import annotations

import functools

import jax

from repro.codegen import run_spec
from repro.core import Traffic
from repro.core.striding import StridingConfig
from repro.kernels import common
from repro.kernels.mxv import specs

_DEFAULT = StridingConfig(stride_unroll=4, portion_unroll=2)


def _resolve(kernel, spec, a, config, mode, extra_reads=0):
    m, n = a.shape
    traffic = Traffic(rows=m, cols=n, dtype=a.dtype,
                      read_arrays=1 + extra_reads)
    return common.resolve_config(kernel, a.shape, a.dtype, config, m,
                                 _DEFAULT, traffic=traffic, mode=mode,
                                 spec=spec)


@functools.partial(jax.jit, static_argnames=("config", "mode"))
def _mxv(a, x, config: StridingConfig, mode: str) -> jax.Array:
    return run_spec(specs.mxv_spec, (a, x), config, mode)


def mxv(a: jax.Array, x: jax.Array, config: StridingConfig | None = None,
        mode: str | None = None) -> jax.Array:
    """y = A @ x (paper mxv / gemvermxv2)."""
    mode = mode or common.kernel_mode()
    cfg = _resolve("mxv", specs.mxv_spec(a, x), a, config, mode)
    return _mxv(a, x, cfg, mode)


@functools.partial(jax.jit, static_argnames=("config", "mode"))
def _mxv_t(a, x, config: StridingConfig, mode: str) -> jax.Array:
    return run_spec(specs.mxv_t_spec, (a, x), config, mode)


def mxv_t(a: jax.Array, x: jax.Array, config: StridingConfig | None = None,
          mode: str | None = None) -> jax.Array:
    """y = Aᵀ @ x (paper Listing 1: gemvermxv1 / doitgen core)."""
    mode = mode or common.kernel_mode()
    cfg = _resolve("mxv_t", specs.mxv_t_spec(a, x), a, config, mode,
                   extra_reads=1)
    return _mxv_t(a, x, cfg, mode)
