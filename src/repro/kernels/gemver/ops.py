"""Jit'd wrappers for gemver: the four steps + the reassembled kernel
(paper §6.4: each step individually tuned, then unified).

The hand-written Pallas bodies are retired (ROADMAP retirement plan):
``gemver_outer`` and ``gemver_sum`` lower the family's ``TraversalSpec``
builders in ``specs.py`` through ``repro.codegen``; the two mxv steps
keep delegating to the (already spec-lowered) ``mxv`` family, with a
tuned entry under their own variant name taking precedence."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.codegen import run_spec
from repro.core import Traffic
from repro.core.striding import StridingConfig
from repro.kernels import common
from repro.kernels.gemver import specs
from repro.kernels.mxv import ops as mxv_ops

_DEFAULT = StridingConfig(stride_unroll=4, portion_unroll=2)


@functools.partial(jax.jit, static_argnames=("config", "mode"))
def _outer(a, u1, v1, u2, v2, config: StridingConfig, mode: str):
    return run_spec(specs.gemver_outer_spec, (a, u1, v1, u2, v2),
                    config, mode)


def gemver_outer(a, u1, v1, u2, v2, config: StridingConfig | None = None,
                 mode: str | None = None):
    """Â = A + u1 v1ᵀ + u2 v2ᵀ (paper gemverouter)."""
    mode = mode or common.kernel_mode()
    m, n = a.shape
    traffic = Traffic(rows=m, cols=n, dtype=a.dtype, read_arrays=1,
                      write_arrays=1)
    cfg = common.resolve_config(
        "gemver_outer", a.shape, a.dtype, config, m, _DEFAULT,
        traffic=traffic, mode=mode,
        spec=specs.gemver_outer_spec(a, u1, v1, u2, v2))
    return _outer(a, u1, v1, u2, v2, cfg, mode)


@functools.partial(jax.jit, static_argnames=("config", "mode"))
def _vsum(x, z, config: StridingConfig, mode: str):
    return run_spec(specs.gemver_sum_spec, (x, z), config, mode)


def gemver_sum(x, z, config: StridingConfig | None = None,
               mode: str | None = None):
    """x = x + z, 1-D loop-blocked into D strides (paper gemversum)."""
    mode = mode or common.kernel_mode()
    if config is None:
        from repro.registry import tunecache
        config = tunecache.cached_config("gemver_sum", x.shape, x.dtype,
                                         mode=mode)
    cfg = config or _DEFAULT
    return _vsum(x, z, cfg, mode)


def _own_tuned(kernel: str, a, config, mode):
    """Tuned entry under this variant's own name; the delegated kernel's
    chain (its tune entry → planner) still applies when this misses."""
    if config is not None:
        return config
    from repro.registry import tunecache
    return tunecache.cached_config(kernel, a.shape, a.dtype,
                                   mode=mode or common.kernel_mode())


def gemver_mxv1(a, y, x, beta, config=None, mode=None):
    """x = x + β Aᵀ y (reuses the multi-strided mxv_t kernel)."""
    config = _own_tuned("gemver_mxv1", a, config, mode)
    return x + beta * mxv_ops.mxv_t(a, y, config=config, mode=mode)


def gemver_mxv2(a, x, alpha, config=None, mode=None):
    """w = α A x (reuses the multi-strided mxv kernel)."""
    config = _own_tuned("gemver_mxv2", a, config, mode)
    return alpha * mxv_ops.mxv(a, x, config=config, mode=mode)


def gemver(a, u1, v1, u2, v2, y, z, alpha, beta,
           config: StridingConfig | None = None, mode: str | None = None):
    """Full gemver: each step with its best striding config (paper §6.4).

    A tuned entry for the composite (one shared config measured
    end-to-end) wins; otherwise each step resolves its own."""
    config = _own_tuned("gemver", a, config, mode)
    a_hat = gemver_outer(a, u1, v1, u2, v2, config=config, mode=mode)
    x = gemver_mxv1(a_hat, y, jnp.zeros_like(z), beta, config=config,
                    mode=mode)
    x = gemver_sum(x, z, config=config, mode=mode)
    w = gemver_mxv2(a_hat, x, alpha, config=config, mode=mode)
    return a_hat, x, w
