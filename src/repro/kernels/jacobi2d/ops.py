"""Jit'd wrapper for jacobi2d.

The hand-written Pallas body is retired (ROADMAP retirement plan): the
wrapper lowers the family's ``TraversalSpec`` builder in ``specs.py``
through ``repro.codegen`` (halo blocks and pad + crop handled by the
emitter)."""
from __future__ import annotations

import functools

import jax

from repro.codegen import run_spec
from repro.core.striding import StridingConfig
from repro.kernels import common
from repro.kernels.jacobi2d import specs

_DEFAULT = StridingConfig(stride_unroll=4, portion_unroll=1)


@functools.partial(jax.jit, static_argnames=("config", "mode"))
def _jacobi2d(x, config: StridingConfig, mode: str):
    return run_spec(specs.jacobi_spec, (x,), config, mode)


def jacobi2d(x: jax.Array, config: StridingConfig | None = None,
             mode: str | None = None):
    """One Jacobi 5-point sweep over the interior (paper jacobi2d)."""
    mode = mode or common.kernel_mode()
    h_out = max(x.shape[0] - 2, 1)
    cfg = common.resolve_config("jacobi2d", x.shape, x.dtype, config, h_out,
                                _DEFAULT, mode=mode, spec=specs.jacobi_spec(x))
    return _jacobi2d(x, cfg, mode)
