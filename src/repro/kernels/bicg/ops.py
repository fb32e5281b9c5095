"""Jit'd wrapper for bicg (PolyBench BiCGStab sub-kernel).

The hand-written Pallas body is retired (ROADMAP retirement plan): the
wrapper lowers the family's ``TraversalSpec`` builders in ``specs.py``
through ``repro.codegen`` — both passes fused into one jitted program so
the pair costs one dispatch, like the hand-written fused kernel did.
Config resolution (tune-cache → planner → default) runs outside jit so
autotune results take effect immediately (see common.resolve_config).
"""
from __future__ import annotations

import functools

import jax

from repro.codegen import run_spec
from repro.core import Traffic
from repro.core.striding import StridingConfig
from repro.kernels import common
from repro.kernels.bicg import specs

_DEFAULT = StridingConfig(stride_unroll=4, portion_unroll=2)


@functools.partial(jax.jit, static_argnames=("config", "mode"))
def _bicg(a, r, p, config: StridingConfig, mode: str):
    return (run_spec(specs.bicg_q_spec, (a, p), config, mode),
            run_spec(specs.bicg_s_spec, (a, r), config, mode))


def bicg(a: jax.Array, r: jax.Array, p: jax.Array,
         config: StridingConfig | None = None, mode: str | None = None):
    """q = A p ; s = Aᵀ r (paper bicg: two sweeps of A, one program)."""
    mode = mode or common.kernel_mode()
    m, n = a.shape
    traffic = Traffic(rows=m, cols=n, dtype=a.dtype, read_arrays=2)
    cfg = common.resolve_config("bicg", a.shape, a.dtype, config, m,
                                _DEFAULT, traffic=traffic, mode=mode,
                                spec=(specs.bicg_q_spec(a, p),
                                      specs.bicg_s_spec(a, r)))
    return _bicg(a, r, p, cfg, mode)
