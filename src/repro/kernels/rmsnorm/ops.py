"""Jit'd wrapper for fused RMSNorm (any leading batch dims).

The hand-written Pallas body is retired (ROADMAP retirement plan): the
wrapper lowers the family's ``TraversalSpec`` builder in ``specs.py``
through ``repro.codegen``; the spec's native second output (the f32
inverse-rms row statistic) is computed either way and simply dropped
here — the ``rmsnorm_gen`` registry variant exposes it."""
from __future__ import annotations

import functools

import jax

from repro.codegen import run_spec
from repro.core.striding import StridingConfig
from repro.kernels import common
from repro.kernels.rmsnorm import specs

_DEFAULT = StridingConfig(stride_unroll=4, portion_unroll=1)


@functools.partial(jax.jit, static_argnames=("eps", "config", "mode"))
def _rmsnorm(x, w, eps: float, config: StridingConfig,
             mode: str) -> jax.Array:
    shape = x.shape
    out, _ = run_spec(specs.rmsnorm_spec, (x.reshape(-1, shape[-1]), w, eps),
                      config, mode)
    return out.reshape(shape)


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6,
            config: StridingConfig | None = None,
            mode: str | None = None) -> jax.Array:
    mode = mode or common.kernel_mode()
    t = 1
    for s in x.shape[:-1]:
        t *= s
    x2 = jax.ShapeDtypeStruct((max(t, 1), x.shape[-1]), x.dtype)
    cfg = common.resolve_config("rmsnorm", x.shape, x.dtype, config,
                                max(t, 1), _DEFAULT, mode=mode,
                                spec=specs.rmsnorm_spec(x2, w))
    return _rmsnorm(x, w, eps, cfg, mode)
