"""Jit'd wrapper for conv3x3.

The hand-written Pallas body is retired (ROADMAP retirement plan): the
wrapper lowers the family's ``TraversalSpec`` builder in ``specs.py``
through ``repro.codegen`` (halo blocks, pad + crop and the nine scalar
weights all handled by the emitter)."""
from __future__ import annotations

import functools

import jax

from repro.codegen import run_spec
from repro.core.striding import StridingConfig
from repro.kernels import common
from repro.kernels.conv3x3 import specs

_DEFAULT = StridingConfig(stride_unroll=4, portion_unroll=1)


@functools.partial(jax.jit, static_argnames=("config", "mode"))
def _conv3x3(x, w, config: StridingConfig, mode: str):
    w9 = [w[r, c] for r in range(3) for c in range(3)]
    return run_spec(specs.conv3x3_spec, (x, *w9), config, mode)


def conv3x3(x: jax.Array, w: jax.Array,
            config: StridingConfig | None = None, mode: str | None = None):
    """3x3 correlation stencil, valid region (paper conv)."""
    mode = mode or common.kernel_mode()
    h_out = max(x.shape[0] - 2, 1)
    cfg = common.resolve_config("conv3x3", x.shape, x.dtype, config, h_out,
                                _DEFAULT, mode=mode,
                                spec=specs.conv3x3_spec(x))
    return _conv3x3(x, w, cfg, mode)
