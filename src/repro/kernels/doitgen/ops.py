"""Jit'd wrapper for doitgen.

The hand-written Pallas body is retired (ROADMAP retirement plan): the
wrapper lowers the family's ``TraversalSpec`` builder in ``specs.py``
through ``repro.codegen`` (the batched 3-D nest keeps ``r`` as a batch
grid dim instead of the hand kernel's flatten-to-2-D reshape)."""
from __future__ import annotations

import functools

import jax

from repro.codegen import run_spec
from repro.core import Traffic
from repro.core.striding import StridingConfig
from repro.kernels import common
from repro.kernels.doitgen import specs

_DEFAULT = StridingConfig(stride_unroll=4, portion_unroll=1)


@functools.partial(jax.jit, static_argnames=("config", "mode"))
def _doitgen(a, c4, config: StridingConfig, mode: str):
    return run_spec(specs.doitgen_spec, (a, c4), config, mode)


def doitgen(a: jax.Array, c4: jax.Array,
            config: StridingConfig | None = None, mode: str | None = None):
    """A[r,q,:] ← A[r,q,:] @ C4 (paper doitgen, incl. writeback)."""
    mode = mode or common.kernel_mode()
    r, q, s = a.shape
    p = c4.shape[1]
    m = r * q
    traffic = Traffic(rows=m, cols=s, dtype=a.dtype, read_arrays=1,
                      write_arrays=1, resident_bytes=s * p * 4)
    cfg = common.resolve_config("doitgen", a.shape, a.dtype, config, m,
                                _DEFAULT, traffic=traffic, mode=mode,
                                spec=specs.doitgen_spec(a, c4))
    return _doitgen(a, c4, cfg, mode)
