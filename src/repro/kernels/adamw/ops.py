"""Jit'd wrapper for the fused AdamW update (any-parameter shape).

The hand-written Pallas body is retired (ROADMAP retirement plan): the
wrapper §5.1.1 loop-blocks the flattened tensor into [rows, 512] tiles
and lowers the family's ``TraversalSpec`` builder in ``specs.py``
through ``repro.codegen`` — one spec writing (p', m', v') as three
native output refs."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.codegen import evaluate, run_spec
from repro.core import Traffic
from repro.core.striding import StridingConfig
from repro.kernels import common
from repro.kernels.adamw import specs

_DEFAULT = StridingConfig(stride_unroll=2, portion_unroll=2)
_COLS = 512


def _blocking(n: int) -> tuple[int, int]:
    cols = min(_COLS, max(128, n))
    rows = -(-n // cols)
    return rows, cols


@functools.partial(jax.jit, static_argnames=("config", "mode"))
def _adamw(p, g, m, v, lr, b1, b2, eps, wd, bc1, bc2,
           config: StridingConfig, mode: str):
    shape = p.shape
    n = p.size
    if mode == "ref":
        # Evaluate the elementwise body at the tensor's NATIVE shape.
        # The [rows, 512] re-block below is free in the emitted kernel
        # (the tiles ARE the traversal) but its reshape boundaries make
        # XLA recompute the shared (m', v') staging inside each of the
        # three output fusions — 14 array-wide multiplies instead of 9,
        # once measured as a 1.133 gen_vs_hand outlier.  The spec's axes
        # only describe the traversal; evaluate() never tiles, so a 2-D
        # stand-in spec plus native-rank operands is exact.
        spec = specs.adamw_spec(p.reshape(-1, shape[-1]) if p.ndim > 1
                                else p.reshape(1, -1), None, None, None)
        po, mo, vo = evaluate(spec, (p, g, m.astype(jnp.float32),
                                     v.astype(jnp.float32),
                                     lr, b1, b2, eps, wd, bc1, bc2))
        return po.astype(p.dtype), mo, vo
    rows, cols = _blocking(max(n, 1))

    def flat(a, dt):
        a = a.reshape(-1).astype(dt)
        return jnp.pad(a, (0, rows * cols - n)).reshape(rows, cols)

    po, mo, vo = run_spec(specs.adamw_spec,
                          (flat(p, p.dtype), flat(g, g.dtype),
                           flat(m, jnp.float32), flat(v, jnp.float32),
                           lr, b1, b2, eps, wd, bc1, bc2), config, mode)

    def unflat(a, dt):
        return a.reshape(-1)[:n].reshape(shape).astype(dt)

    return (unflat(po, p.dtype), unflat(mo, jnp.float32),
            unflat(vo, jnp.float32))


def adamw_update(p: jax.Array, g: jax.Array, m: jax.Array, v: jax.Array,
                 lr, b1=0.9, b2=0.999, eps=1e-8, wd=0.0, bc1=1.0, bc2=1.0,
                 config: StridingConfig | None = None,
                 mode: str | None = None):
    """Fused AdamW for one parameter tensor. Returns (p', m', v')."""
    mode = mode or common.kernel_mode()
    n = 1
    for s in p.shape:
        n *= s
    rows, cols = _blocking(max(n, 1))
    # 4 read + 3 write arrays per stride: write-stream cap applies
    traffic = Traffic(rows=rows, cols=cols, dtype=p.dtype,
                      read_arrays=4, write_arrays=3)
    p2, g2, m2, v2 = (jax.ShapeDtypeStruct((rows, cols), a.dtype)
                      for a in (p, g, m, v))
    cfg = common.resolve_config("adamw_update", p.shape, p.dtype, config,
                                rows, _DEFAULT, traffic=traffic, mode=mode,
                                spec=specs.adamw_spec(p2, g2, m2, v2))
    return _adamw(p, g, m, v, lr, b1, b2, eps, wd, bc1, bc2, cfg, mode)
