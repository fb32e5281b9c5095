"""Jit'd public wrappers for the stream kernels.

The hand-written Pallas bodies are retired (ROADMAP retirement plan):
every wrapper resolves through the family's ``TraversalSpec`` builders
in ``specs.py``, lowered by ``repro.codegen`` — mode dispatch included
(``ref`` runs the spec's pure-jnp interpreter, ``interpret``/``pallas``
the emitted kernel).  Config resolution (tune-cache → planner) still
runs in the plain-Python wrapper — not under jit — so a fresh autotune
result is picked up on the very next call instead of being frozen into
a cached trace.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.codegen import run_spec
from repro.core import Traffic
from repro.core.striding import StridingConfig
from repro.kernels import common
from repro.kernels.stream import specs

_DEFAULT = StridingConfig(stride_unroll=4, portion_unroll=2)


def _resolve(kernel, x_shape, dtype, config, mode, read_arrays, write_arrays,
             spec=None):
    rows, cols = x_shape
    traffic = Traffic(rows=rows, cols=cols, dtype=dtype,
                      read_arrays=read_arrays, write_arrays=write_arrays)
    return common.resolve_config(kernel, x_shape, dtype, config, rows,
                                 _DEFAULT, traffic=traffic, mode=mode,
                                 spec=spec)


@functools.partial(jax.jit, static_argnames=("config", "mode"))
def _read(x, config: StridingConfig, mode: str) -> jax.Array:
    d = config.stride_unroll
    rows, cols = x.shape
    x2 = x.reshape(d, (rows // d) * cols)   # one row per concurrent stream
    return run_spec(specs.read_spec, (x2,), config, mode)


def stream_read(x: jax.Array, config: StridingConfig | None = None,
                mode: str | None = None) -> jax.Array:
    """Per-stream checksums of a [rows, cols] array (paper §4.3 reads)."""
    mode = mode or common.kernel_mode()
    # no spec: its [D, seg·cols] operand depends on the resolved D
    cfg = _resolve("stream_read", x.shape, x.dtype, config, mode, 1, 0)
    return _read(x, cfg, mode)


@functools.partial(jax.jit, static_argnames=("config", "mode"))
def _copy(x, config: StridingConfig, mode: str) -> jax.Array:
    return run_spec(specs.copy_spec, (x,), config, mode)


def stream_copy(x: jax.Array, config: StridingConfig | None = None,
                mode: str | None = None) -> jax.Array:
    """y = x (paper §4.6 copy)."""
    mode = mode or common.kernel_mode()
    cfg = _resolve("stream_copy", x.shape, x.dtype, config, mode, 1, 1,
                   specs.copy_spec(x))
    return _copy(x, cfg, mode)


@functools.partial(jax.jit,
                   static_argnames=("shape", "value", "dtype", "config",
                                    "mode"))
def _init(shape, value, dtype, config: StridingConfig, mode: str):
    build = functools.partial(specs.init_spec, shape, dtype)
    return run_spec(build, (value,), config, mode)


def stream_init(shape: tuple[int, int], value=0.0, dtype=jnp.float32,
                config: StridingConfig | None = None,
                mode: str | None = None) -> jax.Array:
    """Fill (paper 'init' kernel, Table 1): a writes-only spec — zero
    read streams, D strided store positions."""
    mode = mode or common.kernel_mode()
    cfg = _resolve("stream_init", shape, dtype, config, mode, 0, 1,
                   specs.init_spec(shape, dtype))
    return _init(tuple(shape), value, dtype, cfg, mode)


@functools.partial(jax.jit, static_argnames=("config", "mode"))
def _copy_manual(x, config: StridingConfig, mode: str) -> jax.Array:
    return run_spec(specs.copy_spec, (x,), config, mode)


def stream_copy_manual(x: jax.Array, config: StridingConfig | None = None,
                       mode: str | None = None) -> jax.Array:
    """Copy via the explicit multi-buffered DMA pipeline: a non-default
    ``config.lookahead`` selects the emitter's fused manual
    ``make_async_copy`` ring (lookahead=1 = the prefetch-off ablation);
    lookahead=2 is the Pallas auto-pipeline's own double-buffer depth."""
    mode = mode or common.kernel_mode()
    cfg = _resolve("stream_copy_manual", x.shape, x.dtype, config, mode, 1, 1,
                   specs.copy_spec(x))
    return _copy_manual(x, cfg, mode)
