"""Pure-jnp oracles for the stream micro-kernels (paper §4)."""
from __future__ import annotations

import jax.numpy as jnp

__all__ = ["read_ref", "copy_ref", "init_ref"]


def read_ref(x: jnp.ndarray, d: int) -> jnp.ndarray:
    """Per-stream checksums: x viewed as [rows, cols], streams = d equal
    row segments. Returns [d] sums (f32 accumulation)."""
    rows = x.shape[0]
    seg = rows // d
    return x.astype(jnp.float32).reshape(d, seg * x.shape[1]).sum(axis=1)


def copy_ref(x: jnp.ndarray) -> jnp.ndarray:
    return x


def init_ref(shape: tuple[int, int], value, dtype) -> jnp.ndarray:
    return jnp.full(shape, value, dtype=dtype)
