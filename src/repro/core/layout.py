"""Layout rules: tiling alignment and the paper's §4.5 collision model.

On CPU, D concurrent streams spaced at a large power-of-two byte distance
map to the same cache *sets* and evict each other (paper Fig 5: exactly-2GiB
arrays collapse; 1.9GiB arrays don't). On TPU the banked resource with the
same power-of-two failure mode is the HBM channel/bank interleave (and, at
the VMEM level, the (8,128)/(16,128) tile layout). The remedy is identical
to the paper's: perturb the inter-stream spacing so concurrent streams
rotate across channel groups — we pad the trailing dimension by one tile.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

__all__ = [
    "sublane_tile",
    "LANE",
    "pad_to_lane",
    "aliasing_exponent",
    "collides",
    "conflict_free_cols",
    "stream_stagger",
    "vmem_bytes",
]

LANE = 128  # lane width of a TPU vreg tile (last dim)

# sublane count of the (sublane, lane) VMEM tile per dtype itemsize
_SUBLANE = {4: 8, 2: 16, 1: 32}

# Power-of-two aliasing model: two streams collide when their byte spacing
# is divisible by 2**ALIAS_BITS (covers both the CPU set-index field the
# paper measured and HBM channel-interleave granularity on TPU).
ALIAS_BITS = 12  # 4 KiB


def sublane_tile(dtype) -> tuple[int, int]:
    """Native VMEM tile (sublanes, lanes) for dtype."""
    itemsize = jnp.dtype(dtype).itemsize
    if itemsize not in _SUBLANE:
        raise ValueError(f"unsupported itemsize {itemsize} for dtype {dtype}")
    return (_SUBLANE[itemsize], LANE)


def pad_to_lane(n: int) -> int:
    """Round n up to a multiple of the 128-lane tile."""
    return -(-n // LANE) * LANE


def aliasing_exponent(spacing_bytes: int) -> int:
    """Largest e such that 2**e divides spacing_bytes (0 spacing → inf-like 63)."""
    if spacing_bytes == 0:
        return 63
    return int(spacing_bytes & -spacing_bytes).bit_length() - 1


def collides(spacing_bytes: int, alias_bits: int = ALIAS_BITS) -> bool:
    """Paper §4.5: concurrent streams spaced at an *exact* power of two
    (≥ the aliasing granularity) compete for the same sets/banks.

    The exact-power-of-two criterion matches both the paper's design
    (2.0 GiB collapses, 1.9 GiB doesn't — 1.9 GiB spacing has a large odd
    factor) and an earlier host measurement (256 MiB arrays degraded
    19-43% vs 192 MiB = 3·2^26). Modern LLCs hash the set index,
    so only exact 2^k strides alias through the hash; a single odd factor
    (the paper's row padding, our lane padding) de-aliases."""
    if spacing_bytes < (1 << alias_bits):
        return False
    return (spacing_bytes & (spacing_bytes - 1)) == 0


def conflict_free_cols(rows: int, cols: int, d: int, dtype,
                       alias_bits: int = ALIAS_BITS,
                       max_pad_tiles: int = 8) -> tuple[int, bool]:
    """Padded column count so d streams over a row-major [rows, cols] array
    do not alias, plus a residual-alias flag.

    Mirrors the paper's 1.9 GiB-vs-2 GiB experiment: if the inter-stream
    spacing (rows//d)*row_bytes is a multiple of 2**alias_bits, pad each row
    by lane tiles to break the power of two. When the per-pad spacing
    increment (rows//d)*tile_bytes is itself a multiple of the aliasing
    granularity, no row padding can help — return ``aliased=True`` and let
    the kernel apply a per-stream column stagger (``stream_stagger``)
    instead. Returns (lane-aligned cols >= cols, still_aliased).
    """
    itemsize = jnp.dtype(dtype).itemsize
    cols = pad_to_lane(cols)
    if d <= 1:
        return cols, False
    seg = rows // d
    for pad in range(max_pad_tiles + 1):
        c = cols + pad * LANE
        if not collides(seg * c * itemsize, alias_bits):
            return c, False
    return cols, True


def stream_stagger(d: int, spacing_bytes: int, block_bytes: int,
                   alias_bits: int = ALIAS_BITS) -> int:
    """Per-stream column-block rotation (in blocks) breaking residual
    aliasing: stream k starts its column walk at block k*stagger (mod
    column blocks), so concurrent addresses are spaced
    spacing + stagger*block_bytes apart. Returns 0 when no stagger needed,
    else the smallest stagger whose offset de-aliases the streams."""
    if d <= 1 or not collides(spacing_bytes, alias_bits):
        return 0
    for s in range(1, 8):
        if not collides(spacing_bytes + s * block_bytes, alias_bits):
            return s
    return 1  # best effort: any non-zero rotation spreads demand in time


def vmem_bytes(block_shape: tuple[int, ...], dtype, n_buffers: int = 2) -> int:
    """VMEM footprint of one stream's buffer ring."""
    return int(np.prod(block_shape)) * jnp.dtype(dtype).itemsize * n_buffers
