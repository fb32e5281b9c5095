"""Operations and bytes of the work a decode step does, from counts.

The counts are the configuration's reference's (``work(dims)`` in
``bench/refs/<reference>.py``, here ``w``): ``matmul_params``, the
weights a token multiplies by (of an expert layer, the experts a token
is routed to); ``norms``, [width, calls per step] of the RMSNorms;
``attn_layers``, the layers that hold K/V and call ``decode_attn``;
``attn_width``, query heads × head size; ``kv_row``, the K and V
elements of one position in one such layer; ``d_model``.

They are counts of the work, not of today's implementation: the rows a
step advances (not every slot), their live context (``kv_len``, not the
cache's capacity), and weights read once at the bfloat16 compute type
(not the float32 they are stored in).  A change that skips dead cache
blocks or holds bf16 weights then raises a share instead of breaking a
count.  ``kv`` below is a list with one ``kv_len`` (positions attended,
the new one included) per advanced row.
"""
from __future__ import annotations

BF16 = 2


def norm_params(w: dict) -> int:
    """Norm scales a step reads: each call has its own."""
    return sum(width * calls for width, calls in w["norms"])


def attn_flops(w: dict, kv) -> int:
    """Scores and the weighted sum of values, each 2 FLOPs a product,
    per query head and attended position, in one layer."""
    return 4 * w["attn_width"] * sum(kv)


def step_flops(w: dict, kv) -> int:
    return 2 * w["matmul_params"] * len(kv) + w["attn_layers"] * attn_flops(
        w, kv)


def kv_row_bytes(w: dict) -> int:
    """K and V of one position in one layer."""
    return w["kv_row"] * BF16


def step_bytes(w: dict, kv) -> int:
    """Least HBM traffic of one step: every weight once, each advanced
    row's embedding row, its live K/V read and its new K/V row written,
    in every attention layer."""
    weights = (w["matmul_params"] + norm_params(w)) * BF16
    embed = len(kv) * w["d_model"] * BF16
    cache = w["attn_layers"] * kv_row_bytes(w) * (sum(kv) + len(kv))
    return weights + embed + cache


def decode_attn_call(w: dict, kv) -> tuple[int, int]:
    """(FLOPs, bytes) of one layer's decode attention over the advanced
    rows: q in, live K/V read, out written."""
    q = w["attn_width"] * BF16
    return attn_flops(w, kv), len(kv) * 2 * q + kv_row_bytes(w) * sum(kv)


def rmsnorm_call(width: int, rows: int) -> tuple[int, int]:
    """(FLOPs, bytes) of one RMSNorm of ``width`` over ``rows`` rows: x
    and the scale read, the output written; square, sum, scale,
    multiply."""
    return 4 * rows * width, (2 * rows + 1) * width * BF16


def least_seconds(flops: int, nbytes: int, peaks: dict) -> float:
    """The chip's least time for the work: compute or memory bound."""
    return max(flops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])
