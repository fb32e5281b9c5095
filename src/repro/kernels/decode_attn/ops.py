"""Jit'd wrapper for multi-strided flash-decode attention.

The hand-written Pallas body is retired (ROADMAP retirement plan): the
wrapper lowers the family's ``TraversalSpec`` builder in ``specs.py``
through ``repro.codegen`` — a single online-softmax stream-reduction
sweep of the (flattened) cache.  ``kv_len`` masking rides a validity
row stream (the ``masked=True`` spec variant), so a traced length (the
models' decode loop) works under jit.  On the chip a sequence that no D
splits into whole tiles is padded to one and its tail masked."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.codegen import run_spec
from repro.codegen.transforms import LANE
from repro.core import Traffic
from repro.core.striding import StridingConfig
from repro.kernels import common
from repro.kernels.decode_attn import specs

_DEFAULT = StridingConfig(stride_unroll=4, portion_unroll=1)


def _flatten(q, kc, vc):
    b, hq = q.shape[0], q.shape[1]
    s, hkv, dh = kc.shape[1], kc.shape[2], kc.shape[3]
    return (kc.reshape(b, s, hkv * dh), vc.reshape(b, s, hkv * dh),
            q.reshape(b, hq, dh))


@functools.partial(jax.jit, static_argnames=("config", "mode"))
def _decode_attn(q, kc, vc, config: StridingConfig, mode: str):
    hkv, dh = kc.shape[2], kc.shape[3]
    out, lse = run_spec(specs.decode_spec(hkv, dh), _flatten(q, kc, vc),
                        config, mode)
    return (out.reshape(q.shape).astype(q.dtype),
            lse.reshape(q.shape[0], q.shape[1]).astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("config", "mode", "seq_pad"))
def _decode_attn_masked(q, kc, vc, kv_len, config: StridingConfig,
                        mode: str, seq_pad: int = 0):
    b, s, hkv, dh = kc.shape[0], kc.shape[1], kc.shape[2], kc.shape[3]
    kv_len = jnp.minimum(jnp.asarray(kv_len), s)
    if kv_len.ndim == 0:
        kv_len = jnp.full((b,), kv_len)
    if seq_pad:                      # zero positions, masked off below
        pads = ((0, 0), (0, seq_pad), (0, 0), (0, 0))
        kc, vc = jnp.pad(kc, pads), jnp.pad(vc, pads)
    mask = (jnp.arange(s + seq_pad)[None, :]
            < kv_len[:, None]).astype(jnp.float32)
    out, lse = run_spec(specs.decode_spec(hkv, dh, masked=True),
                        (*_flatten(q, kc, vc), mask), config, mode)
    return (out.reshape(q.shape).astype(q.dtype),
            lse.reshape(q.shape[0], q.shape[1]).astype(jnp.float32))


def _spec(q, kc, masked: bool, s: int):
    """The decode spec on placeholders of the flattened operands."""
    b, hq, hkv, dh = q.shape[0], q.shape[1], kc.shape[2], kc.shape[3]
    kv = jax.ShapeDtypeStruct((b, s, hkv * dh), kc.dtype)
    args = (kv, kv, jax.ShapeDtypeStruct((b, hq, dh), q.dtype))
    if masked:
        args += (jax.ShapeDtypeStruct((b, s), jnp.float32),)
    return specs.decode_spec(hkv, dh, masked=masked)(*args)


def decode_attn(q: jax.Array, kc: jax.Array, vc: jax.Array,
                kv_len: jax.Array | int | None = None,
                config: StridingConfig | None = None,
                mode: str | None = None, block_s: int = 128,
                with_lse: bool = False):
    """One-token GQA attention against a [B, S, Hkv, dh] KV cache.

    The sequence axis is stride-unrolled into D concurrent KV streams
    (multi-striding); the online-softmax partial states merge across
    streams and grid steps.  ``block_s`` is advisory (the emitter plans
    its own sequence blocking) and kept for call-site compatibility.

    ``with_lse=True`` also returns the per-(batch, query-head)
    log-sum-exp of the scaled scores as ``(out, lse)`` with lse
    [B, Hq] f32 — the side statistic sequence-sharded flash-decode
    merges partial outputs with (see ``decode_attn.sharded``).
    """
    del block_s
    mode = mode or common.kernel_mode()
    s, hkv, dh = kc.shape[1], kc.shape[2], kc.shape[3]
    masked, seq_pad = kv_len is not None, 0
    if s % common.row_align(_spec(q, kc, masked, s), mode):
        # no D splits S into whole tiles on the chip (the masked spec's
        # validity rows ride the lanes): pad K/V to whole lane tiles and
        # mask the tail — a copy of the cache per call, which a cache
        # allocated in multiples of 128 positions never pays
        masked, seq_pad = True, -s % LANE
        kv_len = s if kv_len is None else kv_len
    traffic = Traffic(rows=s + seq_pad, cols=hkv * dh, dtype=kc.dtype,
                      read_arrays=2)
    cfg = common.resolve_config("decode_attn", kc.shape, kc.dtype, config,
                                s + seq_pad, _DEFAULT, traffic=traffic,
                                mode=mode,
                                spec=_spec(q, kc, masked, s + seq_pad))
    if masked:
        out, lse = _decode_attn_masked(q, kc, vc, kv_len, cfg, mode,
                                       seq_pad=seq_pad)
    else:
        out, lse = _decode_attn(q, kc, vc, cfg, mode)
    return (out, lse) if with_lse else out
