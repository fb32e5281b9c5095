"""Sequence-sharded flash-decode: K-way split of the KV cache merged
with the online-softmax identity.

Each shard runs the ordinary multi-strided decode kernel over its
contiguous slice of the sequence axis and returns ``(out, lse)`` — the
``OnlineSoftmax(with_lse=True)`` side output.  Partials merge exactly:

    m   = max_k lse_k
    w_k = exp(lse_k - m)
    out = sum_k w_k * out_k / sum_k w_k
    lse = m + log sum_k w_k

A shard whose slice lies entirely beyond ``kv_len`` sees an all-masked
score row: its lse is ~-1e30, so its merge weight underflows to exactly
0 and the garbage partial output never contributes.

Two execution strategies share the math:

  * ``decode_attn_sharded`` — static split on one device (the K slices
    run as K kernel launches inside one jit region).  This is the
    portable path and the conformance oracle for the collective one.
  * ``decode_attn_shard_map`` — ``shard_map`` over a mesh axis holding
    the KV cache sequence-sharded; the merge runs as pmax/psum
    collectives.  A 1-sized axis (or no mesh) degrades to the
    unsharded kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels.decode_attn import ops

__all__ = ["merge_partials", "decode_attn_sharded",
           "decode_attn_shard_map", "dispatch"]


def merge_partials(outs: jax.Array, lses: jax.Array):
    """Merge per-shard flash-decode partials.

    outs: [K, B, Hq, dh]; lses: [K, B, Hq].
    Returns (out [B, Hq, dh], lse [B, Hq]) in the input out dtype / f32.
    """
    lses = lses.astype(jnp.float32)
    m = lses.max(axis=0)
    w = jnp.exp(lses - m[None])                      # [K, B, Hq]
    den = w.sum(axis=0)
    num = (w[..., None] * outs.astype(jnp.float32)).sum(axis=0)
    out = num / den[..., None]
    return out.astype(outs.dtype), m + jnp.log(den)


def _vec_kv_len(kv_len, b: int, s: int) -> jax.Array:
    kv_len = jnp.asarray(s if kv_len is None else kv_len)
    if kv_len.ndim == 0:
        kv_len = jnp.full((b,), kv_len)
    return kv_len.astype(jnp.int32)


def decode_attn_sharded(q: jax.Array, kc: jax.Array, vc: jax.Array,
                        kv_len=None, shards: int = 1, config=None,
                        mode: str | None = None, with_lse: bool = False):
    """K-way static sequence split of ``decode_attn`` on one device.

    q: [B, Hq, dh]; kc/vc: [B, S, Hkv, dh]; S must divide by ``shards``.
    ``shards <= 1`` is the unsharded kernel unchanged.
    """
    s = kc.shape[1]
    if shards <= 1:
        return ops.decode_attn(q, kc, vc, kv_len=kv_len, config=config,
                               mode=mode, with_lse=with_lse)
    if s % shards:
        raise ValueError(f"sequence {s} not divisible by {shards} shards")
    b = q.shape[0]
    sp = s // shards
    kv_len = _vec_kv_len(kv_len, b, s)
    outs, lses = [], []
    for j in range(shards):
        local = jnp.clip(kv_len - j * sp, 0, sp)
        o, l = ops.decode_attn(q, kc[:, j * sp:(j + 1) * sp],
                               vc[:, j * sp:(j + 1) * sp], kv_len=local,
                               config=config, mode=mode, with_lse=True)
        outs.append(o)
        lses.append(l)
    out, lse = merge_partials(jnp.stack(outs), jnp.stack(lses))
    out = out.astype(q.dtype)
    return (out, lse) if with_lse else out


def decode_attn_shard_map(q: jax.Array, kc: jax.Array, vc: jax.Array,
                          kv_len=None, mesh=None, axis: str = "model",
                          config=None, mode: str | None = None):
    """Flash-decode over a sequence-sharded KV cache via ``shard_map``.

    The cache's S axis is partitioned over mesh axis ``axis``; each
    device runs the decode kernel on its slice with the slice-local
    ``kv_len``, then the merge runs as pmax/psum collectives.  With no
    mesh or a 1-sized axis this IS the unsharded path.
    """
    n = int(mesh.shape[axis]) if mesh is not None else 1
    if n <= 1:
        return ops.decode_attn(q, kc, vc, kv_len=kv_len, config=config,
                               mode=mode)
    b, s = kc.shape[0], kc.shape[1]
    if s % n:
        raise ValueError(f"sequence {s} not divisible by mesh axis "
                         f"{axis}={n}")
    sp = s // n
    kvl = _vec_kv_len(kv_len, b, s)
    P = jax.sharding.PartitionSpec

    def body(qs, ks, vs, kl):
        idx = jax.lax.axis_index(axis)
        local = jnp.clip(kl - idx * sp, 0, sp)
        o, l = ops.decode_attn(qs, ks, vs, kv_len=local, config=config,
                               mode=mode, with_lse=True)
        m = jax.lax.pmax(l, axis)
        w = jnp.exp(l - m)
        den = jax.lax.psum(w, axis)
        num = jax.lax.psum(w[..., None] * o.astype(jnp.float32), axis)
        return (num / den[..., None]).astype(qs.dtype)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(None, axis, None, None),
                  P(None, axis, None, None), P()),
        out_specs=P(), check_vma=False)
    return fn(q, kc, vc, kvl)


def dispatch(q: jax.Array, kc: jax.Array, vc: jax.Array, kv_len=None,
             shards: int = 1, ctx=None, config=None,
             mode: str | None = None) -> jax.Array:
    """Pick the execution strategy for a K-sharded decode.

    When ``ctx`` carries a mesh whose TP axis is exactly ``shards``
    wide, the collective ``shard_map`` combine runs over it; otherwise
    (single device, no mesh, mismatched axis) the static split serves
    the same numerics.
    """
    mesh = getattr(ctx, "mesh", None) if ctx is not None else None
    axis = getattr(ctx, "tp_axis", "model") if ctx is not None else "model"
    if (shards > 1 and mesh is not None and axis in mesh.shape
            and int(mesh.shape[axis]) == shards):
        obs.counter("decode_attn.dispatch", strategy="shard_map",
                    shards=shards)
        return decode_attn_shard_map(q, kc, vc, kv_len=kv_len, mesh=mesh,
                                     axis=axis, config=config, mode=mode)
    obs.counter("decode_attn.dispatch", strategy="static_split",
                shards=shards)
    return decode_attn_sharded(q, kc, vc, kv_len=kv_len, shards=shards,
                               config=config, mode=mode)
