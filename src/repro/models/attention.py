"""GQA attention: train/prefill (full), decode (multi-strided kernel).

Self- and cross-attention share weights layout:
  wq [D, Hq*dh], wk [D, Hkv*dh], wv [D, Hkv*dh], wo [Hq*dh, D]
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.decode_attn import ops as da_ops
from repro.models import common

_NEG = -1e30


def init_attn(key, cfg: ModelConfig):
    d, dh = cfg.d_model, cfg.head_dim
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    dt = cfg.pdtype()
    return {
        "wq": common.dense_init(ks[0], (d, hq * dh), dtype=dt),
        "wk": common.dense_init(ks[1], (d, hkv * dh), dtype=dt),
        "wv": common.dense_init(ks[2], (d, hkv * dh), dtype=dt),
        "wo": common.dense_init(ks[3], (hq * dh, d), dtype=dt),
    }


def _qkv(p, x, cfg: ModelConfig, rope, ctx=None):
    b, s, _ = x.shape
    dh, hq, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    q = (x @ p["wq"].astype(x.dtype)).reshape(b, s, hq, dh)
    k = (x @ p["wk"].astype(x.dtype)).reshape(b, s, hkv, dh)
    v = (x @ p["wv"].astype(x.dtype)).reshape(b, s, hkv, dh)
    # anchor the projection outputs: batch-sharded, heads TP'd if divisible
    q = common.constrain_act(q, ctx, tp_dim=2)
    k = common.constrain_act(k, ctx, tp_dim=2)
    v = common.constrain_act(v, ctx, tp_dim=2)
    q = common.apply_rope(q, rope, cfg.rope_style).astype(x.dtype)
    k = common.apply_rope(k, rope, cfg.rope_style).astype(x.dtype)
    return q, k, v


def _sdpa_block(q, k, v, causal: bool, q_offset):
    """q: [B,Sq,Hq,dh]; k/v already expanded to [B,Sk,Hq,dh].

    Heads are kept as a single flat Hq dim (NOT [Hkv, g]) so the TP axis
    shards them cleanly — a factored (8×2) head layout forces GSPMD to
    replicate the batch across the data axis instead (16× flop waste,
    measured in the internvl2 baseline; see EXPERIMENTS.md §Perf)."""
    b, sq, hq, dh = q.shape
    sk = k.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(dh).astype(jnp.float32)
    if causal:
        qpos = jnp.arange(sq) + q_offset
        kpos = jnp.arange(sk)
        mask = kpos[None, :] <= qpos[:, None]
        scores = jnp.where(mask[None, None], scores, _NEG)
    p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _pick_q_chunk(b, hq, sq, sk, budget=2 ** 33):
    """Largest q-chunk keeping the (global) score tensor under budget
    elements; must divide sq."""
    qc = max(int(budget // max(b * hq * sk, 1)), 128)
    qc = min(qc, sq)
    while sq % qc:
        qc -= 1
    return qc


def _sdpa(q, k, v, causal: bool, q_offset: int = 0, ctx=None):
    """Memory-efficient exact attention: KV expanded to query heads, the
    query axis processed in checkpointed chunks (scores never exceed
    ~budget elements globally).

    The chunk body re-anchors shardings (constrain_act *inside* the
    scan): Shardy does not propagate the outer constraints into the
    nested while body and replicated the whole prefill per device
    (measured on starcoder2 prefill — EXPERIMENTS.md §Perf).

    When the head count cannot shard over TP (starcoder2: 36, arctic:
    56), attention switches to **sequence-parallel** mode: query
    positions shard over the TP axis (full K/V per device) — otherwise
    the model axis sits idle and every column repeats the full attention
    (measured 15× waste)."""
    b, sq, hq, dh = q.shape
    hkv = k.shape[2]
    if hkv != hq:
        k = jnp.repeat(k, hq // hkv, axis=2)
        v = jnp.repeat(v, hq // hkv, axis=2)
    k = common.constrain_act(k, ctx, tp_dim=2)
    v = common.constrain_act(v, ctx, tp_dim=2)
    sk = k.shape[1]
    if (ctx is not None and hq % ctx.tp != 0 and sq % ctx.tp == 0
            and sq // ctx.tp >= 128):
        return _sdpa_seqshard(q, k, v, causal, q_offset, ctx)
    qc = _pick_q_chunk(b, hq, sq, sk)
    if qc >= sq:
        return _sdpa_block(q, k, v, causal, q_offset)
    nc = sq // qc
    qs = jnp.moveaxis(q.reshape(b, nc, qc, hq, dh), 1, 0)

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def chunk(qi, i):
        qi = common.constrain_act(qi, ctx, tp_dim=2)
        out = _sdpa_block(qi, k, v, causal, q_offset + i * qc)
        return common.constrain_act(out, ctx, tp_dim=2)

    def body(_, inp):
        qi, i = inp
        return None, chunk(qi, i)

    _, out = jax.lax.scan(body, None, (qs, jnp.arange(nc)))
    return jnp.moveaxis(out, 0, 1).reshape(b, sq, hq, dh)


def _sdpa_seqshard(q, k, v, causal: bool, q_offset: int, ctx):
    """Sequence-parallel exact attention: q positions sharded over TP
    ([b, tp, S/tp, H, dh], dim1 on the model axis), K/V replicated over
    TP. q-chunks scan within the per-device slice; causal offsets are
    per TP-block."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    b, sq, hq, dh = q.shape
    tpn = ctx.tp
    sl = sq // tpn
    sk = k.shape[1]
    baxes = ctx.batch_axes(b)
    bspec = (baxes if len(baxes) > 1 else baxes[0]) if baxes else None
    spec5 = NamedSharding(ctx.mesh, P(bspec, ctx.tp_axis, None, None, None))
    q5 = jax.lax.with_sharding_constraint(
        q.reshape(b, tpn, sl, hq, dh), spec5)
    qc = _pick_q_chunk(b * tpn, hq, sl, sk)
    nc = max(sl // qc, 1)
    qc = sl // nc
    qs = jnp.moveaxis(q5.reshape(b, tpn, nc, qc, hq, dh), 2, 0)
    kpos = jnp.arange(sk)
    scale = 1.0 / jnp.sqrt(dh).astype(jnp.float32)

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def chunk(qi, i):
        qi = jax.lax.with_sharding_constraint(qi, spec5)
        s = jnp.einsum("btqhd,bkhd->bthqk", qi, k,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = (q_offset + jnp.arange(tpn)[:, None] * sl + i * qc
                    + jnp.arange(qc)[None, :])             # [tp, qc]
            mask = kpos[None, None, :] <= qpos[:, :, None]  # [tp, qc, sk]
            s = jnp.where(mask[None, :, None], s, _NEG)  # [b,tp,h,qc,sk]
        p = jax.nn.softmax(s, axis=-1).astype(qi.dtype)
        out = jnp.einsum("bthqk,bkhd->btqhd", p, v,
                         preferred_element_type=jnp.float32)
        return jax.lax.with_sharding_constraint(out.astype(qi.dtype),
                                                spec5)

    def body(_, inp):
        qi, i = inp
        return None, chunk(qi, i)

    _, out = jax.lax.scan(body, None, (qs, jnp.arange(nc)))
    # [nc, b, tp, qc, hq, dh] -> [b, tp, nc, qc, ...] -> [b, sq, hq, dh]
    out = jnp.moveaxis(out, 0, 2).reshape(b, sq, hq, dh)
    return out


def attn_forward(p, x, cfg: ModelConfig, rope, causal: bool = True,
                 ctx=None):
    """Train/prefill full attention. Returns (out, (k, v))."""
    q, k, v = _qkv(p, x, cfg, rope, ctx)
    out = _sdpa(q, k, v, causal, ctx=ctx)
    b, s, _ = x.shape
    seqshard = (ctx is not None and cfg.n_heads % ctx.tp != 0
                and s % ctx.tp == 0 and s // ctx.tp >= 128)
    if seqshard:
        # sequence-parallel mode: keep S on the TP axis through the
        # output projection (wo runs on S/tp rows per device); the layer
        # boundary constraint gathers afterwards.
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        baxes = ctx.batch_axes(b)
        bspec = (baxes if len(baxes) > 1 else baxes[0]) if baxes else None
        out = jax.lax.with_sharding_constraint(
            out, NamedSharding(ctx.mesh, P(bspec, ctx.tp_axis, None, None)))
    else:
        out = common.constrain_act(out, ctx, tp_dim=2)
    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
    return out @ p["wo"].astype(x.dtype), (k, v)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    dh, hkv = cfg.head_dim, cfg.n_kv_heads
    return {
        "k": jnp.zeros((batch, max_len, hkv, dh), dtype),
        "v": jnp.zeros((batch, max_len, hkv, dh), dtype),
    }


def attn_decode(p, x, cfg: ModelConfig, cache, pos: jax.Array, rope,
                ctx=None, shards: int = 1):
    """One-token decode: update cache at `pos`, multi-strided flash-decode.

    x: [B, 1, D]; pos: scalar int32 (current length) or a per-row [B]
    vector (ragged continuous batching — each row writes its own cache
    position and attends to its own ``kv_len``); rope built for pos.
    ``shards > 1`` runs the sequence-sharded flash-decode combine (see
    ``kernels.decode_attn.sharded``) over ``ctx``'s mesh.
    """
    q, k, v = _qkv(p, x, cfg, rope, ctx)
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim:
        upd = jax.vmap(
            functools.partial(jax.lax.dynamic_update_slice_in_dim, axis=0))
        kc = upd(cache["k"], k, pos)
        vc = upd(cache["v"], v, pos)
    else:
        kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, pos, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, pos, axis=1)
    if shards > 1:
        from repro.kernels.decode_attn import sharded as da_sharded
        out = da_sharded.dispatch(q[:, 0], kc, vc, kv_len=pos + 1,
                                  shards=shards, ctx=ctx)
    else:
        out = da_ops.decode_attn(q[:, 0], kc, vc, kv_len=pos + 1)
    b = x.shape[0]
    out = out.reshape(b, 1, cfg.n_heads * cfg.head_dim).astype(x.dtype)
    return out @ p["wo"].astype(x.dtype), {"k": kc, "v": vc}


def cross_attn_forward(p, x, cfg: ModelConfig, kv_cache):
    """Cross-attention against precomputed encoder K/V (whisper decode)."""
    b, s, _ = x.shape
    dh, hq = cfg.head_dim, cfg.n_heads
    q = (x @ p["wq"].astype(x.dtype)).reshape(b, s, hq, dh)
    out = _sdpa(q, kv_cache["k"].astype(x.dtype),
                kv_cache["v"].astype(x.dtype), causal=False)
    out = out.reshape(b, s, hq * dh)
    return out @ p["wo"].astype(x.dtype)


def encoder_kv(p, enc_out, cfg: ModelConfig):
    """Precompute cross-attention K/V from encoder output."""
    b, t, _ = enc_out.shape
    dh, hkv = cfg.head_dim, cfg.n_kv_heads
    k = (enc_out @ p["wk"].astype(enc_out.dtype)).reshape(b, t, hkv, dh)
    v = (enc_out @ p["wv"].astype(enc_out.dtype)).reshape(b, t, hkv, dh)
    return {"k": k, "v": v}
