"""Mixture-of-Experts layer.

Two numerically-aligned paths:

* ``moe_dense`` — collective-free: every expert applied to every token,
  combined with routing weights. Exact (no capacity drops); used when no
  mesh is supplied (unit tests, small examples) and as the oracle for the
  EP path test.

* ``moe_ep`` — production expert-parallel path under ``shard_map``:
  tokens are sequence-split across the TP axis inside the layer, routed
  locally into capacity-bounded per-expert buffers, exchanged with
  ``all_to_all`` over the TP axis (experts sharded over TP), FFN'd, and
  combined back. GShard-style capacity dropping applies.

Router: softmax top-k with load-balance auxiliary loss (Switch §2.2).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, MoEConfig
from repro.models import common, ffn


def init_moe(key, cfg: ModelConfig):
    e = cfg.moe
    d, f = cfg.d_model, e.d_ff_expert
    ks = jax.random.split(key, 5)
    dt = cfg.pdtype()
    p = {
        "router": common.dense_init(ks[0], (d, e.n_experts), dtype=dt),
        "w_in": common.dense_init(ks[1], (e.n_experts, d, f), in_axis=1,
                                  dtype=dt),
        "w_gate": common.dense_init(ks[2], (e.n_experts, d, f), in_axis=1,
                                    dtype=dt),
        "w_out": common.dense_init(ks[3], (e.n_experts, f, d), in_axis=1,
                                   dtype=dt),
    }
    if e.dense_residual:
        p["dense"] = ffn.init_ffn(ks[4], d, e.d_ff_dense or cfg.d_ff,
                                  cfg.act, dt)
    return p


def _route(xt, router_w, e: MoEConfig):
    """xt: [t, d] → (probs [t,E], top-k gates [t,k], top-k idx [t,k])."""
    logits = (xt @ router_w.astype(xt.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, e.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return probs, gates.astype(xt.dtype), idx


def _aux_loss(probs, idx, e: MoEConfig, valid=None):
    """Switch load-balance loss: E * Σ_e f_e p̄_e."""
    onehot = jax.nn.one_hot(idx, e.n_experts, dtype=jnp.float32)  # [t,k,E]
    if valid is not None:
        onehot = onehot * valid[:, None, None]
        probs = probs * valid[:, None]
        denom = jnp.maximum(valid.sum(), 1.0)
    else:
        denom = probs.shape[0]
    f = onehot.sum((0, 1)) / jnp.maximum(denom * e.top_k, 1.0)
    p_bar = probs.sum(0) / denom
    return e.n_experts * jnp.sum(f * p_bar)


def _expert_ffn(w_in, w_gate, w_out, xb, dtype):
    """xb: [E?, t, d] per-expert batched SwiGLU FFN."""
    h = jnp.einsum("etd,edf->etf", xb, w_in.astype(dtype))
    g = jnp.einsum("etd,edf->etf", xb, w_gate.astype(dtype))
    return jnp.einsum("etf,efd->etd", jax.nn.silu(g) * h,
                      w_out.astype(dtype))


def moe_dense(p, x, cfg: ModelConfig):
    """Collective-free exact MoE. x: [B, S, D] → (y, aux_loss)."""
    e = cfg.moe
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    probs, gates, idx = _route(xt, p["router"], e)
    aux = _aux_loss(probs, idx, e)
    # all experts on all tokens (small configs only)
    xb = jnp.broadcast_to(xt[None], (e.n_experts, b * s, d))
    yb = _expert_ffn(p["w_in"], p["w_gate"], p["w_out"], xb, x.dtype)
    onehot = jax.nn.one_hot(idx, e.n_experts, dtype=x.dtype)  # [t,k,E]
    w = (onehot * gates[..., None]).sum(1)                    # [t,E]
    y = jnp.einsum("te,etd->td", w, yb)
    if e.dense_residual:
        y = y + ffn.ffn_forward(p["dense"], xt, cfg.act)
    return y.reshape(b, s, d), aux


def _ep_body(tp_axis: str, all_axes: tuple[str, ...], e: MoEConfig,
             cfg: ModelConfig, tp: int, x, router_w, w_in, w_gate, w_out):
    """shard_map body. x: [b_loc, s, d] (replicated over tp);
    w_*: [E/tp, d, f] local expert shards."""
    b_loc, s, d = x.shape
    t_all = b_loc * s
    t_slice = -(-t_all // tp)                     # tokens per tp shard
    pad = t_slice * tp - t_all
    xt = x.reshape(t_all, d)
    if pad:
        xt = jnp.pad(xt, ((0, pad), (0, 0)))
    my = jax.lax.axis_index(tp_axis)
    xs = jax.lax.dynamic_slice_in_dim(xt, my * t_slice, t_slice)  # [ts, d]
    valid = (my * t_slice + jnp.arange(t_slice)) < t_all

    probs, gates, idx = _route(xs, router_w, e)
    aux = _aux_loss(probs, idx, e, valid.astype(jnp.float32))
    aux = jax.lax.pmean(aux, all_axes)

    cap = max(int(t_slice * e.top_k * e.capacity_factor / e.n_experts), 1)
    # position of each (token, slot) within its expert's capacity buffer
    onehot = jax.nn.one_hot(idx, e.n_experts, dtype=jnp.int32)  # [ts,k,E]
    flat = onehot.reshape(t_slice * e.top_k, e.n_experts)
    pos = jnp.cumsum(flat, axis=0) - 1                          # [ts*k, E]
    pos = (pos * flat).sum(-1).reshape(t_slice, e.top_k)
    exp = idx
    keep = (pos < cap) & valid[:, None]

    # scatter tokens into [E, cap, d]
    buf = jnp.zeros((e.n_experts, cap, d), x.dtype)
    esafe = jnp.where(keep, exp, 0)
    psafe = jnp.where(keep, pos, 0)
    src = xs[:, None, :] * keep[..., None].astype(x.dtype)
    buf = buf.at[esafe.reshape(-1), psafe.reshape(-1)].add(
        src.reshape(-1, d))

    # exchange: experts sharded over tp
    e_loc = e.n_experts // tp
    send = buf.reshape(tp, e_loc, cap, d)
    recv = jax.lax.all_to_all(send, tp_axis, split_axis=0, concat_axis=0,
                              tiled=False)                    # [tp, e_loc, cap, d]
    xb = recv.transpose(1, 0, 2, 3).reshape(e_loc, tp * cap, d)
    yb = _expert_ffn(w_in, w_gate, w_out, xb, x.dtype)
    back = yb.reshape(e_loc, tp, cap, d).transpose(1, 0, 2, 3)
    ret = jax.lax.all_to_all(back, tp_axis, split_axis=0, concat_axis=0,
                             tiled=False)                     # [tp, e_loc, cap, d]
    outbuf = ret.reshape(e.n_experts, cap, d)

    # combine: gather each kept slot, weight by gate
    yslot = outbuf[esafe.reshape(-1), psafe.reshape(-1)].reshape(
        t_slice, e.top_k, d)
    yslot = yslot * (gates * keep.astype(gates.dtype))[..., None]
    ys = yslot.sum(1)                                          # [ts, d]

    # restore full token set (replicated over tp) for the dense layers
    yt = jax.lax.all_gather(ys, tp_axis, axis=0, tiled=True)   # [ts*tp, d]
    y = yt[:t_all].reshape(b_loc, s, d)
    return y, aux


def moe_ep(p, x, cfg: ModelConfig, ctx: common.MeshCtx):
    """Expert-parallel MoE via shard_map. x: [B, S, D] → (y, aux)."""
    e = cfg.moe
    tp = ctx.tp
    all_axes = tuple(ctx.mesh.axis_names)
    body = functools.partial(_ep_body, ctx.tp_axis, all_axes, e, cfg, tp)
    # batch=1 decode: replicate the batch across dp (EP still over tp)
    baxes = ctx.batch_axes(x.shape[0])
    bspec = baxes if baxes else None
    y, aux = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(bspec, None, None), P(None, None),
                  P(ctx.tp_axis, None, None), P(ctx.tp_axis, None, None),
                  P(ctx.tp_axis, None, None)),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False,
    )(x, p["router"], p["w_in"], p["w_gate"], p["w_out"])
    if e.dense_residual:
        b, s, d = x.shape
        y = y + ffn.ffn_forward(p["dense"], x.reshape(b * s, d),
                                cfg.act).reshape(b, s, d)
    return y, aux


def moe_forward(p, x, cfg: ModelConfig, ctx: Optional[common.MeshCtx]):
    if ctx is None or cfg.moe.n_experts % ctx.tp != 0:
        return moe_dense(p, x, cfg)
    return moe_ep(p, x, cfg, ctx)
