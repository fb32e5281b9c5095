"""Layer/block composition: homogeneous stacks and Jamba-style periods.

A *period* is the smallest repeating group of layers (1 for homogeneous
archs; ``attn_period`` for hybrids). Stacks scan over periods with
stacked params — compile time is O(period), not O(depth).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention, common, ffn, mamba2, moe


@dataclasses.dataclass(frozen=True)
class LayerDesc:
    mixer: str          # "attn" | "mamba"
    ffn: str            # "dense" | "moe" | "none"
    cross: bool = False # whisper decoder cross-attention


def layer_descriptors(cfg: ModelConfig) -> tuple[LayerDesc, ...]:
    """Descriptors for one period (static composition)."""
    period = cfg.attn_period or 1
    descs = []
    for pos in range(period):
        mixer = "mamba" if cfg.family == "ssm" else "attn"
        if cfg.attn_period:
            mixer = "attn" if pos == cfg.attn_offset else "mamba"
        if cfg.moe is not None:
            is_moe = pos % cfg.moe.every_n_layers == cfg.moe.every_n_layers - 1
            f = "moe" if is_moe else "dense"
        elif cfg.family == "ssm":
            f = "none"
        else:
            f = "dense"
        descs.append(LayerDesc(mixer=mixer, ffn=f, cross=cfg.encdec))
    return tuple(descs)


def n_periods(cfg: ModelConfig) -> int:
    period = cfg.attn_period or 1
    if cfg.n_layers % period:
        raise ValueError(f"{cfg.name}: n_layers {cfg.n_layers} not a "
                         f"multiple of period {period}")
    return cfg.n_layers // period


def _init_layer(key, cfg: ModelConfig, desc: LayerDesc):
    ks = jax.random.split(key, 5)
    dt = cfg.pdtype()
    p = {"norm1": jnp.ones((cfg.d_model,), dt)}
    if desc.mixer == "attn":
        p["attn"] = attention.init_attn(ks[0], cfg)
    else:
        p["mamba"] = mamba2.init_mamba(ks[0], cfg)
    if desc.cross:
        p["norm_x"] = jnp.ones((cfg.d_model,), dt)
        p["cross"] = attention.init_attn(ks[1], cfg)
    if desc.ffn != "none":
        p["norm2"] = jnp.ones((cfg.d_model,), dt)
        if desc.ffn == "moe":
            p["moe"] = moe.init_moe(ks[2], cfg)
        else:
            p["ffn"] = ffn.init_ffn(ks[2], cfg.d_model, cfg.d_ff, cfg.act, dt)
    return p


def init_period(key, cfg: ModelConfig, descs=None):
    descs = descs or layer_descriptors(cfg)
    ks = jax.random.split(key, len(descs))
    return {f"pos{i}": _init_layer(ks[i], cfg, d)
            for i, d in enumerate(descs)}


def init_stack(key, cfg: ModelConfig, descs=None):
    """Stacked period params: leaves have leading [n_periods] axis."""
    keys = jax.random.split(key, n_periods(cfg))
    return jax.vmap(lambda k: init_period(k, cfg, descs))(keys)


# ---------------------------------------------------------------- forward

def _layer_forward(p, x, cfg, desc: LayerDesc, rope, ctx, causal=True,
                   cross_kv=None):
    aux = jnp.zeros((), jnp.float32)
    x = common.constrain_tokens(x, ctx)
    h = common.rms_norm(x, p["norm1"].astype(x.dtype), cfg.norm_eps)
    if desc.mixer == "attn":
        a, _ = attention.attn_forward(p["attn"], h, cfg, rope, causal,
                                      ctx=ctx)
    else:
        a = mamba2.mamba_forward(p["mamba"], h, cfg)
    x = x + common.constrain_tokens(a, ctx)
    if desc.cross and cross_kv is not None:
        h = common.rms_norm(x, p["norm_x"].astype(x.dtype), cfg.norm_eps)
        x = x + attention.cross_attn_forward(p["cross"], h, cfg, cross_kv)
    if desc.ffn != "none":
        h = common.rms_norm(x, p["norm2"].astype(x.dtype), cfg.norm_eps)
        if desc.ffn == "moe":
            f, aux = moe.moe_forward(p["moe"], h, cfg, ctx)
        else:
            f = ffn.ffn_forward(p["ffn"], h, cfg.act, ctx=ctx)
        x = x + common.constrain_tokens(f, ctx)
    return x, aux


def period_forward(pparams, x, cfg, descs, rope, ctx, causal=True,
                   cross_kv=None):
    aux = jnp.zeros((), jnp.float32)
    for i, desc in enumerate(descs):
        ckv = None
        if desc.cross and cross_kv is not None:
            ckv = cross_kv[f"pos{i}"]
        x, a = _layer_forward(pparams[f"pos{i}"], x, cfg, desc, rope, ctx,
                              causal, ckv)
        aux = aux + a
    return x, aux


def stack_forward(stack, x, cfg: ModelConfig, rope, ctx,
                  causal: bool = True, cross_kv=None,
                  remat: bool = True, descs=None):
    """Scan the period stack. cross_kv leaves: [n_periods, period, ...]."""
    descs = descs or layer_descriptors(cfg)
    fwd = functools.partial(period_forward, cfg=cfg, descs=descs, rope=rope,
                            ctx=ctx, causal=causal)
    if remat:
        fwd = jax.checkpoint(
            fwd, policy=jax.checkpoint_policies.nothing_saveable)

    def body(carry, xs):
        x, aux = carry
        if cross_kv is not None:
            pparams, ckv = xs
            x, a = fwd(pparams, x, cross_kv=ckv)
        else:
            x, a = fwd(xs, x)
        return (x, aux + a), None

    xs = (stack, cross_kv) if cross_kv is not None else stack
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
    return x, aux


# ---------------------------------------------------------------- prefill

def _layer_prefill(p, c, x, cfg, desc: LayerDesc, rope, ctx,
                   cross_kv=None):
    """_layer_forward + cache capture (K/V written at position 0, SSM
    final state) — prefill is ONE pass (logits and caches together; the
    two-pass variant doubled prefill compute, §Perf iteration 1)."""
    x = common.constrain_tokens(x, ctx)
    h = common.rms_norm(x, p["norm1"].astype(x.dtype), cfg.norm_eps)
    newc = {}
    if desc.mixer == "attn":
        a, (k, v) = attention.attn_forward(p["attn"], h, cfg, rope,
                                           causal=True, ctx=ctx)
        kc = jax.lax.dynamic_update_slice_in_dim(
            c["attn"]["k"], k.astype(c["attn"]["k"].dtype), 0, 1)
        vc = jax.lax.dynamic_update_slice_in_dim(
            c["attn"]["v"], v.astype(c["attn"]["v"].dtype), 0, 1)
        newc["attn"] = {"k": kc, "v": vc}
    else:
        a, newc["mamba"] = mamba2.mamba_forward(p["mamba"], h, cfg,
                                                return_state=True)
    x = x + common.constrain_tokens(a, ctx)
    if desc.cross and cross_kv is not None:
        h = common.rms_norm(x, p["norm_x"].astype(x.dtype), cfg.norm_eps)
        x = x + attention.cross_attn_forward(p["cross"], h, cfg, cross_kv)
    if desc.ffn != "none":
        h = common.rms_norm(x, p["norm2"].astype(x.dtype), cfg.norm_eps)
        if desc.ffn == "moe":
            f, _ = moe.moe_forward(p["moe"], h, cfg, ctx)
        else:
            f = ffn.ffn_forward(p["ffn"], h, cfg.act, ctx=ctx)
        x = x + common.constrain_tokens(f, ctx)
    return x, newc


def stack_prefill(stack, cache, x, cfg: ModelConfig, rope, ctx,
                  cross_kv=None, descs=None):
    """One scan: hidden states + populated caches."""
    descs = descs or layer_descriptors(cfg)

    def body(x, xs):
        if cross_kv is not None:
            pparams, pcache, ckv = xs
        else:
            pparams, pcache = xs
            ckv = None
        newp = {}
        for i, desc in enumerate(descs):
            lckv = ckv[f"pos{i}"] if (desc.cross and ckv is not None) \
                else None
            x, nc = _layer_prefill(pparams[f"pos{i}"], pcache[f"pos{i}"],
                                   x, cfg, desc, rope, ctx, lckv)
            newp[f"pos{i}"] = nc
        return x, newp

    xs = (stack, cache, cross_kv) if cross_kv is not None else (stack,
                                                                cache)
    x, newcache = jax.lax.scan(body, x, xs)
    return x, newcache


# ---------------------------------------------------------------- decode

def init_layer_cache(cfg: ModelConfig, desc: LayerDesc, batch: int,
                     max_len: int, dtype):
    c = {}
    if desc.mixer == "attn":
        c["attn"] = attention.init_cache(cfg, batch, max_len, dtype)
    else:
        c["mamba"] = mamba2.init_state(cfg, batch, dtype)
    return c


def init_stack_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    descs = layer_descriptors(cfg)
    period = {f"pos{i}": init_layer_cache(cfg, d, batch, max_len, dtype)
              for i, d in enumerate(descs)}
    np_ = n_periods(cfg)
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (np_,) + a.shape), period)


def _layer_decode(p, c, x, cfg, desc, rope, pos, ctx, cross_kv=None,
                  shards: int = 1):
    nmesh = common.kernel_mesh(ctx, shards)
    h = common.rms_norm(x, p["norm1"].astype(x.dtype), cfg.norm_eps, nmesh)
    newc = {}
    if desc.mixer == "attn":
        a, newc["attn"] = attention.attn_decode(p["attn"], h, cfg,
                                                c["attn"], pos, rope,
                                                ctx=ctx, shards=shards)
    else:
        a, newc["mamba"] = mamba2.mamba_decode(p["mamba"], h, cfg,
                                               c["mamba"])
    x = x + a
    if desc.cross and cross_kv is not None:
        h = common.rms_norm(x, p["norm_x"].astype(x.dtype), cfg.norm_eps,
                            nmesh)
        x = x + attention.cross_attn_forward(p["cross"], h, cfg, cross_kv)
    if desc.ffn != "none":
        h = common.rms_norm(x, p["norm2"].astype(x.dtype), cfg.norm_eps,
                            nmesh)
        if desc.ffn == "moe":
            f, _ = moe.moe_forward(p["moe"], h, cfg, ctx)
        else:
            f = ffn.ffn_forward(p["ffn"], h, cfg.act, ctx=ctx)
        x = x + f
    return x, newc


def stack_decode(stack, cache, x, cfg: ModelConfig, rope, pos, ctx,
                 cross_kv=None, descs=None, shards: int = 1):
    descs = descs or layer_descriptors(cfg)

    def body(x, xs):
        if cross_kv is not None:
            pparams, pcache, ckv = xs
        else:
            pparams, pcache = xs
            ckv = None
        newp = {}
        for i, desc in enumerate(descs):
            lckv = None
            if desc.cross and ckv is not None:
                lckv = ckv[f"pos{i}"]
            x, nc = _layer_decode(pparams[f"pos{i}"], pcache[f"pos{i}"], x,
                                  cfg, desc, rope, pos, ctx, lckv,
                                  shards=shards)
            newp[f"pos{i}"] = nc
        return x, newp

    xs = (stack, cache, cross_kv) if cross_kv is not None else (stack, cache)
    x, newcache = jax.lax.scan(body, x, xs)
    return x, newcache
