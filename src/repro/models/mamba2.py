"""Mamba-2 (SSD — state-space duality) block. arXiv:2405.21060.

Training uses the chunked SSD algorithm (quadratic within chunks,
linear state passing across chunks); decode is the O(1) recurrent update.
Layout follows the reference Mamba-2 block:

  in_proj → [z | xBC | dt];  xBC → causal depthwise conv →  [x | B | C]
  y = SSD(x·dt, A·dt, B, C) + D·x ;  out = out_proj(rmsnorm(y · silu(z)))
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, SSMConfig
from repro.models import common

# Leaves the block reads at float32 (they enter the f32 discretisation and
# skip path with no cast to the compute dtype); every other leaf is read
# through ``.astype(x.dtype)``.
F32_LEAVES = frozenset({"dt_bias", "a_log", "d_skip"})


def _dims(cfg: ModelConfig):
    s = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    nh = s.n_heads(d)
    conv_dim = di + 2 * s.n_groups * s.d_state
    return s, d, di, nh, conv_dim


def init_mamba(key, cfg: ModelConfig):
    s, d, di, nh, conv_dim = _dims(cfg)
    ks = jax.random.split(key, 6)
    dt_p = cfg.pdtype()
    d_in_proj = 2 * di + 2 * s.n_groups * s.d_state + nh
    # dt bias init so softplus(dt_bias) spans [dt_min, dt_max]
    u = jax.random.uniform(ks[2], (nh,), jnp.float32)
    dt0 = jnp.exp(u * (math.log(s.dt_max) - math.log(s.dt_min))
                  + math.log(s.dt_min))
    dt_bias = dt0 + jnp.log(-jnp.expm1(-dt0))  # inverse softplus
    return {
        "in_proj": common.dense_init(ks[0], (d, d_in_proj), dtype=dt_p),
        "conv_w": (jax.random.normal(ks[1], (s.d_conv, conv_dim),
                                     jnp.float32) * 0.1).astype(dt_p),
        "conv_b": jnp.zeros((conv_dim,), dt_p),
        "dt_bias": dt_bias.astype(jnp.float32),
        "a_log": jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "norm": jnp.ones((di,), dt_p),
        "out_proj": common.dense_init(ks[3], (di, d), dtype=dt_p),
    }


def _split_proj(cfg, zxbcdt):
    s, d, di, nh, conv_dim = _dims(cfg)
    gn = s.n_groups * s.d_state
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:di + conv_dim]
    dt_raw = zxbcdt[..., di + conv_dim:]
    return z, xbc, dt_raw


def _causal_conv(xbc, w, b):
    """Depthwise causal conv along seq. xbc: [B, L, C]; w: [K, C]."""
    k = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xbc.shape[1], :] * w[i][None, None, :]
              for i in range(k))
    return jax.nn.silu(out + b[None, None, :])


def _ssd_chunked(x, dt, a, b, c, chunk: int):
    """Chunked SSD: ONE sequential scan over chunks carrying the SSM
    state; each step does the intra-chunk quadratic part and the state
    update. Per-step temporaries are O(B·chunk²·H) — processing all
    chunks at once costs nc× that and blows HBM at 4k+ context
    (measured: 92 GB/device on mamba2 train_4k).

    x: [B, L, H, P]; dt: [B, L, H] (softplus'd); a: [H] (negative);
    b, c: [B, L, G, N]. Returns y: [B, L, H, P] (f32).
    """
    bs, l, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    nc = l // chunk
    rep = h // g
    # [nc, B, chunk, ...] scan layout
    xc = jnp.moveaxis(x.reshape(bs, nc, chunk, h, p), 1, 0)
    dtc = jnp.moveaxis(dt.reshape(bs, nc, chunk, h), 1, 0)
    bc = jnp.moveaxis(b.reshape(bs, nc, chunk, g, n), 1, 0)
    cc = jnp.moveaxis(c.reshape(bs, nc, chunk, g, n), 1, 0)
    qi = jnp.arange(chunk)
    causal = qi[:, None] >= qi[None, :]

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def step_body(hprev, xq, dtq, bq, cq):
        da = dtq * a[None, None, :]                     # [b,q,h]
        cum = jnp.cumsum(da, axis=1)
        seg = cum[:, :, None, :] - cum[:, None, :, :]   # [b,i,j,h]
        decay = jnp.where(causal[None, :, :, None], jnp.exp(seg), 0.0)
        cbg = jnp.einsum("bign,bjgn->bijg", cq, bq,
                         preferred_element_type=jnp.float32)
        cbh = jnp.repeat(cbg, rep, axis=-1)             # [b,i,j,h]
        scores = cbh * decay * dtq[:, None, :, :]
        y = jnp.einsum("bijh,bjhp->bihp", scores, xq.astype(jnp.float32))
        # off-diagonal: contribution of the carried state
        ch = jnp.repeat(cq, rep, axis=2)                # [b,q,h,n]
        y += jnp.einsum("bqhn,bhpn,bqh->bqhp", ch.astype(jnp.float32),
                        hprev, jnp.exp(cum))
        # state update
        tail = jnp.exp(cum[:, -1:, :] - cum) * dtq      # [b,q,h]
        bh = jnp.repeat(bq, rep, axis=2)                # [b,q,h,n]
        st = jnp.einsum("bqh,bqhn,bqhp->bhpn", tail,
                        bh.astype(jnp.float32), xq.astype(jnp.float32))
        hnew = hprev * jnp.exp(cum[:, -1, :])[..., None, None] + st
        return hnew, y

    def step(hprev, inp):
        return step_body(hprev, *inp)

    h0 = jnp.zeros((bs, h, p, n), jnp.float32)
    h_final, ys = jax.lax.scan(step, h0, (xc, dtc, bc, cc))
    return jnp.moveaxis(ys, 0, 1).reshape(bs, l, h, p), h_final


def mamba_forward(p, x, cfg: ModelConfig, return_state: bool = False):
    """Train/prefill path. x: [B, L, D] → [B, L, D] (+ decode state)."""
    s, d, di, nh, conv_dim = _dims(cfg)
    bsz, l, _ = x.shape
    zxbcdt = x @ p["in_proj"].astype(x.dtype)
    z, xbc_pre, dt_raw = _split_proj(cfg, zxbcdt)
    xbc = _causal_conv(xbc_pre, p["conv_w"].astype(x.dtype),
                       p["conv_b"].astype(x.dtype))
    gn = s.n_groups * s.d_state
    xs = xbc[..., :di].reshape(bsz, l, nh, s.head_dim)
    b = xbc[..., di:di + gn].reshape(bsz, l, s.n_groups, s.d_state)
    c = xbc[..., di + gn:].reshape(bsz, l, s.n_groups, s.d_state)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"][None, None, :])
    a = -jnp.exp(p["a_log"])
    chunk = min(s.chunk, l)
    if l % chunk:
        chunk = 1 if l == 1 else math.gcd(l, chunk)
    y, h_final = _ssd_chunked(xs, dt, a, b, c, chunk)
    y = y + p["d_skip"][None, None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(bsz, l, di).astype(x.dtype)
    y = y * jax.nn.silu(z)
    y = common.rms_norm(y, p["norm"].astype(x.dtype), cfg.norm_eps)
    out = y @ p["out_proj"].astype(x.dtype)
    if not return_state:
        return out
    # decode state: final SSM state + the last (d_conv-1) pre-activation
    # conv inputs (pad on the left for prompts shorter than the window)
    k = s.d_conv - 1
    pad = jnp.zeros((bsz, max(k - l, 0), conv_dim), x.dtype)
    window = jnp.concatenate([pad, xbc_pre[:, max(l - k, 0):]], axis=1)
    return out, {"conv": window.astype(x.dtype), "ssm": h_final}


def init_state(cfg: ModelConfig, batch: int, dtype):
    s, d, di, nh, conv_dim = _dims(cfg)
    return {
        "conv": jnp.zeros((batch, s.d_conv - 1, conv_dim), dtype),
        "ssm": jnp.zeros((batch, nh, s.head_dim, s.d_state), jnp.float32),
    }


def mamba_decode(p, x, cfg: ModelConfig, state):
    """One-token recurrent update. x: [B, 1, D] → ([B, 1, D], state')."""
    s, d, di, nh, conv_dim = _dims(cfg)
    bsz = x.shape[0]
    zxbcdt = x[:, 0] @ p["in_proj"].astype(x.dtype)   # [B, *]
    z, xbc, dt_raw = _split_proj(cfg, zxbcdt)
    # conv cache roll
    window = jnp.concatenate([state["conv"], xbc[:, None, :]], axis=1)
    w = p["conv_w"].astype(x.dtype)
    conv_out = (window * w[None]).sum(axis=1) + p["conv_b"].astype(x.dtype)
    xbc = jax.nn.silu(conv_out)
    new_conv = window[:, 1:]

    gn = s.n_groups * s.d_state
    xs = xbc[..., :di].reshape(bsz, nh, s.head_dim)
    b = xbc[..., di:di + gn].reshape(bsz, s.n_groups, s.d_state)
    c = xbc[..., di + gn:].reshape(bsz, s.n_groups, s.d_state)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"][None, :])
    a = -jnp.exp(p["a_log"])
    da = jnp.exp(dt * a[None, :])                     # [B, H]
    rep = nh // s.n_groups
    bh = jnp.repeat(b, rep, axis=1)                   # [B, H, N]
    ch = jnp.repeat(c, rep, axis=1)
    h_new = (state["ssm"] * da[..., None, None]
             + dt[..., None, None] * xs.astype(jnp.float32)[..., None]
             * bh.astype(jnp.float32)[:, :, None, :])
    y = (h_new * ch.astype(jnp.float32)[:, :, None, :]).sum(-1)  # [B,H,P]
    y = y + p["d_skip"][None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(bsz, di).astype(x.dtype) * jax.nn.silu(z)
    y = common.rms_norm(y, p["norm"].astype(x.dtype), cfg.norm_eps)
    out = (y @ p["out_proj"].astype(x.dtype))[:, None, :]
    return out, {"conv": new_conv, "ssm": h_new}
