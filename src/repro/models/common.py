"""Shared model components: initializers, norms, RoPE, embeddings, loss."""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.rmsnorm import ops as rmsnorm_ops

Params = Any  # nested dict of arrays


@dataclasses.dataclass(frozen=True)
class MeshCtx:
    """Names of the physical mesh axes used by shard_map layers.

    None ⇒ single-device context (tests/examples): layers use their
    collective-free paths.
    """
    mesh: Any
    dp_axes: tuple[str, ...] = ("data",)
    tp_axis: str = "model"

    @property
    def tp(self) -> int:
        return self.mesh.shape[self.tp_axis]

    def batch_axes(self, b: int) -> tuple[str, ...]:
        n = 1
        for a in self.dp_axes:
            n *= self.mesh.shape[a]
        return self.dp_axes if b % n == 0 else ()


def constrain_act(x, ctx: Optional["MeshCtx"], tp_dim: Optional[int] = None):
    """Anchor an intermediate activation: batch-shard dim 0, optionally
    TP-shard `tp_dim` when divisible.

    GSPMD only fixes shardings at annotated points; with ZeRO-3 weights
    (contraction dim sharded over `data`) and *unshardable* head counts
    (starcoder2's 36, arctic's 56) nothing anchors the QKV/FFN dots and
    the partitioner chose to replicate the tokens across `data` — a
    measured 16× per-device flop blow-up on starcoder2 prefill
    (EXPERIMENTS.md §Perf iteration 1). Constraining each projection
    output makes weight all-gather the only consistent strategy."""
    if ctx is None:
        return x
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    spec = [None] * x.ndim
    baxes = ctx.batch_axes(x.shape[0])
    if baxes:
        spec[0] = baxes if len(baxes) > 1 else baxes[0]
    if tp_dim is not None and x.shape[tp_dim] % ctx.tp == 0:
        spec[tp_dim] = ctx.tp_axis
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, P(*spec)))


def constrain_tokens(x, ctx: Optional["MeshCtx"]):
    """Pin activations at layer boundaries.

    Batch over the data axes (without this, ZeRO-3 params on the
    contraction dim make GSPMD keep tokens REPLICATED and psum every
    matmul over `data` — 16× waste, measured). Sequence over the TP axis
    when divisible (Megatron sequence parallelism): the TP row-parallel
    output psums become reduce-scatters and norms/residuals run on S/tp
    rows — halves the dominant f32 activation all-reduce traffic
    (EXPERIMENTS.md §Perf, mistral-large train)."""
    if ctx is None:
        return x
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    baxes = ctx.batch_axes(x.shape[0])
    spec = [baxes if baxes else None] + [None] * (x.ndim - 1)
    if (x.ndim >= 3 and x.shape[1] % ctx.tp == 0
            and x.shape[1] // ctx.tp >= 128):
        spec[1] = ctx.tp_axis
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, P(*spec)))


def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    """Truncated-normal fan-in init (LeCun-ish, standard for LMs)."""
    fan_in = shape[in_axis]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.float32):
    return (jax.random.normal(key, (vocab, d), jnp.float32)
            * (1.0 / math.sqrt(d))).astype(dtype)


def kernel_mesh(ctx: Optional[MeshCtx], shards: int):
    """The mesh on which a decode step's Pallas kernels run once per
    device, inside ``shard_map``: under KV-sharded serving
    (``shards > 1``) the residual stream is replicated over ``ctx``'s
    mesh, and XLA cannot partition a Pallas kernel.  None otherwise.
    The XLA matmuls around the kernels keep ``ctx``'s tensor-parallel
    sharding either way."""
    return ctx.mesh if ctx is not None and shards > 1 else None


def rms_norm(x, scale, eps, mesh=None):
    """Fused multi-strided kernel on TPU; jnp ref elsewhere (see
    kernels/common.kernel_mode).  With a multi-device ``mesh`` (see
    :func:`kernel_mesh`) the kernel runs once per device inside
    ``shard_map``: XLA cannot partition a Pallas kernel, even one whose
    operands are replicated."""
    if mesh is None or mesh.size == 1:
        return rmsnorm_ops.rmsnorm(x, scale, eps=eps)
    rep = jax.sharding.PartitionSpec()
    return jax.shard_map(
        lambda x, s: rmsnorm_ops.rmsnorm(x, s, eps=eps), mesh=mesh,
        in_specs=(rep, rep), out_specs=rep, check_vma=False)(x, scale)


def make_rope(positions: jax.Array, head_dim: int, theta: float,
              style: str) -> Optional[tuple[jax.Array, jax.Array]]:
    """Rotary embedding tables for given positions [*(B,) S].

    style 'full': rotate all head dims (llama). 'half': rotate only the
    first half of the head dims (ChatGLM's 2D-RoPE layout). 'none': None.
    """
    if style == "none":
        return None
    rot = head_dim if style == "full" else head_dim // 2
    freqs = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., rot/2]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, rope, style: str) -> jax.Array:
    """x: [B, S, H, dh]; rope cos/sin: [B?, S, rot/2] or [S, rot/2]."""
    if rope is None or style == "none":
        return x
    cos, sin = rope
    while cos.ndim < x.ndim - 1:  # broadcast over batch/head dims
        cos, sin = cos[None], sin[None]
    cos, sin = cos[..., None, :], sin[..., None, :]  # add head axis
    dh = x.shape[-1]
    rot = dh if style == "full" else dh // 2
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    yr = jnp.stack([y1, y2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([yr, xp], axis=-1) if rot != dh else yr


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None,
                  z_loss: float = 1e-4) -> jax.Array:
    """Mean token NLL with optional z-loss, f32 stable."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if z_loss:
        nll = nll + z_loss * lse ** 2
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1)
    return nll.mean()


def act_fn(name: str):
    if name == "swiglu":
        raise ValueError("swiglu is handled inside the FFN (two inputs)")
    if name == "gelu":
        return jax.nn.gelu
    if name == "silu":
        return jax.nn.silu
    raise ValueError(name)


def cast(tree, dtype):
    return jax.tree.map(
        lambda a: a.astype(dtype)
        if isinstance(a, jax.Array) and jnp.issubdtype(a.dtype, jnp.floating)
        else a, tree)
