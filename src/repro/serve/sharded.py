"""Mesh plumbing for KV-sharded flash-decode serving.

The engine itself is mesh-agnostic: it passes ``ServeConfig.shards``
into the model's ``decode_step`` and the attention layer picks the
execution strategy (``kernels.decode_attn.sharded.dispatch``) — a
collective ``shard_map`` combine when a mesh axis of exactly ``shards``
devices is available, the numerically identical static split otherwise.
This module builds that mesh/ctx from the local device set, degrading
to None (single-device path) when the host cannot satisfy the request.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from repro.models.common import MeshCtx

__all__ = ["resolve_serving_mesh", "serving_ctx", "place_kv_cache"]


def resolve_serving_mesh(shards: int):
    """1-axis ("model") mesh over the first ``shards`` local devices, or
    None when ``shards <= 1`` or the host has too few devices (the
    static-split path then serves the same numerics on one chip)."""
    if shards <= 1:
        return None
    devs = jax.devices()
    if len(devs) < shards:
        return None
    return jax.sharding.Mesh(np.array(devs[:shards]), ("model",))


def serving_ctx(shards: int) -> Optional[MeshCtx]:
    """MeshCtx for the serving engine: KV sequence sharded over the
    "model" axis, no data parallelism (the slot batch stays replicated —
    every device sees every query row, each contributes its KV slice)."""
    mesh = resolve_serving_mesh(shards)
    if mesh is None:
        return None
    return MeshCtx(mesh=mesh, dp_axes=(), tp_axis="model")


def place_kv_cache(cache, ctx: MeshCtx):
    """Lay a fresh decode cache out over ``ctx``'s mesh: attention K/V
    leaves ``[layers, slots, seq, Hkv, dh]`` split their sequence axis
    over the TP axis (each device holds its slice, which its shard of
    the flash-decode combine reads); any other state is replicated."""
    P = jax.sharding.PartitionSpec

    def place(path, leaf):
        names = [getattr(k, "key", None) for k in path]
        spec = (P(None, None, ctx.tp_axis)
                if "attn" in names and names[-1] in ("k", "v") else P())
        return jax.device_put(leaf, jax.sharding.NamedSharding(ctx.mesh,
                                                               spec))
    return jax.tree_util.tree_map_with_path(place, cache)
