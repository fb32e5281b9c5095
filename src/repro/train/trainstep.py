"""Train step: loss → grads (remat, optional microbatching and pod-axis
compressed gradient sync) → fused AdamW."""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import MeshCtx
from repro.train import optimizer as opt


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any

    def tree(self):
        return {"params": self.params, "opt_state": self.opt_state}


def init_state(model, key) -> dict:
    params = model.init(key)
    return {"params": params, "opt_state": opt.adamw_init(params)}


def make_train_step(model, ocfg: opt.AdamWConfig,
                    ctx: Optional[MeshCtx] = None,
                    grad_accum: int = 1, remat: bool = True,
                    compressed_pod_sync: bool = False):
    """Returns train_step(state, batch) → (state', metrics).

    grad_accum > 1 splits the batch into microbatches scanned
    sequentially (activation memory ÷ accum, same math).
    compressed_pod_sync: int8 error-feedback all-reduce of grads across
    the `pod` axis (see repro.train.compression) — applied by the caller
    wrapping this step in shard_map over `pod`; flag kept here for config
    plumbing/documentation.
    """

    def loss_fn(params, batch):
        loss, metrics = model.loss(params, batch, ctx=ctx, remat=remat)
        return loss, metrics

    def train_step(state, batch):
        params = state["params"]
        if grad_accum == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        else:  # noqa: RET506
            def micro(carry, mb):
                gsum, lsum = carry
                (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, mb)
                return (jax.tree.map(jnp.add, gsum, g), lsum + l), None

            mbs = jax.tree.map(
                lambda a: a.reshape((grad_accum, a.shape[0] // grad_accum)
                                    + a.shape[1:]), batch)
            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum), _ = jax.lax.scan(micro, (zero, 0.0), mbs)
            grads = jax.tree.map(lambda g: g / grad_accum, gsum)
            loss = lsum / grad_accum
            metrics = {}
        new_params, new_opt, om = opt.adamw_step(ocfg, params, grads,
                                                 state["opt_state"])
        metrics = dict(metrics, loss=loss, **om)
        return {"params": new_params, "opt_state": new_opt}, metrics

    return train_step


def make_compressed_train_step(model, ocfg: opt.AdamWConfig, mesh,
                               remat: bool = True):
    """Train step with int8 error-feedback gradient sync across the
    `pod` axis (the DCI link — repro.train.compression).

    The whole grad+optimizer computation runs under a *partial-manual*
    shard_map over `pod` (data/model stay auto/GSPMD): gradients inside
    are pod-local, the cross-pod mean goes over the wire as int8
    (4× fewer DCI bytes than fp32 ring all-reduce), and the quantization
    residual is carried in `state["ef"]`.

    State: {params, opt_state, ef}. Requires a mesh with a `pod` axis.
    """
    from jax.sharding import PartitionSpec as P

    from repro.models.common import MeshCtx
    from repro.train import compression

    if "pod" not in mesh.axis_names:
        raise ValueError("compressed pod sync needs a 'pod' mesh axis")

    def inner(state, batch):
        # inside the shard_map the pod axis is Manual: the model's
        # sharding constraints must target the context ABSTRACT mesh
        # (pod=Manual), not the concrete one, and only use (data, model)
        ctx = MeshCtx(mesh=jax.sharding.get_abstract_mesh(),
                      dp_axes=("data",), tp_axis="model")

        def loss_fn(params, batch):
            loss, metrics = model.loss(params, batch, ctx=ctx,
                                       remat=remat)
            return loss, metrics

        params = state["params"]
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        # ef leaves carry a leading [pods] axis; local block is [1, ...]
        ef_local = jax.tree.map(lambda e: e[0], state["ef"])
        grads, new_ef = compression.ef_compressed_pmean(grads, ef_local,
                                                        "pod")
        new_ef = jax.tree.map(lambda e: e[None], new_ef)
        loss = jax.lax.pmean(loss, "pod")
        new_params, new_opt, om = opt.adamw_step(ocfg, params, grads,
                                                 state["opt_state"])
        om = {k: jax.lax.pmean(v, "pod") for k, v in om.items()}
        metrics = dict(loss=loss, **om)
        return ({"params": new_params, "opt_state": new_opt,
                 "ef": new_ef}, metrics)

    def specs_like(tree, spec):
        return jax.tree.map(lambda _: spec, tree,
                            is_leaf=lambda x: isinstance(
                                x, (jax.Array, jax.ShapeDtypeStruct)))

    def train_step(state, batch):
        state_spec = specs_like(state, P())        # replicated over pod
        ef_spec = specs_like(state["ef"], P("pod"))  # pod-local residual
        state_spec = dict(state_spec, ef=ef_spec)
        batch_spec = jax.tree.map(
            lambda a: P("pod", *([None] * (a.ndim - 1))), batch)
        out_specs = (state_spec, specs_like({"loss": 0, "lr": 0,
                                             "grad_norm": 0}, P()))
        return jax.shard_map(inner, mesh=mesh,
                             in_specs=(state_spec, batch_spec),
                             out_specs=out_specs,
                             axis_names={"pod"}, check_vma=False)(state,
                                                                  batch)

    return train_step


def init_compressed_state(model, key, n_pods: int = 2) -> dict:
    params = model.init(key)
    return {
        "params": params,
        "opt_state": opt.adamw_init(params),
        "ef": jax.tree.map(
            lambda p: jnp.zeros((n_pods,) + p.shape, jnp.float32), params),
    }
