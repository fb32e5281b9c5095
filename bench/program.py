"""The benchmark's one boundary with the program under test.

It takes from the program only: the model config classes and
``build_model``, the shapes of the params pytree (``model.init`` under
``jax.eval_shape``, nothing computed), ``ServingEngine`` with
``ServeConfig`` and ``serving_ctx``, the engine's ``submit``, ``run``
and ``stats``, ``repro.obs.install`` for the ``serve.step``,
``serve.request``, ``kernel.fallback`` and ``decode_attn.dispatch``
records, and ``kernel_mode``.  The engine has no call that stops it at
a time, so the window closes through its own deadline path
(:func:`cut`).
"""
from __future__ import annotations

import dataclasses

import jax

from bench import spec, weights

# the bench's names for two ModelConfig fields
_FIELD = {"vocab": "vocab_size", "head_dim": "d_head"}


def model_config(dims: dict, name: str):
    """The program's ``ModelConfig`` of a configuration's ``dims``: each
    key that names a field of it passes through (``moe`` and ``ssm`` as
    ``MoEConfig`` and ``SSMConfig``), an absent one keeps its default,
    and ``family`` is "dense" unless given.  Keys the program has no
    field for are the reference's alone."""
    from repro.configs.base import ModelConfig, MoEConfig, SSMConfig
    types = {f.name: f.type for f in dataclasses.fields(ModelConfig)}
    kw = {"family": "dense"}
    for key, value in dims.items():
        field = _FIELD.get(key, key)
        if field in types:
            kw[field] = float(value) if types[field] == "float" else value
    for field, cls in (("moe", MoEConfig), ("ssm", SSMConfig)):
        if field in kw:
            kw[field] = cls(**kw[field])
    return ModelConfig(name=name, **kw)


def build_model(dims: dict, name: str):
    from repro.models.lm import build_model
    return build_model(model_config(dims, name))


def _entries(layout: dict, dims: dict):
    """(program path, reference leaf, layer rows, vocabulary axis) of
    every path the layout (``bench/layouts/<reference>.json``) fills
    under ``dims``; rows None is the whole leaf."""
    for key, e in layout["tree"].items():
        if e.get("unless") and dims.get(e["unless"]):
            continue
        positions = e.get("positions")
        if positions is None or len(positions) == 1:
            yield (key.format(p=(positions or [0])[0]), e["leaf"], None,
                   e.get("vocab_axis"))
            continue
        for i, p in enumerate(positions):
            yield (key.format(p=p), e["leaf"],
                   slice(i, None, len(positions)), e.get("vocab_axis"))


def _flat(tree) -> dict:
    return {".".join(k.key for k in path): a for path, a in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def to_program(w: dict, model, layout: dict, dims: dict) -> dict:
    """The benchmark's weights arranged as the program's params pytree;
    raises where a path, shape or dtype differs from ``model.init``'s or
    a weight is left over."""
    import jax.numpy as jnp
    abstract = _flat(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    flat, used = {}, set()
    for path, leaf, rows, vaxis in _entries(layout, dims):
        a = w[leaf] if rows is None else w[leaf][rows]
        if vaxis is not None and path in abstract:
            pad = [(0, 0)] * a.ndim
            pad[vaxis] = (0, abstract[path].shape[vaxis] - a.shape[vaxis])
            a = jnp.pad(a, pad)
        flat[path] = a
        used.add(leaf)
    want = {k: (a.shape, a.dtype) for k, a in abstract.items()}
    got = {k: (a.shape, a.dtype) for k, a in flat.items()}
    if want != got or used != set(w):
        diff = {k: (want.get(k), got.get(k)) for k in sorted(set(want)
                                                             | set(got))
                if want.get(k) != got.get(k)}
        raise ValueError(f"the program's params differ from the "
                         f"reference's layout (path: want, got): {diff}; "
                         f"weights left over: {sorted(set(w) - used)}")
    return spec.nest(flat.items())


def make_weights(config: dict, model, seed: int, ctx=None):
    """The program's params pytree of the benchmark's weights for
    ``seed``, made on the device in one jitted call; replicated over
    ``ctx``'s mesh."""
    ref, layout, dims = spec.reference(config), spec.layout(config), \
        config["dims"]
    out_shardings = None
    if ctx is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        out_shardings = NamedSharding(ctx.mesh, PartitionSpec())
    return jax.jit(lambda key: to_program(weights.generate(key, dims, ref),
                                          model, layout, dims),
                   out_shardings=out_shardings)(weights.seed_key(seed))


def from_program(tree: dict, layout: dict, dims: dict) -> dict:
    """The reference's layout of the same arrays: the exact inverse of
    ``to_program`` (no copy unless the program pads the vocabulary or
    a leaf feeds several period positions)."""
    import jax.numpy as jnp
    flat, parts = _flat(tree), {}
    for path, leaf, rows, vaxis in _entries(layout, dims):
        a = flat[path]
        if vaxis is not None and a.shape[vaxis] != dims["vocab"]:
            a = jax.lax.slice_in_dim(a, 0, dims["vocab"], axis=vaxis)
        parts.setdefault(leaf, []).append((rows, a))
    w = {}
    for leaf, ps in parts.items():
        if ps[0][0] is None:
            w[leaf] = ps[0][1]
            continue
        a0 = ps[0][1]
        out = jnp.zeros((sum(a.shape[0] for _, a in ps),) + a0.shape[1:],
                        a0.dtype)
        for rows, a in ps:
            out = out.at[rows].set(a)
        w[leaf] = out
    return w


def serving_ctx(shards: int):
    from repro.serve import serving_ctx
    return serving_ctx(shards)


def build_engine(model, params, settings: dict, output_len: int, ctx):
    from repro.serve import ServeConfig, ServingEngine
    return ServingEngine(
        model, params,
        ServeConfig(slots=settings["slots"], max_len=settings["max_len"],
                    max_new_tokens=output_len, shards=settings["shards"]),
        ctx=ctx)


def cut(engine) -> None:
    """Retire every request at the engine's next round: queued ones
    unserved, in-slot ones with what they have produced (the engine's
    deadline path)."""
    engine.cfg = dataclasses.replace(engine.cfg, deadline_s=0.0)


def uncut(engine) -> None:
    engine.cfg = dataclasses.replace(engine.cfg, deadline_s=None)


def kernel_mode() -> str:
    from repro.kernels.common import kernel_mode
    return kernel_mode()


def install(collector) -> None:
    from repro import obs
    obs.install(collector)


def uninstall() -> None:
    from repro import obs
    obs.uninstall()
