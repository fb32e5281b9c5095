"""Serving launcher: batched generation with the continuous-batching
engine (multi-strided decode kernel on the hot path; one fused compiled
step per engine round, optionally KV-sharded across local devices).

    python -m repro.launch.serve --layers 8 --slots 8 --max-len 2048

serves yi-9b at its published widths with the depth cut to 8 layers
(one v5e chip); ``--reduced`` shrinks every width instead, for CPU smoke
runs.  ``build_engine`` is the construction the launcher and
``chip_smoke.py`` share.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs import get_config, reduced
from repro.launch.compile_cache import setup_compile_cache
from repro.models.lm import build_model
from repro.serve import ServeConfig, ServingEngine, serving_ctx


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b")
    ap.add_argument("--layers", type=int, default=None,
                    help="keep the first N layers (depth cut; widths stay "
                         "published)")
    ap.add_argument("--reduced", action="store_true",
                    help="shrink every width (configs.reduced) for CPU "
                         "smoke runs")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-len", type=int, default=128,
                    help="KV capacity per slot")
    ap.add_argument("--shards", type=int, default=1,
                    help="KV sequence shards for the flash-decode merge "
                         "(collective shard_map when >= that many local "
                         "devices, static split otherwise)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request wall-clock budget in seconds")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded admission queue (default unbounded)")
    ap.add_argument("--stats", action="store_true",
                    help="dump engine.stats() as JSON on exit")
    return ap


def model_config(args):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    return cfg


def build_engine(args):
    """Config, model, random weights from ``args.seed`` (built on the
    device by one jitted init) and the engine.  With ``shards > 1`` and a
    mesh of that many devices the weights are replicated over it and the
    engine lays its KV cache out along the sequence axis."""
    cfg = model_config(args)
    model = build_model(cfg)
    ctx = serving_ctx(args.shards)
    init = jax.jit(model.init)
    if ctx is not None:
        init = jax.jit(model.init,
                       out_shardings=NamedSharding(ctx.mesh, PartitionSpec()))
    params = init(jax.random.PRNGKey(args.seed))
    engine = ServingEngine(
        model, params,
        ServeConfig(slots=args.slots, max_len=args.max_len,
                    max_new_tokens=args.max_new, shards=args.shards,
                    deadline_s=args.deadline, max_queue=args.max_queue),
        ctx=ctx)
    return cfg, model, params, engine


def decode_step_memory(args, sharding) -> dict[str, int]:
    """Bytes one compiled decode step of ``args``'s configuration holds
    on a device (``memory_analysis()``), compiled ahead of time for
    ``sharding``'s device — a chip, or one of a described topology, so no
    chip is needed — with the engine's weights (``serving_params``, in
    the compute dtype).  ``held_params`` is the tree the caller made and
    keeps beside them (``build_engine`` returns it).  Weights, KV cache,
    outputs and temporaries must fit the chip's memory: ``total`` sizes
    the depth cut."""
    from repro.serve.engine import _decode_fn
    model = build_model(model_config(args))

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)
    held = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = placed(jax.eval_shape(model.serving_params, held))
    cache = placed(jax.eval_shape(
        lambda: model.init_cache(args.slots, args.max_len)))
    toks = placed(jax.ShapeDtypeStruct((args.slots, 1), jnp.int32))
    pos = placed(jax.ShapeDtypeStruct((args.slots,), jnp.int32))
    ma = _decode_fn(model, None, 1).lower(params, toks, cache,
                                          pos).compile().memory_analysis()
    out = {"arguments": ma.argument_size_in_bytes,
           "outputs": ma.output_size_in_bytes,
           "temporaries": ma.temp_size_in_bytes,
           "held_params": sum(a.size * a.dtype.itemsize
                              for a in jax.tree.leaves(held))}
    out["total"] = sum(out.values())
    return out


def prompts(args, vocab: int) -> list[np.ndarray]:
    rng = np.random.default_rng(args.seed)
    return [rng.integers(0, vocab, args.prompt_len)
            for _ in range(args.requests)]


def main(argv=None):
    args = parser().parse_args(argv)
    setup_compile_cache()
    cfg, _model, _params, engine = build_engine(args)
    for uid, toks in enumerate(prompts(args, cfg.vocab_size)):
        engine.submit(uid, toks)
    results = engine.run()
    for uid in sorted(results):
        print(f"req {uid}: {len(results[uid])} tokens -> "
              f"{results[uid][:8]}...")
    if args.stats:
        json.dump(engine.stats(), sys.stdout, indent=1)
        print()
    return results


if __name__ == "__main__":
    main()
