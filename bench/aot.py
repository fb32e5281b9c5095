#!/usr/bin/env python3
"""What one decode step of a deployment holds on a device, compiled ahead of
time for a described TPU v5e: no chip needed.

    JAX_PLATFORMS=cpu python3 bench/aot.py --config yi-9b --slots 8 \
        --max-len 2048 --shards 4      # over a described 2x2
    JAX_PLATFORMS=cpu python3 bench/aot.py --config yi-9b --slots 8 \
        --max-len 2048 --shards 1      # the same deployment on one chip

Prints ``memory_analysis()`` of the step per device (arguments: the
engine's weights, cast to the compute dtype, and the KV cache; outputs:
logits and the new cache, which the engine does not donate;
temporaries), the tree the benchmark makes (its weights in the
parameter dtype, replicated, held through the window for the check),
their total, a bound on a run's peak, and the collectives and kernels
in the compiled program.  It counts as
``repro.launch.serve.decode_step_memory`` does, for the model a
configuration file builds (``bench.program``) and over a mesh: the step
is the engine's (``repro.serve.engine._decode_fn``), with the weights
replicated and the cache split along the sequence when ``shards > 1``.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
GIB = 2**30


def step_memory(config: str, slots: int, max_len: int, n: int,
                topology: str = "v5e:2x2") -> dict:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["REPRO_KERNEL_MODE"] = "pallas"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bench import program, spec
    from repro.serve.engine import _decode_fn
    jax.config.update("jax_enable_compilation_cache", False)
    dims = spec.load_config(config)["dims"]
    model = program.build_model(dims, config)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=topology)
    mesh = jax.sharding.Mesh(np.array(topo.devices[:n]), ("model",))
    ctx = None
    if n > 1:
        from repro.models.common import MeshCtx
        ctx = MeshCtx(mesh=mesh, dp_axes=(), tp_axis="model")

    def place(tree, spec_fn):
        return jax.tree_util.tree_map_with_path(
            lambda path, a: jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=NamedSharding(mesh, spec_fn(path))), tree)

    def kv_spec(path):
        names = [getattr(k, "key", None) for k in path]
        return (P(None, None, "model") if n > 1 and "attn" in names
                and names[-1] in ("k", "v") else P())
    held = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = place(jax.eval_shape(model.serving_params, held), lambda _: P())
    cache = place(jax.eval_shape(lambda: model.init_cache(slots, max_len)),
                  kv_spec)
    toks = place(jax.ShapeDtypeStruct((slots, 1), jnp.int32), lambda _: P())
    pos = place(jax.ShapeDtypeStruct((slots,), jnp.int32), lambda _: P())
    compiled = _decode_fn(model, ctx, n).lower(params, toks, cache,
                                               pos).compile()
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    out = {"arguments": ma.argument_size_in_bytes,
           "outputs": ma.output_size_in_bytes,
           "temporaries": ma.temp_size_in_bytes,
           "held_params": sum(a.size * a.dtype.itemsize
                              for a in jax.tree.leaves(held))}
    out["total"] = sum(out.values())
    out["all-reduce"] = text.count("all-reduce(")
    out["tpu_custom_call"] = text.count('custom_call_target="tpu_custom_call"')
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--slots", type=int, required=True)
    ap.add_argument("--max-len", type=int, required=True)
    ap.add_argument("--shards", type=int, default=1,
                    help="chips the cache is split over (1: one chip)")
    args = ap.parse_args(argv)
    m = step_memory(args.config, args.slots, args.max_len, args.shards)
    print(f"{args.config} {args.slots} x {args.max_len} shards="
          f"{args.shards}: per device "
          f"arguments {m['arguments'] / GIB:.3f} GiB, outputs "
          f"{m['outputs'] / GIB:.3f} GiB, temporaries "
          f"{m['temporaries'] / GIB:.3f} GiB, held tree "
          f"{m['held_params'] / GIB:.3f} GiB, total {m['total'] / GIB:.3f} "
          f"GiB; {m['all-reduce']} all-reduce, {m['tpu_custom_call']} "
          "tpu_custom_call")
    return 0


if __name__ == "__main__":
    sys.exit(main())
