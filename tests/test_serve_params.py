"""The engine serves weights cast to the compute dtype once, at
construction (``CausalLM.serving_params``), so the jitted decode step
converts no weight: the same bf16 values reach the same matmuls, and
logits, caches and tokens stay bitwise those of the float32 params."""
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config, reduced
from repro.models import mamba2
from repro.models.lm import build_model
from repro.serve import ServeConfig, ServingEngine
from repro.serve.engine import _decode_fn

# the families the engine serves: dense full RoPE, dense half RoPE
# (chatglm3), MoE, pure mamba2, attention + mamba2 + MoE hybrid
ARCHS = ["yi-9b", "chatglm3-6b", "qwen3-moe-30b-a3b", "mamba2-2.7b",
         "jamba-1.5-large-398b"]

# leaves the layers read through ``.astype(compute dtype)``, by name
CAST = {"embed", "head", "final_norm", "norm1", "norm2", "wq", "wk", "wv",
        "wo", "w_in", "w_gate", "w_out", "router", "in_proj", "conv_w",
        "conv_b", "out_proj", "norm"}


def _model(arch):
    model = build_model(reduced(get_config(arch)))
    return model, model.init(jax.random.PRNGKey(0))


def _names(path):
    return [getattr(k, "key", None) for k in path]


def _decode3(model, params, slots=2, max_len=16):
    """Three steps of the engine's jitted decode step, greedy, rows at
    ragged positions; returns every step's logits and the last cache."""
    step = _decode_fn(model, None, 1)
    cache = model.init_cache(slots, max_len)
    toks = jnp.arange(3, 3 + slots, dtype=jnp.int32)[:, None]
    pos = jnp.arange(slots, dtype=jnp.int32)
    logits = []
    for _ in range(3):
        out, cache = step(params, toks, cache, pos)
        logits.append(out)
        toks = jnp.argmax(out, -1).astype(jnp.int32)[:, None]
        pos = pos + 1
    return logits, cache


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_from_cast_params_is_bitwise_the_f32_decode(arch):
    model, params = _model(arch)
    got = _decode3(model, model.serving_params(params))
    want = _decode3(model, params)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("arch", ARCHS)
def test_cast_leaves_in_compute_dtype_f32_leaves_kept(arch):
    model, params = _model(arch)
    cdt = model.cfg.cdtype()
    served = model.serving_params(params)
    seen = set()
    for path, a in jax.tree_util.tree_flatten_with_path(served)[0]:
        names = _names(path)
        seen.add(names[-1])
        if "mamba" in names and names[-1] in mamba2.F32_LEAVES:
            assert a.dtype == jnp.float32, names
        else:
            assert names[-1] in CAST, names
            assert a.dtype == cdt, names
    assert {"embed", "final_norm", "norm1"} <= seen
    if arch != "mamba2-2.7b":
        assert {"wq", "wk", "wv", "wo", "w_in", "w_gate", "w_out"} <= seen
    if arch in ("mamba2-2.7b", "jamba-1.5-large-398b"):
        assert mamba2.F32_LEAVES | {"in_proj", "conv_w", "out_proj"} <= seen
    if arch in ("qwen3-moe-30b-a3b", "jamba-1.5-large-398b"):
        assert "router" in seen
    # the given tree is untouched: the caller keeps its f32 weights
    assert {a.dtype for a in jax.tree.leaves(params)} == {
        jnp.dtype("float32")}


def test_engine_tokens_equal_a_hand_loop_over_f32_params():
    """Slot 0 serves two requests in turn; each one's tokens equal a
    hand-driven greedy loop of ``decode_step`` over the f32 params."""
    model, params = _model("yi-9b")
    prompts = {1: [5, 9, 2, 7], 2: [11, 3]}
    max_new, max_len = 5, 32
    eng = ServingEngine(model, params,
                        ServeConfig(slots=1, max_len=max_len,
                                    max_new_tokens=max_new))
    for uid, p in prompts.items():
        eng.submit(uid, p)
    served = eng.run()
    step = jax.jit(model.decode_step)
    for uid, prompt in prompts.items():
        cache = model.init_cache(1, max_len)
        seq, out = list(prompt), []
        for t in range(len(prompt) - 1 + max_new):
            logits, cache = step(params, jnp.array([[seq[t]]], jnp.int32),
                                 cache, jnp.array([t], jnp.int32))
            if t >= len(prompt) - 1:
                out.append(int(jnp.argmax(logits[0])))
                seq.append(out[-1])
        assert served[uid] == out


@pytest.mark.parametrize("arch,kept", [("yi-9b", 0), ("mamba2-2.7b", 3)])
def test_stats_count_cast_and_kept_leaves(arch, kept):
    model, params = _model(arch)
    with obs.collect() as col:
        eng = ServingEngine(model, params, ServeConfig(slots=1))
    leaves = jax.tree.leaves(params)
    st = eng.stats()["params"]
    assert st == {"cast_leaves": len(leaves) - kept,
                  "cast_bytes": sum(a.size * 2 for a in leaves) - sum(
                      a.size * 2 for p, a in
                      jax.tree_util.tree_flatten_with_path(params)[0]
                      if _names(p)[-1] in mamba2.F32_LEAVES),
                  "kept_leaves": kept}
    (span,) = col.named("serve.cast_params")
    assert span.kind == "span" and span.value > 0
    assert "serve.cast_params" in eng.stats()["host"]["self_s"]


class _ToyModel:
    """Next token = (token + 1) mod vocab; no params, no serving_params."""

    vocab = 7

    def init_cache(self, slots, max_len):
        return jnp.zeros((slots, max_len))

    def decode_step(self, params, toks, cache, pos, ctx=None):
        return jax.nn.one_hot((toks[:, 0] + 1) % self.vocab,
                              self.vocab), cache


def test_engine_without_params_still_runs():
    eng = ServingEngine(_ToyModel(), None, ServeConfig(slots=2,
                                                       max_new_tokens=3))
    eng.submit(1, [1, 2])
    assert eng.run() == {1: [3, 4, 5]}
    assert eng.params is None
    assert eng.stats()["params"] == {"cast_leaves": 0, "cast_bytes": 0,
                                     "kept_leaves": 0}


# ``stablehlo.convert %x : (tensor<64x128xf32>) -> tensor<64x128xbf16>``
_CONVERT_F32 = re.compile(
    r"stablehlo\.convert [^:\n]*: \(tensor<([0-9x]*)xf32>")


def _weight_convert_shapes(model, params, slots=3):
    """Shapes of the f32 operands of the lowered decode step's converts
    that are a weight's shape, stacked or one layer's slice."""
    shapes = set()
    for path, a in jax.tree_util.tree_flatten_with_path(params)[0]:
        shapes.add(a.shape)
        if "blocks" in _names(path):
            shapes.add(a.shape[1:])
    cache = jax.eval_shape(lambda: model.init_cache(slots, 16))
    text = _decode_fn(model, None, 1).lower(
        params, jax.ShapeDtypeStruct((slots, 1), jnp.int32), cache,
        jax.ShapeDtypeStruct((slots,), jnp.int32)).as_text()
    found = {tuple(int(d) for d in m.split("x")) if m else ()
             for m in _CONVERT_F32.findall(text)}
    return found & shapes


def test_decode_step_converts_no_weight():
    """Lowered with the serving params' shapes, the dense decode step
    holds no convert of an f32 weight; lowered with the f32 params it
    holds one per weight (what the per-step converts looked like)."""
    model = build_model(reduced(get_config("yi-9b")))
    given = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    served = jax.eval_shape(model.serving_params, given)
    assert _weight_convert_shapes(model, served) == set()
    assert len(_weight_convert_shapes(model, given)) >= 5


def test_decode_step_memory_counts_served_and_held_params():
    from repro.launch import serve
    args = serve.parser().parse_args(["--reduced", "--slots", "2",
                                      "--max-len", "32"])
    model = build_model(serve.model_config(args))
    given = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    f32 = sum(a.size * 4 for a in jax.tree.leaves(given))
    cache = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(
        jax.eval_shape(lambda: model.init_cache(2, 32))))
    mem = serve.decode_step_memory(
        args, jax.sharding.SingleDeviceSharding(jax.devices()[0]))
    assert mem["held_params"] == f32
    # the step's arguments: bf16 weights (half the held tree), the cache,
    # tokens and positions
    assert mem["arguments"] == f32 // 2 + cache + 2 * 2 * 4
    assert mem["total"] == (mem["arguments"] + mem["outputs"]
                            + mem["temporaries"] + mem["held_params"])


_SHARDED_SCRIPT = r"""
import jax, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.configs import get_config, reduced
from repro.models.lm import build_model
model = build_model(reduced(get_config("yi-9b")))
mesh = Mesh(np.array(jax.devices()[:4]), ("model",))
params = jax.jit(model.init, out_shardings=NamedSharding(mesh, P()))(
    jax.random.PRNGKey(0))
served = model.serving_params(params)
for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(served)):
    assert b.dtype == jax.numpy.bfloat16, b.dtype
    assert b.sharding.is_equivalent_to(a.sharding, a.ndim), b.sharding
print("CAST_KEEPS_SHARDING")
"""


def test_cast_keeps_replicated_sharding_over_a_mesh():
    """Weights replicated over a four-device mesh (the ``shards > 1``
    path) stay replicated after the cast."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT],
                         capture_output=True, text=True, timeout=300,
                         env=env, cwd=root)
    assert "CAST_KEEPS_SHARDING" in res.stdout, res.stdout + res.stderr
