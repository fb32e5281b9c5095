"""A configuration of another architecture than the dense decoder comes
in through new files only: its configuration, its reference (leaves,
shapes, draws, work, forward pass) and its layout on the program's
tree.  The files of two such configurations live under ``arch/`` here;
the harness finds them by name as it finds ``bench/``'s.  The dense
decoder's weights and program tree stay what they were before the
layout became data."""
import bench_fixtures

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import program, run, spec, weights
from bench.refs import dense_decoder

ARCH = bench_fixtures.ROOT / "tests" / "bench" / "arch"


@pytest.fixture
def arch_files(monkeypatch):
    """The harness looks up configurations, references and layouts in
    ``arch/`` as it would in ``bench/``."""
    monkeypatch.setattr(spec, "CONFIG_DIR", ARCH / "configs")
    monkeypatch.setattr(spec, "REF_DIR", ARCH / "refs")
    monkeypatch.setattr(spec, "LAYOUT_DIR", ARCH / "layouts")


# ---------------------------------------------------------------- (a)
# the dense decoder's weights and program tree as the harness made them
# while both were fixed in code (bench/weights.py, bench/program.py)
OLD_LEAVES = ("embed", "head", "final_norm", "norm1", "wq", "wk", "wv", "wo",
              "norm2", "w_gate", "w_up", "w_down")
OLD_BLOCK = {"norm1": "norm1", "norm2": "norm2",
             "attn": {"wq": "wq", "wk": "wk", "wv": "wv", "wo": "wo"},
             "ffn": {"w_in": "w_up", "w_gate": "w_gate", "w_out": "w_down"}}


def _old_shapes(dims):
    d, f, v, n = dims["d_model"], dims["d_ff"], dims["vocab"], \
        dims["n_layers"]
    hq, hkv, dh = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    return {
        "embed": (v, d), "head": (d, v), "final_norm": (d,),
        "norm1": (n, d), "wq": (n, d, hq * dh), "wk": (n, d, hkv * dh),
        "wv": (n, d, hkv * dh), "wo": (n, hq * dh, d), "norm2": (n, d),
        "w_gate": (n, d, f), "w_up": (n, d, f), "w_down": (n, f, d),
    }


def _old_generate(key, dims):
    dt = jnp.dtype(dims["param_dtype"])
    out = {}
    for name, shape in _old_shapes(dims).items():
        k = jax.random.fold_in(key, OLD_LEAVES.index(name))
        if name in ("final_norm", "norm1", "norm2"):
            a = jax.random.uniform(k, shape, jnp.float32, 0.75, 1.25)
        else:
            fan_in = shape[1] if name == "embed" else shape[-2]
            a = jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)
        out[name] = a.astype(dt)
    return out


def _old_to_program(w, model):
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    vpad = abstract["embed"].shape[0]
    v = w["embed"].shape[0]

    def nest(s):
        return {k: nest(x) if isinstance(x, dict) else w[x]
                for k, x in s.items()}
    return {"embed": jnp.pad(w["embed"], ((0, vpad - v), (0, 0))),
            "head": jnp.pad(w["head"], ((0, 0), (0, vpad - v))),
            "final_norm": w["final_norm"],
            "blocks": {"pos0": nest(OLD_BLOCK)}}


# yi-9b (8 q heads per KV head, RoPE on the whole head) and chatglm3-6b
# (16 per KV head, RoPE on half) at tiny widths; vocabularies that the
# program pads
SHAPED = {
    "yi-9b": dict(n_layers=3, d_model=64, n_heads=8, n_kv_heads=1,
                  head_dim=8, d_ff=172, vocab=1000),
    "chatglm3-6b": dict(n_layers=2, d_model=64, n_heads=16, n_kv_heads=1,
                        head_dim=8, d_ff=214, vocab=650),
}


@pytest.mark.parametrize("name", sorted(SHAPED))
def test_dense_weights_and_tree_as_before(name):
    config = spec.load_config(name)
    dims = dict(config["dims"], **SHAPED[name])
    config = dict(config, dims=dims)
    model = program.build_model(dims, name)
    seed = 2**31 + 17
    key = weights.seed_key(seed)
    got_w = jax.jit(lambda k: weights.generate(k, dims, dense_decoder))(key)
    want_w = jax.jit(lambda k: _old_generate(k, dims))(key)
    assert list(got_w) == list(want_w)
    for k in want_w:
        assert got_w[k].dtype == want_w[k].dtype
        np.testing.assert_array_equal(np.asarray(got_w[k]),
                                      np.asarray(want_w[k]))
    got = program.make_weights(config, model, seed)
    want = jax.jit(lambda k: _old_to_program(_old_generate(k, dims),
                                             model))(key)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    back = program.from_program(got, spec.layout(config), dims)
    assert set(back) == set(want_w)
    for k in want_w:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(want_w[k]))


def test_dense_configs_build_the_same_model():
    from repro.configs.base import ModelConfig
    for name in ("yi-9b", "chatglm3-6b"):
        d = spec.load_config(name)["dims"]
        want = ModelConfig(
            name=name, family="dense", n_layers=d["n_layers"],
            d_model=d["d_model"], n_heads=d["n_heads"],
            n_kv_heads=d["n_kv_heads"], d_ff=d["d_ff"],
            vocab_size=d["vocab"], d_head=d["head_dim"],
            rope_theta=float(d["rope_theta"]), rope_style=d["rope_style"],
            norm_eps=float(d["norm_eps"]), act="swiglu",
            param_dtype=d["param_dtype"], compute_dtype=d["compute_dtype"])
        assert program.model_config(d, name) == want


# ---------------------------------------------------------------- (b)
def test_moe_config_nests_and_counts_the_routed_experts(arch_files):
    config = spec.load_config("tiny-moe")
    dims = config["dims"]
    assert dims["moe"] == {"n_experts": 4, "top_k": 2, "d_ff_expert": 64}
    cfg = program.model_config(dims, "tiny-moe")
    assert (cfg.family, cfg.moe.n_experts, cfg.moe.top_k) == ("moe", 4, 2)
    # per layer: q, k, v, o; the router; two of the four experts
    layer = 64 * (4 + 2 * 2) * 16 + 4 * 16 * 64 + 64 * 4 + 2 * 3 * 64 * 64
    assert spec.work(config)["matmul_params"] == 2 * layer + 64 * 512


def test_moe_reference_matches_program_forward(arch_files):
    config = spec.load_config("tiny-moe")
    dims = dict(config["dims"], compute_dtype="float32")
    config = dict(config, dims=dims)
    model = program.build_model(dims, "tiny-moe")
    params = program.make_weights(config, model, 2**31 + 5)
    w = program.from_program(params, spec.layout(config), dims)
    toks = jnp.asarray(np.random.default_rng(1).integers(
        0, dims["vocab"], (2, 24)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = model.logits(params, {"tokens": toks})
    got = spec.reference(config).logits(w, toks, dims)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_moe_cell_runs_correct_through_the_harness(arch_files):
    """At float32 compute the served tokens are the reference's own
    choices.  (At bfloat16 a near tie in the router flips an expert now
    and then: at this width 2 of 76 requests then read gaps over 2.)"""
    config = spec.load_config("tiny-moe")
    config = dict(config, dims=dict(config["dims"], compute_dtype="float32"))
    cell = dataclasses.replace(bench_fixtures.tiny_cell(), name="tiny-moe",
                               config=config)
    out = run.run_cell(cell, 2**31 + 11, 2.0, False, require_chip=False)
    assert out["correct"], out["check"]
    assert out["check"]["logit_gap"]["value"] < 1e-3
    assert out["check"]["unserved"]["value"] == 0
    assert out["attempted"] > 0


# ---------------------------------------------------------------- (c)
def test_hybrid_layout_round_trips(arch_files):
    config = spec.load_config("tiny-hybrid")
    dims = config["dims"]
    model = program.build_model(dims, "tiny-hybrid")
    assert (model.cfg.attn_period, model.cfg.attn_offset) == (8, 4)
    assert model.cfg.tie_embeddings and model.cfg.n_layers == 16
    ref, layout = spec.reference(config), spec.layout(config)
    w = weights.generate(weights.seed_key(2**31 + 3), dims, ref)
    assert "head" not in w and w["in_proj"].shape[0] == 14
    tree = program.to_program(w, model, layout, dims)
    assert "head" not in tree
    assert tree["blocks"]["pos1"]["moe"]["w_in"].dtype == jnp.bfloat16
    assert tree["blocks"]["pos0"]["mamba"]["a_log"].dtype == jnp.float32
    # position 5 of the second period is the 12th Mamba layer (row 11)
    np.testing.assert_array_equal(
        np.asarray(tree["blocks"]["pos5"]["mamba"]["in_proj"][1]),
        np.asarray(w["in_proj"][11]))
    back = program.from_program(tree, layout, dims)
    assert set(back) == set(w)
    for k in w:
        assert back[k].dtype == w[k].dtype
        np.testing.assert_array_equal(np.asarray(back[k]), np.asarray(w[k]))
    again = program.to_program(back, model, layout, dims)
    assert jax.tree.structure(again) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_layout_that_misses_the_program_is_refused(arch_files):
    config = spec.load_config("tiny-hybrid")
    dims = config["dims"]
    ref, layout = spec.reference(config), spec.layout(config)
    w = weights.generate(weights.seed_key(1), dims, ref)
    # the program unties the embeddings: it wants a head nobody gives
    model = program.build_model(dict(dims, tie_embeddings=False), "untied")
    with pytest.raises(ValueError, match="head"):
        program.to_program(w, model, layout, dims)
    # a leaf of the wrong width
    model = program.build_model(dims, "tiny-hybrid")
    bad = dict(w, out_proj=w["out_proj"][:, :-1])
    with pytest.raises(ValueError, match="out_proj"):
        program.to_program(bad, model, layout, dims)
