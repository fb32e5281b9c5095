"""Sequence-sharded flash-decode: the online-softmax (out, lse) merge
must match the unsharded kernel/oracle at 1e-6, including fully-masked
shards; plus ragged per-slot kv_len vectors through the batched
vector-pos decode step, and the shard_map path (single-device degrade
inline, true 4-device combine via subprocess)."""
import dataclasses
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attn import ops as da_ops
from repro.kernels.decode_attn import ref as da_ref
from repro.kernels.decode_attn import sharded as da_sharded

B, S, HQ, HKV, DH = 2, 64, 4, 2, 16


def _inputs(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, HQ, DH), jnp.float32),
            jax.random.normal(ks[1], (B, S, HKV, DH), jnp.float32),
            jax.random.normal(ks[2], (B, S, HKV, DH), jnp.float32))


# ------------------------------------------------------ K-way merge

@pytest.mark.parametrize("mode", ["ref", "interpret"])
@pytest.mark.parametrize("shards", [2, 4])
def test_sharded_matches_unsharded_oracle(mode, shards):
    q, kc, vc = _inputs()
    kv_len = jnp.asarray([S, S - 17])         # ragged, shard-unaligned
    want = da_ref.decode_attn_ref(q, kc, vc, kv_len=kv_len)
    one = da_ops.decode_attn(q, kc, vc, kv_len=kv_len, mode=mode)
    got = da_sharded.decode_attn_sharded(q, kc, vc, kv_len=kv_len,
                                         shards=shards, mode=mode)
    np.testing.assert_allclose(got, one, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_fully_masked_shard_contributes_zero(mode):
    """kv_len far below a shard boundary: the all-masked shards' merge
    weights underflow to exactly 0 — no NaN, oracle-exact output."""
    q, kc, vc = _inputs(seed=3)
    kv_len = jnp.asarray([5, 3])              # shards 1..3 of 4 all masked
    want = da_ref.decode_attn_ref(q, kc, vc, kv_len=kv_len)
    got = da_sharded.decode_attn_sharded(q, kc, vc, kv_len=kv_len,
                                         shards=4, mode=mode)
    assert np.all(np.isfinite(np.asarray(got)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_merged_lse_matches_ref():
    q, kc, vc = _inputs(seed=5)
    out, lse = da_sharded.decode_attn_sharded(q, kc, vc, shards=4,
                                              mode="ref", with_lse=True)
    ref_out, ref_lse = da_ref.decode_attn_lse_ref(q, kc, vc)
    np.testing.assert_allclose(lse, ref_lse, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out, ref_out, rtol=1e-5, atol=1e-5)


def test_merge_partials_identity():
    """Merging hand-split ref partials reproduces the unsplit ref."""
    q, kc, vc = _inputs(seed=9)
    outs, lses = [], []
    for j in range(2):
        o, l = da_ref.decode_attn_lse_ref(q, kc[:, j * 32:(j + 1) * 32],
                                          vc[:, j * 32:(j + 1) * 32])
        outs.append(o)
        lses.append(l)
    out, lse = da_sharded.merge_partials(jnp.stack(outs), jnp.stack(lses))
    ref_out, ref_lse = da_ref.decode_attn_lse_ref(q, kc, vc)
    np.testing.assert_allclose(out, ref_out, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(lse, ref_lse, rtol=1e-6, atol=1e-6)


# ------------------------------------------------------ shard_map path

def test_shard_map_single_axis_degrades_to_unsharded():
    q, kc, vc = _inputs(seed=11)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("model",))
    kv_len = jnp.asarray([S, 40])
    got = da_sharded.decode_attn_shard_map(q, kc, vc, kv_len=kv_len,
                                           mesh=mesh, mode="ref")
    want = da_ops.decode_attn(q, kc, vc, kv_len=kv_len, mode="ref")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_dispatch_without_mesh_uses_static_split():
    q, kc, vc = _inputs(seed=13)
    kv_len = jnp.asarray([50, 33])
    got = da_sharded.dispatch(q, kc, vc, kv_len=kv_len, shards=2,
                              ctx=None, mode="ref")
    want = da_ref.decode_attn_ref(q, kc, vc, kv_len=kv_len)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


_SHARD_MAP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["REPRO_KERNEL_MODE"] = "ref"
import jax, jax.numpy as jnp, numpy as np
from repro.kernels.decode_attn import ops as da_ops
from repro.kernels.decode_attn import sharded as da_sharded

B, S, HQ, HKV, DH = 2, 64, 4, 2, 16
ks = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(ks[0], (B, HQ, DH), jnp.float32)
kc = jax.random.normal(ks[1], (B, S, HKV, DH), jnp.float32)
vc = jax.random.normal(ks[2], (B, S, HKV, DH), jnp.float32)
kv_len = jnp.asarray([S, 23])
mesh = jax.sharding.Mesh(np.array(jax.devices()), ("model",))
got = jax.jit(lambda q, k, v, l: da_sharded.decode_attn_shard_map(
    q, k, v, kv_len=l, mesh=mesh))(q, kc, vc, kv_len)
want = da_ops.decode_attn(q, kc, vc, kv_len=kv_len)
np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           rtol=1e-6, atol=1e-6)
print("SHARD_MAP_OK")

# engine end-to-end over the collective path: 4-way KV-sharded serving
from repro.serve import ServeConfig, ServingEngine, serving_ctx
from repro.configs import get_config, reduced
from repro.models.lm import build_model
import dataclasses
cfg = dataclasses.replace(reduced(get_config("yi-9b")),
                          compute_dtype="float32")
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
prompts = {1: [5, 9, 2], 2: [7, 1, 4, 8, 3]}
def run(shards, ctx):
    eng = ServingEngine(model, params,
                        ServeConfig(slots=2, max_len=32, max_new_tokens=4,
                                    shards=shards), ctx=ctx)
    for uid, p in prompts.items():
        eng.submit(uid, p)
    return eng.run()
ctx = serving_ctx(4)
assert ctx is not None and ctx.tp == 4
compiled = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, secs, fun_name=None, **kw: compiled.append(fun_name)
    if event == "/jax/core/compile/backend_compile_duration" else None)
sharded = run(4, ctx)
# the carry goes back in as it comes out: one executable for every step
assert compiled.count("jit(engine_step)") == 1, compiled
assert sharded == run(1, None)
print("ENGINE_SHARDED_OK")
"""


@pytest.mark.slow
def test_shard_map_multi_device_matches():
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _SHARD_MAP_SCRIPT],
                         capture_output=True, text=True, timeout=560,
                         env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert "SHARD_MAP_OK" in res.stdout, res.stdout + res.stderr
    assert "ENGINE_SHARDED_OK" in res.stdout, res.stdout + res.stderr


# ----------------------------------- ragged kv_len through the batched step

def _small_model():
    from repro.configs import get_config, reduced
    from repro.models.lm import build_model
    cfg = dataclasses.replace(reduced(get_config("yi-9b")),
                              compute_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _teacher_force_ragged(model, params, tokens, lens, shards=1):
    """Engine-style loop: per-row position vector, rows past their
    prompt step a pad token without committing; captures each row's
    logits at its last prompt token."""
    n = tokens.shape[0]
    cache = model.init_cache(n, 16)
    lengths = np.zeros(n, np.int32)
    captured = {}
    kw = {} if shards == 1 else {"shards": shards}
    for t in range(max(lens)):
        toks = np.zeros((n, 1), np.int32)
        adv = [r for r in range(n) if t < lens[r]]
        for r in adv:
            toks[r, 0] = int(tokens[r, t])
        # jnp.array copies: on the CPU jnp.asarray may alias the numpy
        # buffer, and the in-place length bump below would race the
        # still-running asynchronous step
        logits, cache = model.decode_step(params, jnp.asarray(toks), cache,
                                          jnp.array(lengths, jnp.int32),
                                          **kw)
        for r in adv:
            lengths[r] += 1
            if t == lens[r] - 1:
                captured[r] = np.asarray(logits[r], np.float32)
    return captured


def test_ragged_vector_pos_matches_full_forward():
    """Each ragged row's next-token logits from the batched vector-pos
    step must match the full-context forward of that row alone — the
    per-row kv_len masks the other rows' longer histories AND the pad
    writes beyond this row's length."""
    cfg, model, params = _small_model()
    rng = np.random.default_rng(0)
    lens = [5, 9]
    tokens = rng.integers(0, cfg.vocab_size, (2, max(lens)))
    captured = _teacher_force_ragged(model, params, tokens, lens)
    for r, ln in enumerate(lens):
        full = model.logits(params,
                            {"tokens": jnp.asarray(tokens[r:r + 1, :ln])})
        np.testing.assert_allclose(captured[r],
                                   np.asarray(full[0, ln - 1], np.float32),
                                   rtol=2e-3, atol=2e-3)


def test_sharded_batched_step_matches_unsharded():
    """shards=2 through the full model decode step equals shards=1."""
    cfg, model, params = _small_model()
    rng = np.random.default_rng(1)
    lens = [4, 7]
    tokens = rng.integers(0, cfg.vocab_size, (2, max(lens)))
    base = _teacher_force_ragged(model, params, tokens, lens, shards=1)
    split = _teacher_force_ragged(model, params, tokens, lens, shards=2)
    for r in base:
        np.testing.assert_allclose(split[r], base[r], rtol=1e-5, atol=1e-5)
