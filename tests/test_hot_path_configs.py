"""The (D, P) the chip cells' two Pallas kernels resolve to.

The benchmark's serving cells run yi-9b and chatglm3-6b at 8 slots of
2048 positions, and every decode step calls ``decode_attn`` once per
layer and ``rmsnorm`` around every sub-layer.  ``decode_attn_roofline``
and ``rmsnorm_roofline`` measure those kernels at the configs pinned
here.  They are resolved as on the chip: ``pallas`` mode, no tune cache,
a fresh plan memo, the planner's own DMA model.  The decode step is
traced at the configs' published widths (one layer: depth does not
change a kernel's shapes), so the shapes are the call sites' own.  A
change to the planner, the DMA model or the resolver that moves one of
these configs moves the chip numbers, and must say so.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro import analysis
from repro.configs import chatglm3_6b, yi_9b
from repro.kernels import common
from repro.models.lm import build_model
from repro.registry import tunecache

SLOTS, MAX_LEN = 8, 2048

# (config, shards, kernel, shape at the call site, (D, P))
CASES = {
    "decode_attn-yi-9b": (yi_9b, 1, "decode_attn", (8, 2048, 4, 128), (4, 8)),
    "decode_attn-chatglm3-6b": (chatglm3_6b, 1, "decode_attn",
                                (8, 2048, 2, 128), (8, 4)),
    "decode_attn-yi-9b-shards4": (yi_9b, 4, "decode_attn",
                                  (8, 512, 4, 128), (4, 2)),
    "decode_attn-chatglm3-6b-shards4": (chatglm3_6b, 4, "decode_attn",
                                        (8, 512, 2, 128), (4, 2)),
    "rmsnorm": (yi_9b, 1, "rmsnorm", (8, 1, 4096), (1, 1)),
}


@pytest.fixture
def resolved(tmp_path, monkeypatch):
    """Trace one decode step and return ``(kernel, shape, dtype, spec,
    config)`` for every config the kernel wrappers resolved in it."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "pallas")
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tune.json"))
    monkeypatch.delenv("REPRO_DMA_DESCRIPTOR_NS", raising=False)
    tunecache.reset_default_cache()
    common.reset_plan_memo()
    calls = []
    real = common.resolve_config

    def spy(kernel, shape, dtype, *args, **kwargs):
        cfg = real(kernel, shape, dtype, *args, **kwargs)
        calls.append((kernel, tuple(shape), jnp.dtype(dtype),
                      kwargs.get("spec"), cfg))
        return cfg
    monkeypatch.setattr(common, "resolve_config", spy)

    def trace(config_module, shards: int):
        model = build_model(dataclasses.replace(config_module.CONFIG,
                                                n_layers=1))
        params = jax.eval_shape(lambda: model.serving_params(
            jax.eval_shape(model.init, jax.random.PRNGKey(0))))
        cache = jax.eval_shape(lambda: model.init_cache(SLOTS, MAX_LEN))
        jax.eval_shape(
            lambda p, c, t, pos: model.decode_step(p, t, c, pos,
                                                   shards=shards),
            params, cache, jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32),
            jax.ShapeDtypeStruct((SLOTS,), jnp.int32))
        return list(calls)
    yield trace
    tunecache.reset_default_cache()
    common.reset_plan_memo()


def _calls(resolved, case):
    config_module, shards, kernel, shape, _ = CASES[case]
    calls = [c for c in resolved(config_module, shards) if c[0] == kernel]
    assert calls, f"the decode step resolved no {kernel} config"
    assert {c[1] for c in calls} == {shape}
    assert {c[2] for c in calls} == {jnp.dtype(jnp.bfloat16)}
    return calls


@pytest.mark.parametrize("case", list(CASES))
def test_hot_path_kernel_resolves_to_pinned_config(resolved, case):
    d, p = CASES[case][4]
    for *_, cfg in _calls(resolved, case):
        assert (cfg.stride_unroll, cfg.portion_unroll) == (d, p)
        assert cfg.lookahead == 2
        assert cfg.arrangement == "grouped"
        assert cfg.block_rows == 0


@pytest.mark.parametrize("case", list(CASES))
def test_hot_path_config_passes_static_check(resolved, case):
    """On the chip a rejected config would be a ``kernel.fallback``,
    which the benchmark refuses."""
    for kernel, _, _, spec, cfg in _calls(resolved, case):
        assert spec is not None
        errors = [f for f in analysis.check(spec, cfg)
                  if f.severity == "error"]
        assert not errors, f"{kernel} at {cfg}: {errors}"
