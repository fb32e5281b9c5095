"""The hot-path kernels compile for a TPU v5e at yi-9b widths.

Interpret mode runs a Pallas kernel body in Python and accepts block
shapes the chip's compiler (Mosaic) refuses, so these tests compile each
kernel ahead of time for one chip of a described ``v5e:2x2`` topology —
no chip needed — and check that the compiled program holds the kernel
(``tpu_custom_call``) rather than an XLA fallback.  The topology is
described inside a fixture, never at import: only one process may load
the TPU library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.adamw import ops as adamw_ops
from repro.kernels.decode_attn import ops as decode_ops
from repro.kernels.rmsnorm import ops as rmsnorm_ops

# yi-9b widths: d_model 4096, 32 query / 4 KV heads of 128, d_ff 11008;
# serving at 8 slots of 2048 positions
D_MODEL, HQ, HKV, DH, D_FF = 4096, 32, 4, 128, 11008
SLOTS, MAX_LEN = 8, 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure means no TPU lib
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile written to the persistent cache cannot be read back
    # without a chip, so keep the cache off around these compiles
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("shape", [(2048, D_MODEL), (SLOTS, 1, D_MODEL),
                                   (1, 159, D_MODEL)],
                         ids=["prefill", "decode", "odd_rows"])
def test_rmsnorm_compiles_for_v5e(one_chip, shape):
    """Every layer's norm, at the resolver's config: prefill rows, the
    8 decode rows (D clamps to 1) and a row count no D divides."""
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((D_MODEL,), jnp.bfloat16, sharding=one_chip)
    text = _compiled_text(
        lambda x, w: rmsnorm_ops.rmsnorm(x, w, 1e-5, mode="pallas"), x, w)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("masked", [True, False],
                         ids=["masked", "full"])
@pytest.mark.parametrize("seq", [MAX_LEN, 1000], ids=["tiled", "odd_len"])
def test_decode_attn_compiles_for_v5e(one_chip, masked, seq):
    """Every decode step's attention; the models' loop passes a traced
    per-row kv_len, which selects the masked spec.  A cache length no D
    splits into whole lane tiles is padded to one."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    q = sds((SLOTS, HQ, DH), jnp.bfloat16)
    kc = sds((SLOTS, seq, HKV, DH), jnp.bfloat16)
    kv_len = sds((SLOTS,), jnp.int32)
    if masked:
        text = _compiled_text(
            lambda q, k, v, n: decode_ops.decode_attn(q, k, v, kv_len=n,
                                                      mode="pallas"),
            q, kc, kc, kv_len)
    else:
        text = _compiled_text(
            lambda q, k, v: decode_ops.decode_attn(q, k, v, mode="pallas"),
            q, kc, kc)
    assert "tpu_custom_call" in text


def test_kernels_keep_their_names_without_wrappers(one_chip):
    """A device trace finds each kernel by its custom call's name.  The
    kernel names itself (``pallas_call(name=...)``, the spec's name), so
    the name survives calling it without the jitted wrapper that used to
    lend it one."""
    import re

    from repro.core.striding import StridingConfig

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    cfg = StridingConfig(1, 1)
    norm = rmsnorm_ops._rmsnorm.__wrapped__
    attn = decode_ops._decode_attn_masked.__wrapped__
    text = _compiled_text(
        lambda x, w, q, k, n: (norm(x, w, 1e-5, cfg, "pallas"),
                               attn(q, k, k, n, cfg, "pallas")),
        sds((SLOTS, 1, D_MODEL), jnp.bfloat16), sds((D_MODEL,), jnp.bfloat16),
        sds((SLOTS, HQ, DH), jnp.bfloat16),
        sds((SLOTS, MAX_LEN, HKV, DH), jnp.bfloat16),
        sds((SLOTS,), jnp.int32))
    names = sorted(re.findall(r"^\s*(?:ROOT )?%(\S+) = .*tpu_custom_call",
                              text, re.M))
    assert len(names) == 2
    assert names[0].startswith("decode_attn") and \
        names[1].startswith("rmsnorm")


def test_sharded_decode_step_compiles_for_v5e_2x2(topo, one_chip,
                                                  monkeypatch):
    """The whole KV-sharded serving step (``shards=4``) over the four
    chips, at yi-9b widths with one layer: every Pallas kernel sits
    inside ``shard_map`` (XLA cannot partition one), attention takes the
    collective flash-decode path, the cache stays split, and the
    projections and FFN stay tensor-parallel: each chip does less work
    than the one-chip step."""
    import dataclasses

    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import obs
    from repro.configs import get_config
    from repro.models.common import MeshCtx
    from repro.models.lm import build_model
    from repro.serve.engine import _decode_fn

    monkeypatch.setenv("REPRO_KERNEL_MODE", "pallas")   # the model's ops
    mesh = jax.sharding.Mesh(np.array(topo.devices[:4]), ("model",))
    model = build_model(dataclasses.replace(get_config("yi-9b"),
                                            n_layers=1))

    def place(tree, spec):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec)), tree)
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)), P())
    cache = place(jax.eval_shape(lambda: model.init_cache(SLOTS, MAX_LEN)),
                  P(None, None, "model"))
    toks = place(jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32), P())
    pos = place(jax.ShapeDtypeStruct((SLOTS,), jnp.int32), P())
    step = _decode_fn(model, MeshCtx(mesh=mesh, dp_axes=(),
                                     tp_axis="model"), 4)
    with obs.collect() as col:
        compiled = step.lower(params, toks, cache, pos).compile()
    assert {e.attrs["strategy"]
            for e in col.named("decode_attn.dispatch")} == {"shard_map"}
    assert "tpu_custom_call" in compiled.as_text()
    assert {s.spec for s in jax.tree.leaves(compiled.output_shardings[1])} \
        == {P(None, None, "model")}

    def one(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    single = _decode_fn(model, None, 1).lower(
        one(params), one(toks), one(cache), one(pos)).compile()

    def flops(c):
        ca = c.cost_analysis()
        return (ca[0] if isinstance(ca, list) else ca)["flops"]
    # the unsharded head keeps this well above a quarter (1 layer)
    assert flops(compiled) < 0.8 * flops(single)


def test_depth_cut_fits_one_v5e(one_chip, monkeypatch):
    """The depth cut ``chip_smoke.py`` serves at (8 of yi-9b's 48 layers,
    8 slots of 2048 positions): its decode step fits one v5e's 16 GiB
    with room for the logits check's own cache and program, and 12
    layers would not leave that room."""
    import chip_smoke
    from repro.launch import serve
    monkeypatch.setenv("REPRO_KERNEL_MODE", "pallas")

    def total(layers):
        args = serve.parser().parse_args(
            ["--layers", str(layers), "--slots", str(SLOTS),
             "--max-len", str(MAX_LEN)])
        return serve.decode_step_memory(args, one_chip)["total"] / 2**30
    room = 4.0                                   # GiB
    assert chip_smoke.LAYERS == 8
    assert total(8) < 16 - room < total(12)


def test_adamw_compiles_for_v5e(one_chip):
    """Every optimizer step, on the largest yi-9b weight (the FFN)."""
    p = jax.ShapeDtypeStruct((D_MODEL, D_FF), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda p, g, m, v: adamw_ops.adamw_update(
            p, g, m, v, 1e-3, 0.9, 0.95, 1e-8, 0.1, 0.5, 0.5, mode="pallas"),
        p, p, p, p)
    assert "tpu_custom_call" in text


def test_peaks_keyed_by_device_kind(topo):
    """The described chip's kind has peaks; an unknown kind is an error,
    never a silent v5e."""
    from repro.roofline.hw import TPU_V5E_HW, hw_for
    assert hw_for(topo.devices[0].device_kind) is TPU_V5E_HW
    with pytest.raises(KeyError, match="no hardware peaks"):
        hw_for("TPU v99")
