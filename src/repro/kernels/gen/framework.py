"""Codegen variants of the framework kernel families: flash-decode GQA
attention, fused RMSNorm, and the fused AdamW step.

The spec builders live with their families
(``kernels/decode_attn/specs.py``, ``kernels/rmsnorm/specs.py``,
``kernels/adamw/specs.py``) and are shared verbatim by the public
``ops.py`` wrappers and the ``*_gen`` registry rows here.

  * ``decode_attn_gen`` — ONE generated *stride-axis reduction* sweep
    over the KV cache (``b`` a batch grid dim, the sequence axis split
    into D streams): the sweep is reduced with the paired-state
    :class:`~repro.codegen.OnlineSoftmax` combinator, so each block's
    (max, rescaled Σ softmax·V, rescaled Σ w) partial state merges
    numerically-stably across the D merged streams and grid steps and
    K/V are each read exactly once — the single-pass flash-decode the
    two-pass max+sum decomposition used to approximate.  With
    ``with_lse=True`` the combinator's finalize ALSO emits the per-row
    log-sum-exp as a second native output (its own ``Hq``-wide access
    map) — the flash-attention side statistic sharded-attention
    combines rescale with.
  * ``rmsnorm_gen``     — ``full_width`` streaming nest: the body takes
    a per-row mean over the whole vector extent and emits the f32
    inverse-rms row statistic as a native rank-1 SECOND output next to
    the rank-2 normalized matrix (per-output access maps).
  * ``adamw_update_gen`` — one 2-D nest over the §5.1.1-blocked
    flattened parameter writing p′/m′/v′ as three *native* outputs
    (three Pallas store streams, no stacked free axis, no unstack
    copies).  Ref mode evaluates the elementwise body at the tensor's
    NATIVE shape: the re-block reshapes otherwise make XLA recompute
    the shared (m′, v′) staging inside every output fusion — the
    BENCH_PR4 1.133 ``gen_vs_hand`` outlier.
"""
import functools

import jax
import jax.numpy as jnp

from repro.codegen import run_spec
from repro.core import Traffic
from repro.core.striding import StridingConfig
from repro.kernels.adamw import ref as _adamw_ref
from repro.kernels.adamw.ops import _adamw, _blocking as _adamw_blocking
from repro.kernels.adamw.specs import adamw_spec
from repro.kernels.common import example_input as _rand
from repro.kernels.decode_attn import ref as _da_ref
from repro.kernels.decode_attn.specs import decode_spec as _decode_spec
from repro.kernels.gen.polybench import _guarded, _mode, _resolve
from repro.kernels.rmsnorm import ref as _rms_ref
from repro.kernels.rmsnorm.specs import rmsnorm_spec
from repro.registry.base import KernelSpec, register

_S = jax.ShapeDtypeStruct   # specs built on placeholders (resolve, registry)

__all__ = ["decode_attn_gen", "rmsnorm_gen", "adamw_update_gen",
           # family specs re-exported for spec-level consumers
           "adamw_spec", "rmsnorm_spec"]


# --------------------------------------------------------- decode attn

@functools.partial(jax.jit, static_argnames=("hkv", "dh", "config", "mode"))
def _decode_run(q, kc, vc, hkv, dh, config, mode):
    b, hq = q.shape[0], q.shape[1]
    s, e = kc.shape[1], hkv * dh
    kc2, vc2 = kc.reshape(b, s, e), vc.reshape(b, s, e)
    q3 = q.reshape(b, hq, dh)
    out, lse = run_spec(_decode_spec(hkv, dh), (kc2, vc2, q3), config, mode)
    return out.reshape(b, hq, dh).astype(q.dtype), lse.reshape(b, hq)


def decode_attn_gen(q, kc, vc, config=None, mode=None, with_lse=False):
    """One-token GQA attention against a [B, S, Hkv, dh] KV cache,
    generated: a single online-softmax stream-reduction sweep of the
    (flattened) cache — K and V each read once.  ``with_lse=True`` also
    returns the per-(batch, head) f32 log-sum-exp emitted as the
    kernel's native second output."""
    mode = _mode(mode)
    s, hkv, dh = kc.shape[1], kc.shape[2], kc.shape[3]
    traffic = Traffic(rows=s, cols=hkv * dh, dtype=kc.dtype, read_arrays=2)
    e = hkv * dh
    spec = _decode_spec(hkv, dh)(_S((q.shape[0], s, e), kc.dtype),
                                 _S((q.shape[0], s, e), kc.dtype),
                                 _S((q.shape[0], q.shape[1], dh), q.dtype))
    cfg = _resolve("decode_attn_gen", kc, config, mode, s,
                   StridingConfig(4, 1), traffic, spec)
    out, lse = _guarded(
        "decode_attn_gen",
        lambda c, km: _decode_run(q, kc, vc, hkv=hkv, dh=dh, config=c,
                                  mode=km),
        kc, cfg, mode, s, traffic, spec)
    return (out, lse) if with_lse else out


# ------------------------------------------------------------- rmsnorm

@functools.partial(jax.jit, static_argnames=("config", "mode"))
def _rms_run(x, w, eps, config, mode):
    shape = x.shape
    out, inv = run_spec(rmsnorm_spec, (x.reshape(-1, shape[-1]), w, eps),
                        config, mode)
    return out.reshape(shape), inv.reshape(shape[:-1])


def rmsnorm_gen(x, w, eps=1e-6, config=None, mode=None,
                with_inv_rms=False):
    """Fused RMSNorm, generated.  ``with_inv_rms=True`` also returns
    the f32 inverse-rms per row (the kernel's native second output)."""
    mode = _mode(mode)
    t = 1
    for s in x.shape[:-1]:
        t *= s
    traffic = Traffic(rows=max(t, 1), cols=x.shape[-1], dtype=x.dtype,
                      read_arrays=1, write_arrays=1,
                      resident_bytes=x.shape[-1] * 4)
    spec = rmsnorm_spec(_S((max(t, 1), x.shape[-1]), x.dtype), w)
    cfg = _resolve("rmsnorm_gen", x, config, mode, max(t, 1),
                   StridingConfig(4, 1), traffic, spec)
    out, inv = _guarded(
        "rmsnorm_gen",
        lambda c, km: _rms_run(x, w, eps, config=c, mode=km),
        x, cfg, mode, max(t, 1), traffic, spec)
    return (out, inv) if with_inv_rms else out


# --------------------------------------------------------------- adamw

_ADAMW_COLS = 512   # §5.1.1 blocking of the flattened tensor (ops._COLS)
_ADAMW_DEFAULT = StridingConfig(2, 2)


def adamw_update_gen(p, g, m, v, lr, b1=0.9, b2=0.999, eps=1e-8, wd=0.0,
                     bc1=1.0, bc2=1.0, config=None, mode=None):
    """Fused-AdamW step (generated): the flattened tensor is §5.1.1
    loop-blocked into [rows, 512] tiles and one spec writes (p', m', v')
    as three native output refs.  Returns (p', m', v')."""
    mode = _mode(mode)
    n = 1
    for s in p.shape:
        n *= s
    rows, cols = _adamw_blocking(max(n, 1))
    # rows=None: pad+crop inside the emitter makes any D valid, no
    # divisibility clamp against the tile count
    traffic = Traffic(rows=rows, cols=cols, dtype=p.dtype,
                      read_arrays=4, write_arrays=3)
    cfg = _resolve("adamw_update_gen", p, config, mode, None,
                   _ADAMW_DEFAULT, traffic)
    return _guarded(
        "adamw_update_gen",
        lambda c, km: _adamw(p, g, m, v, lr, b1, b2, eps, wd, bc1, bc2,
                             config=c, mode=km),
        p, cfg, mode, None, traffic)


# ---------------------------------------------------------- registry

_DA_SIZES = {"b": 1, "s": 256, "hq": 4, "hkv": 2, "dh": 64}
_DA_ALIASED = {"b": 1, "s": 512, "hq": 4, "hkv": 2, "dh": 64}


def _da_inputs(s, dt):
    return (_rand((s["b"], s["hq"], s["dh"]), 0, dt),
            _rand((s["b"], s["s"], s["hkv"], s["dh"]), 1, dt),
            _rand((s["b"], s["s"], s["hkv"], s["dh"]), 2, dt))


register(KernelSpec(
    name="decode_attn_gen", family="gen", fn=decode_attn_gen,
    make_inputs=_da_inputs,
    # side outputs ride the conformance matrix: the lse row statistic
    # is checked against its oracle at every (D, P) point in both legs
    run=lambda inp, cfg, mode: decode_attn_gen(inp[0], inp[1], inp[2],
                                               config=cfg, mode=mode,
                                               with_lse=True),
    ref=lambda inp, cfg: _da_ref.decode_attn_lse_ref(inp[0], inp[1],
                                                     inp[2]),
    default_sizes=_DA_SIZES, aliased_sizes=_DA_ALIASED,
    traffic=lambda s, dt: Traffic(rows=s["s"], cols=s["hkv"] * s["dh"],
                                  dtype=dt, read_arrays=2),
    # decode_spec is a per-(Hkv, dh) builder factory: apply it to the
    # flattened-cache placeholders the wrapper reshapes to
    traversal=lambda s, dt: _decode_spec(s["hkv"], s["dh"])(
        _S((s["b"], s["s"], s["hkv"] * s["dh"]), dt),
        _S((s["b"], s["s"], s["hkv"] * s["dh"]), dt),
        _S((s["b"], s["hq"], s["dh"]), dt)),
    cache_shape=lambda s: (s["b"], s["s"], s["hkv"], s["dh"]),
    bench_sizes={"b": 8, "s": 8192, "hq": 32, "hkv": 8, "dh": 128},
    rtol=2e-5, atol=2e-5, tags=("framework", "gen")))

register(KernelSpec(
    name="rmsnorm_gen", family="gen", fn=rmsnorm_gen,
    make_inputs=lambda s, dt: (_rand((s["t"], s["dm"]), 0, dt),
                               _rand((s["dm"],), 1, dt)),
    run=lambda inp, cfg, mode: rmsnorm_gen(inp[0], inp[1], config=cfg,
                                           mode=mode, with_inv_rms=True),
    ref=lambda inp, cfg: _rms_ref.rmsnorm_stats_ref(inp[0], inp[1]),
    default_sizes={"t": 32, "dm": 256}, aliased_sizes={"t": 32, "dm": 128},
    traffic=lambda s, dt: Traffic(rows=s["t"], cols=s["dm"], dtype=dt,
                                  read_arrays=1, write_arrays=1,
                                  resident_bytes=s["dm"] * 4),
    traversal=lambda s, dt: rmsnorm_spec(_S((s["t"], s["dm"]), dt),
                                         _S((s["dm"],), dt)),
    cache_shape=lambda s: (s["t"], s["dm"]),
    bench_sizes={"t": 4096, "dm": 4096},
    rtol=1e-5, atol=1e-5, tags=("framework", "gen")))

_ADAMW_HYPER = dict(lr=1e-3, b1=0.9, b2=0.999, eps=1e-8, wd=0.01,
                    bc1=0.5, bc2=0.25)


def _adamw_inputs(s, dt):
    shape = (s["rows"], s["cols"])
    return (_rand(shape, 0, dt), _rand(shape, 1, dt), _rand(shape, 2, dt),
            jnp.abs(_rand(shape, 3)))


register(KernelSpec(
    name="adamw_update_gen", family="gen", fn=adamw_update_gen,
    make_inputs=_adamw_inputs,
    run=lambda inp, cfg, mode: adamw_update_gen(*inp, config=cfg,
                                                mode=mode, **_ADAMW_HYPER),
    ref=lambda inp, cfg: _adamw_ref.adamw_ref(*inp, **_ADAMW_HYPER),
    default_sizes={"rows": 60, "cols": 100},
    aliased_sizes={"rows": 128, "cols": 128},
    # 4 read + 3 write arrays per stride at the nominal 1-D blocking
    traffic=lambda s, dt: Traffic(
        rows=max(s["rows"] * s["cols"] // 1024, 4), cols=1024, dtype=dt,
        read_arrays=4, write_arrays=3),
    # the spec the wrapper actually lowers: the flattened tensor at its
    # §5.1.1 re-blocked [rows, 512] shape
    traversal=lambda s, dt: adamw_spec(
        *(_S(_adamw_blocking(max(s["rows"] * s["cols"], 1)), dt)
          for _ in range(4))),
    cache_shape=lambda s: (s["rows"], s["cols"]),
    bench_sizes={"rows": 4096, "cols": 1024},
    rtol=1e-5, atol=1e-6, tags=("framework", "gen")))
