"""Matrix-vector kernels (paper mxv / Listing 1 mxv_t)."""
from repro.core import Traffic
from repro.kernels.common import example_input as _rand
from repro.kernels.mxv import ref as _ref
from repro.kernels.mxv.ops import mxv, mxv_t
from repro.registry.base import KernelSpec, register

__all__ = ["mxv", "mxv_t"]

_SIZES = {"m": 48, "n": 256}
_ALIASED = {"m": 32, "n": 128}   # (32/4)*128*4 B = 4 KiB spacing (§4.5)


def _shape(s):
    return (s["m"], s["n"])


register(KernelSpec(
    name="mxv", family="mxv", fn=mxv,
    make_inputs=lambda s, dt: (_rand(_shape(s), 0, dt),
                               _rand((s["n"],), 1, dt)),
    run=lambda inp, cfg, mode: mxv(inp[0], inp[1], config=cfg, mode=mode),
    ref=lambda inp, cfg: _ref.mxv_ref(inp[0], inp[1]),
    default_sizes=_SIZES, aliased_sizes=_ALIASED,
    traffic=lambda s, dt: Traffic(rows=s["m"], cols=s["n"], dtype=dt,
                                  read_arrays=1),
    cache_shape=_shape))

register(KernelSpec(
    name="mxv_t", family="mxv", fn=mxv_t,
    make_inputs=lambda s, dt: (_rand(_shape(s), 0, dt),
                               _rand((s["m"],), 1, dt)),
    run=lambda inp, cfg, mode: mxv_t(inp[0], inp[1], config=cfg, mode=mode),
    ref=lambda inp, cfg: _ref.mxv_t_ref(inp[0], inp[1]),
    default_sizes=_SIZES, aliased_sizes=_ALIASED,
    traffic=lambda s, dt: Traffic(rows=s["m"], cols=s["n"], dtype=dt,
                                  read_arrays=2),
    cache_shape=_shape))
