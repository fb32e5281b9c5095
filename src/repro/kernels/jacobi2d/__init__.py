"""2D Jacobi 5-point stencil sweep (PolyBench jacobi-2d)."""
from repro.core import Traffic as _Traffic
from repro.kernels.common import example_input as _rand
from repro.kernels.jacobi2d import ref as _ref
from repro.kernels.jacobi2d.ops import jacobi2d
from repro.registry.base import KernelSpec, register

__all__ = ["jacobi2d"]

_SIZES = {"h": 34, "w": 130}
_ALIASED = {"h": 34, "w": 128}   # pow-2 input row length → aliased streams

register(KernelSpec(
    name="jacobi2d", family="jacobi2d", fn=jacobi2d,
    make_inputs=lambda s, dt: (_rand((s["h"], s["w"]), 0, dt),),
    run=lambda inp, cfg, mode: jacobi2d(inp[0], config=cfg, mode=mode),
    ref=lambda inp, cfg: _ref.jacobi2d_ref(inp[0]),
    default_sizes=_SIZES, aliased_sizes=_ALIASED,
    traffic=lambda s, dt: _Traffic(rows=s["h"] - 2, cols=s["w"], dtype=dt,
                                   read_arrays=3, write_arrays=1),
    cache_shape=lambda s: (s["h"], s["w"]),
    rtol=1e-5, atol=1e-5))
