"""Fused RMSNorm (framework kernel)."""
from repro.core import Traffic as _Traffic
from repro.kernels.common import example_input as _rand
from repro.kernels.rmsnorm import ref as _ref
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.registry.base import KernelSpec, register

__all__ = ["rmsnorm"]

_SIZES = {"t": 32, "dm": 256}
_ALIASED = {"t": 32, "dm": 128}   # (32/4)*128*4 B = 4 KiB spacing (§4.5)

register(KernelSpec(
    name="rmsnorm", family="rmsnorm", fn=rmsnorm,
    make_inputs=lambda s, dt: (_rand((s["t"], s["dm"]), 0, dt),
                               _rand((s["dm"],), 1, dt)),
    run=lambda inp, cfg, mode: rmsnorm(inp[0], inp[1], config=cfg,
                                       mode=mode),
    ref=lambda inp, cfg: _ref.rmsnorm_ref(inp[0], inp[1]),
    default_sizes=_SIZES, aliased_sizes=_ALIASED,
    traffic=lambda s, dt: _Traffic(rows=s["t"], cols=s["dm"], dtype=dt,
                                   read_arrays=1, write_arrays=1,
                                   resident_bytes=s["dm"] * 4),
    cache_shape=lambda s: (s["t"], s["dm"]),
    rtol=1e-5, atol=1e-5))
