"""Fused BiCG kernel (paper Table 1, PolyBench bicg)."""
from repro.core import Traffic
from repro.kernels.bicg import ref as _ref
from repro.kernels.bicg.ops import bicg
from repro.kernels.common import example_input as _rand
from repro.registry.base import KernelSpec, register

__all__ = ["bicg"]

_SIZES = {"m": 48, "n": 256}
_ALIASED = {"m": 32, "n": 128}   # 4 KiB inter-stream spacing (§4.5)

register(KernelSpec(
    name="bicg", family="bicg", fn=bicg,
    make_inputs=lambda s, dt: (_rand((s["m"], s["n"]), 0, dt),
                               _rand((s["m"],), 1, dt),
                               _rand((s["n"],), 2, dt)),
    run=lambda inp, cfg, mode: bicg(inp[0], inp[1], inp[2], config=cfg,
                                    mode=mode),
    ref=lambda inp, cfg: _ref.bicg_ref(inp[0], inp[1], inp[2]),
    default_sizes=_SIZES, aliased_sizes=_ALIASED,
    traffic=lambda s, dt: Traffic(rows=s["m"], cols=s["n"], dtype=dt,
                                  read_arrays=2),
    cache_shape=lambda s: (s["m"], s["n"])))
