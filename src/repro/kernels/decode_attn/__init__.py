"""Multi-strided flash-decode GQA attention (framework kernel)."""
from repro.core import Traffic
from repro.kernels.common import example_input as _rand
from repro.kernels.decode_attn import ref as _ref
from repro.kernels.decode_attn.ops import decode_attn
from repro.registry.base import KernelSpec, register

__all__ = ["decode_attn"]

_SIZES = {"b": 1, "s": 256, "hq": 4, "hkv": 2, "dh": 64}
_ALIASED = {"b": 1, "s": 512, "hq": 4, "hkv": 2, "dh": 64}


def _inputs(s, dt):
    return (_rand((s["b"], s["hq"], s["dh"]), 0, dt),
            _rand((s["b"], s["s"], s["hkv"], s["dh"]), 1, dt),
            _rand((s["b"], s["s"], s["hkv"], s["dh"]), 2, dt))


register(KernelSpec(
    name="decode_attn", family="decode_attn", fn=decode_attn,
    make_inputs=_inputs,
    run=lambda inp, cfg, mode: decode_attn(inp[0], inp[1], inp[2],
                                           config=cfg, mode=mode),
    ref=lambda inp, cfg: _ref.decode_attn_ref(inp[0], inp[1], inp[2]),
    default_sizes=_SIZES, aliased_sizes=_ALIASED,
    traffic=lambda s, dt: Traffic(rows=s["s"], cols=s["hkv"] * s["dh"],
                                  dtype=dt, read_arrays=2),
    cache_shape=lambda s: (s["b"], s["s"], s["hkv"], s["dh"]),
    rtol=2e-5, atol=2e-5))
