"""Per-chip hardware peaks, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" — per chip 197 TFLOP/s
bf16, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect (four 50 GB/s links).  A device missing from the table is
an error, never a default: a roofline share against the wrong peaks is
a wrong number."""
import dataclasses


@dataclasses.dataclass(frozen=True)
class HwSpec:
    name: str
    peak_flops_bf16: float   # FLOP/s per chip
    hbm_bw: float            # bytes/s per chip
    ici_link_bw: float       # bytes/s per link
    hbm_bytes: int           # capacity per chip


TPU_V5E_HW = HwSpec(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    hbm_bw=819e9,
    ici_link_bw=50e9,
    hbm_bytes=16 * 1024 ** 3,
)

HW_BY_KIND = {"TPU v5 lite": TPU_V5E_HW}


def hw_for(device_kind: str) -> HwSpec:
    """Peaks of the chip JAX reports as ``device_kind``; raises KeyError
    for a kind the table does not hold."""
    try:
        return HW_BY_KIND[device_kind]
    except KeyError:
        raise KeyError(f"no hardware peaks for device kind {device_kind!r} "
                       f"(known: {sorted(HW_BY_KIND)})") from None
