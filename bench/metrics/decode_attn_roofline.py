"""decode_attn's share of its roofline: the chip's least time for the
window's decode attention (one call per attention layer and step, over the
advanced rows' live context; bench.costs.decode_attn_call) over the
device time of the kernel's operations in the trace, summed over every
chip that ran them."""
from bench import costs, trace


def read(rec):
    if rec.trace is None or rec.peaks is None:
        return None
    device_s, calls = trace.kernel_seconds(rec.trace).get("decode_attn",
                                                          (0.0, 0))
    if not calls:
        return None
    least = sum(
        costs.least_seconds(*costs.decode_attn_call(rec.work, kv), rec.peaks)
        for kv in rec.step_kv())
    return 100.0 * rec.work["attn_layers"] * least / device_s
