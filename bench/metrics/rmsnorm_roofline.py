"""rmsnorm's share of its roofline: the chip's least time for the
window's norms (each of the step's calls, of its width, over the
advanced rows; bench.costs.rmsnorm_call) over the device time of the
kernel's operations in the trace, summed over every chip that ran
them."""
from bench import costs, trace


def read(rec):
    if rec.trace is None or rec.peaks is None:
        return None
    device_s, calls = trace.kernel_seconds(rec.trace).get("rmsnorm",
                                                          (0.0, 0))
    if not calls:
        return None
    share = 0.0
    for width, per_step in rec.work["norms"]:
        least = sum(
            costs.least_seconds(*costs.rmsnorm_call(width, len(kv)),
                                rec.peaks)
            for kv in rec.step_kv())
        share += 100.0 * per_step * least / device_s
    return share
