"""Model FLOPs of the rows the window's steps advanced, over the window
and the chips' bf16 peak: two FLOPs per weight of every projection, FFN
matrix and the head per row, and attention over each row's live
context (bench.costs.step_flops)."""
from bench import costs


def read(rec):
    if rec.peaks is None or not rec.steps:
        return None
    flops = sum(costs.step_flops(rec.work, kv) for kv in rec.step_kv())
    return 100.0 * flops / rec.window_s / (rec.chips
                                           * rec.peaks["flops_bf16"])
