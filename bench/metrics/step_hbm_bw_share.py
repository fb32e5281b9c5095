"""Least HBM bytes of the window's steps over the window and the chips'
HBM bandwidth: every weight read once at bf16, each advanced row's live
K/V read and new K/V row written (bench.costs.step_bytes)."""
from bench import costs


def read(rec):
    if rec.peaks is None or not rec.steps:
        return None
    nbytes = sum(costs.step_bytes(rec.work, kv) for kv in rec.step_kv())
    return 100.0 * nbytes / rec.window_s / (
        rec.chips * rec.peaks["hbm_bytes_per_s"])
