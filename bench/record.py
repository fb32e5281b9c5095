"""What one run leaves for the metric readers (``bench/metrics``).

A reader is a module with ``read(rec) -> float | None``; None means it
found nothing to read, and the run then leaves that metric out.
"""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass
class Record:
    work: dict                  # the reference's counts (bench.costs)
    chips: int
    peaks: Optional[dict]       # bench.hw, None off the chip
    window_s: float
    setup_s: float
    steps: list                 # (t, phase, slots, pos, latency_s)
    reqs: list                  # bench.loop.Req, every request sent
    stats_open: dict            # engine.stats() at the window's edges
    stats_close: dict
    trace: Optional[object] = None   # bench.trace.Trace of the window

    def step_kv(self):
        """Per step in the window, the kv_len of each advanced row."""
        for _, _, _, pos, _ in self.steps:
            yield [p + 1 for p in pos]
