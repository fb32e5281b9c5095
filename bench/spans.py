"""What the engine's own spans say about the window.

Two sources, both the program's:

- ``engine.stats()["host"]``, running totals of the engine's host time
  kept from its spans (``serve.dispatch``, ``serve.sync``, the gap
  between steps, queue and first-token waits).  :func:`host_delta`
  takes one total's change across the window over a count's.
- The spans themselves in a profiler trace: ``repro.obs.span`` opens a
  ``TraceAnnotation`` named ``serve.*`` on the host plane, on the clock
  of the device's operations.  :func:`stretches` puts each idle stretch
  of each chip inside ``bench.window`` down to the innermost engine span
  that covers most of it, to the loop's wait for clients
  (``bench.mark.idle`` up to the engine's next span), or to neither
  (:data:`OUTSIDE`); :func:`idle_by_label` sums them.
"""
from __future__ import annotations

import bisect

import numpy as np

from bench import trace

ENGINE = "serve."
WAIT = "wait for clients"
OUTSIDE = "outside the engine"
STEP_MARKS = ("decode", "prefill")


def host_delta(rec, total: str, count: str):
    """Change of ``host[total]`` over change of ``host[count]`` between
    the window's edges; None where the engine keeps no such totals or
    the count did not move."""
    a = (rec.stats_open or {}).get("host")
    b = (rec.stats_close or {}).get("host")
    if a is None or b is None:
        return None
    n = b[count] - a[count]
    if n <= 0:
        return None
    return (b[total] - a[total]) / n


def _read(data):
    """(window, engine spans [(start, end, name)], idle marks, step
    marks, {chip: [(start, end)] of its operations})."""
    window, spans, idle, steps, ops = None, [], [], [], {}
    for plane in data.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m:
                if line.name == trace.OPS_LINE:
                    ops[int(m.group(1))] = [
                        (ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events]
                continue
            for ev in line.events:
                name = ev.name
                if name.startswith(ENGINE):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  name))
                elif name == trace.WINDOW:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif name.startswith(trace.MARK):
                    what = name[len(trace.MARK):]
                    if what == "idle":
                        idle.append(ev.start_ns)
                    elif what in STEP_MARKS:
                        steps.append(ev.start_ns)
    if window is None:
        raise ValueError(f"no {trace.WINDOW} annotation in the trace")
    return window, spans, sorted(idle), sorted(steps), ops


def _waits(idle, spans, steps, hi):
    """[(start, end, WAIT)]: each wait for clients, from its mark to the
    engine's next span (or, in a trace without engine spans, the next
    step's mark)."""
    starts = sorted(s for s, _, _ in spans)
    out = []
    for t in idle:
        ends = [hi]
        i = bisect.bisect_right(starts, t)
        if i < len(starts):
            ends.append(starts[i])
        j = bisect.bisect_right(steps, t)
        if j < len(steps):
            ends.append(steps[j])
        out.append((t, min(ends), WAIT))
    return out


def timeline(spans):
    """Cut properly nested intervals [(start, end, label)] into
    consecutive pieces, each labelled by the innermost interval over
    it: (bounds, labels), labels[i] over [bounds[i], bounds[i+1]), None
    where no interval is open."""
    bounds, labels = [], []

    def put(a, b, label):
        if b > a:
            bounds.append(a)
            labels.append(label)

    stack = []                          # [(end, label)], innermost last
    cursor = None
    for s, e, label in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            put(cursor, end, top)
            cursor = end
        if cursor is not None:
            put(cursor, s, stack[-1][1] if stack else None)
        cursor = s
        # a span that outlives its parent (another thread's) is cut there
        stack.append((min(e, stack[-1][0]) if stack else e, label))
    while stack:
        end, top = stack.pop()
        put(cursor, end, top)
        cursor = end
    if cursor is not None:
        bounds.append(cursor)
        labels.append(None)
    return bounds, labels


def _label(bounds, labels, a, b):
    """The label covering most of [a, b); unlabelled time is OUTSIDE."""
    cover: dict = {}
    i = bisect.bisect_right(bounds, a) - 1      # the piece holding a
    t = a
    while t < b:
        end = min(bounds[i + 1] if i + 1 < len(bounds) else b, b)
        label = (labels[i] if i >= 0 else None) or OUTSIDE
        cover[label] = cover.get(label, 0.0) + end - t
        t = end
        i += 1
    return max(cover, key=cover.get)


def _stretches(data):
    window, spans, idle, steps, ops = _read(data)
    lo, hi = window
    bounds, labels = timeline(spans + _waits(idle, spans, steps, hi))
    out = []
    for chip, evs in sorted(ops.items()):
        evs = [e for e in evs if e[0] < hi and e[1] > lo]
        starts = np.clip(np.array([e[0] for e in evs], np.float64), lo, hi)
        ends = np.clip(np.array([e[1] for e in evs], np.float64), lo, hi)
        _, gaps = trace.union(starts, ends, lo, hi)
        for start, length in gaps:
            out.append((chip, start, length,
                        _label(bounds, labels, start, start + length)))
    return len(ops), out


def stretches(data) -> list:
    """Every idle stretch of every chip inside the window, as
    (chip, start_ns, length_ns, label), in order of start per chip."""
    return _stretches(data)[1]


def idle_by_label(data) -> dict:
    """{label: idle seconds inside the window}, averaged over chips; the
    labels are engine span names, :data:`WAIT` and :data:`OUTSIDE`."""
    chips, got = _stretches(data)
    out: dict = {}
    for _, _, length, label in got:
        out[label] = out.get(label, 0.0) + length / 1e9 / max(chips, 1)
    return out
