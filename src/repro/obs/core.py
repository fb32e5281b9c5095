"""Structured-telemetry core: events, counters, spans, and collectors.

The paper's argument is *measured* effective bandwidth (§4–§6); this
module is the measurement substrate for the repro itself.  Three record
kinds flow through one ``Event`` type:

  * ``event``   — a point-in-time fact with key/value attributes
                  (e.g. one config resolution, one tune trial);
  * ``counter`` — a named increment (cache hits, fallbacks);
  * ``span``    — a timed region: its duration, wall-clock start and
                  enclosing span are stamped when the region exits.

Emission is routed to the installed *collector*.  When none is
installed (the default — ``REPRO_OBS`` unset) every emit function
returns after a single ``is None`` check, so instrumented hot paths
(op dispatch, per-token decode) pay no measurable cost.  A span also
opens a ``jax.profiler.TraceAnnotation`` while a profiler session
records, so the same regions show in a device trace on its clock; with
no session and no collector a span costs a couple of microseconds (its
clock reads, the per-thread span stack, two checks).  Two collectors
ship: :class:`MemoryCollector` (tests, programmatic inspection) and the
JSONL file sink in :mod:`repro.obs.sinks`.

This module imports nothing from the rest of ``repro`` so any layer
(core, registry, kernels, serve) can instrument without an
import cycle.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Any, Callable, Iterator, Optional

__all__ = [
    "Event", "MemoryCollector", "enabled", "active_collector",
    "event", "counter", "span", "install", "uninstall", "collect",
]


@dataclasses.dataclass(frozen=True)
class Event:
    """One telemetry record (point event, counter increment, or span)."""

    kind: str                      # "event" | "counter" | "span"
    name: str                      # dotted event name, e.g. "tune.trial"
    attrs: dict[str, Any]
    value: float = 1.0             # counter increment / span duration_s
    ts: float = 0.0                # wall-clock seconds (time.time)
    start: float = 0.0             # span: wall-clock seconds at entry
    parent: Optional[str] = None   # span: name of the enclosing span

    def to_dict(self) -> dict[str, Any]:
        return {"kind": self.kind, "name": self.name, "value": self.value,
                "ts": self.ts, "start": self.start, "parent": self.parent,
                "attrs": dict(self.attrs)}


class MemoryCollector:
    """In-memory event store for tests and programmatic inspection."""

    def __init__(self) -> None:
        self.events: list[Event] = []
        self._lock = threading.Lock()

    def record(self, ev: Event) -> None:
        with self._lock:
            self.events.append(ev)

    # ------------------------------------------------------------ queries
    def named(self, name: str) -> list[Event]:
        """All records with an exact dotted name, oldest first."""
        return [e for e in self.events if e.name == name]

    def counters(self) -> dict[str, float]:
        """{counter name: summed increments} over everything recorded."""
        out: dict[str, float] = {}
        for e in self.events:
            if e.kind == "counter":
                out[e.name] = out.get(e.name, 0.0) + e.value
        return out

    def counter_value(self, name: str) -> float:
        return self.counters().get(name, 0.0)

    def clear(self) -> None:
        with self._lock:
            self.events.clear()

    def close(self) -> None:   # collector protocol (sinks flush files)
        pass


# The installed collector.  ``None`` means disabled: the emit functions
# below return immediately, which is the near-zero-overhead contract the
# hot paths (resolve_config, per-token decode) rely on.
_collector: Optional[Any] = None
_install_lock = threading.Lock()


def enabled() -> bool:
    """True when a collector is installed (telemetry flows somewhere)."""
    return _collector is not None


def active_collector() -> Optional[Any]:
    """The installed collector, or None when telemetry is disabled."""
    return _collector


def install(collector: Any) -> None:
    """Install a collector (anything with ``record(Event)``)."""
    global _collector
    with _install_lock:
        prev = _collector
        _collector = collector
        if prev is not None and prev is not collector:
            close = getattr(prev, "close", None)
            if close:
                close()


def uninstall() -> None:
    """Remove the installed collector; emission becomes a no-op again."""
    global _collector
    with _install_lock:
        prev, _collector = _collector, None
        if prev is not None:
            close = getattr(prev, "close", None)
            if close:
                close()


@contextlib.contextmanager
def collect() -> Iterator[MemoryCollector]:
    """Scoped MemoryCollector: install on entry, restore prior on exit.

    The test-suite idiom::

        with obs.collect() as col:
            K.mxv(a, x)
        assert col.named("kernel.resolve")
    """
    global _collector
    with _install_lock:
        prev = _collector
        col = MemoryCollector()
        _collector = col
    try:
        yield col
    finally:
        with _install_lock:
            _collector = prev


# ------------------------------------------------------------- emission

def event(name: str, **attrs: Any) -> None:
    """Record a point event; no-op (one None check) when disabled."""
    c = _collector
    if c is None:
        return
    c.record(Event("event", name, attrs, 1.0, time.time()))


def counter(name: str, value: float = 1.0, **attrs: Any) -> None:
    """Record a counter increment; no-op when disabled."""
    c = _collector
    if c is None:
        return
    c.record(Event("counter", name, attrs, value, time.time()))


_SCALARS = (str, int, float, bool)


def _annotation_args(attrs: dict) -> dict:
    """The attributes a profiler annotation can carry: scalars as they
    are, a list or tuple joined by spaces (the annotation's own encoding
    separates arguments by commas), nothing else."""
    out = {}
    for k, v in attrs.items():
        if isinstance(v, _SCALARS):
            out[k] = v
        elif isinstance(v, (list, tuple)):
            out[k] = " ".join(map(str, v))
    return out


# jax.profiler.TraceAnnotation, imported at the first span: this module
# stays importable (and cheap to import) without jax
_Annotation: Optional[Any] = None


def _annotation_class() -> Any:
    global _Annotation
    if _Annotation is None:
        from jax.profiler import TraceAnnotation
        _Annotation = TraceAnnotation
    return _Annotation


class _Stack(threading.local):
    """The open spans of the calling thread, innermost last."""

    def __init__(self) -> None:
        self.spans: list = []


_open = _Stack()


class Span:
    """A timed region; see :func:`span`.

    After exit it keeps its times (``time.perf_counter`` seconds):
    ``start``, ``end``, ``duration_s``, and ``self_s``, the duration
    less the part that spans opened inside it covered."""

    __slots__ = ("name", "attrs", "emit", "tally", "parent", "start", "end",
                 "child_s", "_ann")

    def __init__(self, name: str, attrs: dict, emit: bool,
                 tally: Optional[dict]):
        self.name, self.attrs, self.emit, self.tally = name, attrs, emit, tally
        self.parent: Optional[str] = None
        self.start = self.end = 0.0
        self.child_s = 0.0
        self._ann = None

    def set(self, **attrs: Any) -> None:
        """Attach attributes discovered inside the region."""
        self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**_annotation_args(attrs))

    @property
    def duration_s(self) -> float:
        return self.end - self.start

    @property
    def self_s(self) -> float:
        return self.end - self.start - self.child_s

    def __enter__(self) -> "Span":
        ann = _annotation_class()
        if ann.is_enabled():          # a profiler session is recording
            self._ann = ann(self.name, **_annotation_args(self.attrs))
            self._ann.__enter__()
        stack = _open.spans
        if stack:
            self.parent = stack[-1].name
        stack.append(self)
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.end = end = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        stack = _open.spans
        stack.pop()
        dur = end - self.start
        if stack:
            stack[-1].child_s += dur
        tally = self.tally
        if tally is not None:
            tally[self.name] = tally.get(self.name, 0.0) + self.self_s
        if self.emit:
            # read at exit: the collector may have been swapped inside
            c = _collector
            if c is not None:
                ts = time.time()
                c.record(Event("span", self.name, self.attrs, dur, ts,
                               start=ts - dur, parent=self.parent))


def span(name: str, /, *, emit: bool = True, tally: Optional[dict] = None,
         **attrs: Any) -> Span:
    """Timed region, used as ``with obs.span(name, **attrs) as sp:``.

    Every span opens a ``jax.profiler.TraceAnnotation`` of its name and
    scalar attributes while a profiler session records, so it lands in
    the trace on the clock the device's operations are stamped with.
    On exit, with a collector installed and ``emit`` true, it records a
    ``span`` Event: ``value`` its duration, ``start`` its wall-clock
    start, ``parent`` the name of the span it was opened in.  Pass
    ``emit=False`` on per-step hot paths whose caller keeps its own
    totals.  With ``tally`` (a dict) it adds its self seconds to
    ``tally[name]``.  ``sp.set(key=value)`` attaches results discovered
    inside the region."""
    return Span(name, attrs, emit, tally)
