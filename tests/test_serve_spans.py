"""The serving engine's spans and host totals (``repro.obs.span``).

Each span opens a profiler annotation while a trace records, records an
Event (start, duration, enclosing span) only where it is not on the
per-step hot path and a collector is installed, and adds its self time
to the engine's ``stats()["host"]`` totals either way.
"""
import glob
import time

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.obs import core as obs_core
from repro.serve import ServeConfig, ServingEngine

SPANS = ("serve.run", "serve.admit", "serve.prefill", "serve.round",
         "serve.dispatch", "serve.sync", "serve.bookkeep", "serve.retire")


@pytest.fixture(autouse=True)
def _no_ambient_collector():
    prev = obs_core._collector
    obs_core._collector = None
    yield
    obs_core._collector = prev


class _ToyModel:
    """Next token = (token + 1) mod vocab; no params."""

    vocab = 7

    def init_cache(self, slots, max_len):
        return jnp.zeros((slots, max_len))

    def decode_step(self, params, toks, cache, pos, ctx=None):
        return jax.nn.one_hot((toks[:, 0] + 1) % self.vocab,
                              self.vocab), cache


def _engine(slots=2, max_new_tokens=4):
    return ServingEngine(_ToyModel(), None,
                         ServeConfig(slots=slots,
                                     max_new_tokens=max_new_tokens))


def _script(eng, prompts):
    for uid, prompt in enumerate(prompts):
        eng.submit(uid, prompt)
    return eng.run()


# ------------------------------------------------------------ obs.span

def test_span_event_carries_start_and_parent():
    with obs.collect() as col:
        with obs.span("outer", k=1):
            with obs.span("inner") as sp:
                time.sleep(0.005)
            with obs.span("quiet", emit=False):
                pass
    inner, outer = col.events
    assert [inner.name, outer.name] == ["inner", "outer"]
    assert inner.parent == "outer" and outer.parent is None
    assert inner.value == pytest.approx(sp.duration_s)
    assert outer.start <= inner.start
    assert inner.start == pytest.approx(inner.ts - inner.value)
    assert outer.start + outer.value >= inner.start + inner.value
    d = inner.to_dict()
    assert d["parent"] == "outer" and d["start"] == inner.start


def test_span_tally_takes_self_time():
    tally = {}
    with obs.span("a", emit=False, tally=tally) as a:
        with obs.span("b", emit=False, tally=tally) as b:
            time.sleep(0.004)
        time.sleep(0.002)
    assert tally["b"] == pytest.approx(b.duration_s)
    assert tally["a"] == pytest.approx(a.duration_s - b.duration_s)
    assert a.self_s == pytest.approx(tally["a"])
    assert sum(tally.values()) == pytest.approx(a.duration_s)


def test_no_collector_no_event(monkeypatch):
    """With no collector the engine's spans build no Event at all."""
    def no_event(*a, **k):
        raise AssertionError("an Event was built with no collector")
    monkeypatch.setattr(obs_core, "Event", no_event)
    eng = _engine()
    assert _script(eng, [[1, 2, 3], [4]]) == {0: [4, 5, 6, 0],
                                              1: [5, 6, 0, 1]}
    assert eng.stats()["host"]["steps"] == 6


def test_hot_path_spans_emit_no_event():
    with obs.collect() as col:
        _script(_engine(), [[1, 2, 3], [4], [5, 6]])
    names = {e.name for e in col.events if e.kind == "span"}
    assert names == {"serve.run", "serve.admit", "serve.prefill",
                     "serve.retire"}
    admit = col.named("serve.admit")
    assert [e.attrs["uid"] for e in admit] == [0, 1, 2]
    assert all(e.parent == "serve.run" for e in admit)
    assert all(e.parent == "serve.admit"
               for e in col.named("serve.prefill"))


def test_span_costs_little_with_nothing_listening():
    """The engine's four spans of a decode step, with no profiler and
    no collector: a few microseconds (the bound is loose, for loaded
    test machines; an Event built or a collector called per span is
    several times slower than it allows)."""
    n = 20_000
    tally = {}
    t0 = time.perf_counter()
    for _ in range(n):
        with obs.span("serve.round", emit=False, tally=tally) as rnd:
            rnd.set(uids=[0, 1, 2, 3, 4, 5, 6, 7])
            for name in ("serve.dispatch", "serve.sync", "serve.bookkeep"):
                with obs.span(name, emit=False, tally=tally, phase="decode"):
                    pass
    per_step = (time.perf_counter() - t0) / n
    assert per_step < 100e-6, f"{per_step * 1e6:.1f} us a step"


# ------------------------------------------------------- host totals

def test_host_totals_cover_the_run():
    eng = _engine(max_new_tokens=8)
    with obs.collect() as col:
        _script(eng, [[1, 2, 3, 4], [2, 3], [3, 4, 5, 6, 1]])
    run = col.named("serve.run")[0].value
    host = eng.stats()["host"]
    assert host["steps"] == eng.stats()["decode_steps"] + \
        eng.stats()["prefill_steps"]
    assert host["betweens"] == host["steps"] - 1      # one run() call
    covered = host["dispatch_s"] + host["sync_s"] + host["between_s"]
    assert 0.9 * run <= covered <= run
    assert host["admitted"] == host["first_tokens"] == 3
    assert set(host["self_s"]) == set(SPANS)
    # self times partition the run: every engine span lies inside it
    assert sum(host["self_s"].values()) == pytest.approx(run, rel=1e-6)
    reqs = {e.attrs["uid"]: e.attrs for e in col.named("serve.request")}
    assert host["queue_s"] == pytest.approx(
        sum(r["queue_s"] for r in reqs.values()))
    assert host["first_token_wait_s"] == pytest.approx(
        sum(r["first_token_wait_s"] for r in reqs.values()))
    for r in reqs.values():
        assert r["ttft_s"] == pytest.approx(
            r["queue_s"] + r["first_token_wait_s"])


def test_queue_wait_holds_every_earlier_step():
    """Two slots, three requests: the third waits in the queue through
    every step run before its admission."""
    eng = _engine(slots=2, max_new_tokens=3)
    seen = []

    class Snapshots(obs.MemoryCollector):
        def record(self, ev):
            super().record(ev)
            if ev.name == "serve.step":
                seen.append((ev.attrs["uids"], eng.stats()["host"]))

    col = Snapshots()
    obs.install(col)
    try:
        _script(eng, [[1, 2, 3], [4, 5], [6, 1, 2]])
    finally:
        obs.uninstall()
    first = next(i for i, (uids, _) in enumerate(seen) if 2 in uids)
    assert first >= 4
    # the step before its first is read back inside its admission (it
    # was in flight then): take the totals one read earlier
    before = seen[first - 2][1]
    queue_s = next(e.attrs["queue_s"] for e in col.named("serve.request")
                   if e.attrs["uid"] == 2)
    assert queue_s >= before["dispatch_s"] + before["sync_s"] > 0
    for uids, _ in seen:
        assert len(uids) == len(set(uids))


def test_step_event_names_the_requests():
    with obs.collect() as col:
        _script(_engine(), [[1, 2, 3], [4]])
    steps = col.named("serve.step")
    assert [e.attrs["uids"] for e in steps] == \
        [[0], [0], [0, 1], [0, 1], [0, 1], [0, 1]]


# --------------------------------------------------- the profiler trace

def _trace_of(tmp_path, fn):
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    return jax.profiler.ProfileData.from_file(path)


def _events(data, names):
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def test_spans_land_in_a_profiler_trace(tmp_path):
    eng = _engine()
    _script(eng, [[3]])                      # compile outside the trace
    data = _trace_of(tmp_path, lambda: _script(eng, [[1, 2, 3], [4, 5]]))
    evs = _events(data, SPANS)
    assert {e[0] for e in evs} == set(SPANS)

    def inside(inner, outer):
        return outer[1] <= inner[1] and inner[2] <= outer[2]

    run, = [e for e in evs if e[0] == "serve.run"]
    admits = [e for e in evs if e[0] == "serve.admit"]
    prefills = [e for e in evs if e[0] == "serve.prefill"]
    assert [a[3]["uid"] for a in admits] == [0, 1]
    assert all(inside(a, run) for a in admits)
    for a in admits:
        uid = a[3]["uid"]
        assert a[3]["slot"] == uid and a[3]["queue_s"] >= 0
        p, = [p for p in prefills if p[3]["uid"] == uid]
        assert inside(p, a) and p[3]["tokens"] == 2 - uid
        steps = [e for e in evs if e[0] in ("serve.dispatch", "serve.sync")
                 and inside(e, p)]
        # each dispatch but the run's first reads the step before it
        assert len([e for e in steps if e[0] == "serve.dispatch"]) == \
            p[3]["tokens"]
        assert len([e for e in steps if e[0] == "serve.sync"]) == \
            p[3]["tokens"] - (uid == 0)
        assert all(e[3]["phase"] == "prefill" for e in steps)
        r, = [r for r in evs if r[0] == "serve.retire"
              and r[3]["uid"] == uid]
        assert r[3]["n_tokens"] == 4 and inside(r, run)
    rounds = [e for e in evs if e[0] == "serve.round"]
    assert len(rounds) == 4 and all(inside(r, run) for r in rounds)
    assert all(r[3]["uids"] == "0 1" for r in rounds)
