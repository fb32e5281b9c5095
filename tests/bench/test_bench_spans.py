"""The readers of the engine's host totals, and the idle stretches of a
trace put down to the engine's spans (``bench.spans``): on hand-made
records and events, on a parent-era trace without engine spans, on a
traced run of the tiny cell on the CPU, and on a small trace recorded on
a TPU v5e with the spans (committed gzipped under data/)."""
import bench_fixtures

import gzip
import types

import pytest

from bench import run, spans, spec, trace
from bench.record import Record

OLD = bench_fixtures.ROOT / "tests" / "bench" / "data" / \
    "yi9b_reason_window.xplane.pb.gz"
NEW = bench_fixtures.ROOT / "tests" / "bench" / "data" / \
    "yi9b_reason_spans.xplane.pb.gz"
SPANS = ("serve.run", "serve.admit", "serve.prefill", "serve.round",
         "serve.dispatch", "serve.sync", "serve.bookkeep", "serve.retire")
READERS = ("step_dispatch_ms", "step_gap_ms", "queue_wait_s",
           "first_token_wait_s")


def _host(**kw):
    base = {"steps": 0, "dispatch_s": 0.0, "sync_s": 0.0,
            "bookkeep_s": 0.0, "between_s": 0.0, "betweens": 0,
            "queue_s": 0.0, "admitted": 0, "first_token_wait_s": 0.0,
            "first_tokens": 0, "self_s": {}}
    return {**base, **kw}


def _rec(open_host, close_host):
    stats = lambda h: {"prefill_steps": 0, "decode_steps": 0,
                       **({} if h is None else {"host": h})}
    return Record(work={}, chips=1, peaks=None, window_s=40.0, setup_s=1.0,
                  steps=[], reqs=[], stats_open=stats(open_host),
                  stats_close=stats(close_host))


def _read(name, rec):
    return spec.load_module(spec.BENCH_DIR / "metrics" / f"{name}.py").read(
        rec)


def test_readers_take_the_window_difference():
    rec = _rec(_host(steps=10, dispatch_s=0.5, between_s=1.0, betweens=9,
                     queue_s=4.0, admitted=2, first_token_wait_s=1.0,
                     first_tokens=1),
               _host(steps=1010, dispatch_s=2.5, between_s=1.5, betweens=1008,
                     queue_s=22.0, admitted=6, first_token_wait_s=21.0,
                     first_tokens=5))
    assert _read("step_dispatch_ms", rec) == pytest.approx(2.0)
    assert _read("step_gap_ms", rec) == pytest.approx(0.5 / 999 * 1000)
    assert _read("queue_wait_s", rec) == pytest.approx(4.5)
    assert _read("first_token_wait_s", rec) == pytest.approx(5.0)


@pytest.mark.parametrize("name", READERS)
def test_readers_silent_on_an_empty_window(name):
    same = _host(steps=5, dispatch_s=0.1, between_s=0.1, betweens=4,
                 queue_s=1.0, admitted=1, first_token_wait_s=1.0,
                 first_tokens=1)
    assert _read(name, _rec(same, dict(same))) is None
    # an engine without host totals (before they existed) gives nothing
    assert _read(name, _rec(None, None)) is None


def test_benchmark_lists_the_readers():
    bench = spec.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    reason, chat = ["yi9b-reason", "chatglm3-reason"], ["yi9b-chat"]
    for name, cells, moves in (
            ("step_dispatch_ms", reason, "output_tokens_per_s"),
            ("step_gap_ms", reason, "output_tokens_per_s"),
            ("queue_wait_s", chat, "ttft_p50_s"),
            ("first_token_wait_s", chat, "ttft_p50_s")):
        m = entries[name]
        assert m["workloads"] == cells and m["moves"] == moves
        assert m["source"] == "program_span" and m["layer"] == "serve.engine"


# ------------------------------------------------------------- traces

def _ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=float(start),
                                 duration_ns=float(dur),
                                 stats=list(stats.items()))


def _data(chips, host_events):
    line = lambda name, evs: types.SimpleNamespace(name=name, events=evs)
    planes = [types.SimpleNamespace(name=f"/device:TPU:{i}",
                                    lines=[line("XLA Ops", evs)])
              for i, evs in enumerate(chips)]
    planes.append(types.SimpleNamespace(name="/host:CPU",
                                        lines=[line("python3", host_events)]))
    return types.SimpleNamespace(planes=planes)


def _by_hand():
    """A window [0, 1000) ns.  Chip 0 runs three operations, so it idles
    over [0, 100), [300, 400), [600, 900) and [950, 1000); chip 1 is
    busy throughout.  The loop waits for clients from 0; the engine runs
    [60, 700): an admission whose prefill step is dispatched at 70 and
    synced until 310, then a decode round dispatched at 365."""
    dev = [_ev("%a = f32[] fusion(x)", 100, 200),
           _ev("%b = f32[] fusion(x)", 400, 200),
           _ev("%c = f32[] fusion(x)", 900, 50)]
    host = [_ev("bench.window", 0, 1000), _ev("bench.mark.idle", 0, 1),
            _ev("serve.run", 60, 640),
            _ev("serve.admit", 60, 290, uid=7, slot=0),
            _ev("serve.prefill", 70, 270, uid=7, tokens=1),
            _ev("serve.dispatch", 70, 40), _ev("serve.sync", 110, 200),
            _ev("serve.bookkeep", 310, 20),
            _ev("bench.mark.prefill", 325, 1),
            _ev("serve.round", 360, 330, uids="7"),
            _ev("serve.dispatch", 365, 55), _ev("serve.sync", 420, 190),
            _ev("serve.bookkeep", 610, 30),
            _ev("serve.retire", 650, 30, uid=7, n_tokens=1)]
    return _data([dev, [_ev("%d = f32[] fusion(x)", 0, 1000)]], host)


def test_stretches_by_hand():
    got = spans.stretches(_by_hand())
    assert [(c, s, n, label) for c, s, n, label in got] == [
        (0, 0.0, 100.0, spans.WAIT),            # 60 waiting, 40 engine
        (0, 300.0, 100.0, "serve.dispatch"),    # 35 of 100
        (0, 600.0, 300.0, spans.OUTSIDE),       # 200 after the run
        (0, 950.0, 50.0, spans.OUTSIDE)]
    by = spans.idle_by_label(_by_hand())
    assert by == pytest.approx({spans.WAIT: 50e-9,
                                "serve.dispatch": 50e-9,
                                spans.OUTSIDE: 175e-9})


def test_timeline_labels_the_innermost_span():
    bounds, labels = spans.timeline([(0, 10, "a"), (2, 5, "b"),
                                     (3, 4, "c"), (12, 14, "d")])
    assert bounds == [0, 2, 3, 4, 5, 10, 12, 14]
    assert labels == ["a", "b", "c", "b", "a", None, "d", None]


def test_no_window_is_an_error():
    with pytest.raises(ValueError, match="bench.window"):
        spans.stretches(_data([[]], []))


def test_trace_without_engine_spans():
    """A trace taken before the engine had spans: every idle second is
    the loop's wait or outside the engine, and they add up to the
    window's idle time."""
    import jax
    data = jax.profiler.ProfileData.from_serialized_xspace(
        gzip.decompress(OLD.read_bytes()))
    by = spans.idle_by_label(data)
    assert set(by) == {spans.WAIT, spans.OUTSIDE}
    tr = trace.reduce(data)
    assert sum(by.values()) == pytest.approx(
        tr.window_s - trace.busy_s(tr), rel=1e-9)


def test_traced_tiny_run_reports_the_host_metrics():
    """A traced run of the tiny cell on the CPU reads all four."""
    from bench.spec import Metric
    cell = bench_fixtures.tiny_cell()
    cell = cell.__class__(**{**cell.__dict__, "metrics": tuple(
        Metric(n, "u", "lower", "per_layer", {}) for n in READERS)})
    out = run.run_cell(cell, 2**31 + 5, 2.0, True, require_chip=False)
    assert out["correct"]
    assert set(out["metrics"]) == set(READERS)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["step_dispatch_ms"] > 0 and m["step_gap_ms"] > 0
    assert m["queue_wait_s"] >= 0 and m["first_token_wait_s"] > 0


def test_recorded_chip_trace_with_engine_spans():
    """A second of a yi9b-reason window traced on a TPU v5e (a
    ``--seconds 1 --trace 1`` run) with the engine's spans: all eight
    lie on the host plane, one request's spans share its uid, the
    kernels carry their own names and counts per step, and the chip's
    idle time is put down to the engine's spans or the loop's wait."""
    import jax
    data = jax.profiler.ProfileData.from_serialized_xspace(
        gzip.decompress(NEW.read_bytes()))
    host = [(ev.name, dict(ev.stats)) for plane in data.planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("serve.")]
    assert {name for name, _ in host} == set(SPANS)
    admitted = {st["uid"] for name, st in host if name == "serve.admit"}
    prefilled = {st["uid"] for name, st in host if name == "serve.prefill"}
    assert admitted and admitted == prefilled
    # the close retires every request, admitted or still queued
    assert admitted < {st["uid"] for name, st in host
                       if name == "serve.retire"}
    customs = [ev.name.split(" = ")[0] for plane in data.planes
               if plane.name.startswith("/device:")
               for line in plane.lines if line.name == trace.OPS_LINE
               for ev in line.events if trace.PALLAS in ev.name]
    assert customs and all(c.startswith(("%rmsnorm", "%decode_attn"))
                           for c in customs)
    tr = trace.reduce(data)
    _, attn_n = trace.kernel_seconds(tr)["decode_attn"]
    _, norm_n = trace.kernel_seconds(tr)["rmsnorm"]
    assert attn_n % 8 == 0 and norm_n == attn_n // 8 * 17
    by = spans.idle_by_label(data)
    idle = tr.window_s - trace.busy_s(tr)
    assert sum(by.values()) == pytest.approx(idle, rel=1e-9)
    assert by.get(spans.OUTSIDE, 0.0) <= 0.1 * idle
    assert {"serve.dispatch", spans.WAIT} <= set(by)
