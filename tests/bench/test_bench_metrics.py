"""Each metric reader on a record made by hand, and the benchmark file
against the contract it must keep (names, keys, readers for every
metric, a data file for every configuration, mix and cell)."""
import bench_fixtures  # noqa: F401  (puts the benchmark on sys.path)

import json
import math
import types

import pytest

from bench import costs, hw, spec
from bench.record import Record


def _req(sent, stamps):
    return types.SimpleNamespace(sent=sent, stamps=stamps)


def _rec(reqs=(), steps=(), trace=None, window_s=10.0):
    return Record(work=spec.work(spec.load_config("yi-9b")), chips=1,
                  peaks=hw.peaks("TPU v5 lite"), window_s=window_s,
                  setup_s=12.5, steps=list(steps),
                  reqs=list(reqs),
                  stats_open={"prefill_steps": 1, "decode_steps": 1},
                  stats_close={"prefill_steps": 31, "decode_steps": 91},
                  trace=trace)


def _reader(name):
    return spec.load_module(spec.BENCH_DIR / "metrics" / f"{name}.py")


def test_tokens_per_second_and_setup():
    rec = _rec([_req(0, [1, 2, 3]), _req(0, [4])])
    assert _reader("output_tokens_per_s").read(rec) == 0.4
    assert _reader("setup_s").read(rec) == 12.5


def test_ttft_median_counts_unanswered_as_latest():
    read = _reader("ttft_p50_s").read
    assert read(_rec([_req(0, [1]), _req(1, [4]), _req(2, [])])) == 3
    assert read(_rec([_req(0, [1]), _req(1, []), _req(2, [])])) is None


def test_itl_runs():
    itl = _reader("itl_p95_ms")
    # 94 gaps of 25 ms and 5 stalls of 0.4 s, over two requests: every
    # gap counts as it is, so the 95th percentile lies between the two
    stamps = [0.025 * i for i in range(48)]
    stamps += [stamps[-1] + 0.4 * (i + 1) for i in range(3)]
    other = [0.025 * i for i in range(48)]
    other += [other[-1] + 0.4 * (i + 1) for i in range(2)]
    rec = _rec([_req(0, stamps), _req(0, other)])
    vals = sorted([0.025] * 94 + [0.4] * 5)
    pos = 0.95 * (len(vals) - 1)
    want = vals[int(pos)] + (pos - int(pos)) * (vals[int(pos) + 1]
                                                - vals[int(pos)])
    assert itl.read(rec) == pytest.approx(1000 * want)
    # no gap is taken between two requests
    many = [_req(0, [0.025 * i for i in range(200)]) for _ in range(3)]
    assert itl.read(_rec(many)) == pytest.approx(25.0)
    assert itl.read(_rec([_req(0, [0, 1])])) is None


def test_engine_counters():
    steps = [(0, "decode", [0, 1], [9, 19], 0.02),
             (0, "prefill", [2], [0], 0.03)]
    rec = _rec(steps=steps)
    assert _reader("prefill_step_share").read(rec) == 25.0
    assert _reader("step_ms").read(rec) == pytest.approx(25.0)
    d = rec.work
    flops = costs.step_flops(d, [10, 20]) + costs.step_flops(d, [1])
    assert _reader("step_mfu").read(rec) == pytest.approx(
        100 * flops / 10.0 / 197e12)
    nbytes = costs.step_bytes(d, [10, 20]) + costs.step_bytes(d, [1])
    assert _reader("step_hbm_bw_share").read(rec) == pytest.approx(
        100 * nbytes / 10.0 / 819e9)
    # the chat cell's names read the same numbers
    for name in ("step_ms", "step_mfu"):
        assert _reader(name + ".chat").read(rec) == _reader(name).read(rec)


def test_trace_readers_silent_without_a_trace():
    rec = _rec(steps=[(0, "decode", [0], [5], 0.02)])
    for name in ("decode_attn_roofline", "rmsnorm_roofline",
                 "device_idle_share"):
        assert _reader(name).read(rec) is None


def test_roofline_readers_on_a_trace():
    tr = types.SimpleNamespace(
        kernels={"decode_attn": [8e3, 8], "rmsnorm": [17e3, 17]},
        busy_ns={0: 6e9}, window_s=10.0, chips=1)
    rec = _rec(steps=[(0, "decode", [0, 1], [99, 199], 0.02)], trace=tr)
    d, v5e = rec.work, rec.peaks
    attn = costs.least_seconds(*costs.decode_attn_call(d, [100, 200]), v5e)
    assert _reader("decode_attn_roofline").read(rec) == pytest.approx(
        100 * 8 * attn / 8e-6)
    norm = costs.least_seconds(*costs.rmsnorm_call(d["d_model"], 2), v5e)
    assert _reader("rmsnorm_roofline").read(rec) == pytest.approx(
        100 * 17 * norm / 17e-6)
    assert _reader("device_idle_share").read(rec) == pytest.approx(40.0)
    assert _reader("device_idle_share.chat").read(rec) == pytest.approx(40.0)


def test_benchmark_file_keeps_the_contract():
    bench = spec.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (spec.BENCH_DIR / "metrics" / f"{m['name']}.py").exists()
        assert m["better"] in ("lower", "higher")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
    for c in bench["configs"]:
        cfg = spec.load_config(c["name"])
        assert (spec.ROOT / c["file"]).exists()
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
        for key in c["reduced"]:
            assert cfg["config"][key] != cfg["published"][key]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= 1
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        kinds = {m.kind for m in cell.metrics}
        assert kinds == {"end_to_end", "per_layer"}
        assert len(cell.metrics_of("end_to_end")) >= 2
        assert cell.settings["limits"]["logit_gap"] > 0
        assert len(w["why"]) <= 200
    assert json.loads(json.dumps(bench)) == bench
    assert math.isfinite(bench["run_seconds"])
