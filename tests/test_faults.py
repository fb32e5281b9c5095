"""Fault-injection harness + the robustness paths it exercises:
guarded dispatch fallback chain, self-healing tune cache, autotune
candidate skipping, and telemetry-sink self-heal.

The acceptance scenario for the robustness PR lives here: with a fault
plan forcing a lowering failure on a registered ``*_gen`` kernel, the
op must still return the correct result via the fallback chain, emit a
``kernel.fallback`` event recording the failure class and the tier that
served the result, and quarantine the failing config in the tune cache.
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels as K
from repro import obs
from repro.core.striding import SINGLE_STRIDED, StridingConfig
from repro.kernels import common
from repro.registry import autotune, tunecache
from repro.runtime import faults
from repro.runtime.faults import InjectedFault


@pytest.fixture
def isolated_cache(tmp_path, monkeypatch):
    """Repoint the default tune cache at a per-test file."""
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tune.json"))
    tunecache.reset_default_cache()
    common.reset_plan_memo()
    yield tunecache.default_cache()
    tunecache.reset_default_cache()
    common.reset_plan_memo()


# ------------------------------------------------------------ the plan

def test_parse_plan_grammar():
    plan = faults.parse_plan("lower:mxv_gen:1, sink_io , cache_corrupt:x")
    assert len(plan.rules) == 3
    r = plan.rules[0]
    assert (r.site, r.target, r.count) == ("lower", "mxv_gen", 1)
    assert plan.rules[1].target == "" and plan.rules[1].count is None


@pytest.mark.parametrize("bad", ["lower:x:1:2", "lower:x:zero",
                                 "lower:x:0", ":target"])
def test_parse_plan_rejects_malformed(bad):
    with pytest.raises(ValueError):
        faults.parse_plan(bad)


def test_rule_count_caps_fires():
    with faults.inject("lower:mxv:2"):
        assert faults.should_fire("lower", "mxv_gen")   # substring match
        assert faults.should_fire("lower", "mxv_gen")
        assert not faults.should_fire("lower", "mxv_gen")
        assert not faults.should_fire("lower", "other")  # target filter
        assert not faults.should_fire("tune_trial", "mxv")  # site filter


def test_inject_scopes_and_restores():
    assert not faults.enabled()
    with faults.inject("sink_io"):
        assert faults.enabled()
        with pytest.raises(InjectedFault):
            faults.fire_if("sink_io", "anything")
    assert not faults.enabled()
    assert not faults.should_fire("sink_io")


def test_env_plan_is_read_once(monkeypatch):
    monkeypatch.setenv("REPRO_FAULTS", "tune_trial:abc:1")
    faults.reset()
    try:
        assert faults.enabled()
        assert faults.should_fire("tune_trial", "abc123")
        assert not faults.should_fire("tune_trial", "abc123")
    finally:
        monkeypatch.delenv("REPRO_FAULTS")
        faults.reset()
    assert not faults.enabled()


def test_fired_rules_emit_audit_events():
    with obs.collect() as col:
        with faults.inject("serve_slow::1"):
            faults.sleep_if("serve_slow", "slot0", seconds=0.0)
    evs = col.named("fault.injected")
    assert len(evs) == 1
    assert evs[0].attrs["site"] == "serve_slow"


# ----------------------------------------------- guarded dispatch chain

def test_classify_failure_classes():
    assert common.classify_failure(InjectedFault("x")) == "injected"
    assert common.classify_failure(NotImplementedError()) == "unsupported"
    assert common.classify_failure(
        RuntimeError("VMEM limit exceeded")) == "resource"
    assert common.classify_failure(ValueError("bad D")) == "invalid_config"
    assert common.classify_failure(RuntimeError("boom")) == "backend"


def test_gen_kernel_falls_back_correct_and_quarantined(isolated_cache):
    """The PR's acceptance scenario (simple make_kernel_op path)."""
    a = jnp.arange(64 * 32, dtype=jnp.float32).reshape(64, 32) / 100
    x = jnp.ones((32,), jnp.float32)
    with obs.collect() as col:
        with faults.inject("lower:mxv_gen"):
            out = K.mxv_gen(a, x, mode="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ x),
                               rtol=1e-5, atol=1e-5)
    evs = col.named("kernel.fallback")
    assert len(evs) == 1
    ev = evs[0].attrs
    assert ev["failure"] == "injected"
    # the unlimited rule also kills both alt-config tiers, so the ref
    # oracle must have served the result
    assert ev["tier"] == "ref" and ev["to_mode"] == "ref"
    qkey = tunecache.cache_key("mxv_gen", a.shape, a.dtype,
                               mode="interpret")
    quarantined = isolated_cache.quarantined(qkey)
    assert quarantined, "failing config must be quarantined"
    assert all(q["reason"] == "injected" for q in quarantined.values())


def test_composite_gen_wrapper_falls_back(isolated_cache):
    """The composite wrappers (own jit'd run, not make_kernel_op) ride
    the same chain."""
    x = jnp.arange(8 * 128, dtype=jnp.float32).reshape(8, 128) / 50
    w = jnp.ones((128,), jnp.float32)
    expected = np.asarray(K.rmsnorm_gen(x, w, mode="ref"))
    with obs.collect() as col:
        with faults.inject("lower:rmsnorm_gen"):
            out = K.rmsnorm_gen(x, w, mode="interpret")
    np.testing.assert_allclose(np.asarray(out), expected,
                               rtol=1e-5, atol=1e-5)
    assert col.named("kernel.fallback")


def test_single_fault_lands_on_alt_config_tier(isolated_cache):
    """A once-only fault kills the first attempt; the next-ranked
    planner config (same mode) serves the result."""
    a = jnp.arange(64 * 32, dtype=jnp.float32).reshape(64, 32) / 100
    x = jnp.ones((32,), jnp.float32)
    with obs.collect() as col:
        with faults.inject("lower:mxv_gen:1"):
            out = K.mxv_gen(a, x, mode="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ x),
                               rtol=1e-5, atol=1e-5)
    ev = col.named("kernel.fallback")[0].attrs
    assert ev["tier"] == "alt_config"
    assert ev["to_mode"] == "interpret"
    assert (ev["d"], ev["p"]) != (ev["failed_d"], ev["failed_p"])


def test_quarantined_config_not_re_resolved(isolated_cache):
    """Resolution must never hand back a config the chain watched fail."""
    a = jnp.arange(64 * 32, dtype=jnp.float32).reshape(64, 32) / 100
    x = jnp.ones((32,), jnp.float32)
    with faults.inject("lower:mxv_gen:1"):
        K.mxv_gen(a, x, mode="interpret")
    qkey = tunecache.cache_key("mxv_gen", a.shape, a.dtype,
                               mode="interpret")
    bad = list(isolated_cache.quarantined(qkey).values())
    assert bad
    failed = StridingConfig(bad[0]["d"], bad[0]["p"],
                            block_rows=bad[0]["block_rows"])
    with obs.collect() as col:
        out = K.mxv_gen(a, x, mode="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ x),
                               rtol=1e-5, atol=1e-5)
    for ev in col.named("kernel.resolve"):
        assert (ev.attrs["d"], ev.attrs["p"],
                ev.attrs["block_rows"]) != (failed.stride_unroll,
                                            failed.portion_unroll,
                                            failed.block_rows)


def test_ref_mode_failure_reraises_untouched(isolated_cache):
    """A ref-oracle failure is a bug, not a degradable fault."""
    def run(cfg, mode):
        raise RuntimeError("oracle bug")
    with pytest.raises(RuntimeError, match="oracle bug"):
        common.guarded_run("fake_kernel", run, SINGLE_STRIDED, "ref",
                           shape=(4, 4), dtype=jnp.float32)


def test_all_tiers_exhausted_reraises_original(isolated_cache):
    calls = []

    def run(cfg, mode):
        calls.append(mode)
        raise NotImplementedError("no tier works")

    with pytest.raises(NotImplementedError):
        common.guarded_run("fake_kernel", run, SINGLE_STRIDED,
                           "interpret", shape=(4, 4), dtype=jnp.float32)
    assert "ref" in calls     # the chain did reach the last tier


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_interpret_tier_never_taken_on_tpu(isolated_cache, monkeypatch,
                                           backend):
    """A failed pallas kernel degrades through interpret only off the
    chip: on a TPU the interpreter is a silent slowdown of orders of
    magnitude, so the chain goes straight to the ref oracle."""
    monkeypatch.setattr(common.jax, "default_backend", lambda: backend)
    calls = []

    def run(cfg, mode):
        calls.append(mode)
        if mode != "ref":
            raise NotImplementedError("refused")
        return jnp.zeros(())

    common.guarded_run("fake_kernel", run, SINGLE_STRIDED, "pallas",
                       shape=(4, 4), dtype=jnp.float32)
    assert calls[-1] == "ref"
    assert ("interpret" in calls) == (backend != "tpu")


# ------------------------------------------------- self-healing caches

def test_corrupt_cache_quarantined_and_rebuilt(tmp_path):
    path = str(tmp_path / "tune.json")
    with open(path, "w") as f:
        f.write('{"entries": {"k": ')      # torn mid-write
    with obs.collect() as col:
        cache = tunecache.TuneCache(path)
        cache.store("k|s|d|cpu|ref", {"d": 4, "p": 2})
    assert os.path.exists(path + ".corrupt")
    assert col.counter_value("tunecache.corrupt_quarantined") == 1
    # the rebuilt file round-trips
    assert tunecache.TuneCache(path).lookup("k|s|d|cpu|ref") == {
        "d": 4, "p": 2}
    with open(path) as f:
        payload = json.load(f)
    assert payload["schema"] == tunecache.SCHEMA_VERSION


def test_cache_corrupt_fault_site(tmp_path):
    path = str(tmp_path / "tune.json")
    tunecache.TuneCache(path).store("k", {"d": 2, "p": 1})
    with faults.inject("cache_corrupt"):
        cache = tunecache.TuneCache(path)
        assert cache.lookup("k") is None      # torn read → rebuilt empty
    assert os.path.exists(path + ".corrupt")


def test_legacy_flat_cache_migrates(tmp_path):
    path = str(tmp_path / "tune.json")
    with open(path, "w") as f:
        json.dump({"k|s|d|cpu|ref": {"d": 8, "p": 2}}, f)
    cache = tunecache.TuneCache(path)
    assert cache.lookup("k|s|d|cpu|ref") == {"d": 8, "p": 2}
    cache.store("other", {"d": 1, "p": 1})
    with open(path) as f:
        payload = json.load(f)
    assert payload["schema"] == tunecache.SCHEMA_VERSION
    assert "k|s|d|cpu|ref" in payload["entries"]


def test_store_is_atomic_no_tmp_left_behind(tmp_path):
    path = str(tmp_path / "tune.json")
    cache = tunecache.TuneCache(path)
    for i in range(3):
        cache.store(f"k{i}", {"d": 2, "p": 1})
    leftovers = [p for p in os.listdir(tmp_path)
                 if p not in ("tune.json",)]
    assert not leftovers, f"tmp files left behind: {leftovers}"
    assert len(tunecache.TuneCache(path).entries()) == 3


def test_stale_entry_rejected_by_config_for(tmp_path):
    cache = tunecache.TuneCache(str(tmp_path / "t.json"))
    key = tunecache.cache_key("kx", (4, 4), jnp.float32, mode="ref")
    cache.store(key, {"d": 4, "p": 2,
                      "provenance": {"jax_version": "0.0.0-other"}})
    assert cache.config_for("kx", (4, 4), jnp.float32, mode="ref") is None
    cache.store(key, {"d": 4, "p": 2})       # no provenance = fresh
    assert cache.config_for("kx", (4, 4), jnp.float32,
                            mode="ref") is not None


# ------------------------------------------------- autotune robustness

def test_autotune_skips_failing_candidates(tmp_path):
    cache = tunecache.TuneCache(str(tmp_path / "t.json"))
    with obs.collect() as col:
        with faults.inject("tune_trial:mxv_gen:2"):
            r = autotune.tune("mxv_gen", mode="ref", cache=cache,
                              iters=1, warmup=0, timestamp=0.0)
    assert not r.from_cache and r.seconds < float("inf")
    assert col.counter_value("tune.candidate_failed") == 2
    # the two crashed candidates are quarantined under the tune key
    assert len(cache.quarantined(r.key)) == 2


def test_autotune_all_candidates_failing_returns_floor(tmp_path):
    cache = tunecache.TuneCache(str(tmp_path / "t.json"))
    with obs.collect() as col:
        with faults.inject("tune_trial:mxv_gen"):
            r = autotune.tune("mxv_gen", mode="ref", cache=cache,
                              iters=1, warmup=0, timestamp=0.0)
    assert r.config == SINGLE_STRIDED
    assert r.seconds == float("inf")
    assert col.named("tune.exhausted")
    assert cache.lookup(r.key) is None       # no poisoned winner stored


def test_autotune_trial_timeout_abandons_candidate(tmp_path):
    cache = tunecache.TuneCache(str(tmp_path / "t.json"))
    # warm every candidate's jit trace so cold-compile latency can't
    # trip the (deliberately tight) budget below
    autotune.tune("mxv_gen", mode="ref", cache=cache, iters=1, warmup=0,
                  timestamp=0.0)
    with obs.collect() as col:
        with faults.inject("tune_slow:mxv_gen:1"):
            r = autotune.tune("mxv_gen", mode="ref", cache=cache,
                              iters=1, warmup=0, timestamp=0.0,
                              force=True, trial_timeout_s=0.02)
    assert col.counter_value("tune.trial_timeout") == 1
    assert r.seconds < 0.02        # winner is a candidate that ran fast


def test_mad_outlier_rejection():
    kept, rejected = autotune._reject_outliers(
        [1.0, 1.01, 0.99, 1.02, 100.0])
    assert rejected == 1 and 100.0 not in kept
    kept, rejected = autotune._reject_outliers([1.0, 1.0, 1.0])
    assert rejected == 0 and kept == [1.0, 1.0, 1.0]   # degenerate MAD


def test_autotune_stale_hit_retunes(tmp_path, monkeypatch):
    cache = tunecache.TuneCache(str(tmp_path / "t.json"))
    monkeypatch.setenv("REPRO_TUNE_ITERS", "1")
    monkeypatch.setenv("REPRO_TUNE_WARMUP", "0")
    r1 = autotune.tune("mxv_gen", mode="ref", cache=cache, timestamp=0.0)
    entry = cache.lookup(r1.key)
    entry["provenance"]["jax_version"] = "0.0.0-other"
    cache.store(r1.key, entry)
    with obs.collect() as col:
        r2 = autotune.tune("mxv_gen", mode="ref", cache=cache,
                           timestamp=0.0)
    assert not r2.from_cache
    assert col.counter_value("tune.cache.stale") == 1
    # the re-tune overwrote the stale provenance
    assert (cache.lookup(r1.key)["provenance"]["jax_version"]
            != "0.0.0-other")


# --------------------------------------------------- telemetry sinks

def test_read_jsonl_tolerates_truncated_tail(tmp_path):
    path = str(tmp_path / "obs.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "event", "name": "a"}) + "\n")
        f.write(json.dumps({"kind": "event", "name": "b"}) + "\n")
        f.write('{"kind": "event", "na')          # killed mid-write
    recs = obs.read_jsonl(path)
    assert [r["name"] for r in recs] == ["a", "b"]
    assert obs.read_jsonl.skipped == 1
    with pytest.raises(json.JSONDecodeError):
        obs.read_jsonl(path, strict=True)


def test_jsonl_sink_survives_io_faults(tmp_path):
    from repro.obs.sinks import JsonlSink
    path = str(tmp_path / "obs.jsonl")
    sink = JsonlSink(path)
    obs.install(sink)
    try:
        with faults.inject("sink_io::2"):
            obs.event("x", i=0)     # dropped
            obs.event("x", i=1)     # dropped
            obs.event("x", i=2)     # lands
    finally:
        obs.uninstall()
    sink.close()
    assert sink.dropped == 2
    recs = obs.read_jsonl(path)
    # the two dropped "x" events never land; their fault.injected audit
    # lines do (written outside the armed window via the reentrancy
    # guard), as does the third "x"
    assert [r["attrs"]["i"] for r in recs if r["name"] == "x"] == [2]
    assert sum(r["name"] == "fault.injected" for r in recs) == 2
