"""The batched-decode fix: one FUSED compiled step per engine round
(the per-slot stepping was an S× throughput bug), bit-equal outputs on
ragged prompts, hoisted jit reuse across engines, terminal shed records,
and mid-prefill deadline expiry."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.runtime import faults
from repro.serve import ServeConfig, ServingEngine


class _ToyModel:
    """Deterministic next-token = (token + 1) mod vocab; no params."""

    vocab = 7

    def init_cache(self, slots, max_len):
        return jnp.zeros((slots, max_len))

    def decode_step(self, params, toks, cache, pos, ctx=None):
        return jax.nn.one_hot((toks[:, 0] + 1) % self.vocab,
                              self.vocab), cache


def _engine(**kw):
    return ServingEngine(_ToyModel(), None, ServeConfig(**kw))


class _Counting:
    def __init__(self, fn):
        self.fn = fn
        self.calls = 0

    def __call__(self, *args):
        self.calls += 1
        return self.fn(*args)


# ------------------------------------------- one fused step per round

def test_one_decode_call_and_event_per_round():
    """With 2 active slots an engine round is ONE dispatch of the
    engine's step and ONE serve.step event, not one per slot."""
    eng = _engine(slots=2, max_new_tokens=3)
    eng._engine_step = _Counting(eng._engine_step)
    with obs.collect() as col:
        eng.submit(1, [1, 2])                 # 1 prefill step
        eng.submit(2, [3])                    # none
        results = eng.run()
    assert results == {1: [3, 4, 5], 2: [4, 5, 6]}
    decode_events = [e for e in col.named("serve.step")
                     if e.attrs["phase"] == "decode"]
    assert len(decode_events) == 3            # 3 rounds, both slots active
    assert all(e.attrs["slots"] == [0, 1] for e in decode_events)
    assert all(e.attrs["active_slots"] == 2 for e in decode_events)
    # total dispatches: 1 prefill + 3 fused decode rounds
    assert eng._engine_step.calls == 4
    assert eng.stats()["decode_steps"] == 3


def _real_engine(model, params, prompts, slots=2, max_new=4):
    eng = ServingEngine(model, params,
                        ServeConfig(slots=slots, max_len=32,
                                    max_new_tokens=max_new))
    for uid, prompt in prompts.items():
        eng.submit(uid, prompt)
    return eng.run()


def test_batched_ragged_bit_equal_vs_isolated():
    """The fused ragged step must not leak state across slots: tokens
    generated with both slots active are bit-identical to running each
    request alone (same batch shape, row independence)."""
    from repro.configs import get_config, reduced
    from repro.models.lm import build_model
    cfg = dataclasses.replace(reduced(get_config("yi-9b")),
                              compute_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    prompts = {1: rng.integers(0, cfg.vocab_size, 3),
               2: rng.integers(0, cfg.vocab_size, 7)}   # ragged lengths
    together = _real_engine(model, params, prompts)
    alone = {}
    for uid, prompt in prompts.items():
        alone.update(_real_engine(model, params, {uid: prompt}))
    assert together == alone, (together, alone)


# --------------------------------------------------- hoisted jit step

def test_decode_step_jit_hoisted_across_engines():
    """Constructing N engines over the same (model, ctx, shards) must
    reuse one jitted step — the per-instance re-jit threw away XLA's
    compile cache for every test/chaos-leg engine."""
    model = _ToyModel()
    e1 = ServingEngine(model, None, ServeConfig(slots=1))
    e2 = ServingEngine(model, None, ServeConfig(slots=2, max_new_tokens=5))
    assert e1._decode is e2._decode


def test_jit_hoist_keyed_by_model_equality():
    """Hashable model dataclasses share the step across *equal* (not
    just identical) instances; distinct toy instances do not collide."""
    from repro.configs import get_config, reduced
    from repro.models.lm import build_model
    cfg = reduced(get_config("yi-9b"))
    m1, m2 = build_model(cfg), build_model(cfg)
    e1 = ServingEngine(m1, None, ServeConfig(slots=1))
    e2 = ServingEngine(m2, None, ServeConfig(slots=1))
    assert e1._decode is e2._decode
    t1 = ServingEngine(_ToyModel(), None, ServeConfig(slots=1))
    t2 = ServingEngine(_ToyModel(), None, ServeConfig(slots=1))
    assert t1._decode is not t2._decode


# ------------------------------------------------ terminal shed records

def test_shed_requests_get_terminal_stats_records():
    eng = _engine(slots=1, max_new_tokens=2, max_queue=1)
    assert eng.submit(1, [1]) is True
    assert eng.submit(2, [2]) is False        # rejected
    results = eng.run()
    stats = eng.stats()
    assert set(stats["requests"]) == {1, 2}   # one terminal outcome each
    assert stats["requests"][2] == {"n_tokens": 0, "ttft_s": 0.0,
                                    "tokens_per_s": 0.0,
                                    "deadline_exceeded": False,
                                    "shed": True}
    assert stats["requests"][1]["shed"] is False
    assert 2 not in results                   # rejected uid never ran


def test_drop_oldest_victim_gets_terminal_record():
    eng = _engine(slots=1, max_new_tokens=2, max_queue=1,
                  shed_policy="drop_oldest")
    eng.submit(1, [1])
    eng.submit(2, [2])                        # evicts 1
    results = eng.run()
    stats = eng.stats()
    assert set(stats["requests"]) == {1, 2}
    assert stats["requests"][1]["shed"] is True
    assert stats["requests"][2]["shed"] is False
    assert results[1] == [] and len(results[2]) == 2


# ------------------------------------------------- mid-prefill deadline

def test_prefill_deadline_expires_mid_prompt_and_slot_reusable():
    """A long prompt must not burn unbounded prefill steps past the
    deadline; the lapse frees the slot for the next request."""
    eng = _engine(slots=1, max_new_tokens=2, deadline_s=0.12)
    with obs.collect() as col:
        with faults.inject("serve_slow:slot0"):   # +50ms per slot0 step
            eng.submit(1, list(range(1, 7)))      # 6 tokens → 5 prefill
            results = eng.run()
    assert results == {1: []}
    evs = col.named("serve.deadline")
    assert len(evs) == 1
    assert evs[0].attrs["where"] == "prefill"
    stats = eng.stats()
    assert stats["deadline_expired"] == 1
    assert stats["requests"][1]["deadline_exceeded"] is True
    assert 1 <= stats["prefill_steps"] < 5        # cut off mid-prompt
    assert eng.active_slots() == 0
    # the partially-written slot is immediately reusable
    eng.submit(2, [1, 2, 3])
    results = eng.run()
    assert len(results[2]) == 2
    assert eng.stats()["requests"][2]["deadline_exceeded"] is False


def test_serve_launcher_widths_and_shared_construction():
    """The launcher keeps published widths unless ``--reduced`` (the
    depth cut alone leaves them), and its ``build_engine`` serves every
    request — the construction ``chip_smoke.py`` reuses."""
    from repro.launch import serve

    args = serve.parser().parse_args(["--layers", "8"])
    full = serve.model_config(args)
    assert (full.d_model, full.n_heads, full.n_kv_heads, full.d_ff,
            full.vocab_size, full.n_layers) == (4096, 32, 4, 11008, 64000, 8)

    args = serve.parser().parse_args(
        ["--reduced", "--layers", "1", "--requests", "3", "--slots", "2",
         "--prompt-len", "4", "--max-new", "3", "--max-len", "16"])
    cfg, _model, _params, engine = serve.build_engine(args)
    assert cfg.n_layers == 1 and cfg.d_model < full.d_model
    assert engine.cfg.max_len == 16
    for uid, toks in enumerate(serve.prompts(args, cfg.vocab_size)):
        engine.submit(uid, toks)
    results = engine.run()
    assert sorted(results) == [0, 1, 2]
    assert all(len(r) == 3 for r in results.values())
