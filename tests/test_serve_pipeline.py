"""The engine runs one step ahead of the host: each step's greedy argmax
stays on the device as the next step's token, and step n+1 is
dispatched before step n is read back.

What must not change: the tokens (equal to a synchronous greedy loop's,
under ragged prompts, slot refills, a ``max_len`` cap and an
end-of-sequence token), and what the ``serve.step`` events say: every
request's decode events add up to its output, admissions (``pos`` 0)
come in submit order, also when a deadline cuts a run mid-generation or
mid-prompt.  And one compiled step serves every batch mix."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.serve import ServeConfig, ServingEngine
from repro.serve.engine import _decode_fn

_BACKEND = "/jax/core/compile/backend_compile_duration"


@pytest.fixture(scope="module")
def yi():
    from repro.configs import get_config, reduced
    from repro.models.lm import build_model
    cfg = dataclasses.replace(reduced(get_config("yi-9b")),
                              compute_dtype="float32")
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _sync_loop(model, params, prompt, slots, max_len, max_new, eos):
    """One request alone in row 0: teacher-force the prompt, then take
    the host's argmax of each step's logits, one step at a time."""
    step = _decode_fn(model, None, 1)
    cache = model.init_cache(slots, max_len)
    seq, out = [int(t) for t in prompt], []
    t = 0
    while True:
        toks = np.zeros((slots, 1), np.int32)
        toks[0, 0] = seq[t]
        pos = np.zeros(slots, np.int32)
        pos[0] = t
        logits, cache = step(params, jnp.asarray(toks), cache,
                             jnp.asarray(pos))
        t += 1
        if t < len(prompt):
            continue
        out.append(int(np.argmax(np.asarray(logits[0]))))
        seq.append(out[-1])
        if out[-1] == eos or len(out) >= max_new or t >= max_len - 1:
            return out


def _serve(model, params, prompts, slots, max_len, max_new, eos):
    eng = ServingEngine(model, params,
                        ServeConfig(slots=slots, max_len=max_len,
                                    max_new_tokens=max_new, eos_id=eos))
    for uid, p in prompts.items():
        eng.submit(uid, p)
    return eng.run(), eng.stats()["host"]


def _eos_mid_stream(want: dict, max_new: int):
    """(eos id, uid): a token that one request makes mid-stream, first
    at that point of its answer, and no other request makes at all."""
    for uid, out in want.items():
        others = {t for u, o in want.items() if u != uid for t in o}
        for j in range(1, max_new - 2):
            if out[j] not in others and out[j] not in out[:j]:
                return out[j], uid
    raise AssertionError(f"no token ends one answer alone: {want}")


@pytest.mark.parametrize("slots,n_req,max_len,max_new,eos", [
    # ragged prompts, more requests than slots; max_len caps the answers
    # of the longer prompts, so slots free and refill while others decode
    (2, 5, 12, 6, False),
    (3, 7, 12, 6, False),
    (4, 9, 12, 6, False),
    (3, 6, 12, 20, False),       # max_len caps every answer
    (3, 3, 32, 8, True),         # one answer ends at its eos mid-stream
], ids=["2slots", "3slots", "4slots", "max_len", "eos"])
def test_pipelined_tokens_equal_a_synchronous_loop(yi, slots, n_req,
                                                   max_len, max_new, eos):
    cfg, model, params = yi
    rng = np.random.default_rng(slots * 100 + n_req)
    lens = rng.integers(1, 10, n_req)
    prompts = {uid: rng.integers(0, cfg.vocab_size, n)
               for uid, n in enumerate(lens)}
    eos_id, stopped = -1, None
    want = {uid: _sync_loop(model, params, p, slots, max_len, max_new, -1)
            for uid, p in prompts.items()}
    if eos:
        eos_id, stopped = _eos_mid_stream(want, max_new)
        out = want[stopped]
        want[stopped] = out[:out.index(eos_id) + 1]
        assert len(want[stopped]) < max_new
    got, host = _serve(model, params, prompts, slots, max_len, max_new,
                       eos_id)
    assert got == want
    assert len({len(o) for o in want.values()}) > 1
    if max_len < max_new:
        assert all(len(p) + len(want[u]) == max_len
                   for u, p in prompts.items())
    # the answer that ended was advanced once more, in the step
    # dispatched before its end was read; that token alone is dropped
    assert host["dropped_tokens"] == (1 if eos else 0)


# ----------------------------------------------- events under the pipeline

class _ToyModel:
    """Next token = (token + 1) mod vocab; no params."""

    vocab = 7

    def init_cache(self, slots, max_len):
        return jnp.zeros((slots, max_len))

    def decode_step(self, params, toks, cache, pos, ctx=None):
        return jax.nn.one_hot((toks[:, 0] + 1) % self.vocab,
                              self.vocab), cache


class _Client(obs.MemoryCollector):
    """Reads the events as a benchmark's client loop does: a slot
    advanced at position 0 has taken the next submitted request, and
    each decode event gives one token to each slot it names.  With
    ``cut_at`` (phase, n) it sets the engine's deadline to 0 at the
    n-th event of that phase, as a window's close does."""

    def __init__(self, eng, cut_at=None):
        super().__init__()
        self.eng, self.cut_at = eng, cut_at
        self.fifo, self.slot_uid = [], {}
        self.tokens: dict = {}
        self.admitted: list = []
        self.seen = {"prefill": 0, "decode": 0}

    def submit(self, uid, prompt):
        assert self.eng.submit(uid, prompt)
        self.fifo.append(uid)

    def record(self, ev):
        super().record(ev)
        if ev.name != "serve.step":
            return
        a = ev.attrs
        for slot, pos, uid in zip(a["slots"], a["pos"], a["uids"]):
            if pos == 0:
                self.slot_uid[slot] = self.fifo.pop(0)
                self.admitted.append(uid)
            assert self.slot_uid[slot] == uid
            if a["phase"] == "decode":
                self.tokens[uid] = self.tokens.get(uid, 0) + 1
        self.seen[a["phase"]] += 1
        if self.cut_at == (a["phase"], self.seen[a["phase"]]):
            self.eng.cfg = dataclasses.replace(self.eng.cfg, deadline_s=0.0)


@pytest.mark.parametrize("cut_at,where", [
    (None, None),
    (("decode", 5), "slot"),      # mid-generation
    (("prefill", 6), "prefill"),  # mid-prompt: uid 3's third token
], ids=["whole", "cut-mid-generation", "cut-mid-prompt"])
def test_step_events_add_up_to_the_answers(cut_at, where):
    eng = ServingEngine(_ToyModel(), None,
                        ServeConfig(slots=2, max_new_tokens=4))
    client = _Client(eng, cut_at)
    prompts = [[1, 2, 3], [4], [5, 6], [1] * 8, [2, 3]]
    obs.install(client)
    try:
        for uid, p in enumerate(prompts):
            client.submit(uid, p)
        results = eng.run()
    finally:
        obs.uninstall()
    assert set(results) == set(range(len(prompts)))
    for uid, out in results.items():
        assert client.tokens.get(uid, 0) == len(out), uid
    assert client.admitted == sorted(client.admitted)
    host = eng.stats()["host"]
    assert host["ahead_steps"] == host["steps"] - 1       # one run()
    assert host["dropped_tokens"] == 0
    expired = client.named("serve.deadline")
    if where is None:
        assert not expired
        assert all(len(o) == 4 for o in results.values())
    else:
        assert where in {e.attrs["where"] for e in expired}
        assert any(len(o) < 4 for o in results.values())


def test_a_second_window_compiles_nothing():
    """The first window compiles the engine's step once, with the carry
    it starts from and the carry it gives back; a second window, with
    another batch mix, compiles nothing at all."""
    compiles = []

    def listen(event, secs, **kw):
        if event == _BACKEND:
            compiles.append(kw.get("fun_name"))
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        eng = ServingEngine(_ToyModel(), None,
                            ServeConfig(slots=3, max_new_tokens=3))
        for uid, p in enumerate([[1, 2], [3], [4, 5, 6]]):
            eng.submit(uid, p)
        eng.run()
        assert compiles.count("jit(engine_step)") == 1, compiles
        first = len(compiles)
        for uid, p in enumerate([[2], [3, 4, 5, 6], [1, 1], [5], [6, 2]]):
            eng.submit(10 + uid, p)
        eng.run()
        assert compiles[first:] == []
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    host = eng.stats()["host"]
    assert host["ahead_steps"] == host["steps"] - 2       # two run()s


def test_decode_fn_lowering_still_compiles(yi):
    """``_decode_fn``'s ``(params, toks, cache, pos) -> (logits, cache)``,
    which the AOT memory count and the launcher lower, is unchanged."""
    cfg, model, params = yi
    slots = 2
    cache = jax.eval_shape(lambda: model.init_cache(slots, 16))
    compiled = _decode_fn(model, None, 1).lower(
        params, jax.ShapeDtypeStruct((slots, 1), jnp.int32), cache,
        jax.ShapeDtypeStruct((slots,), jnp.int32)).compile()
    logits, new_cache = compiled.out_info
    assert logits.shape == (slots, cfg.vocab_size)
    assert jax.tree.structure(new_cache) == jax.tree.structure(cache)
