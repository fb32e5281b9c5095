"""Batched serving engine: continuous-batching slot manager over the
model's prefill/decode steps.

Requests are admitted into fixed `slots` (static shapes keep one compiled
step). Each slot tracks its own length; decode runs ONE fused
compiled step per engine round for all active slots against the shared
KV cache — the token vector is [slots, 1] and the position vector is the
per-slot length, so ragged slots write their own cache rows and attend
to their own ``kv_len`` inside a single dispatch.  Finished slots
(EOS/max_tokens) are retired and refilled from the queue. The decode
attention path is the multi-strided flash-decode kernel (on TPU), so the
paper's technique is on the hot path of every generated token; with
``ServeConfig.shards > 1`` the KV cache is sequence-sharded and the
kernel's (out, lse) partials merge with the online-softmax identity
(``kernels.decode_attn.sharded``).

The engine runs one step ahead of the host.  Its compiled step takes
the greedy argmax itself and keeps each row's newest token on the
device (the carry), where the row's next decode step reads it; every
other input (which rows advance, their positions, prompt tokens)
follows from counts the host already has.  So step n+1 is dispatched
before step n's tokens are read back, and the host's bookkeeping of
step n overlaps the device's work on step n+1.  Only an end-of-sequence
token needs the value: the row it ends has been advanced once more by
then, and that token is dropped.  At most one step is in flight; it is
read before a deadline retires a row and at the end of ``run()``.

Serving telemetry (always collected engine-side; exported via
``stats()`` and, with ``repro.obs`` enabled, per-step/per-request
events):

  * ``serve.step``    — one event per fused decode/prefill step, when
    its tokens are read back: latency (the step period, from the
    previous step's result on the host, or this step's dispatch where
    none was in flight, to this step's), phase, the advanced slots +
    their positions and request uids, and the active-slot count and
    queue depth as it was dispatched;
  * ``serve.request`` — one event per retired request: time-to-first-
    token split into its queue wait and the wait from admission,
    tokens/s, generated-token count;
  * ``serve.shed``    — a request refused (or evicted) by the bounded
    admission queue;
  * ``serve.deadline``— a request retired because its per-request
    deadline expired (queued, mid-prefill, or mid-generation);
  * ``serve.slow_step`` — a slot's step slower than
    ``slow_step_factor`` × the slot's rolling median (StepMonitor
    straggler machinery).

Spans (``repro.obs.span``: profiler annotations while a trace records;
``uid`` names the request): ``serve.cast_params`` the weights cast to
the compute dtype once, at construction; ``serve.run`` one ``run()``;
``serve.admit`` one request from the queue into a slot, its prefill
included; ``serve.prefill`` its teacher-forced prompt; ``serve.round``
one decode round; per step ``serve.dispatch`` (stall checks, inputs to
the device, the jitted call), ``serve.sync`` (the previous step's
tokens copied to the host: the wait on the device) and
``serve.bookkeep`` (monitor, heartbeat, the ``serve.step`` emission,
the tokens appended and their requests retired); ``serve.retire``.  The
per-step and per-round spans emit no Event; their times add up in
``stats()["host"]`` whether or not ``repro.obs`` is enabled.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.runtime.fault_tolerance import HeartbeatRegistry, StepMonitor
from repro.serve.sharded import place_kv_cache


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    slots: int = 8               # concurrent sequences (batch of the step)
    max_len: int = 2048          # KV capacity per slot
    max_new_tokens: int = 128
    eos_id: int = -1             # -1: never stops early
    greedy: bool = True
    shards: int = 1              # KV sequence shards (flash-decode merge)
    # ------------------------------------------------ robustness knobs
    deadline_s: Optional[float] = None   # per-request wall-clock budget
    max_queue: Optional[int] = None      # bounded admission (None = ∞)
    shed_policy: str = "reject"          # "reject" new | "drop_oldest"
    slow_step_factor: float = 3.0        # slow-step flag vs rolling median
    heartbeat_timeout_s: float = 60.0    # engine-loop liveness window


@dataclasses.dataclass
class Request:
    uid: int
    tokens: np.ndarray           # prompt [len]
    out: list = dataclasses.field(default_factory=list)
    done: bool = False           # retired; a token still in flight drops
    submitted_at: float = 0.0    # perf_counter at submit()
    admitted_at: float = 0.0     # perf_counter when it took a slot
    first_token_at: float = 0.0  # perf_counter at first generated token
    issued: int = 0              # decode steps dispatched for it


class _Row(NamedTuple):
    """One row a dispatched step advances."""
    slot: int
    req: Request
    pos: int
    stall_s: float               # injected before the dispatch
    last: bool                   # the request's last decode step


@dataclasses.dataclass
class _InFlight:
    """A dispatched step whose tokens the host has not read yet."""
    nxt: Any                     # [slots] int32 on the device: the carry
    phase: str
    rows: list[_Row]
    started: float               # perf_counter at its dispatch's start
    active_slots: int            # slots held, and requests queued, as
    queue_depth: int             # it was dispatched


# Hoisted jitted steps, shared across engine instances: the key is
# (model, ctx, shards), so repeated engine construction (tests, the
# chaos leg, sweep points) reuses one traced + compiled pair instead of
# re-jitting per instance.  Unhashable models (ad-hoc test doubles) fall
# back to a per-call jit.
_DECODE_JIT_CACHE: dict = {}


def _replicated(ctx):
    """The sharding of the engine's carry: replicated over ``ctx``'s
    mesh, or None on one device."""
    if ctx is None:
        return None
    return jax.sharding.NamedSharding(ctx.mesh, jax.sharding.PartitionSpec())


def _jitted(model, ctx, shards: int):
    """``(decode, step)`` for ``(model, ctx, shards)``.

    ``decode(params, toks [slots, 1], cache, pos [slots]) -> (logits,
    cache)`` is the model's step alone.  ``step(params, inputs [4,
    slots], carry [slots], cache) -> (carry, cache)`` is what the engine
    runs: ``inputs`` holds per row the host's token, whether to use it
    (else the carry's), whether the row advances, and its position.
    The new carry is each advancing row's greedy argmax, and the old
    carry's token for every other row, so a row keeps its newest token
    through steps that do not advance it."""
    key: Any = (model, ctx, shards)
    try:
        cached = _DECODE_JIT_CACHE.get(key)
    except TypeError:
        key, cached = None, None
    if cached is not None:
        return cached
    if shards != 1:
        def decode(p, t, c, pos):
            return model.decode_step(p, t, c, pos, ctx=ctx, shards=shards)
    else:
        # plain call keeps duck-typed models (no ``shards`` kwarg) working
        def decode(p, t, c, pos):
            return model.decode_step(p, t, c, pos, ctx=ctx)
    sharding = _replicated(ctx)

    def engine_step(p, inputs, carry, c):
        tok, use_host, advance, pos = inputs
        toks = jnp.where(use_host != 0, tok, carry)
        logits, c = decode(p, toks[:, None], c, pos)
        best = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt = jnp.where(advance != 0, best, carry)
        if sharding is not None:
            # the carry goes back in as it comes out: one executable
            nxt = jax.lax.with_sharding_constraint(nxt, sharding)
        return nxt, c
    cached = (jax.jit(decode), jax.jit(engine_step))
    if key is not None:
        _DECODE_JIT_CACHE[key] = cached
    return cached


def _decode_fn(model, ctx, shards: int):
    """The jitted ``decode(params, toks, cache, pos) -> (logits,
    cache)`` of :func:`_jitted`, as it is lowered on its own."""
    return _jitted(model, ctx, shards)[0]


def _cast_counts(given, served) -> dict[str, int]:
    """What casting ``given`` to ``served`` did: the leaves whose dtype
    changed and the bytes written for them, and the floating leaves
    served as given."""
    cast = kept = nbytes = 0
    for a, b in zip(jax.tree.leaves(given), jax.tree.leaves(served)):
        if a.dtype != b.dtype:
            cast += 1
            nbytes += b.size * b.dtype.itemsize
        elif jnp.issubdtype(a.dtype, jnp.floating):
            kept += 1
    return {"cast_leaves": cast, "cast_bytes": nbytes, "kept_leaves": kept}


class ServingEngine:
    def __init__(self, model, params, cfg: ServeConfig, ctx=None):
        self.model = model
        self.cfg = cfg
        self.ctx = ctx
        # weights in the compute dtype, cast once here (the model's
        # ``serving_params``) so the decode step converts none; no
        # reference to the given tree is kept
        self._self_s: dict[str, float] = {}    # span name -> self seconds
        served = params
        if hasattr(model, "serving_params"):
            with obs.span("serve.cast_params", tally=self._self_s):
                served = jax.block_until_ready(model.serving_params(params))
        self._param_counts = _cast_counts(params, served)
        self.params = served
        self.queue: deque[Request] = deque()
        self.slots: list[Optional[Request]] = [None] * cfg.slots
        self.lengths = np.zeros(cfg.slots, np.int32)
        self.cache = None
        self._carry = None          # each row's newest token, on the device
        self._inflight: Optional[_InFlight] = None
        self._results: dict[int, list] = {}        # the current run()'s
        self._decode, self._engine_step = _jitted(model, ctx, cfg.shards)
        # running telemetry (cheap scalars; stats() snapshots them)
        self._steps = {"decode": 0, "prefill": 0}
        self._step_s = {"decode": 0.0, "prefill": 0.0}
        self._last_step_s = 0.0
        self._tokens_generated = 0
        self._requests: dict[int, dict[str, float]] = {}
        # host time by span, totals over the engine's life (stats()["host"])
        self._host = {"steps": 0, "dispatch_s": 0.0, "sync_s": 0.0,
                      "bookkeep_s": 0.0, "between_s": 0.0, "betweens": 0,
                      "queue_s": 0.0, "admitted": 0,
                      "first_token_wait_s": 0.0, "first_tokens": 0,
                      "ahead_steps": 0, "dropped_tokens": 0}
        # the host's last return from the device (a dispatch or a read),
        # in run(); the last read; stalls injected since the last read
        self._resumed_at: Optional[float] = None
        self._synced_at = float("-inf")
        self._stall_s = 0.0
        # robustness state: bounded-queue shedding, per-request deadlines,
        # slow-step/straggler detection over per-slot step times
        self._shed = 0
        self._deadline_expired = 0
        self._slow_steps = 0
        self._evicted_uids: list[int] = []
        self.monitor = StepMonitor(window=50)
        self.heartbeats = HeartbeatRegistry(
            timeout_s=cfg.heartbeat_timeout_s)

    # ------------------------------------------------------------ admit
    def submit(self, uid: int, tokens) -> bool:
        """Enqueue a request; returns False when the bounded queue sheds
        it (``shed_policy="reject"``).  With ``"drop_oldest"`` the oldest
        *queued* request is evicted instead and the new one admitted —
        back-pressure favouring freshness over fairness.  Every shed uid
        gets a terminal ``{shed: True}`` record in ``stats()`` so every
        submitted request has exactly one terminal outcome."""
        cfg = self.cfg
        if cfg.max_queue is not None and len(self.queue) >= cfg.max_queue:
            if cfg.shed_policy == "drop_oldest" and self.queue:
                victim = self.queue.popleft()
                self._shed += 1
                self._evicted_uids.append(victim.uid)
                self._record_shed(victim.uid)
                if obs.enabled():
                    obs.event("serve.shed", uid=victim.uid,
                              policy="drop_oldest",
                              queue_depth=len(self.queue))
            else:
                self._shed += 1
                self._record_shed(uid)
                if obs.enabled():
                    obs.event("serve.shed", uid=uid, policy="reject",
                              queue_depth=len(self.queue))
                return False
        self.queue.append(Request(uid=uid, tokens=np.asarray(tokens),
                                  submitted_at=time.perf_counter()))
        return True

    def _record_shed(self, uid: int) -> None:
        self._requests[uid] = {"n_tokens": 0, "ttft_s": 0.0,
                               "tokens_per_s": 0.0,
                               "deadline_exceeded": False, "shed": True}

    def _expired(self, req: Request,
                 now: Optional[float] = None) -> bool:
        if self.cfg.deadline_s is None:
            return False
        now = time.perf_counter() if now is None else now
        return now - req.submitted_at > self.cfg.deadline_s

    def _expire(self, req: Request, where: str) -> None:
        """Retire a request whose deadline lapsed (queued or in-slot)."""
        self._deadline_expired += 1
        if obs.enabled():
            obs.event("serve.deadline", uid=req.uid, where=where,
                      n_tokens=len(req.out),
                      waited_s=time.perf_counter() - req.submitted_at)
        self._retire(req, deadline_exceeded=True)

    def new_cache(self):
        """A fresh decode cache for every slot.  KV-sharded over a mesh
        (``shards > 1``), its K/V lie split along the sequence axis."""
        cache = self.model.init_cache(self.cfg.slots, self.cfg.max_len)
        if self.ctx is not None and self.cfg.shards > 1:
            cache = place_kv_cache(cache, self.ctx)
        return cache

    def _new_carry(self):
        """A carry of zeros, laid out as the step gives its carry back
        (replicated over a mesh), so the step compiles once."""
        zeros = np.zeros(self.cfg.slots, np.int32)
        sharding = _replicated(self.ctx)
        if sharding is None:
            return jnp.asarray(zeros)
        return jax.device_put(zeros, sharding)

    def _admit(self) -> None:
        """Fill free slots: per-slot prefill via teacher-forced decode of
        the prompt (single compiled step reused; avoids a second compiled
        prefill graph for ragged prompt lengths).  Queued requests whose
        deadline already lapsed are expired here instead of wasting a
        prefill on them; a deadline lapsing *mid-prefill* frees the slot
        immediately (where="prefill") so the next queued request reuses
        it."""
        cfg = self.cfg
        if self.cache is None:
            self.cache = self.new_cache()
            self._carry = self._new_carry()
        for i in range(cfg.slots):
            while self.slots[i] is None and self.queue:
                req = self.queue.popleft()
                if self._expired(req):
                    self._expire(req, where="queue")
                    continue         # expired: try the next queued request
                with obs.span("serve.admit", tally=self._self_s,
                              uid=req.uid, slot=i) as sp:
                    req.admitted_at = sp.start
                    queue_s = sp.start - req.submitted_at
                    sp.set(queue_s=queue_s)
                    self._host["queue_s"] += queue_s
                    self._host["admitted"] += 1
                    self.slots[i] = req
                    self.lengths[i] = 0
                    self._prefill(i, req)  # on lapse the slot is free again

    def _prefill(self, i: int, req: Request) -> bool:
        """Teacher-force the prompt into slot ``i`` one token per fused
        step; the deadline is re-checked between prefill tokens so a
        long prompt cannot burn unbounded steps past ``deadline_s``.
        Returns False (slot freed, partial cache rows reusable — the
        next occupant restarts at length 0 and overwrites them) when the
        deadline lapses mid-prompt."""
        prompt = req.tokens[:-1]                     # last token: decode
        with obs.span("serve.prefill", tally=self._self_s, uid=req.uid,
                      tokens=len(prompt)):
            for t_idx, tok in enumerate(prompt):
                if t_idx and self._expired(req):
                    self._drain()
                    self.slots[i] = None
                    self.lengths[i] = 0
                    self._expire(req, where="prefill")
                    return False
                toks = np.zeros(self.cfg.slots, np.int32)
                toks[i] = int(tok)
                self._step(toks, [i], phase="prefill")
        return True

    def _step(self, toks: np.ndarray, advance: list[int],
              phase: str = "decode", carried: tuple = ()) -> None:
        """Dispatch ONE fused compiled step for the whole slot batch,
        then read back and book the step dispatched before it.  Rows
        listed in ``advance`` commit their write (length bump) — the
        others step a pad token whose cache row is overwritten before it
        is ever attended to.  A row in ``carried`` takes its token from
        the device, its newest; every other row takes ``toks`` [slots].
        A decode row whose request has now been given its last step
        leaves its slot here; the request retires when that token is
        read.

        Per-slot stall injection (``serve_slow:slot<i>``) is timed
        per advancing slot so slow-step/straggler attribution survives
        the fusion: each slot's recorded latency is the shared step
        period plus its own injected stall.
        """
        from repro.runtime import faults
        cfg, host = self.cfg, self._host
        with obs.span("serve.dispatch", emit=False, tally=self._self_s,
                      phase=phase) as dispatch:
            stalls = []
            for i in advance:
                s0 = time.perf_counter()
                faults.sleep_if("serve_slow", f"slot{i}")  # injected stall
                stalls.append(time.perf_counter() - s0)
            # token, use the token, advance, position; a fresh array,
            # which jnp.asarray may alias on the CPU
            inputs = np.zeros((4, cfg.slots), np.int32)
            inputs[0] = toks
            inputs[1] = 1
            inputs[1, list(carried)] = 0
            inputs[2, advance] = 1
            inputs[3] = self.lengths
            self._carry, self.cache = self._engine_step(
                self.params, jnp.asarray(inputs), self._carry, self.cache)
        if self._resumed_at is not None:
            host["between_s"] += dispatch.start - self._resumed_at
            host["betweens"] += 1
        self._resumed_at = dispatch.end
        host["steps"] += 1
        host["dispatch_s"] += dispatch.duration_s
        host["ahead_steps"] += self._inflight is not None
        self._stall_s += sum(stalls)
        held, queued = self.active_slots(), len(self.queue)
        rows = []
        for i, stall in zip(advance, stalls):
            req = self.slots[i]
            last = False
            if phase == "decode":
                req.issued += 1
                last = (req.issued >= cfg.max_new_tokens
                        or self.lengths[i] + 1 >= cfg.max_len - 1)
                if last:
                    self.slots[i] = None
            rows.append(_Row(i, req, int(self.lengths[i]), stall, last))
            self.lengths[i] += 1
        prev, self._inflight = self._inflight, _InFlight(
            self._carry, phase, rows, dispatch.start, held, queued)
        if prev is not None:
            self._read(prev)

    def _drain(self) -> None:
        """Read back the step in flight, if there is one."""
        prev, self._inflight = self._inflight, None
        if prev is not None:
            self._read(prev)

    def _read(self, step: _InFlight) -> None:
        """Copy a dispatched step's tokens to the host and book it: the
        step's period and the slow-step monitor, its ``serve.step``
        event, and for a decode step each row's token appended and the
        requests it ends retired.  A row whose request was retired after
        the step was dispatched (its previous token was the end of
        sequence) is dropped: not appended, not named."""
        cfg, host, tally = self.cfg, self._host, self._self_s
        with obs.span("serve.sync", emit=False, tally=tally,
                      phase=step.phase) as sync:
            nxt = np.asarray(step.nxt)                 # the step edge
        with obs.span("serve.bookkeep", emit=False, tally=tally,
                      phase=step.phase) as bookkeep:
            now = self._resumed_at = sync.end
            latency = now - max(step.started, self._synced_at)
            self._synced_at = now
            host["sync_s"] += sync.duration_s
            base = max(latency - self._stall_s, 0.0)
            self._stall_s = 0.0
            rows = [r for r in step.rows if not r.req.done]
            host["dropped_tokens"] += len(step.rows) - len(rows)
            self._steps[step.phase] += 1
            self._step_s[step.phase] += latency
            self._last_step_s = latency
            self.heartbeats.beat("engine")
            for r in rows:
                slot = f"slot{r.slot}"
                slot_lat = base + r.stall_s
                med = self.monitor.medians().get(slot, 0.0)
                self.monitor.record(slot, slot_lat)
                if med > 0 and slot_lat > self.cfg.slow_step_factor * med:
                    self._slow_steps += 1
                    if obs.enabled():
                        obs.event("serve.slow_step", slot=r.slot,
                                  phase=step.phase, latency_s=slot_lat,
                                  median_s=med)
            if obs.enabled():
                obs.event("serve.step", phase=step.phase,
                          slots=[r.slot for r in rows],
                          uids=[r.req.uid for r in rows],
                          latency_s=latency,
                          active_slots=step.active_slots,
                          queue_depth=step.queue_depth,
                          pos=[r.pos for r in rows])
            if step.phase == "decode":
                for i, req, _, _, last in rows:
                    req.out.append(int(nxt[i]))
                    if not req.first_token_at:
                        req.first_token_at = now
                        host["first_token_wait_s"] += now - req.admitted_at
                        host["first_tokens"] += 1
                    if last or req.out[-1] == cfg.eos_id:
                        if self.slots[i] is req:
                            self.slots[i] = None
                        self._retire(req)
        host["bookkeep_s"] += bookkeep.duration_s

    # ------------------------------------------------------------ stats
    def active_slots(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    def _retire(self, req: Request, deadline_exceeded: bool = False,
                ) -> None:
        """Record per-request serving metrics as the slot frees, and
        the request's tokens among the current ``run()``'s results."""
        req.done = True
        self._results[req.uid] = req.out
        n = len(req.out)
        with obs.span("serve.retire", tally=self._self_s, uid=req.uid,
                      n_tokens=n) as sp:
            first = req.first_token_at
            gen_s = sp.start - (first or req.submitted_at)
            rec = {"n_tokens": n,
                   "ttft_s": first - req.submitted_at if first else 0.0,
                   "tokens_per_s": (n / gen_s if gen_s > 0 else 0.0),
                   "deadline_exceeded": deadline_exceeded, "shed": False}
            self._requests[req.uid] = rec
            self._tokens_generated += n
            if obs.enabled():
                obs.event("serve.request", uid=req.uid, **rec,
                          queue_s=(req.admitted_at - req.submitted_at
                                   if req.admitted_at else 0.0),
                          first_token_wait_s=(first - req.admitted_at
                                              if first else 0.0))

    def stats(self) -> dict[str, Any]:
        """Serving-telemetry snapshot (plain dict, json-clean).

        ``decode_steps``/``prefill_steps`` + mean/last step latencies,
        current ``slot_occupancy`` (active / configured) and
        ``queue_depth``, total ``tokens_generated``, one terminal
        record per submitted uid ``{uid: {n_tokens, ttft_s,
        tokens_per_s, deadline_exceeded, shed}}``, plus robustness
        counters: ``shed_requests``, ``deadline_expired``,
        ``slow_steps``, the StepMonitor's ``straggler_slots``, and
        ``heartbeat_alive`` (engine-loop liveness within
        ``heartbeat_timeout_s``).

        ``host`` holds running totals of the engine's host time, read
        from its spans: ``steps`` and their ``dispatch_s``, ``sync_s``
        and ``bookkeep_s``; ``between_s`` over ``betweens``, from one
        step's synced result to the next step's start within one
        ``run()``; ``queue_s`` over ``admitted`` (submit → slot);
        ``first_token_wait_s`` over ``first_tokens`` (slot → first
        generated token); and ``self_s``, each span's self seconds.

        ``params`` counts the cast to the served weights made at
        construction: ``cast_leaves`` changed dtype, writing
        ``cast_bytes``; ``kept_leaves`` floating leaves are served as
        given.
        """
        dec, pre = self._steps["decode"], self._steps["prefill"]
        return {
            "shed_requests": self._shed,
            "deadline_expired": self._deadline_expired,
            "slow_steps": self._slow_steps,
            "straggler_slots": list(self.monitor.stragglers()),
            "heartbeat_alive": "engine" in self.heartbeats.alive(),
            "decode_steps": dec,
            "prefill_steps": pre,
            "mean_decode_step_s": (self._step_s["decode"] / dec
                                   if dec else 0.0),
            "mean_prefill_step_s": (self._step_s["prefill"] / pre
                                    if pre else 0.0),
            "last_step_s": self._last_step_s,
            "active_slots": self.active_slots(),
            "slot_occupancy": self.active_slots() / self.cfg.slots,
            "queue_depth": len(self.queue),
            "tokens_generated": self._tokens_generated,
            "requests": {uid: dict(rec)
                         for uid, rec in self._requests.items()},
            "host": {**self._host, "self_s": dict(self._self_s)},
            "params": dict(self._param_counts),
        }

    # ------------------------------------------------------------- run
    def run(self, max_steps: int = 10_000) -> dict[int, list[int]]:
        """Drain the queue; returns {uid: generated tokens}.

        Every engine round is ONE fused decode step regardless of how
        many slots are active: the per-slot token/position vectors make
        the batch ragged-correct, so a round costs one compiled dispatch
        instead of one per active slot.  Each step is dispatched before
        the one ahead of it is read back; ``run()`` returns with no step
        in flight."""
        with obs.span("serve.run", tally=self._self_s):
            self._resumed_at = None  # the host gap counts within one run
            self._results = {}
            return self._run(max_steps)

    def _run(self, max_steps: int) -> dict[int, list[int]]:
        cfg, results = self.cfg, self._results
        rounds = 0
        self._admit()
        while rounds < max_steps:
            if not any(s is not None for s in self.slots):
                # the tokens in flight may bring requests (a client
                # that sends on its reply)
                self._drain()
                self._admit()
                if not any(s is not None for s in self.slots):
                    break
            with obs.span("serve.round", emit=False,
                          tally=self._self_s) as rnd:
                if any(r is not None and self._expired(r)
                       for r in self.slots):
                    # deadline lapsed mid-generation: return the partial
                    # output, the token in flight included, rather than
                    # burning more steps
                    self._drain()
                    for i, req in enumerate(self.slots):
                        if req is not None and self._expired(req):
                            self.slots[i] = None
                            self._expire(req, where="slot")
                active = [i for i, r in enumerate(self.slots)
                          if r is not None]
                if active:
                    rnd.set(uids=[self.slots[i].uid for i in active])
                    toks = np.zeros(cfg.slots, np.int32)
                    carried = []
                    for i in active:
                        req = self.slots[i]
                        if req.issued:
                            carried.append(i)
                        else:
                            toks[i] = int(req.tokens[-1])
                    self._step(toks, active, "decode", tuple(carried))
            self._admit()
            rounds += 1
        self._drain()
        for i, req in enumerate(self.slots):
            if req is not None:
                self.slots[i] = None
                self._retire(req)
        # requests shed before reaching a slot still get a (empty)
        # result entry so callers are never left waiting
        for uid in self._evicted_uids:
            results.setdefault(uid, [])
        self._evicted_uids.clear()
        return results
