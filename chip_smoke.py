#!/usr/bin/env python3
"""Smoke test of the serving path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # KV-sharded decode over four chips

One chip: yi-9b at its published widths (d_model 4096, 32 query and 4
KV heads of 128, d_ff 11008, vocab 64000) with the depth cut to
``LAYERS`` of its 48 layers and random weights from ``--seed``, served
by the ``ServingEngine`` that ``repro.launch.serve.build_engine`` builds
(8 slots, 2048 positions each): 8 requests, prompts of 128 tokens, 32
new tokens each.  Then one request's prompt and output are replayed
through the compiled decode step and the KV cache, and its logits are
compared with the same model's full forward pass.

``--chips 4`` runs only the sharded path and what it is compared with:
the same requests served with ``shards=1`` on one chip and with
``shards=4`` over a four-device mesh (the ``shard_map`` flash-decode
combine, the KV cache split along the sequence over the devices), the
replayed logits of the two compared, and the combine checked against
the one-chip kernel on random K/V that fill every device's slice.

The script fails, and prints no result line, when JAX finds no TPU, when
kernels would not run as compiled Pallas (``kernel_mode() != "pallas"``),
when any guarded kernel falls back (a ``kernel.fallback`` event), or
when any phase fails.  Its last line of output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Everything runs in this one process, which holds the chips.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# Depth cut, sized from ``memory_analysis()`` of the decode step compiled
# for one v5e (16 GiB HBM; ``repro.launch.serve.decode_step_memory``,
# kept true by tests/test_chip_compile.py): at 8 layers it needs 3.81 GiB
# of arguments (the engine's bf16 weights and the bf16 cache), 0.25 GiB
# of outputs and under 1 MiB of temporaries, beside the 7.11 GiB of f32
# weights this script keeps for the full-forward check, 11.17 GiB in
# all; at 12 layers 15.28 GiB, too close to the 16 GiB once the logits
# check keeps its own cache and program.
LAYERS = 8

# Tolerance of the logits check.  Both sides run bf16 activations
# through the same weights but in different orders: the decode step
# attends through the Pallas flash-decode kernel one token at a time,
# the full forward through XLA's attention over all positions.  One
# bf16 rounding is 2^-8 of a value, and such roundings accumulate over
# the 8 layers and the head, so the two may differ by a few percent of
# the logits' magnitude; 5% of the largest |logit| bounds that while
# still failing on a wrong position, cache row or mask.
REL_TOL = 0.05

# Tolerance of the sharded attention check.  Each device's partial
# output is rounded to bf16 (2^-9 relative) before the f32 merge, and
# the merged output is rounded again; the one-chip kernel rounds once.
# 2% of the largest |output| covers that with room, and still fails on
# a dropped or double-counted slice.
MERGE_TOL = 0.02


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def say(msg: str) -> None:
    print(msg, flush=True)


def serve_args(shards: int, requests: int, prompt_len: int, max_new: int,
               seed: int, layers: int = LAYERS, reduced: bool = False):
    """The launcher's own arguments for this run."""
    from repro.launch import serve
    argv = ["--layers", str(layers), "--slots", "8", "--max-len", "2048",
            "--requests", str(requests), "--prompt-len", str(prompt_len),
            "--max-new", str(max_new), "--shards", str(shards),
            "--seed", str(seed)]
    if reduced:
        argv += ["--reduced", "--max-len", "256"]
    return serve.parser().parse_args(argv)


def describe(cfg) -> str:
    return (f"config {cfg.name}: layers {cfg.n_layers} of 48 (depth cut), "
            f"d_model {cfg.d_model}, heads {cfg.n_heads} q / "
            f"{cfg.n_kv_heads} kv x {cfg.head_dim}, d_ff {cfg.d_ff}, vocab "
            f"{cfg.vocab_size}, params {cfg.n_params() / 1e9:.3f} B "
            f"({cfg.param_dtype}), compute {cfg.compute_dtype}")


class CompileLog:
    """JAX's own compile events over the run: seconds of backend compile
    (a persistent-cache hit costs only its retrieval), seconds of
    tracing and lowering, and the cache's hits and misses."""

    def __init__(self):
        import jax.monitoring as mon
        self.backend = self.trace = 0.0
        self.hits = self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend += secs
        elif event in ("/jax/core/compile/jaxpr_trace_duration",
                       "/jax/core/compile/jaxpr_to_mlir_module_duration"):
            self.trace += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def __str__(self):
        return (f"{self.backend!r} s backend compile (persistent cache: "
                f"{self.hits} hits, {self.misses} misses), {self.trace!r} s "
                "tracing and lowering")


def compile_decode(engine):
    """AOT-compile the engine's decode step; returns (seconds to trace
    and lower, seconds to compile, HLO text, the compiler's FLOP count
    per device)."""
    import jax.numpy as jnp
    slots = engine.cfg.slots
    cache = engine.new_cache()
    t0 = time.perf_counter()
    lowered = engine._decode.lower(
        engine.params, jnp.zeros((slots, 1), jnp.int32), cache,
        jnp.zeros((slots,), jnp.int32))
    t1 = time.perf_counter()
    compiled = lowered.compile()
    secs = time.perf_counter() - t1
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    return t1 - t0, secs, compiled.as_text(), cost.get("flops")


def serve(engine, args, vocab: int):
    """Answer ``args.requests`` requests; returns (prompts, results,
    seconds)."""
    from repro.launch.serve import prompts
    reqs = prompts(args, vocab)
    for uid, toks in enumerate(reqs):
        engine.submit(uid, toks)
    t0 = time.perf_counter()
    results = engine.run()
    wall = time.perf_counter() - t0
    if sorted(results) != list(range(args.requests)):
        fail(f"answered {sorted(results)}, submitted {args.requests}")
    short = {u: len(r) for u, r in results.items() if len(r) != args.max_new}
    if short:
        fail(f"requests ended short of {args.max_new} tokens: {short}")
    return reqs, results, wall


def replay_logits(engine, seq):
    """Teacher-force ``seq`` through the engine's compiled decode step
    and a fresh cache in slot 0; returns the [len(seq), vocab] logits."""
    import jax.numpy as jnp
    import numpy as np
    slots = engine.cfg.slots
    cache = engine.new_cache()
    out = []
    for t, tok in enumerate(seq):
        toks = np.zeros((slots, 1), np.int32)
        toks[0, 0] = tok
        pos = np.zeros((slots,), np.int32)
        pos[0] = t
        logits, cache = engine._decode(engine.params, jnp.asarray(toks),
                                       cache, jnp.asarray(pos))
        out.append(np.asarray(logits[0], np.float32))
    return np.stack(out)


def compare(name: str, got, want) -> float:
    import numpy as np
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        fail(f"{name}: non-finite logits")
    scale = float(np.abs(want).max())
    err = float(np.abs(got - want).max())
    agree = float((got.argmax(-1) == want.argmax(-1)).mean())
    say(f"{name}: max |diff| {err!r} vs max |logit| {scale!r} "
        f"(rel {err / scale!r}, tolerance {REL_TOL}); argmax agreement "
        f"{agree!r} over {got.shape[0]} positions")
    if not err <= REL_TOL * scale:
        fail(f"{name}: logits differ by {err!r} > {REL_TOL} x {scale!r}")
    return err / scale


def serve_phase(args) -> None:
    """One chip: serve the requests, check the compiled step and the
    logits.  ``args`` are the launcher's (see ``serve_args``)."""
    import jax
    import numpy as np
    from repro.kernels.common import kernel_mode
    from repro.launch.serve import build_engine

    t0 = time.perf_counter()
    cfg, model, params, engine = build_engine(args)
    jax.block_until_ready(params)
    say(describe(cfg))
    say(f"weights built on device in {time.perf_counter() - t0!r} s "
        f"(seed {args.seed})")
    lower_s, compile_s, hlo, _ = compile_decode(engine)
    n_kernels = hlo.count("tpu_custom_call")
    say(f"decode step: traced and lowered in {lower_s!r} s, compiled in "
        f"{compile_s!r} s; tpu_custom_call in the compiled step: "
        f"{n_kernels > 0} ({n_kernels} occurrences)")
    say(f"kernel_mode: {kernel_mode()}")
    reqs, results, wall = serve(engine, args, cfg.vocab_size)
    st = engine.stats()
    say(f"served {len(results)} requests in {wall!r} s: "
        f"{st['tokens_generated']} tokens generated, "
        f"{st['prefill_steps']} prefill + {st['decode_steps']} decode "
        f"steps, mean decode step {st['mean_decode_step_s']!r} s")
    if jax.default_backend() == "tpu" and not n_kernels:
        fail("the compiled decode step holds no Pallas kernel")
    seq = np.concatenate([reqs[0], np.asarray(results[0])])[:-1]
    dec = replay_logits(engine, seq)
    full = np.asarray(jax.jit(lambda p, t: model.logits(p, {"tokens": t}))(
        params, seq[None])[0], np.float32)
    compare("logits, decode through the cache vs full forward", dec, full)
    gen = dec[len(reqs[0]) - 1:].argmax(-1)
    say(f"replayed argmax equals the served tokens of request 0: "
        f"{bool((gen == np.asarray(results[0])).all())}")


def sharded_phase(args1, args4, col) -> None:
    """Four chips: the same requests with shards=1 on one chip and with
    shards=4 over a mesh of four; logits of request 0 compared.  ``col``
    is the run's ``obs`` collector."""
    import jax
    import numpy as np
    from repro.launch.serve import build_engine

    cfg, _, params, engine = build_engine(args1)
    say(describe(cfg))
    flops1 = compile_decode(engine)[3]
    reqs, res1, wall1 = serve(engine, args1, cfg.vocab_size)
    say(f"shards=1, one chip: {len(res1)} requests in {wall1!r} s, mean "
        f"decode step {engine.stats()['mean_decode_step_s']!r} s, "
        f"{flops1!r} FLOP per step")
    seq = np.concatenate([reqs[0], np.asarray(res1[0])])[:-1]
    ref = replay_logits(engine, seq)
    del engine, params

    cfg, _, params, engine = build_engine(args4)
    if engine.ctx is None:
        fail("serving_ctx(4) found no four-device mesh")
    mesh = engine.ctx.mesh
    say(f"shards=4 mesh: {dict(mesh.shape)} over "
        f"{[str(d) for d in mesh.devices.flat]}")
    lower_s, compile_s, hlo, flops4 = compile_decode(engine)
    say(f"sharded decode step: traced and lowered in {lower_s!r} s, "
        f"compiled in {compile_s!r} s; all-reduce in "
        f"the compiled step: {hlo.count('all-reduce')}; "
        f"tpu_custom_call: {hlo.count('tpu_custom_call')}; "
        f"{flops4!r} FLOP per device per step (tensor-parallel matmuls)")
    if jax.default_backend() == "tpu" and "tpu_custom_call" not in hlo:
        fail("the compiled sharded decode step holds no Pallas kernel")
    _, res4, wall4 = serve(engine, args4, cfg.vocab_size)
    strategies = {e.attrs.get("strategy")
                  for e in col.named("decode_attn.dispatch")}
    say(f"decode_attn dispatch strategies traced: {sorted(strategies)}")
    if strategies != {"shard_map"}:
        fail(f"the sharded decode took {sorted(strategies)}, "
             "not only shard_map")
    seq_len = engine.cfg.max_len
    for path, leaf in jax.tree_util.tree_leaves_with_path(engine.cache):
        shards = leaf.addressable_shards
        devs = {s.device for s in shards}
        lens = sorted({s.data.shape[2] for s in shards})
        name = jax.tree_util.keystr(path)
        say(f"KV cache {name}: {leaf.shape} over {len(devs)} devices, "
            f"sequence slice per device {lens}")
        if len(devs) != 4 or lens != [seq_len // 4]:
            fail(f"KV cache {name} is not split over four devices")
    say(f"shards=4, four chips: {len(res4)} requests in {wall4!r} s, mean "
        f"decode step {engine.stats()['mean_decode_step_s']!r} s; "
        f"tokens equal to shards=1 for "
        f"{sum(res4[u] == res1[u] for u in res1)} of {len(res1)} requests")
    got = replay_logits(engine, seq)
    compare("logits, shards=4 vs shards=1", got, ref)
    merge_check(engine.ctx, cfg, engine.cfg.slots, seq_len, args4.seed)


def merge_check(ctx, cfg, slots: int, seq_len: int, seed: int) -> None:
    """The served sequences above fill only the first of the four
    sequence slices, where the merge is exact.  Here random K/V fill
    the whole cache and each slot's length ends in a different slice,
    so every device's partial carries weight: the shard_map combine is
    compared with the one-chip kernel on the same data."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.kernels.decode_attn import ops, sharded

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    kv_shape = (slots, seq_len, cfg.n_kv_heads, cfg.head_dim)
    q = jax.random.normal(kq, (slots, cfg.n_heads, cfg.head_dim),
                          jnp.bfloat16)
    k = jax.random.normal(kk, kv_shape, jnp.bfloat16)
    v = jax.random.normal(kv, kv_shape, jnp.bfloat16)
    lens = jnp.asarray(np.linspace(1, seq_len, slots).round(), jnp.int32)
    want = np.asarray(jax.jit(ops.decode_attn)(q, k, v, kv_len=lens),
                      np.float32)
    split = NamedSharding(ctx.mesh, P(None, ctx.tp_axis))
    rep = NamedSharding(ctx.mesh, P())
    got = jax.jit(lambda q, k, v, n: sharded.dispatch(
        q, k, v, kv_len=n, shards=4, ctx=ctx))(
        jax.device_put(q, rep), jax.device_put(k, split),
        jax.device_put(v, split), jax.device_put(lens, rep))
    got = np.asarray(got, np.float32)
    if not np.isfinite(got).all():
        fail("sharded decode attention: non-finite output")
    err = float(np.abs(got - want).max())
    scale = float(np.abs(want).max())
    say(f"decode attention, shard_map over 4 vs one chip, kv_len "
        f"{np.asarray(lens).tolist()}: max |diff| {err!r} vs max |out| "
        f"{scale!r} (tolerance {MERGE_TOL})")
    if not err <= MERGE_TOL * scale:
        fail(f"sharded decode attention differs by {err!r} > "
             f"{MERGE_TOL} x {scale!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        fail(f"the repro package is not next to this script ({e})")
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX runs on {dev.platform!r}")
    if len(devices) < args.chips:
        fail(f"{args.chips} chips asked for, JAX sees {len(devices)}")
    from repro.kernels.common import kernel_mode
    from repro.launch.compile_cache import setup_compile_cache
    from repro.roofline.hw import hw_for
    from repro import obs
    if kernel_mode() != "pallas":
        fail(f"kernel_mode() is {kernel_mode()!r}, not 'pallas'")
    hw = hw_for(dev.device_kind)
    say(f"device: {dev.device_kind} x {len(devices)}; peaks "
        f"{hw.peak_flops_bf16 / 1e12:.0f} TFLOP/s bf16, "
        f"{hw.hbm_bw / 1e9:.0f} GB/s HBM")
    say(f"compile cache: {setup_compile_cache()}")

    compiles = CompileLog()
    t0 = time.perf_counter()
    with obs.collect() as col:
        if args.chips == 1:
            serve_phase(serve_args(1, 8, 128, 32, args.seed))
        else:
            sharded_phase(serve_args(1, 8, 32, 16, args.seed),
                          serve_args(4, 8, 32, 16, args.seed), col)
    fallbacks = col.named("kernel.fallback")
    say(f"kernel.fallback events: {len(fallbacks)}")
    if fallbacks:
        fail(f"kernels fell back: {[e.attrs for e in fallbacks]}")
    say(f"compile over the run: {compiles}")
    say(f"total {time.perf_counter() - t0!r} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
