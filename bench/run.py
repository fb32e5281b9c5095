#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload yi9b-reason --seed 7 --seconds 40 \
        --trace 0

Set-up makes the cell's weights on the device from ``--seed``, builds
the program's serving engine and warms its step up; then a closed loop
of clients (``bench/traffic/<mix>.json``) runs against the engine for
``--seconds``, and the engine is cut at the close.  With ``--trace 1``
the window runs under the profiler and the run reports the cell's
per-layer metrics; otherwise its end-to-end metrics.  Afterwards the
served tokens of a sample of the finished requests are compared with
the plain reference (``bench.check``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced), and last ``check``, each number compared beside its
limit.  The run exits non-zero and prints no such line when JAX finds
no TPU or fewer chips than the cell asks for, when kernels would not
run as compiled Pallas, or when any kernel falls back.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CACHE_DIR = ROOT / ".jax_cache"          # fixed: the path keys the cache


class RunFailed(RuntimeError):
    """The run cannot give a result (no chip, fallback kernels...)."""


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def setup_jax(cache: bool):
    import jax
    if not cache:
        return jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def check_devices(jax, chips: int, require_chip: bool):
    from bench import program
    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            raise RunFailed(f"no TPU: JAX runs on {devices[0].platform!r}")
        if len(devices) < chips:
            raise RunFailed(f"the cell asks for {chips} chips, JAX sees "
                            f"{len(devices)}")
        if program.kernel_mode() != "pallas":
            raise RunFailed(f"kernel_mode() is {program.kernel_mode()!r}, "
                            "not 'pallas'")
    return devices


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def drive(jax, loop, seconds: float, traced: bool):
    """Warm the engine up, then run the window (under the profiler when
    ``traced``).  Returns (compiles inside the window, the trace as
    XSpace bytes or None)."""
    from bench import compiles, trace
    log = compiles.CompileLog()
    loop.warm()
    session = None
    if traced:
        # the session jax.profiler.start_trace wraps, kept in memory:
        # stop() hands back the trace instead of writing it to disk
        from jax._src.lib import _profiler
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        session = _profiler.ProfilerSession(opts)
        window = jax.profiler.TraceAnnotation(trace.WINDOW)

        def mark(what):
            with jax.profiler.TraceAnnotation(trace.MARK + what):
                pass
        loop.on_mark = mark
        loop.on_open = window.__enter__
        loop.on_close = lambda: window.__exit__(None, None, None)
    snap = log.snapshot()
    loop.drive(seconds)
    in_window = log.since(snap)
    return in_window, (session.stop() if session is not None else None)


def compare(cell, loop, params, seed: int, control: bool):
    """The served tokens of a sample of finished requests against the
    reference, after the program's state is freed.  Returns (numbers
    compared with their limits, correct, the control's verdict or
    None).  The control, the float8 reference in the program's place,
    is judged by the same limits."""
    from bench import check, program, spec, traffic
    dims, mix = cell.config["dims"], cell.traffic
    weights = program.from_program(params, spec.layout(cell.config), dims)
    finished = [r for r in loop.finished()
                if r.served is not None and len(r.served) == len(r.stamps)]
    picked = check.sample(finished, mix, seed)
    length = -(-traffic.longest_sequence(mix) // 128) * 128
    t0 = time.perf_counter()
    got, ctl = check.gaps(spec.reference(cell.config), weights, dims,
                          picked, length, control)
    say(f"reference over {len(picked)} requests "
        f"({sum(len(r.served) for r in picked)} served tokens) in "
        f"{time.perf_counter() - t0!r} s")
    limit = cell.settings["limits"]["logit_gap"]
    compared = {
        "logit_gap": {"value": max(got, default=None), "limit": limit},
        "unserved": {"value": check.unserved(loop, dims["vocab"]),
                     "limit": 0},
    }
    verdict = None
    if control:
        # the control serves nothing of its own: only its gap is compared
        theirs = {"logit_gap": {"value": max(ctl, default=None),
                                "limit": limit}}
        verdict = {"correct": check.passes(theirs), "check": theirs,
                   "per_request": ctl, "program_per_request": got}
    return compared, check.passes(compared), verdict


def run_cell(cell, seed: int, seconds: float, traced: bool,
             require_chip: bool = True, control: bool = False,
             t_start: float = T_START) -> dict:
    """One run of ``cell`` (a ``bench.spec.Cell``); returns the result
    object.  ``require_chip=False`` lets the CPU tests drive it."""
    jax = setup_jax(cache=require_chip)
    t_jax = time.perf_counter()
    from bench import hw, program, spec, trace, traffic
    from bench.loop import ClosedLoop
    from bench.record import Record

    devices = check_devices(jax, cell.chips, require_chip)
    t_devices = time.perf_counter()
    peaks = hw.peaks(devices[0].device_kind) if require_chip else None
    dims, settings, mix = cell.config["dims"], cell.settings, cell.traffic

    model = program.build_model(dims, cell.config["name"])
    ctx = program.serving_ctx(settings["shards"])
    if settings["shards"] > 1 and ctx is None:
        raise RunFailed(f"no mesh of {settings['shards']} devices")
    t_model = time.perf_counter()
    params = program.make_weights(cell.config, model, seed, ctx)
    jax.block_until_ready(params)
    t_weights = time.perf_counter()
    loop = ClosedLoop(program.build_engine(model, params, settings,
                                           mix["output_len"], ctx),
                      traffic.schedule(mix, seed, dims["vocab"]))
    t_engine = time.perf_counter()
    program.install(loop)
    try:
        in_window, xspace = drive(jax, loop, seconds, traced)
    finally:
        program.uninstall()
    mem = memory_peak(devices[:cell.chips])
    setup_s = loop.t_open - t_start
    say(f"set-up {setup_s!r} s: import jax {t_jax - t_start!r} s, TPU "
        f"runtime start {t_devices - t_jax!r} s, model "
        f"{t_model - t_devices!r} s, weights {t_weights - t_model!r} s, "
        f"engine {t_engine - t_weights!r} s, warm-up "
        f"{loop.t_open - t_engine!r} s")
    say(f"compiles inside the window: "
        f"{in_window['compiles']} (traces {in_window['traces']}, cache "
        f"hits {in_window['cache_hits']}, misses "
        f"{in_window['cache_misses']})")
    if loop.fallbacks:
        raise RunFailed(f"kernels fell back: {loop.fallbacks}")
    if settings["shards"] > 1 and loop.strategies != {"shard_map"}:
        raise RunFailed(f"sharded decode took {sorted(loop.strategies)}, "
                        "not only shard_map")
    say(f"window {loop.window_s!r} s: {len(loop.steps)} steps, "
        f"{len(loop.sent())} requests sent, {len(loop.finished())} "
        f"finished, {sum(len(r.stamps) for r in loop.sent())} tokens")

    tr = None
    if xspace is not None:
        tr = trace.reduce(jax.profiler.ProfileData.from_serialized_xspace(
            xspace))
    rec = Record(work=spec.work(cell.config), chips=cell.chips, peaks=peaks,
                 window_s=loop.window_s, setup_s=setup_s, steps=loop.steps,
                 reqs=loop.sent(), stats_open=loop.stats_open,
                 stats_close=loop.stats_close, trace=tr)
    metrics = {}
    for m in cell.metrics_of("per_layer" if traced else "end_to_end"):
        value = m.reader().read(rec)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}

    loop.engine = None                   # free the program's state
    gc.collect()
    compared, correct, ctl = compare(cell, loop, params, seed, control)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    out = {"correct": correct, "attempted": len(loop.sent()),
           "failed": sum(r.refused for r in loop.sent()),
           "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"] = trace.busy_s(tr)
        device["window_s"] = tr.window_s
        out["breakdown"] = trace.breakdown(tr)
    if ctl is not None:
        out["control"] = ctl
        for name, c in ctl["check"].items():
            say(f"control {name} {c['value']!r} limit {c['limit']!r}")
        say(f"control correct {ctl['correct']!r}")
    out["check"] = compared
    for name, c in compared.items():
        say(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return out


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        from bench import spec
        cell = spec.load_cell(args.workload)
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except (RunFailed, ImportError, KeyError, FileNotFoundError) as e:
        say(f"FAIL: {type(e).__name__}: {e}")
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
