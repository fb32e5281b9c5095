"""Shared kernel utilities: mode dispatch, padding, divisibility, and
the guarded-dispatch fallback chain (classify a kernel failure →
degrade alt-config → interpret → ref, quarantining the failing config
in the tune cache)."""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.striding import (SINGLE_STRIDED, StridingConfig,
                                 choose_block, pad_to_multiple)

__all__ = [
    "kernel_mode", "use_pallas", "interpret_mode",
    "pad_axis", "pad_to_multiple", "choose_block", "resolve_config",
    "reset_plan_memo", "example_input",
    "classify_failure", "guarded_run",
]


def example_input(shape, key: int = 0, dtype=jnp.float32) -> jax.Array:
    """Deterministic example operand for registry specs / conformance."""
    return jax.random.normal(jax.random.PRNGKey(key), shape,
                             jnp.float32).astype(dtype)


def kernel_mode() -> str:
    """Kernel dispatch mode.

    'pallas'    — compiled pallas_call (TPU target)
    'interpret' — pallas_call(interpret=True): kernel body runs in Python
                  on CPU; used by tests to validate against ref oracles
    'ref'       — pure-jnp reference (XLA ops); default on CPU so the
                  dry-run/roofline HLO reflects the same math without
                  interpret-mode overhead

    Override with REPRO_KERNEL_MODE.
    """
    env = os.environ.get("REPRO_KERNEL_MODE")
    if env:
        if env not in ("pallas", "interpret", "ref"):
            raise ValueError(f"bad REPRO_KERNEL_MODE={env}")
        return env
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def use_pallas() -> bool:
    return kernel_mode() in ("pallas", "interpret")


def interpret_mode() -> bool:
    return kernel_mode() == "interpret"


def pad_axis(x: jax.Array, axis: int, multiple: int, value=0) -> jax.Array:
    """Zero-pad `axis` of x up to a multiple (paper §5.1.2: step-size
    divisibility — we pad+crop instead of processing leftovers)."""
    n = x.shape[axis]
    target = -(-n // multiple) * multiple
    if target == n:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - n)
    return jnp.pad(x, pads, constant_values=value)


# pad_to_multiple / choose_block live in repro.core.striding (shared
# with repro.codegen.transforms) and are re-exported here for the ops
# wrappers.


def effective_config(config: StridingConfig | None, rows: int | None,
                     default: StridingConfig,
                     align: int = 1) -> StridingConfig:
    """Clamp a config's stride_unroll to divide `rows` (``rows=None`` =
    no divisibility constraint — the kernel pads+crops instead).  With
    ``align > 1`` each of the D streams must also hold a multiple of
    ``align`` rows where some D allows it (see :func:`row_align`), so 8
    decode rows run as D=1."""
    cfg = config or default
    if rows is None:
        return cfg
    d = cfg.stride_unroll
    while d > 1 and (rows % d or (rows // d) % align):
        d -= 1
    if d != cfg.stride_unroll:
        cfg = cfg.replace(stride_unroll=max(d, 1))
    return cfg


# planner results are pure in (kernel, shape, dtype, backend, spec) —
# memoized so a hot loop (e.g. adamw per tensor per step) doesn't re-rank
# on every call.  The backend is part of the key: the DMA model's
# parameters are per-machine, so a result planned under one backend must
# not leak into another.  The tune-cache lookup stays per-call: a fresh
# autotune write must win.
_plan_memo: dict[tuple, StridingConfig | None] = {}


def reset_plan_memo() -> None:
    """Drop memoized planner results (tests repoint backends / DMA-model
    env knobs; pair with ``tunecache.reset_default_cache()``)."""
    _plan_memo.clear()


def row_align(spec, mode: str | None) -> int:
    """Rows each of the D streams must hold a multiple of.  In
    ``pallas`` mode that is Mosaic's tiling rule for the kernel's spec
    (or tuple of specs), see ``codegen.transforms.row_align``; without
    a spec, the 8-row sublane tile every block needs.  The interpreter
    and the oracle take any row count."""
    if mode != "pallas":
        return 1
    if spec is None:
        return 8
    from repro.codegen.transforms import row_align as spec_row_align
    return spec_row_align(spec)


def resolve_config(kernel: str, shape, dtype, config, rows: int | None,
                   default: StridingConfig, traffic=None,
                   mode: str | None = None, spec=None) -> StridingConfig:
    """Config resolution chain for an op wrapper (paper §6.3 policy):

        explicit config  >  tune-cache (measured best)  >  planner model
        >  static default

    Runs *outside* jax.jit on purpose: a tune-cache write must be visible
    to the next call, which a jit-cached trace would freeze out.  The
    result is clamped so stride_unroll divides ``rows``; pass
    ``rows=None`` when the kernel's pad+crop makes any D valid (§5.1.1
    loop-blocked 1-D nests).  ``spec`` (the kernel's ``TraversalSpec``,
    or a tuple for a composite) screens the planner's candidates and,
    in ``pallas`` mode, sets how many rows each stream must hold
    (:func:`row_align`): D is clamped further to meet it.

    With telemetry on, every call emits one ``kernel.resolve`` event
    recording which source won and the resolved config, plus
    ``kernel.plan_memo.hit``/``.miss`` counters for the planner memo.
    """
    source = "explicit"
    if config is None:
        source = "default"
        from repro.registry import tunecache
        config = tunecache.cached_config(kernel, shape, dtype, mode=mode)
        if config is not None:
            source = "tuned"
        elif traffic is not None:
            names = tuple(s.name for s in (
                spec if isinstance(spec, tuple) else (spec,))
                if s is not None)
            key = (kernel, tuple(shape), str(jnp.dtype(dtype)), names,
                   jax.default_backend())
            if key in _plan_memo:
                config = _plan_memo[key]
                obs.counter("kernel.plan_memo.hit", kernel=kernel)
            else:
                from repro.core.planner import plan
                try:
                    config = plan(traffic, spec=spec).config
                except ValueError:
                    config = None
                _plan_memo[key] = config
                obs.counter("kernel.plan_memo.miss", kernel=kernel)
            if config is not None:
                source = "planned"
    align = row_align(spec, mode)
    cfg = effective_config(config, rows, default, align)
    if source != "explicit":
        # a config the guarded fallback chain watched fail must never be
        # re-resolved: the tuned source already skips quarantined entries
        # (tunecache.config_for); this guards the planned/default sources
        from repro.registry import tunecache
        cache = tunecache.default_cache()
        qkey = tunecache.cache_key(kernel, shape, dtype, mode=mode)
        if cache.is_quarantined(qkey, cfg):
            cfg = _next_unquarantined(cache, qkey, cfg, rows, default,
                                      traffic, spec=spec, align=align)
            source = "quarantine_alt"
            obs.counter("kernel.quarantine_skip", kernel=kernel)
    if obs.enabled():
        obs.event("kernel.resolve", kernel=kernel, source=source,
                  d=cfg.stride_unroll, p=cfg.portion_unroll,
                  block_rows=cfg.block_rows, arrangement=cfg.arrangement,
                  mode=mode)
    return cfg


def _next_unquarantined(cache, qkey: str, failed: StridingConfig,
                        rows: int | None, default: StridingConfig,
                        traffic, spec, align: int) -> StridingConfig:
    """Best non-quarantined alternative: next planner-ranked configs,
    then the static default, then single-strided (D=1 streams one
    contiguous run — the most conservative point in the space, kept as
    the unconditional floor even if it too is quarantined: resolution
    must return *something* and D=1 is the least likely to re-fail)."""
    cands = []
    if traffic is not None:
        from repro.core.planner import rank_configs
        try:
            cands = [c for c, _bw, _cols in rank_configs(traffic,
                                                         spec=spec)]
        except ValueError:
            cands = []
    cands += [default, SINGLE_STRIDED]
    for cand in cands:
        cand = effective_config(cand, rows, cand, align)
        if not cache.is_quarantined(qkey, cand):
            return cand
    return SINGLE_STRIDED


# ------------------------------------------------- guarded dispatch

# failure classes the guard distinguishes (recorded in the quarantine
# entry and the kernel.fallback event):
#   injected        — repro.runtime.faults fired at an injection point
#   analysis        — the static verifier rejected the plan BEFORE any
#                     emission (repro.analysis: race/bounds/VMEM rules)
#   unsupported     — the emitter refused the (spec, config) combination
#   resource        — VMEM/scratch/memory exhaustion in lowering/compile
#   invalid_config  — config rejected by validation (ValueError & kin)
#   backend         — XLA/runtime execution failure
_RESOURCE_MARKERS = ("vmem", "out of memory", "resource exhausted",
                     "scratch", "allocat")


def classify_failure(exc: BaseException) -> str:
    """Map a kernel lowering/execution failure onto a degradation class."""
    from repro.runtime.faults import InjectedFault
    from repro.analysis.findings import AnalysisError
    if isinstance(exc, InjectedFault):
        return "injected"
    if isinstance(exc, AnalysisError):
        # checked before the marker scan: a RES001 finding's message
        # names VMEM, which would otherwise misclassify as "resource"
        return "analysis"
    if isinstance(exc, NotImplementedError):
        return "unsupported"
    msg = str(exc).lower()
    if any(m in msg for m in _RESOURCE_MARKERS):
        return "resource"
    if isinstance(exc, (ValueError, TypeError)):
        return "invalid_config"
    return "backend"


def _fallback_tiers(cache, qkey: str, failed: StridingConfig,
                    mode: str, rows: int | None, traffic, spec):
    """The degradation chain after ``failed`` crashed in ``mode``:
    next-ranked planner configs (same mode) → interpret → ref oracle.
    On a TPU backend the interpret tier is left out: the interpreter
    there is orders of magnitude slower than either kernel or oracle."""
    align = row_align(spec, mode)
    tiers = []
    if traffic is not None:
        from repro.core.planner import rank_configs
        try:
            ranked = [c for c, _bw, _cols in rank_configs(traffic,
                                                          spec=spec)]
        except ValueError:
            ranked = []
        seen = {(failed.stride_unroll, failed.portion_unroll,
                 failed.block_rows)}
        for cand in ranked:
            cand = effective_config(cand, rows, cand, align)
            key = (cand.stride_unroll, cand.portion_unroll,
                   cand.block_rows)
            if key in seen or cache.is_quarantined(qkey, cand):
                continue
            seen.add(key)
            tiers.append(("alt_config", cand, mode))
            if len(tiers) >= 2:
                break
    if mode == "pallas" and jax.default_backend() != "tpu":
        # interpret escapes backend/VMEM failures (the body runs in
        # Python) while still exercising the generated lowering
        tiers.append(("interpret", failed, "interpret"))
    tiers.append(("ref", failed, "ref"))
    return tiers


def guarded_run(kernel: str, run, cfg: StridingConfig, mode: str, *,
                shape, dtype, rows: int | None = None, traffic=None,
                spec=None):
    """Execute ``run(cfg, mode)`` behind the fallback chain.

    On failure the error is classified (:func:`classify_failure`), the
    failing config is quarantined in the tune cache under the same key
    resolution uses (so it is never re-resolved), and the call degrades
    down the chain — next-ranked planner config, interpret mode, ref
    oracle — emitting one ``kernel.fallback`` event recording the
    failure class and the tier that served the result.  ``ref`` mode has
    no tier below it: a ref failure is an oracle bug and re-raises
    untouched.

    ``spec`` rides into the planner's candidate ranking (and sets the
    rows each stream of an alternative holds, :func:`row_align`) so
    alternative tiers are themselves pre-screened by the static
    verifier — a statically-rejected config (failure class
    ``analysis``) degrades straight past the emitting tiers to the ref
    oracle with ZERO ``pallas_call`` construction attempts.

    The ``lower`` fault-injection site fires here (non-ref modes), so
    ``REPRO_FAULTS=lower:<kernel>`` forces any guarded kernel down the
    chain deterministically.
    """
    from repro.runtime import faults

    def attempt(c: StridingConfig, m: str):
        if m != "ref":
            faults.fire_if("lower", kernel)
        return run(c, m)

    try:
        return attempt(cfg, mode)
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception as exc:                 # noqa: BLE001 — classified below
        if mode == "ref":
            raise
        failure = classify_failure(exc)
        from repro.registry import tunecache
        cache = tunecache.default_cache()
        qkey = tunecache.cache_key(kernel, shape, dtype, mode=mode)
        cache.quarantine(qkey, cfg, failure)
        obs.counter("kernel.fallback.count", kernel=kernel)
        for tier, tcfg, tmode in _fallback_tiers(cache, qkey, cfg, mode,
                                                 rows, traffic, spec):
            try:
                out = attempt(tcfg, tmode)
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as exc2:        # noqa: BLE001 — keep degrading
                if tier == "alt_config":
                    cache.quarantine(qkey, tcfg, classify_failure(exc2))
                continue
            obs.event("kernel.fallback", kernel=kernel, failure=failure,
                      tier=tier, from_mode=mode, to_mode=tmode,
                      failed_d=cfg.stride_unroll,
                      failed_p=cfg.portion_unroll,
                      failed_block_rows=cfg.block_rows,
                      d=tcfg.stride_unroll, p=tcfg.portion_unroll)
            return out
        raise exc
