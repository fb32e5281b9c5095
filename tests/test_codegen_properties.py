"""Property-based differential tests for the codegen pipeline.

Random small ``TraversalSpec``s (≤4 axes; affine access maps with
optional halos, rank-1 row streams, resident reads and scalars;
reduce / no-reduce including paired-state and finalizing combinators;
multi-output with SHARED and with DISTINCT per-write access maps — a
rank-1 row statistic or a log-sum-exp next to a matrix write;
per-write combinators — a row-max accumulator next to a row-sum;
transposed stores — the write map permuting the stride axis after the
vector axis; writes-only; batch axes incl. 4-D batched nests;
combinators under ``block_rows`` blocking; 1-D blocked nests) × random
legal schedules
(StridingConfig points — D × P × block_rows × arrangement × lookahead —
plus raw unroll / interchange / stride_split / block compositions),
checked two ways:

  * the *schedule algebra* property: every legal transform composition
    ``preserves_domain`` (covers the iteration domain exactly once), and
    illegal factors raise;
  * the *differential* property: when the default §5.1 schedule
    preserves the domain, the emitted Pallas kernel
    (``pallas_call(interpret=True)``) equals the pure-jnp ``evaluate()``
    oracle — the Hashemi et al. lesson that access-pattern machinery is
    only trustworthy under adversarial pattern coverage.

The case generator is written against a tiny ``Draw`` adapter, so ONE
generator drives both the hypothesis strategies (CI codegen job:
``--hypothesis-profile=ci``, 120 examples per test per kernel-mode leg)
and a seeded stdlib-``random`` sweep that runs even where hypothesis is
not installed.  Both run identically under either ``REPRO_KERNEL_MODE``
leg: the comparison is always emitted-interpret vs ``evaluate``.
"""
import dataclasses
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.codegen import (Access, Axis, OnlineSoftmax, TraversalSpec,
                           classify, emit_spec, evaluate, tap, transforms)
from repro.codegen.combine import SumCombine
from repro.core.striding import StridingConfig


class _SumAndTotal(SumCombine):
    """Test-local finalizing single-state combinator: finalize emits
    the accumulated row AND its total — one state, two writes with
    distinct access maps."""

    name = "sum_with_total"
    finalizing = True

    def finalize(self, state):
        row = state[0]
        return row, row.sum(axis=-1, keepdims=True)

try:
    from hypothesis import given
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# --------------------------------------------------------- draw adapter

class Draw:
    """One generator, two engines: hypothesis ``data.draw`` (strategy-
    aware shrinking) or a seeded ``random.Random`` (no hypothesis
    needed)."""

    def __init__(self, data=None, rng=None):
        self.data, self.rng = data, rng

    def integer(self, lo, hi):
        if self.data is not None:
            return self.data.draw(st.integers(lo, hi))
        return self.rng.randint(lo, hi)

    def sample(self, options):
        options = list(options)
        if self.data is not None:
            return self.data.draw(st.sampled_from(options))
        return self.rng.choice(options)

    def boolean(self):
        return bool(self.sample([False, True]))


def _divisors(n):
    return [d for d in range(1, n + 1) if n % d == 0]


def _arr(shape, key):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


# ------------------------------------------------------- spec generator

@dataclasses.dataclass
class Case:
    spec: TraversalSpec
    inputs: tuple
    d_options: tuple      # legal stride_unroll values
    rtol: float = 2e-5
    atol: float = 2e-5


def draw_case(draw: Draw) -> Case:
    rows = draw.sample([4, 6, 8, 12])
    cols = draw.sample([3, 5, 8, 16])
    kind = draw.sample(["map", "multiout", "stencil", "vecred",
                        "stridered", "osm", "batch", "fill", "1d",
                        "multiout_maps", "multiout_vecred", "batch4d",
                        "osm_lse", "perwrite_vecred", "transpose"])
    any_d = (1, 2, 4)

    if kind == "map":
        x = _arr((rows, cols), 0)
        reads = [Access("x", ("i", "j"))]
        inputs = [x]
        terms = ['env["x"]']
        if draw.boolean():                       # second streamed read
            reads.append(Access("y", ("i", "j")))
            inputs.append(_arr((rows, cols), 1))
            terms.append('2.0 * env["y"]')
        if draw.boolean():                       # resident vector read
            reads.append(Access("v", ("j",)))
            inputs.append(_arr((cols,), 2))
            terms.append('env["v"][None, :]')
        if draw.boolean():                       # rank-1 row stream
            reads.append(Access("u", ("i",)))
            inputs.append(_arr((rows,), 3))
            terms.append('env["u"][..., None]')
        scalars = ()
        if draw.boolean():
            scalars = ("alpha",)
            inputs.append(1.5)
            terms.append('env["alpha"] * env["x"]')
        expr = " + ".join(terms)
        spec = TraversalSpec(
            name="prop_map",
            axes=(Axis("i", rows), Axis("j", cols)),
            reads=tuple(reads),
            writes=(Access("z", ("i", "j")),),
            scalars=scalars,
            body=eval(f'lambda env: {expr}'),  # noqa: S307 — test-local
        )
        return Case(spec, tuple(inputs), any_d)

    if kind == "multiout":
        x, y = _arr((rows, cols), 0), _arr((rows, cols), 1)
        n_out = draw.sample([2, 3])
        writes = tuple(Access(f"z{o}", ("i", "j")) for o in range(n_out))
        spec = TraversalSpec(
            name="prop_multiout",
            axes=(Axis("i", rows), Axis("j", cols)),
            reads=(Access("x", ("i", "j")), Access("y", ("i", "j"))),
            writes=writes,
            body=lambda env: tuple(
                env["x"] * (o + 1.0) - o * env["y"] for o in range(n_out)),
            out_dtype=(jnp.float32,) * n_out,
        )
        return Case(spec, (x, y), any_d)

    if kind == "multiout_vecred":
        # multi-output vector-axis reduction: one f32 accumulator per
        # write, additive partials (the historical vecred contract)
        x, y = _arr((rows, cols), 0), _arr((rows, cols), 1)
        spec = TraversalSpec(
            name="prop_multiout_vecred",
            axes=(Axis("i", rows), Axis("j", cols, kind="reduction")),
            reads=(Access("x", ("i", "j")), Access("y", ("i", "j"))),
            writes=(Access("a", ("i",)), Access("b", ("i",))),
            body=lambda env: (
                env["x"].astype(jnp.float32).sum(axis=-1),
                (env["x"] * env["y"]).astype(jnp.float32).sum(axis=-1)),
            out_dtype=(jnp.float32, jnp.float32),
        )
        return Case(spec, (x, y), any_d)

    if kind == "multiout_maps":
        # DISTINCT per-write access maps: the rank-2 map output next to
        # a rank-1 row statistic (rmsnorm's inv-rms archetype); under a
        # non-default lookahead this also exercises the manual ring's
        # per-output staging widths
        x = _arr((rows, cols), 0)
        spec = TraversalSpec(
            name="prop_multiout_maps",
            axes=(Axis("i", rows), Axis("j", cols)),
            reads=(Access("x", ("i", "j")),),
            writes=(Access("z", ("i", "j")), Access("r", ("i",))),
            body=lambda env: (env["x"] * 2.0 + 1.0,
                              env["x"].astype(jnp.float32).sum(axis=-1)),
            out_dtype=(jnp.float32, jnp.float32),
            full_width=True,    # the row statistic needs whole rows
        )
        return Case(spec, (x,), any_d)

    if kind == "batch4d":
        b = draw.sample([2, 3])
        if draw.boolean():              # 4-D batched map with free axis
            f = draw.sample([2, 4])
            x = _arr((b, rows, cols), 0)
            c = _arr((f, cols), 1)
            spec = TraversalSpec(
                name="prop_batch4d_map",
                axes=(Axis("b", b, kind="batch"), Axis("i", rows),
                      Axis("f", f), Axis("j", cols)),
                reads=(Access("x", ("b", "i", "j")),
                       Access("c", ("f", "j"))),
                writes=(Access("z", ("b", "i", "f", "j")),),
                body=lambda env: (env["x"][..., :, None, :]
                                  * env["c"][None, :, :]),
                out_dtype=jnp.float32,
            )
            return Case(spec, (x, c), any_d)
        # 4-D batched stride-reduction with a finalizing combinator and
        # per-write maps: the reduced row next to its (b, t) total
        x = _arr((b, rows, cols), 0)
        spec = TraversalSpec(
            name="prop_batch4d_red_total",
            axes=(Axis("b", b, kind="batch"),
                  Axis("i", rows, kind="reduction"), Axis("j", cols),
                  Axis("t", 1)),
            reads=(Access("x", ("b", "i", "j")),),
            writes=(Access("y", ("b", "j")), Access("tt", ("b", "t"))),
            body=lambda env: env["x"].astype(jnp.float32).sum(axis=-2),
            out_dtype=(jnp.float32, jnp.float32),
            reduce=_SumAndTotal(), full_width=True,
        )
        return Case(spec, (x,), tuple(_divisors(rows)))

    if kind == "osm_lse":
        # combinator-under-blocking with distinct write maps: the
        # paired-state online softmax emits (weighted average, lse) per
        # softmax group from one accumulated [groups, …] state;
        # draw_config's block_rows splits the row grid so partial
        # states merge across steps too
        x = _arr((rows, cols), 0)
        v = _arr((rows, cols), 1)
        g = draw.sample(_divisors(cols))
        vw = cols // g

        def body(env):
            r = env["x"].shape[0]
            sc = env["x"].astype(jnp.float32).reshape(r, g, vw).sum(-1).T
            m = sc.max(axis=-1, keepdims=True)             # (g, 1)
            w = jnp.exp(sc - m)                            # (g, r)
            vb = env["v"].astype(jnp.float32).reshape(r, g, vw)
            num = jnp.einsum("gr,rgv->gv", w, vb)          # (g, vw)
            return (m, num, w.sum(axis=-1, keepdims=True))

        spec = TraversalSpec(
            name="prop_osm_lse",
            axes=(Axis("i", rows, kind="reduction"), Axis("j", cols),
                  Axis("h", g), Axis("c", vw)),
            reads=(Access("x", ("i", "j")), Access("v", ("i", "j"))),
            writes=(Access("o", ("h", "c")), Access("l", ("h",))),
            body=body, out_dtype=(jnp.float32, jnp.float32),
            reduce=OnlineSoftmax(groups=g, vwidth=vw, with_lse=True),
            full_width=True,
        )
        return Case(spec, (x, v), tuple(_divisors(rows)),
                    rtol=1e-4, atol=1e-4)

    if kind == "perwrite_vecred":
        # PR-6 per-write combinators: a row-max accumulator next to a
        # row-sum in ONE vecred sweep (full-width — the emitter refuses
        # zero-padded lanes under a non-sum combinator, so whole rows)
        x = _arr((rows, cols), 0)
        spec = TraversalSpec(
            name="prop_perwrite_vecred",
            axes=(Axis("i", rows), Axis("j", cols, kind="reduction")),
            reads=(Access("x", ("i", "j")),),
            writes=(Access("mx", ("i",)), Access("sm", ("i",))),
            body=lambda env: (env["x"].astype(jnp.float32).max(axis=-1),
                              env["x"].astype(jnp.float32).sum(axis=-1)),
            out_dtype=(jnp.float32, jnp.float32),
            reduce=("max", "sum"), full_width=True,
        )
        return Case(spec, (x,), any_d)

    if kind == "transpose":
        # PR-6 transposed stores: a write whose index map permutes the
        # stride axis after the vector axis, optionally next to a plain
        # (i, j) sibling write — the body returns each block in its
        # write's index order
        x = _arr((rows, cols), 0)
        if draw.boolean():
            spec = TraversalSpec(
                name="prop_transpose_pair",
                axes=(Axis("i", rows), Axis("j", cols)),
                reads=(Access("x", ("i", "j")),),
                writes=(Access("z", ("i", "j")), Access("xt", ("j", "i"))),
                body=lambda env: (env["x"] * 2.0,
                                  jnp.swapaxes(env["x"], -2, -1)),
                out_dtype=(jnp.float32, jnp.float32),
            )
        else:
            spec = TraversalSpec(
                name="prop_transpose",
                axes=(Axis("i", rows), Axis("j", cols)),
                reads=(Access("x", ("i", "j")),),
                writes=(Access("xt", ("j", "i")),),
                body=lambda env: jnp.swapaxes(env["x"], -2, -1),
            )
        return Case(spec, (x,), any_d)

    if kind == "stencil":
        rlo, rhi = draw.sample([(0, 0), (1, 1), (1, 0)])
        clo, chi = draw.sample([(1, 1), (0, 1), (0, 0)])
        if (rlo, rhi) == (0, 0) and (clo, chi) == (0, 0):
            clo = chi = 1
        halo = ((rlo, rhi), (clo, chi))
        x = _arr((rows + rlo + rhi, cols + clo + chi), 0)

        def body(env, _h=halo):
            acc = None
            for dr in range(-_h[0][0], _h[0][1] + 1):
                for dc in range(-_h[1][0], _h[1][1] + 1):
                    t = tap(env["x"], _h, dr, dc)
                    acc = t if acc is None else acc + t
            return acc

        spec = TraversalSpec(
            name="prop_stencil",
            axes=(Axis("i", rows), Axis("j", cols)),
            reads=(Access("x", ("i", "j"), halo=halo),),
            writes=(Access("z", ("i", "j")),),
            body=body,
        )
        return Case(spec, (x,), any_d)

    if kind == "vecred":
        x = _arr((rows, cols), 0)
        spec = TraversalSpec(
            name="prop_vecred",
            axes=(Axis("i", rows), Axis("j", cols, kind="reduction")),
            reads=(Access("x", ("i", "j")),),
            writes=(Access("y", ("i",)),),
            body=lambda env: env["x"].astype(jnp.float32).sum(axis=-1),
            out_dtype=jnp.float32,
        )
        return Case(spec, (x,), any_d)

    if kind == "stridered":
        x = _arr((rows, cols), 0)
        reduce = draw.sample(["sum", "max"])
        if reduce == "sum" and draw.boolean():   # rank-1 stream, mxv_t-like
            r = _arr((rows,), 1)
            spec = TraversalSpec(
                name="prop_stridered_dot",
                axes=(Axis("i", rows, kind="reduction"),
                      Axis("j", cols)),
                reads=(Access("x", ("i", "j")), Access("r", ("i",))),
                writes=(Access("s", ("j",)),),
                body=lambda env: jnp.dot(
                    env["r"], env["x"],
                    preferred_element_type=jnp.float32),
                out_dtype=jnp.float32,
            )
            return Case(spec, (x, r), tuple(_divisors(rows)))
        body = ((lambda env: env["x"].astype(jnp.float32).max(axis=0))
                if reduce == "max"
                else (lambda env: env["x"].astype(jnp.float32).sum(axis=0)))
        spec = TraversalSpec(
            name="prop_stridered",
            axes=(Axis("i", rows, kind="reduction"), Axis("j", cols)),
            reads=(Access("x", ("i", "j")),),
            writes=(Access("s", ("j",)),),
            body=body, reduce=reduce, out_dtype=jnp.float32,
        )
        return Case(spec, (x,), tuple(_divisors(rows)))

    if kind == "osm":
        # softmax over per-row scores (row sums), V-weighted average:
        # the paired-state OnlineSoftmax combinator end-to-end
        x = _arr((rows, cols), 0)
        v = _arr((rows, cols), 1)

        def body(env):
            sc = env["x"].astype(jnp.float32).sum(axis=-1)
            m = sc.max(keepdims=True)[None]                # (1, 1)
            w = jnp.exp(sc - m[0])
            num = (w[:, None] * env["v"].astype(jnp.float32)).sum(
                axis=0, keepdims=True)                     # (1, cols)
            return (m, num, w.sum(keepdims=True)[None])

        spec = TraversalSpec(
            name="prop_osm",
            axes=(Axis("i", rows, kind="reduction"), Axis("j", cols)),
            reads=(Access("x", ("i", "j")), Access("v", ("i", "j"))),
            writes=(Access("o", ("j",)),),
            body=body, out_dtype=jnp.float32,
            reduce=OnlineSoftmax(groups=1, vwidth=cols), full_width=True,
        )
        return Case(spec, (x, v), tuple(_divisors(rows)),
                    rtol=1e-4, atol=1e-4)

    if kind == "batch":
        b = draw.sample([2, 3])
        x = _arr((b, rows, cols), 0)
        if draw.boolean():                       # batched elementwise
            spec = TraversalSpec(
                name="prop_batch_map",
                axes=(Axis("b", b, kind="batch"), Axis("i", rows),
                      Axis("j", cols)),
                reads=(Access("x", ("b", "i", "j")),),
                writes=(Access("z", ("b", "i", "j")),),
                body=lambda env: env["x"] * 0.5 + 1.0,
            )
            return Case(spec, (x,), any_d)
        spec = TraversalSpec(                    # batched stride-reduction
            name="prop_batch_red",
            axes=(Axis("b", b, kind="batch"),
                  Axis("i", rows, kind="reduction"), Axis("j", cols)),
            reads=(Access("x", ("b", "i", "j")),),
            writes=(Access("y", ("b", "j")),),
            body=lambda env: env["x"].astype(jnp.float32).sum(axis=-2),
            out_dtype=jnp.float32,
        )
        return Case(spec, (x,), tuple(_divisors(rows)))

    if kind == "fill":
        value = draw.sample([0.0, 1.0, -2.5])
        spec = TraversalSpec(
            name="prop_fill",
            axes=(Axis("i", rows), Axis("j", cols)),
            reads=(),
            writes=(Access("z", ("i", "j")),),
            scalars=("value",),
            body=lambda env: env["value"],
            out_dtype=jnp.float32,
        )
        return Case(spec, (value,), any_d)

    # kind == "1d": §5.1.1 loop-blocked nest, optionally multi-output
    n = draw.sample([60, 100, 257])
    x, y = _arr((n,), 0), _arr((n,), 1)
    if draw.boolean():
        spec = TraversalSpec(
            name="prop_1d_multiout",
            axes=(Axis("i", n),),
            reads=(Access("x", ("i",)), Access("y", ("i",))),
            writes=(Access("a", ("i",)), Access("b", ("i",))),
            body=lambda env: (env["x"] + env["y"], env["x"] - env["y"]),
            out_dtype=(jnp.float32, jnp.float32),
        )
    else:
        spec = TraversalSpec(
            name="prop_1d",
            axes=(Axis("i", n),),
            reads=(Access("x", ("i",)), Access("y", ("i",))),
            writes=(Access("z", ("i",)),),
            body=lambda env: env["x"] + 3.0 * env["y"],
        )
    return Case(spec, (x, y), any_d)


def draw_config(draw: Draw, case: Case) -> StridingConfig:
    return StridingConfig(
        stride_unroll=draw.sample(case.d_options),
        portion_unroll=draw.sample([1, 2]),
        arrangement=draw.sample(["grouped", "interleaved"]),
        lookahead=draw.sample([1, 2, 3]),
        block_rows=draw.sample([0, 1, 2, 4]),
    )


# --------------------------------------------- the two property checks

def check_differential(draw: Draw):
    """preserves_domain(default §5.1 schedule) ∧ emitted == evaluate."""
    case = draw_case(draw)
    spec, cfg = case.spec, draw_config(draw, case)
    # the static-verifier soundness direction: every generated legal
    # (spec, config) point the differential is about to prove correct
    # must also pass the checker — "checker passes ⇒ differential
    # passes" over the whole adversarial case space (warnings allowed)
    from repro import analysis
    flagged = [f for f in analysis.check(spec, cfg)
               if f.severity == "error"]
    assert not flagged, (spec.name, cfg, [f.as_dict() for f in flagged])
    info = classify(spec)
    if not info.blocked:
        # replicate the emitter's padding, then check the actual
        # schedule it will run covers the domain exactly once
        bp = transforms.plan_blocks(spec, cfg)
        targets = {info.stride_axis: bp.rows, info.vector_axis: bp.cols}
        padded = dataclasses.replace(spec, axes=tuple(
            dataclasses.replace(ax, extent=targets.get(ax.name, ax.extent))
            for ax in spec.axes))
        sched = transforms.default_schedule(padded, cfg, blocks=bp)
        assert transforms.preserves_domain(sched), (spec.name, cfg)
    got = emit_spec(spec, case.inputs, cfg, interpret=True)
    want = evaluate(spec, case.inputs)
    got_l, want_l = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got_l) == len(want_l) == len(spec.writes)
    for g, w in zip(got_l, want_l):
        assert g.shape == w.shape and g.dtype == w.dtype, (spec.name, cfg)
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            rtol=case.rtol, atol=case.atol,
            err_msg=f"{spec.name} cfg={cfg}")


_TRANSFORMS = ("unroll", "stride_split", "vector_block", "block",
               "interchange")


def check_schedule_algebra(draw: Draw):
    """Random legal unroll × interchange × stride_split × block chains
    preserve the iteration domain; illegal split factors raise."""
    case = draw_case(draw)
    spec = case.spec
    s = transforms.schedule(spec)
    for _ in range(draw.integer(1, 4)):
        t = draw.sample(_TRANSFORMS)
        if t == "interchange":
            order = list(range(len(s.loops)))
            i = draw.integer(0, len(order) - 1)
            j = draw.integer(0, len(order) - 1)
            order[i], order[j] = order[j], order[i]
            s = transforms.interchange(s, order)
            continue
        axis = draw.sample([ax.name for ax in spec.axes])
        grid = [l for l in s.loops
                if l.axis == axis and l.kind == transforms.GRID]
        if not grid:
            continue                      # axis fully split already
        extent = grid[0].extent
        factor = draw.sample(_divisors(extent))
        fn = getattr(transforms, t)
        s = fn(s, axis, factor)
        assert transforms.preserves_domain(s), (spec.name, t, axis, factor)
        # a factor larger than the (first) grid loop's extent can never
        # divide it — §5.1.2 divisibility must raise, not mis-cover
        with pytest.raises(ValueError):
            fn(s, axis, extent + 1)
    assert transforms.preserves_domain(s)


# ------------------------------------------------- seeded sweep (always)

@pytest.mark.parametrize("seed", range(54))
def test_differential_seeded(seed):
    # 54 seeds over 15 archetypes: every archetype (incl. the PR-5
    # per-output-map / 4-D batched / combinator-under-blocking cases and
    # the PR-6 per-write-combinator and transposed-store cases) is drawn
    # at least once by this range
    check_differential(Draw(rng=random.Random(seed)))


@pytest.mark.parametrize("seed", range(15))
def test_schedule_algebra_seeded(seed):
    check_schedule_algebra(Draw(rng=random.Random(1000 + seed)))


# ---------------------------------------------- hypothesis sweep (CI)

if HAVE_HYPOTHESIS:

    @given(data=st.data())
    def test_differential_hypothesis(data):
        check_differential(Draw(data=data))

    @given(data=st.data())
    def test_schedule_algebra_hypothesis(data):
        check_schedule_algebra(Draw(data=data))

# ----------------------------- adversarial archetypes (static rejection)

# The complement of the differential sweep: spec/config points the
# checker must REJECT, proven to die before emission — the guarded op
# either serves the evaluate() oracle through the ref tier or re-raises
# the AnalysisError, and in both cases zero pallas_call is constructed.

@pytest.mark.parametrize("name", ["race", "redsplit", "halo"])
def test_adversarial_archetype_rejected_without_emission(
        name, tmp_path, monkeypatch):
    from repro import analysis
    from repro.analysis import fixtures
    from repro.codegen import emit as emit_mod
    from repro.kernels import common
    from repro.registry import tunecache

    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tune.json"))
    tunecache.reset_default_cache()
    common.reset_plan_memo()

    def boom(*a, **k):
        raise AssertionError("pallas_call constructed for a statically "
                             "rejected plan")

    monkeypatch.setattr(emit_mod.pl, "pallas_call", boom)
    fx = fixtures.build(name)
    flagged = {f.rule for f in analysis.check(fx.spec, fx.config,
                                              **fx.check_kwargs)
               if f.severity == "error"}
    assert fx.rule in flagged
    op = emit_mod.make_kernel_op(f"t_adv_{name}", lambda *xs: fx.spec,
                                 default=fx.config)
    shape = tuple(ax.extent for ax in fx.spec.axes)
    inputs = tuple(
        jnp.arange(np.prod(shape), dtype=jnp.float32).reshape(shape) / 97
        for _ in fx.spec.reads)
    try:
        want = evaluate(fx.spec, inputs)
    except ValueError:
        # the defect poisons the oracle too (e.g. the out-of-halo tap):
        # with no tier left the original AnalysisError must surface
        with pytest.raises(analysis.AnalysisError) as ei:
            op(*inputs, config=fx.config, mode="interpret")
        assert fx.rule in str(ei.value)
    else:
        got = op(*inputs, config=fx.config, mode="interpret")
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(g, np.float32),
                                       np.asarray(w, np.float32),
                                       rtol=1e-5, atol=1e-5)
    tunecache.reset_default_cache()
    common.reset_plan_memo()
