"""Combine-algebra laws (repro.codegen.combine).

The stride-axis reduction emitter folds partial states in whatever
bracketing the (D streams × row grid) sweep produces, so every
combinator must be a monoid: associative merge, two-sided identity from
``init``.  ``OnlineSoftmax`` additionally exercises the rescaling path
— merging states whose maxima arrive in either order must agree (the
disjoint-max ordering case) and must equal the direct full-softmax
computation — in its ``[groups, …]`` state layout, with and without
leading batch dims.  The padded-rows refusal is checked for EVERY combinator:
zero-padded stride rows cannot be trusted to contribute the combine
identity through an arbitrary body, so the emitter must raise rather
than silently corrupt.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.codegen import (MAX, SUM, Access, Axis, OnlineSoftmax,
                           TraversalSpec, emit_spec, resolve_combine)
from repro.codegen.combine import NEG_INF
from repro.core.striding import StridingConfig

KEY = jax.random.PRNGKey(0)


def _osm():
    return OnlineSoftmax(groups=2, vwidth=4)


def _osm_state(key, m_scale=1.0, m_shift=0.0, lead=()):
    """A (m, num, den) state of ``_osm()``: [groups, 1] / [groups,
    vwidth] / [groups, 1], after any ``lead`` batch dims."""
    k1, k2, k3 = jax.random.split(key, 3)
    m = jax.random.normal(k1, lead + (2, 1), jnp.float32) * m_scale + m_shift
    num = jax.random.normal(k2, lead + (2, 4), jnp.float32)
    den = jnp.abs(jax.random.normal(k3, lead + (2, 1), jnp.float32)) + 0.1
    return (m, num, den)


def _fold_state(keys):
    return [_osm_state(k) for k in jax.random.split(KEY, keys)]


def _assert_state_close(a, b, rtol=1e-5, atol=1e-6):
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


# ------------------------------------------------------------ the laws

@pytest.mark.parametrize("comb", [SUM, MAX], ids=["sum", "max"])
def test_fold_combinators_associative_and_identity(comb):
    xs = jax.random.normal(KEY, (3, 16), jnp.float32)
    a, b, c = xs[0], xs[1], xs[2]
    left = comb.merge(comb.merge((a,), (b,)), (c,))
    right = comb.merge((a,), comb.merge((b,), (c,)))
    # sum is associative up to f32 rounding; max exactly
    _assert_state_close(left, right)
    ident = comb.init([a.shape])
    _assert_state_close(comb.merge(ident, (a,)), (a,), rtol=0, atol=0)
    _assert_state_close(comb.merge((a,), ident), (a,), rtol=0, atol=0)
    np.testing.assert_array_equal(np.asarray(comb.finalize((a,))),
                                  np.asarray(a))


def test_online_softmax_associative():
    comb = _osm()
    s1, s2, s3 = _fold_state(3)
    left = comb.merge(comb.merge(s1, s2), s3)
    right = comb.merge(s1, comb.merge(s2, s3))
    _assert_state_close(left, right, rtol=1e-5, atol=1e-6)


def test_online_softmax_identity():
    comb = _osm()
    s = _osm_state(KEY)
    ident = comb.init([x.shape for x in s])
    _assert_state_close(comb.merge(ident, s), s, rtol=0, atol=0)
    _assert_state_close(comb.merge(s, ident), s, rtol=0, atol=0)
    # identity finalizes to zeros (den floored at eps), not NaN
    fin = np.asarray(comb.finalize(ident))
    assert np.all(np.isfinite(fin)) and np.all(fin == 0.0)


def test_online_softmax_rescaling_disjoint_max_ordering():
    """Merging (huge max, tiny max) must equal (tiny max, huge max) AND
    the direct two-block softmax: the rescale factors exp(mᵢ - m) hit
    1 and underflow-to-0 in opposite orders."""
    comb = _osm()
    lo = (jnp.full((2, 1), -50.0), jnp.ones((2, 4)), jnp.full((2, 1), 0.5))
    hi = (jnp.full((2, 1), +40.0), 2.0 * jnp.ones((2, 4)),
          jnp.full((2, 1), 2.0))
    ab = comb.merge(lo, hi)
    ba = comb.merge(hi, lo)
    _assert_state_close(ab, ba, rtol=1e-6, atol=0)
    # the -50 block's contribution underflows against the +40 max:
    # finalize == hi's weighted average exactly
    np.testing.assert_allclose(np.asarray(comb.finalize(ab)),
                               np.asarray(comb.finalize(hi)), rtol=1e-6)
    # moderate separation: against a direct softmax over both blocks
    s1 = _osm_state(jax.random.PRNGKey(1), m_shift=+3.0)
    s2 = _osm_state(jax.random.PRNGKey(2), m_shift=-3.0)
    merged = comb.finalize(comb.merge(s1, s2))
    m = np.maximum(np.asarray(s1[0]), np.asarray(s2[0]))

    def lift(s):
        a = np.exp(np.asarray(s[0]) - m)
        return np.asarray(s[1]) * a, np.asarray(s[2]) * a
    n1, d1 = lift(s1)
    n2, d2 = lift(s2)
    want = (n1 + n2) / (d1 + d2)
    np.testing.assert_allclose(np.asarray(merged), want, rtol=1e-5,
                               atol=1e-6)


def test_online_softmax_state_widths_validate():
    comb = _osm()
    assert comb.state_widths(8) == (2, 8, 2)
    assert comb.state_shapes(8) == ((1, 2, 1), (1, 2, 4), (1, 2, 1))
    with pytest.raises(ValueError):
        comb.state_widths(9)
    with pytest.raises(ValueError):
        comb.state_shapes(9)


def test_online_softmax_merges_batched_states_row_by_row():
    """A decode step's state carries leading batch dims ([b, Hq, …]):
    merging and finalizing the batched state equals doing it per row."""
    comb = OnlineSoftmax(groups=2, vwidth=4, with_lse=True)
    s1 = _osm_state(jax.random.PRNGKey(3), m_shift=+2.0, lead=(3,))
    s2 = _osm_state(jax.random.PRNGKey(4), m_shift=-2.0, lead=(3,))
    out, lse = comb.finalize(comb.merge(s1, s2))
    assert out.shape == (3, 2, 4) and lse.shape == (3, 2, 1)
    for b in range(3):
        row = comb.finalize(comb.merge(tuple(x[b] for x in s1),
                                       tuple(x[b] for x in s2)))
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(row[0]),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(lse[b]), np.asarray(row[1]),
                                   rtol=1e-6)


def test_resolve_combine():
    assert resolve_combine("sum") is SUM
    assert resolve_combine("max") is MAX
    comb = _osm()
    assert resolve_combine(comb) is comb
    with pytest.raises(ValueError):
        resolve_combine("min")
    with pytest.raises(ValueError):
        TraversalSpec(
            name="bad", axes=(Axis("i", 4),),
            reads=(Access("x", ("i",)),), writes=(Access("y", ("i",)),),
            body=lambda env: env["x"], reduce="median")


# ----------------------------------------- padded-rows refusal, all of them

def _stride_red_spec(rows, cols, reduce):
    def body(env):
        x = env["x"].astype(jnp.float32)
        if isinstance(reduce, OnlineSoftmax):       # one group: [1, …]
            sc = x.sum(axis=-1)
            m = sc.max(keepdims=True)
            w = jnp.exp(sc - m)
            return (m[None], (w[:, None] * x).sum(axis=0, keepdims=True),
                    w.sum(keepdims=True)[None])
        if reduce == "max":
            return x.max(axis=0)
        return x.sum(axis=0)
    return TraversalSpec(
        name=f"padguard_{getattr(reduce, 'name', reduce)}",
        axes=(Axis("i", rows, kind="reduction"), Axis("j", cols)),
        reads=(Access("x", ("i", "j")),),
        writes=(Access("y", ("j",)),),
        body=body, reduce=reduce, out_dtype=jnp.float32,
        full_width=isinstance(reduce, OnlineSoftmax),
    )


@pytest.mark.parametrize(
    "reduce", ["sum", "max", OnlineSoftmax(groups=1, vwidth=8)],
    ids=["sum", "max", "online_softmax"])
def test_padded_rows_refused_for_every_combinator(reduce):
    """10 rows at D=4 would need 2 zero-padded rows: every combinator
    must refuse (identity-through-the-body cannot be guaranteed), and
    run cleanly at a dividing D."""
    rows, cols = 10, 8
    x = jax.random.normal(KEY, (rows, cols), jnp.float32)
    spec = _stride_red_spec(rows, cols, reduce)
    with pytest.raises(ValueError, match="cannot pad the stride axis"):
        emit_spec(spec, (x,), StridingConfig(4, 1), interpret=True)
    got = emit_spec(spec, (x,), StridingConfig(2, 1), interpret=True)
    assert np.all(np.isfinite(np.asarray(got)))


def test_neg_inf_identity_survives_exp():
    """exp(NEG_INF - m) must underflow to exactly 0 for any finite m the
    rescale path can see (the identity's contribution vanishes)."""
    for m in (-1e4, 0.0, 1e4, NEG_INF):
        assert float(jnp.exp(jnp.float32(NEG_INF) - jnp.float32(m))) in (0.0, 1.0)
    assert float(jnp.exp(jnp.float32(NEG_INF - NEG_INF))) == 1.0


def test_online_softmax_with_lse_finalize():
    """with_lse finalize emits (out, m + log(den)) — the lse equals the
    direct log-sum-exp of the merged scores, in ANY merge bracketing,
    and the primary output is unchanged vs the with_lse=False path."""
    rng = np.random.default_rng(0)
    groups, vwidth = 2, 4
    base = OnlineSoftmax(groups=groups, vwidth=vwidth)
    lse_c = OnlineSoftmax(groups=groups, vwidth=vwidth, with_lse=True)
    assert lse_c.finalizing and base.finalizing

    def part(scores, values):
        m = scores.max(axis=-1, keepdims=True)
        w = np.exp(scores - m)
        num = np.einsum("gs,gsv->gv", w, values)
        return (jnp.asarray(m, jnp.float32),
                jnp.asarray(num, jnp.float32),
                jnp.asarray(w.sum(axis=-1, keepdims=True), jnp.float32))

    scores = rng.normal(size=(2, groups, 8))
    values = rng.normal(size=(2, groups, 8, vwidth))
    s1, s2 = (part(scores[i], values[i]) for i in range(2))
    merged = lse_c.merge(s1, s2)
    out, lse = lse_c.finalize(merged)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(base.finalize(merged)), rtol=1e-6)
    all_scores = np.concatenate([scores[0], scores[1]], axis=-1)
    m = all_scores.max(axis=-1, keepdims=True)
    want_lse = m + np.log(np.exp(all_scores - m).sum(axis=-1, keepdims=True))
    np.testing.assert_allclose(np.asarray(lse), want_lse, rtol=1e-5)
