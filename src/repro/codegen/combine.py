"""Reduction combine algebra for stride-axis reductions.

A :class:`Combine` is a monoid over a *tuple* of f32 accumulators: the
emitter keeps one VMEM scratch buffer per state component, folds every
stream's (and every row-grid step's) partial state in with
:meth:`Combine.merge`, and applies :meth:`Combine.finalize` once at the
end of the sweep to turn the accumulated state into the written output.
``sum`` and ``max`` are the degenerate single-state instances (finalize
is the identity); :class:`OnlineSoftmax` is the paired-state instance
the paper's flash-decode pattern needs — a running max plus a
max-rescaled weighted sum, merged with the standard online-softmax
rescaling identity:

    m  = max(m1, m2)
    n  = n1 * exp(m1 - m) + n2 * exp(m2 - m)
    d  = d1 * exp(m1 - m) + d2 * exp(m2 - m)

which is associative and has (m=-inf, n=0, d=0) as its identity, so
partial states merge across D concurrent streams and sequential grid
steps in any bracketing (tests/test_combine.py checks the laws).

Body contract: a spec whose stride axis is reduced with an ``n_state >
1`` combinator returns the *partial state tuple* for its block (one
array per component, shapes per :meth:`state_widths`); single-state
combinators keep the historical contract of returning the partial
array directly.  The pure-jnp interpreter (``loopir.evaluate``) applies
the body once over the whole domain and finalizes the resulting state —
same totals, no Pallas.

Zero-padded stride rows would have to contribute the combine *identity*
through the body, which no generic body guarantees (and ``max`` /
``online_softmax`` structurally cannot) — the emitter therefore refuses
to pad the stride axis for every combinator (see ``emit.emit_spec``).
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax.numpy as jnp

__all__ = ["Combine", "SumCombine", "MaxCombine", "OnlineSoftmax",
           "SUM", "MAX", "resolve_combine", "NEG_INF"]

NEG_INF = -1e30   # finite -inf stand-in: exp(NEG_INF - m) underflows to 0


class Combine:
    """Paired-state reduction combinator (init / merge / finalize).

    ``finalizing`` declares that :meth:`finalize` maps the accumulated
    state to the *written* block(s) — the body then returns partial
    STATE, and ``finalize`` may emit one block per spec write (the
    per-output-access-map hook: e.g. ``OnlineSoftmax(with_lse=True)``
    finalizes ``(attention, lse)``).  Every ``n_state > 1`` combinator
    is inherently finalizing; a single-state combinator may opt in to
    add derived side outputs (see ``SumWithTotal`` uses in
    ``kernels/gen``).  Non-finalizing single-state combinators keep the
    historical identity-finalize contract: the body's partial IS the
    output block.
    """

    name: str = "combine"
    n_state: int = 1
    finalizing: bool = False

    def state_widths(self, out_width: int) -> tuple[int, ...]:
        """Lane width of each f32 state component, given the width of
        the output block the reduction produces."""
        raise NotImplementedError

    def state_shapes(self, out_width: int) -> tuple[tuple[int, ...], ...]:
        """Shape of each f32 accumulator the emitter allocates: one lane
        row per component unless a combinator lays its state out in
        2-D (``OnlineSoftmax``)."""
        return tuple((1, w) for w in self.state_widths(out_width))

    def init(self, shapes: Sequence[tuple[int, ...]]) -> tuple:
        """Identity state: one f32 array per component shape."""
        raise NotImplementedError

    def merge(self, state: tuple, part: tuple) -> tuple:
        """Fold one partial state into the accumulated state."""
        raise NotImplementedError

    def finalize(self, state: tuple):
        """Accumulated state → output block."""
        raise NotImplementedError


class SumCombine(Combine):
    name = "sum"

    def state_widths(self, out_width):
        return (out_width,)

    def init(self, shapes):
        return (jnp.zeros(shapes[0], jnp.float32),)

    def merge(self, state, part):
        return (state[0] + part[0],)

    def finalize(self, state):
        return state[0]


class MaxCombine(Combine):
    name = "max"

    def state_widths(self, out_width):
        return (out_width,)

    def init(self, shapes):
        return (jnp.full(shapes[0], NEG_INF, jnp.float32),)

    def merge(self, state, part):
        return (jnp.maximum(state[0], part[0]),)

    def finalize(self, state):
        return state[0]


@dataclasses.dataclass(frozen=True)
class OnlineSoftmax(Combine):
    """Numerically-stable streaming softmax-weighted average.

    State is ``(m, num, den)`` per softmax group, kept in 2-D
    ``[groups, …]`` form: running score max ``m`` and max-rescaled
    weight sum ``den`` as ``[groups, 1]`` columns, max-rescaled weighted
    value sum ``num`` as ``[groups, vwidth]`` rows (the emitter's
    accumulators add a leading 1).  Merge and finalize are then plain
    broadcasts: no reshape moves data between the lane and sublane
    axes, which Mosaic does not lower.  ``finalize`` divides, so a spec
    reduced with this combinator writes ``softmax(scores) @ V`` in ONE
    sweep of the streamed operands — the single-pass flash-decode
    pattern.

    The body must return the block's partial state ``(m, num, den)`` in
    that layout (any leading batch dims are kept):
      * ``m``   — ``[groups, 1]`` per-group max of the block's scores,
      * ``num`` — ``[groups, vwidth]`` sum of ``exp(score - m) * value``,
      * ``den`` — ``[groups, 1]`` sum of ``exp(score - m)``.

    ``with_lse=True`` makes ``finalize`` ALSO emit the per-group
    log-sum-exp ``m + log(den)`` (``[groups, 1]``) as a second output
    block — the flash-attention side statistic sharded-attention
    combines need; the spec then declares a second (``groups``-wide)
    write access.
    """

    groups: int            # independent softmax rows in the output
    vwidth: int            # value lanes per group (num width = g * v)
    eps: float = 1e-20     # finalize denominator floor
    with_lse: bool = False   # finalize emits (out, logsumexp) pairs
    name: str = dataclasses.field(default="online_softmax", repr=False)
    n_state: int = dataclasses.field(default=3, repr=False)
    finalizing: bool = dataclasses.field(default=True, repr=False)

    def state_widths(self, out_width):
        if out_width != self.groups * self.vwidth:
            raise ValueError(
                f"online_softmax: output width {out_width} != groups "
                f"({self.groups}) * vwidth ({self.vwidth})")
        return (self.groups, out_width, self.groups)

    def state_shapes(self, out_width):
        self.state_widths(out_width)            # validates the width
        g = self.groups
        return ((1, g, 1), (1, g, self.vwidth), (1, g, 1))

    def init(self, shapes):
        m_shape, num_shape, den_shape = shapes
        return (jnp.full(m_shape, NEG_INF, jnp.float32),
                jnp.zeros(num_shape, jnp.float32),
                jnp.zeros(den_shape, jnp.float32))

    def merge(self, state, part):
        m1, n1, d1 = state
        m2, n2, d2 = part
        m = jnp.maximum(m1, m2)
        a1 = jnp.exp(m1 - m)
        a2 = jnp.exp(m2 - m)
        return (m, n1 * a1 + n2 * a2, d1 * a1 + d2 * a2)

    def finalize(self, state):
        m, num, den = state
        den = jnp.maximum(den, self.eps)
        out = num / den
        return (out, m + jnp.log(den)) if self.with_lse else out


SUM = SumCombine()
MAX = MaxCombine()


def resolve_combine(reduce) -> Combine:
    """Spec ``reduce`` field → combinator ("sum" | "max" | instance)."""
    if isinstance(reduce, Combine):
        return reduce
    if reduce == "sum":
        return SUM
    if reduce == "max":
        return MAX
    raise ValueError(f"unknown reduce {reduce!r} (expected 'sum', 'max', "
                     "or a codegen.Combine instance)")
