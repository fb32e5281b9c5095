"""Seeded random weights, made by the benchmark in its reference's layout.

The benchmark owns the weights: it makes them on the device from the
run's seed, hands them to the program in the program's own layout
(``bench.program.to_program``), and gives the same arrays, which the
program cannot change, to the reference afterwards.  Nothing the
program computes reaches the reference.

The reference (``bench/refs/<reference>.py``) says what there is to
draw: ``LEAVES``, whose order fixes each leaf's ``fold_in`` index,
``shapes(dims)``, ``draw(name, key, shape)`` (float32 values) and
``F32_LEAVES``, the leaves held in float32 whatever the parameter dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> np.ndarray:
    """The raw PRNG key of a run's seed (any whole number that fits 64
    bits; ``jax.random.PRNGKey`` wraps larger ones silently)."""
    ss = np.random.SeedSequence([7, seed % 2**64, int(seed < 0)])
    return ss.generate_state(2).astype(np.uint32)


def generate(key, dims: dict, ref) -> dict:
    """All of ``ref``'s leaves from one key, in the parameter dtype (the
    float32-held ones in float32); jit it."""
    dt = jnp.dtype(dims["param_dtype"])
    out = {}
    for name, shape in ref.shapes(dims).items():
        a = ref.draw(name, jax.random.fold_in(key, ref.LEAVES.index(name)),
                     shape)
        out[name] = a.astype(jnp.float32 if name in ref.F32_LEAVES else dt)
    return out
