"""The benchmark's plain reference against the program's model, at
reduced widths on the CPU, for both RoPE styles.  With float32 compute
the two are the same function, so they agree to float32 rounding; this
also checks that the benchmark's weights reach the program's params
pytree leaf for leaf."""
import bench_fixtures

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import program, spec, weights
from bench.refs import dense_decoder


@pytest.mark.parametrize("rope_style", ["full", "half"])
def test_reference_matches_program_forward(rope_style):
    dims = dict(bench_fixtures.TINY_DIMS, rope_style=rope_style,
                compute_dtype="float32")
    config = {"name": "tiny", "reference": "dense_decoder", "dims": dims}
    model = program.build_model(dims, "tiny")
    params = program.make_weights(config, model, 2**31 + 3)
    w = program.from_program(params, spec.layout(config), dims)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, dims["vocab"], (2, 24)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = model.logits(params, {"tokens": toks})
    got = dense_decoder.logits(w, toks, dims)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_rope_styles_differ():
    """A reference that ignored the style would pass the test above for
    one style only; make sure the styles give different logits."""
    dims = dict(bench_fixtures.TINY_DIMS)
    key = weights.seed_key(5)
    w = weights.generate(key, dims, dense_decoder)
    toks = jnp.arange(12, dtype=jnp.int32)[None]
    full = dense_decoder.logits(w, toks, dims)
    half = dense_decoder.logits(w, toks, dict(dims, rope_style="half"))
    assert float(jnp.abs(full - half)[0, 1:].max()) > 1e-3


def test_seeds_make_different_weights_and_one_seed_the_same():
    dims = dict(bench_fixtures.TINY_DIMS)
    a = weights.generate(weights.seed_key(2**31 + 1), dims, dense_decoder)
    b = weights.generate(weights.seed_key(2**31 + 1), dims, dense_decoder)
    c = weights.generate(weights.seed_key(2**31 + 2), dims, dense_decoder)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["wq"], c["wq"])
    assert set(a) == set(dense_decoder.LEAVES)
