"""Retirement parity: the hand-written Pallas bodies are deleted and
their public ``ops`` wrappers re-pointed at the families'
``TraversalSpec`` builders — the outputs must not drift.

Two recordings, one per retirement wave:

* ``tests/data/retired_hand_oracles.npz`` — the stream/mxv wave (PR 5).
  Single-array oracles keyed by conformance point.
* ``tests/data/retired_hand_oracles_pr6.npz`` — the remaining nine
  families (bicg, gemver×4 + composite, conv3x3, doitgen, jacobi2d,
  rmsnorm, adamw, decode_attn).  Multi-output kernels record one array
  per output leaf, keyed ``{point}__k{i}``.

Both hold the *hand bodies'* actual interpret-mode outputs, recorded at
every (D, P) conformance-matrix point immediately before deletion.
Kernels whose generated fold reproduces the hand body's f32 accumulation
order exactly must stay byte-identical.  The rest are pinned at f32-ulp
tolerance: the generated kernels compute the *clean* per-block f32 fold,
while the recorded hand bodies deviated from that fold in the last ulps
(bicg's hand ``s`` pass and decode's two-pass max+sum decomposition most
visibly) — exact equality there would enshrine the hand quirk, not the
math.
"""
import importlib
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro import registry

_DATA = os.path.join(os.path.dirname(__file__), "data",
                     "retired_hand_oracles.npz")
_DATA_PR6 = os.path.join(os.path.dirname(__file__), "data",
                         "retired_hand_oracles_pr6.npz")

RETIRED = ("stream_read", "stream_copy", "stream_init",
           "stream_copy_manual", "mxv", "mxv_t")
RETIRED_PR6 = ("bicg", "gemver_outer", "gemver_sum", "gemver_mxv1",
               "gemver_mxv2", "gemver", "conv3x3", "doitgen", "jacobi2d",
               "rmsnorm", "adamw_update", "decode_attn")
# byte-identical vs the recorded hand outputs
EXACT = {"stream_copy", "stream_copy_manual", "stream_init", "mxv",
         "gemver_outer", "gemver_sum", "gemver_mxv1", "gemver_mxv2",
         "gemver", "doitgen", "jacobi2d", "adamw_update"}
# f32-ulp bounds for the reassociated reductions
_TOL = {"mxv_t": dict(rtol=2e-4, atol=2e-5),
        "stream_read": dict(rtol=1e-5, atol=5e-5),
        "bicg": dict(rtol=2e-4, atol=2e-5),
        "conv3x3": dict(rtol=1e-5, atol=1e-6),
        "rmsnorm": dict(rtol=1e-5, atol=1e-6),
        "decode_attn": dict(rtol=2e-4, atol=2e-5)}


def _points():
    data = np.load(_DATA)
    pts = [(point, kernel, sizes, cfg)
           for point, kernel, sizes, cfg in registry.conformance_points()
           if kernel in RETIRED]
    assert {p for p, *_ in pts} == set(data.files)   # all 36 recorded
    return pts


def _points_pr6():
    data = np.load(_DATA_PR6)
    pts = [(point, kernel, sizes, cfg)
           for point, kernel, sizes, cfg in registry.conformance_points()
           if kernel in RETIRED_PR6]
    # every point has a __k0 leaf; every recorded leaf has a point
    recorded = {k.rsplit("__k", 1)[0] for k in data.files}
    assert {p for p, *_ in pts} == recorded          # all 72 recorded
    return pts


_POINTS = _points()
_POINTS_PR6 = _points_pr6()


@pytest.mark.parametrize("point,kernel,sizes,config", _POINTS,
                         ids=[p[0] for p in _POINTS])
def test_repointed_wrapper_matches_recorded_hand_oracle(
        point, kernel, sizes, config):
    data = np.load(_DATA)
    spec = registry.get(kernel)
    inputs = spec.make_inputs(sizes, jnp.float32)
    got = np.asarray(spec.run(inputs, config, "interpret"))
    want = data[point]
    assert got.shape == want.shape and got.dtype == want.dtype, point
    if kernel in EXACT:
        np.testing.assert_array_equal(got, want, err_msg=point)
    else:
        np.testing.assert_allclose(got, want, err_msg=point,
                                   **_TOL[kernel])


@pytest.mark.parametrize("point,kernel,sizes,config", _POINTS_PR6,
                         ids=[p[0] for p in _POINTS_PR6])
def test_pr6_repointed_wrapper_matches_recorded_hand_oracle(
        point, kernel, sizes, config):
    data = np.load(_DATA_PR6)
    spec = registry.get(kernel)
    inputs = spec.make_inputs(sizes, jnp.float32)
    got = spec.run(inputs, config, "interpret")
    leaves = got if isinstance(got, tuple) else (got,)
    for i, leaf in enumerate(leaves):
        leaf = np.asarray(leaf)
        want = data[f"{point}__k{i}"]
        tag = f"{point}__k{i}"
        assert leaf.shape == want.shape and leaf.dtype == want.dtype, tag
        if kernel in EXACT:
            np.testing.assert_array_equal(leaf, want, err_msg=tag)
        else:
            np.testing.assert_allclose(leaf, want, err_msg=tag,
                                       **_TOL[kernel])
    # no recorded leaf beyond the ones the wrapper returned
    assert f"{point}__k{len(leaves)}" not in data.files


def test_every_retired_kernel_covers_all_six_matrix_points():
    by_kernel: dict[str, int] = {}
    for _p, kernel, _s, _c in _POINTS + _POINTS_PR6:
        by_kernel[kernel] = by_kernel.get(kernel, 0) + 1
    assert by_kernel == {k: 6 for k in RETIRED + RETIRED_PR6}


def test_hand_bodies_deleted_and_wrappers_resolve_through_specs():
    """The retired modules are gone; the ops wrappers import the spec
    builders (and nothing else kernel-shaped)."""
    for gone in ("repro.kernels.stream.stream", "repro.kernels.mxv.mxv",
                 "repro.kernels.bicg.bicg", "repro.kernels.gemver.gemver",
                 "repro.kernels.conv3x3.conv3x3",
                 "repro.kernels.doitgen.doitgen",
                 "repro.kernels.jacobi2d.jacobi2d",
                 "repro.kernels.rmsnorm.rmsnorm",
                 "repro.kernels.adamw.adamw",
                 "repro.kernels.decode_attn.decode_attn"):
        with pytest.raises(ImportError):
            importlib.import_module(gone)
    from repro.codegen import TraversalSpec
    from repro.kernels.mxv import ops as mxv_ops
    from repro.kernels.mxv import specs as mxv_specs
    from repro.kernels.stream import ops as stream_ops
    from repro.kernels.stream import specs as stream_specs
    assert stream_ops.specs is stream_specs
    assert mxv_ops.specs is mxv_specs
    for fam in ("bicg", "gemver", "conv3x3", "doitgen", "jacobi2d",
                "rmsnorm", "adamw", "decode_attn"):
        ops = importlib.import_module(f"repro.kernels.{fam}.ops")
        specs = importlib.import_module(f"repro.kernels.{fam}.specs")
        assert ops.specs is specs, fam
    a = jnp.ones((8, 8))
    assert isinstance(stream_specs.copy_spec(a), TraversalSpec)
    assert isinstance(mxv_specs.mxv_t_spec(a, jnp.ones((8,))),
                      TraversalSpec)
    # the gen variants share the very same builders
    from repro.kernels import gen
    from repro.kernels.bicg import specs as bicg_specs
    from repro.kernels.gen import framework, polybench
    from repro.kernels.rmsnorm import specs as rms_specs
    assert gen.copy_spec is stream_specs.copy_spec
    assert gen.mxv_spec is mxv_specs.mxv_spec
    assert polybench.bicg_q_spec is bicg_specs.bicg_q_spec
    assert framework.rmsnorm_spec is rms_specs.rmsnorm_spec


def test_retired_names_still_resolve_through_registry():
    """Every retired hand name keeps its registry row — same public
    contract, spec-lowered execution."""
    for name in RETIRED + RETIRED_PR6:
        spec = registry.get(name)
        assert spec.name == name
        assert callable(spec.run) and callable(spec.ref)
