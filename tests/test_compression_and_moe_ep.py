"""Multi-device tests (8 host CPU devices via subprocess): compressed
all-reduce correctness/error-bound and MoE EP-vs-dense equivalence."""
import subprocess
import sys

import numpy as np
import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P

# ---------------- compressed pmean ----------------
from repro.train.compression import compressed_pmean, ef_compressed_pmean, ef_init
mesh = jax.make_mesh((2, 4), ("pod", "data"))
g = jax.random.normal(jax.random.PRNGKey(0), (2, 257))  # pod-varying grads

# full-manual shard_map (no axis_names): the compression math only
# needs the pod axis collectives
def sync(x):
    return jax.shard_map(lambda v: compressed_pmean(v, "pod"), mesh=mesh,
                         in_specs=P("pod"), out_specs=P("pod"),
                         check_vma=False)(x)

out = jax.jit(sync)(g)
true = jnp.broadcast_to(g.mean(axis=0, keepdims=True), g.shape)
err = float(jnp.max(jnp.abs(out - true)))
scale = float(jnp.max(jnp.abs(g))) / 127.0
assert err <= 3 * scale, (err, scale)
print("COMP_OK", err, scale)

# error feedback: mean over many steps converges to the true mean
gs = jax.random.normal(jax.random.PRNGKey(1), (2, 257))

def body(v, e):
    sg, new_e = ef_compressed_pmean({"g": v}, {"g": e}, "pod")
    return sg["g"], new_e["g"]

ef_step = jax.jit(jax.shard_map(
    body, mesh=mesh, in_specs=(P("pod"), P("pod")),
    out_specs=(P("pod"), P("pod")), check_vma=False))
total = jnp.zeros((2, 257))
ef = jnp.zeros((2, 257))
for _ in range(64):
    synced, ef = ef_step(gs, ef)
    total = total + synced
true_total = jnp.broadcast_to(gs.mean(0, keepdims=True), gs.shape) * 64
drift = float(jnp.max(jnp.abs(total - true_total))) / 64
assert drift <= 0.5 * scale, (drift, scale)  # EF keeps bias bounded
print("EF_OK", drift)

# ---------------- MoE EP vs dense ----------------
from repro.configs import get_config, reduced
from repro.models import moe
from repro.models.common import MeshCtx
import dataclasses
cfg = reduced(get_config("qwen3-moe-30b-a3b"))
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
    cfg.moe, capacity_factor=8.0))  # no drops -> exact match vs dense
mesh2 = jax.make_mesh((2, 4), ("data", "model"))
ctx = MeshCtx(mesh=mesh2, dp_axes=("data",), tp_axis="model")
p = moe.init_moe(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                      jnp.float32)
y_dense, aux_d = moe.moe_dense(p, x, cfg)
y_ep, aux_e = jax.jit(lambda p, x: moe.moe_ep(p, x, cfg, ctx))(p, x)
np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_dense),
                           rtol=2e-4, atol=2e-4)
# aux: per-slice stats pmean'd vs global stats — same estimator family,
# not bitwise equal (nonlinear in the routing fractions)
assert abs(float(aux_d) - float(aux_e)) / max(float(aux_d), 1e-9) < 0.25
print("MOE_OK")
"""


@pytest.mark.slow
def test_multidevice_compression_and_moe_ep():
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _SCRIPT],
                         capture_output=True, text=True, timeout=560,
                         env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert "COMP_OK" in res.stdout, res.stdout + res.stderr
    assert "EF_OK" in res.stdout, res.stdout + res.stderr
    assert "MOE_OK" in res.stdout, res.stdout + res.stderr
