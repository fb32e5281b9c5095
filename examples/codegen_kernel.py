"""A brand-new kernel with ZERO hand-written Pallas (README § Codegen).

Defines SAXPY-with-offset — z[i,j] = alpha*x[i,j] + y[i,j+2] — purely as
a ``repro.codegen.TraversalSpec``, then walks the whole pipeline:

  1. spec        the ~12-line loop-nest description below
  2. plan        ``core.planner`` ranks (D, P) from the spec's derived
                 Traffic signature (no hand-written planner glue)
  3. emit        ``make_kernel_op`` lowers spec → schedule → Pallas;
                 the same op runs in ref (jnp interpreter) and
                 interpret (pallas_call(interpret=True)) modes
  4. registry    one ``register(KernelSpec(...))`` call puts it in the
                 conformance matrix

Run: PYTHONPATH=src python examples/codegen_kernel.py
"""
import jax.numpy as jnp
import numpy as np

from repro import registry
from repro.codegen import (Access, Axis, TraversalSpec, make_kernel_op,
                           tap, traffic_of)
from repro.core import rank_configs
from repro.core.striding import StridingConfig
from repro.kernels.common import example_input

OFF = 2                                # column offset of the y tap
_HALO = ((0, 0), (0, OFF))


# 1. ---- the spec: the entire kernel definition ------------------------
def saxpy_offset_spec(x, y, alpha=0.0) -> TraversalSpec:
    rows, cols = x.shape
    return TraversalSpec(
        name="saxpy_offset",
        axes=(Axis("i", rows), Axis("j", cols)),
        reads=(Access("x", ("i", "j")),
               Access("y", ("i", "j"), halo=_HALO)),
        writes=(Access("z", ("i", "j")),),
        scalars=("alpha",),
        body=lambda env: env["alpha"] * env["x"] + tap(env["y"], _HALO, 0, OFF),
    )


saxpy_offset = make_kernel_op("saxpy_offset", saxpy_offset_spec,
                              default=StridingConfig(4, 1))

# 2. ---- planner: (D, P) ranking straight from the access maps ---------
rows, cols = 4096, 4096
traffic = traffic_of(saxpy_offset_spec(
    jnp.zeros((rows, cols)), jnp.zeros((rows, cols + OFF))))
print(f"derived Traffic: rows={traffic.rows} cols={traffic.cols} "
      f"L={traffic.read_arrays} S={traffic.write_arrays}")
print("planner (D,P) ranking at benchmark scale:")
for cfg, bw, _ in rank_configs(traffic)[:5]:
    print(f"  D={cfg.stride_unroll:2d} P={cfg.portion_unroll}  "
          f"predicted {bw / 1e9:7.1f} GB/s")

# 3. ---- run it, ref + interpret, several (D, P) points ----------------
x = example_input((32, 256), 0)
y = example_input((32, 256 + OFF), 1)
alpha = 2.5
want = alpha * x + y[:, OFF:]
for mode in ("ref", "interpret"):
    for d, p in [(1, 1), (2, 2), (4, 1)]:
        got = saxpy_offset(x, y, alpha, config=StridingConfig(d, p),
                           mode=mode)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        print(f"saxpy_offset {mode:9s} D={d} P={p}  ✓")

# 4. ---- registry: conformance matrix + fig6 pick it up ----------------
registry.register(registry.KernelSpec(
    name="saxpy_offset", family="gen", fn=saxpy_offset,
    make_inputs=lambda s, dt: (example_input((s["rows"], s["cols"]), 0, dt),
                               example_input((s["rows"], s["cols"] + OFF),
                                             1, dt),
                               jnp.asarray(alpha, dt)),
    run=lambda inp, cfg, mode: saxpy_offset(*inp, config=cfg, mode=mode),
    ref=lambda inp, cfg: (inp[2] * inp[0] + inp[1][:, OFF:]
                          ).astype(inp[0].dtype),
    default_sizes={"rows": 32, "cols": 256},
    aliased_sizes={"rows": 32, "cols": 128},
    traffic=lambda s, dt: traffic_of(saxpy_offset_spec(
        jnp.zeros((s["rows"], s["cols"]), dt),
        jnp.zeros((s["rows"], s["cols"] + OFF), dt)), dt),
    cache_shape=lambda s: (s["rows"], s["cols"])))

points = [p for p in registry.conformance_points() if p[1] == "saxpy_offset"]
print(f"\nconformance matrix now carries {len(points)} saxpy_offset rows:")
for pid, kernel, sizes, cfg in points:
    spec = registry.get(kernel)
    inputs = spec.make_inputs(sizes, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(spec.run(inputs, cfg, "interpret")),
        np.asarray(spec.ref(inputs, cfg)), rtol=1e-4, atol=1e-4)
    print(f"  {pid:24s} ✓ vs oracle")

print("\nend-to-end: spec → plan → emit → registry → conformance,"
      "\nwithout writing a single pl.pallas_call by hand.")
