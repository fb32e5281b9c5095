"""Benchmark entry point — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows; ``--json PATH`` also
persists every table's rows as structured JSON so per-PR perf
trajectories (``BENCH_*.json``) can be diffed.

  fig2_stream      paper Fig 2 (stream bw vs stride count)
  fig34_stalls     paper Fig 3/4 (stalls + hit ratios, modeled)
  fig5_collisions  paper Fig 5 (power-of-two collision)
  fig6_kernels     paper Fig 6 (kernel (D,P) sweeps)
  fig7_sota        paper Fig 7 (vs BLAS/XLA baselines)
  roofline         §Roofline table from dry-run artifacts
  serving_sweep    engine tokens/s vs concurrency (and KV shards)
"""
from __future__ import annotations

import argparse
import json
import sys


def _json_payload(tables: dict[str, list[dict]], quick: bool) -> dict:
    """Structured benchmark artifact: per-table rows annotated with the
    machine context (backend, kernel mode) and microseconds per call."""
    import jax

    from repro import obs
    from repro.kernels.common import kernel_mode
    meta = {
        "backend": jax.default_backend(),
        "mode": kernel_mode(),
        "quick": quick,
        "jax_version": jax.__version__,
        "obs_enabled": obs.enabled(),
    }
    out = {"meta": meta, "tables": {}}
    for name, rows in tables.items():
        out["tables"][name] = [
            dict(r, us_per_call=round(float(r.get("seconds", 0.0)) * 1e6, 3))
            for r in rows
        ]
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated table names")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write every table's rows as structured "
                         "JSON (kernel, config, us_per_call, GiB/s, "
                         "backend, mode)")
    args = ap.parse_args(argv)

    from benchmarks import (decode_kernel_sweep, descriptor_sweep,
                            fig2_stream, fig5_collisions, fig6_kernels,
                            fig7_sota, fig34_stalls, roofline_table,
                            serving_sweep)
    tables = {
        "fig2_stream": fig2_stream.run,
        "fig34_stalls": fig34_stalls.run,
        "fig5_collisions": fig5_collisions.run,
        "fig6_kernels": fig6_kernels.run,
        "fig7_sota": fig7_sota.run,
        "decode_kernel_sweep": decode_kernel_sweep.run,
        "descriptor_sweep": descriptor_sweep.run,
        "roofline": roofline_table.run,
        "serving_sweep": serving_sweep.run,
    }
    only = set(args.only.split(",")) if args.only else None
    results: dict[str, list[dict]] = {}
    for name, fn in tables.items():
        if only and name not in only:
            continue
        print(f"# --- {name} ---", file=sys.stderr)
        results[name] = fn(quick=args.quick) or []
    if args.json:
        with open(args.json, "w") as f:
            json.dump(_json_payload(results, args.quick), f, indent=1,
                      default=str)
        print(f"# wrote {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
