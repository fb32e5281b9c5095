#!/usr/bin/env python3
"""Readings that set a cell's ``logit_gap`` limit, on the chip.

    python3 bench/control.py --workload yi9b-reason --seconds 40 \
        --seeds 11,12,13

For each seed, one run of the cell as ``bench/run.py`` makes it (same
weights, traffic, window and sample), in a process of its own as the
benchmark's runs are, and then the control: the reference with every
operand rounded through float8 (e4m3), the precision below the
configuration's bfloat16, read at the same positions (``bench.check``)
and judged by the cell's own limits.  Prints one JSON line per seed
with the program's verdict and widest gap and the control's; the
control has to come out not correct.  The benchmark's own runs never
run the control.  This process never touches JAX: each seed's process
holds the chips while it runs.
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time

import run  # bench/run.py: puts the repository on sys.path


def one(workload: str, seed: int, seconds: float) -> dict:
    from bench import spec
    cell = spec.load_cell(workload)
    out = run.run_cell(cell, seed, seconds, False, control=True,
                       t_start=time.perf_counter())
    ctl = out["control"]
    return {"workload": workload, "seed": seed, "correct": out["correct"],
            "program": out["check"]["logit_gap"]["value"],
            "control_correct": ctl["correct"],
            "control": ctl["check"]["logit_gap"]["value"],
            "limit": ctl["check"]["logit_gap"]["limit"],
            "per_request": ctl["per_request"],
            "program_per_request": ctl["program_per_request"],
            "metrics": out["metrics"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--one", action="store_true",
                    help="run the single seed given, in this process")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.one:
        print(json.dumps(one(args.workload, seeds[0], args.seconds)),
              flush=True)
        return 0
    rc = 0
    for seed in seeds:
        res = subprocess.run(
            [sys.executable, __file__, "--one", "--workload", args.workload,
             "--seconds", str(args.seconds), "--seeds", str(seed)],
            stdout=subprocess.PIPE, text=True)
        lines = res.stdout.strip().splitlines()
        if res.returncode != 0 or not lines:
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "returncode": res.returncode}), flush=True)
            rc = 1
            continue
        print(lines[-1], flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
