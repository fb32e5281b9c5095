"""Finds what belongs to one cell by the names in ``BENCHMARK.json``.

Each piece sits in a file of its own, so adding a configuration, a
traffic mix, a cell or a metric adds files and entries and edits none:

  bench/configs/<config>.json   model sizes as run, with their source
  bench/traffic/<traffic>.json  the mix's parameters (bench.traffic)
  bench/cells/<workload>.json   engine settings and correctness limits
  bench/metrics/<metric>.py     one reader per metric (``read(rec)``)
  bench/refs/<reference>.py     the plain reference a config names: its
                                forward pass, weights and work per step
  bench/layouts/<reference>.json  the reference's weights on the
                                program's params tree (bench.program)
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CONFIG_DIR = BENCH_DIR / "configs"
REF_DIR = BENCH_DIR / "refs"
LAYOUT_DIR = BENCH_DIR / "layouts"


def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    """Import a file by path (names may hold '-' and '.')."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    kind: str                   # "end_to_end" | "per_layer"
    entry: dict

    def applies_to(self, workload: str) -> bool:
        cells = self.entry.get("workloads")
        return cells is None or workload in cells

    def reader(self):
        return load_module(BENCH_DIR / "metrics" / f"{self.name}.py")


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with every file it names, loaded."""
    name: str
    chips: int
    config: dict                # bench/configs/<config>.json
    traffic: dict               # bench/traffic/<traffic>.json
    settings: dict              # bench/cells/<workload>.json
    metrics: tuple              # Metric, those that apply to this cell

    def metrics_of(self, kind: str) -> list:
        return [m for m in self.metrics if m.kind == kind]


def load_benchmark() -> dict:
    return _json(ROOT / "BENCHMARK.json")


def load_cell(workload: str) -> Cell:
    bench = load_benchmark()
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(entries)})")
    entry = entries[workload]
    metrics = [Metric(m["name"], m["unit"], m["better"], kind, m)
               for kind in ("end_to_end", "per_layer") for m in bench[kind]]
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config=load_config(entry["config"]),
        traffic=_json(BENCH_DIR / "traffic" / f"{entry['traffic']}.json"),
        settings=_json(BENCH_DIR / "cells" / f"{workload}.json"),
        metrics=tuple(m for m in metrics if m.applies_to(workload)))


def load_config(name: str) -> dict:
    """A configuration file, with its sizes also under the bench's own
    names (``dims``): the file keeps the source's keys, its ``keys``
    table says which of them is which, and ``derived`` holds the rest.
    A dotted name nests (``moe.n_experts`` → ``dims["moe"]["n_experts"]``)."""
    cfg = _json(CONFIG_DIR / f"{name}.json")
    pairs = [(ours, cfg["config"][theirs])
             for ours, theirs in cfg["keys"].items()]
    return {**cfg, "dims": nest(pairs + list(cfg["derived"].items()))}


def nest(pairs) -> dict:
    """A nested dict of (dotted name, value) pairs."""
    out: dict = {}
    for name, value in pairs:
        *outer, last = name.split(".")
        d = out
        for k in outer:
            d = d.setdefault(k, {})
        d[last] = value
    return out


def reference(config: dict):
    return load_module(REF_DIR / f"{config['reference']}.py")


def layout(config: dict) -> dict:
    return _json(LAYOUT_DIR / f"{config['reference']}.json")


def work(config: dict) -> dict:
    """What a decode step of the configuration does, by its reference
    (``bench.costs``)."""
    return reference(config).work(config["dims"])
