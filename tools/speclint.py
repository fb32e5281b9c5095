#!/usr/bin/env python3
"""Static analysis over the codegen registry, plus the repo lint.

Three modes:

  * default — sweep every registered kernel variant that publishes its
    ``traversal`` IR through the static verifier (``repro.analysis``):
    the config-independent rules once, then every config the planner's
    candidate ranking or the conformance matrix would actually run.
    Nothing is executed or lowered — this is the whole registry's
    race/bounds/VMEM/numerics audit in a few seconds.

        python tools/speclint.py
        python tools/speclint.py --kernel mxv_gen --json report.json

  * ``--fixture NAME`` — run one adversarial fixture from
    ``repro.analysis.fixtures`` (race, redsplit, halo, vmem, reassoc)
    and verify the checker flags its known defect.  The fixture IS a
    violation, so finding the expected rule exits 1; *missing* it is
    the infrastructure failure and exits 2.  CI asserts every fixture
    exits non-zero with the right rule id.

  * ``--repo-lint`` — AST-based structural lint (no regex, no grep):

      1. ``pallas_call`` is constructed only under ``src/repro/codegen/``
         — any ``.pallas_call`` attribute or ``from ... import
         pallas_call`` elsewhere in src/tests/tools fails
         (subsumes the old CI grep, and docstrings no longer false-
         positive);
      2. every kernel family package ships a ``specs.py`` and every
         ``kernels/gen`` module lowers builders imported from one —
         plus every gen-family registry row publishes a ``traversal``
         so the sweep above actually covers it;
      3. every obs event/counter/span name emitted from src/ appears in
         the README § Observability table.

Exit codes: 0 = clean; 1 =
findings/violations; 2 = missing/malformed input or a fixture whose
expected rule did not fire.
"""
from __future__ import annotations

import argparse
import ast
import json
import os
import sys
from typing import Any, Optional

__all__ = ["SpeclintError", "sweep", "run_fixture", "repo_lint",
           "collect_emitted_names", "documented_names", "main"]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# dirs the pallas containment rule scans / the one dir allowed to emit
_SCAN_DIRS = ("src", "tests", "tools")
_EMITTER_PREFIX = os.path.join("src", "repro", "codegen") + os.sep


class SpeclintError(Exception):
    """Missing/malformed input (CLI exit code 2)."""


# ------------------------------------------------------- registry sweep

def _candidate_configs(traffic) -> list:
    """The configs a variant will actually face: the conformance-matrix
    points plus the planner's own ranked candidates (unfiltered —
    ``spec=None`` — because the point is to see what the filter WOULD
    reject)."""
    from repro.core.planner import rank_configs
    from repro.registry.base import CONFORMANCE_CONFIGS

    cands = [cfg for _label, cfg in CONFORMANCE_CONFIGS]
    if traffic is not None:
        try:
            cands += [c for c, _bw, _cols in rank_configs(traffic)]
        except ValueError:
            pass
    seen, out = set(), []
    for c in cands:
        key = (c.stride_unroll, c.portion_unroll, c.block_rows,
               c.arrangement)
        if key not in seen:
            seen.add(key)
            out.append(c)
    return out


def sweep(kernels: Optional[list[str]] = None) -> dict[str, Any]:
    """Static-verify every registered traversal at its default and
    aliased sizes, against every candidate config."""
    import jax.numpy as jnp

    from repro.analysis import checker
    from repro.registry import base

    report: dict[str, Any] = {"kernels": {}, "skipped": [],
                              "findings": 0, "errors": 0}
    for spec in base.all_specs():
        if kernels and spec.name not in kernels:
            continue
        if spec.traversal is None:
            report["skipped"].append(spec.name)
            continue
        rows = []
        for sizes in (spec.default_sizes, spec.aliased_sizes):
            sizes = dict(sizes)
            trav = spec.traversal(sizes, jnp.float32)
            traffic = (spec.traffic(sizes, jnp.float32)
                       if spec.traffic is not None else None)
            found = list(checker.check(trav))
            n_cfg = 0
            for cfg in _candidate_configs(traffic):
                n_cfg += 1
                found += checker.check(trav, cfg, static=False)
            rows.append({"sizes": sizes, "configs": n_cfg,
                         "findings": [f.as_dict() for f in found]})
            report["findings"] += len(found)
            report["errors"] += sum(f.severity == "error" for f in found)
        report["kernels"][spec.name] = rows
    if kernels:
        missing = set(kernels) - set(report["kernels"])
        if missing:
            raise SpeclintError(
                f"no traversal-publishing kernel named {sorted(missing)}")
    return report


def format_sweep(report: dict[str, Any]) -> str:
    lines = ["# speclint: registry sweep"]
    for name, rows in report["kernels"].items():
        for row in rows:
            flagged = [f for f in row["findings"]]
            mark = ("clean" if not flagged else
                    ", ".join(f"{f['rule']}({f['severity']})"
                              for f in flagged))
            lines.append(f"{name:28s} {str(row['sizes']):38s} "
                         f"configs={row['configs']:<3d} {mark}")
    if report["skipped"]:
        lines.append("no traversal (skipped): "
                     + ", ".join(report["skipped"]))
    lines.append(f"findings: {report['findings']} "
                 f"({report['errors']} errors)")
    return "\n".join(lines)


# ------------------------------------------------------------ fixtures

def run_fixture(name: str) -> dict[str, Any]:
    from repro.analysis import checker, fixtures

    try:
        fx = fixtures.build(name)
    except ValueError as e:
        raise SpeclintError(str(e))
    found = checker.check(fx.spec, fx.config, **fx.check_kwargs)
    return {
        "fixture": name,
        "expected_rule": fx.rule,
        "findings": [f.as_dict() for f in found],
        "flagged": any(f.rule == fx.rule for f in found),
    }


# ----------------------------------------------------------- repo lint

def _parse(path: str) -> ast.AST:
    try:
        with open(path, encoding="utf-8") as f:
            return ast.parse(f.read(), filename=path)
    except (OSError, SyntaxError) as e:
        raise SpeclintError(f"{path}: cannot parse ({e})")


def _py_files(root: str, subdirs) -> list[str]:
    out = []
    for sub in subdirs:
        top = os.path.join(root, sub)
        for dirpath, _dirnames, filenames in os.walk(top):
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    out.append(os.path.join(dirpath, fn))
    return sorted(out)


def _lint_pallas_containment(root: str) -> list[str]:
    """Rule 1: pallas_call exists only under src/repro/codegen/."""
    problems = []
    for path in _py_files(root, _SCAN_DIRS):
        rel = os.path.relpath(path, root)
        if rel.startswith(_EMITTER_PREFIX):
            continue
        for node in ast.walk(_parse(path)):
            if (isinstance(node, ast.Attribute)
                    and node.attr == "pallas_call"):
                problems.append(
                    f"{rel}:{node.lineno}: pallas_call outside "
                    "src/repro/codegen/ — hand-written kernel bodies are "
                    "retired; express kernels as TraversalSpecs")
            elif isinstance(node, ast.ImportFrom):
                if any(a.name == "pallas_call"
                       for a in node.names):
                    problems.append(
                        f"{rel}:{node.lineno}: imports pallas_call "
                        "directly — only src/repro/codegen/ may construct "
                        "kernels")
    return problems


def _lint_specs_layout(root: str) -> list[str]:
    """Rule 2: one specs.py per family; gen modules lower spec builders;
    gen registry rows publish their traversal IR."""
    problems = []
    kdir = os.path.join(root, "src", "repro", "kernels")
    for entry in sorted(os.listdir(kdir)):
        fam = os.path.join(kdir, entry)
        if (not os.path.isdir(fam) or entry == "gen"
                or not os.path.exists(os.path.join(fam, "__init__.py"))):
            continue
        if not os.path.exists(os.path.join(fam, "specs.py")):
            problems.append(
                f"src/repro/kernels/{entry}/: family package without a "
                "specs.py — every variant must be reachable from a "
                "TraversalSpec builder")
    gdir = os.path.join(kdir, "gen")
    for fn in sorted(os.listdir(gdir)):
        if not fn.endswith(".py"):
            continue
        path = os.path.join(gdir, fn)
        imports_specs = any(
            isinstance(node, ast.ImportFrom) and node.module
            and "specs" in node.module
            for node in ast.walk(_parse(path)))
        if not imports_specs:
            problems.append(
                f"src/repro/kernels/gen/{fn}: lowers no specs.py builder "
                "— generated variants must import their IR from a family "
                "specs module")
    try:
        from repro.registry import base
        for spec in base.all_specs():
            if spec.family == "gen" and spec.traversal is None:
                problems.append(
                    f"registry: {spec.name} publishes no traversal — the "
                    "static verifier cannot screen it")
    except Exception as e:   # registry import needs jax; surface loudly
        raise SpeclintError(f"cannot load registry for lint: {e}")
    return problems


def collect_emitted_names(root: str) -> dict[str, str]:
    """{event name: file:line} for every literal obs emission."""
    names: dict[str, str] = {}
    for path in _py_files(root, ("src",)):
        rel = os.path.relpath(path, root)
        for node in ast.walk(_parse(path)):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("event", "counter", "span")
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue
            base = node.func.value
            if isinstance(base, ast.Name) and base.id in ("obs", "_obs"):
                names.setdefault(node.args[0].value,
                                 f"{rel}:{node.lineno}")
    return names


def _expand_braces(token: str) -> list[str]:
    """``a.{x,y}`` -> [a.x, a.y] (single brace group, no regex)."""
    if "{" not in token:
        return [token]
    head, rest = token.split("{", 1)
    body, tail = rest.split("}", 1)
    return [head + alt + tail for alt in body.split(",")]


def documented_names(readme_path: str) -> set[str]:
    """Event names from the README Observability table (`name` cells)."""
    try:
        with open(readme_path, encoding="utf-8") as f:
            lines = f.read().splitlines()
    except OSError as e:
        raise SpeclintError(f"{readme_path}: cannot read ({e})")
    names: set[str] = set()
    in_table = False
    for line in lines:
        cells = [c.strip() for c in line.strip().split("|")]
        if len(cells) >= 4 and cells[1] == "name" and cells[2] == "layer":
            in_table = True
            continue
        if not in_table:
            continue
        if not line.strip().startswith("|"):
            in_table = False
            continue
        first = cells[1] if len(cells) > 1 else ""
        # a cell may hold several backticked names (`a` / `b` / `c`)
        parts = first.split("`")
        for tok in parts[1::2]:
            if set(tok) <= {"-", ":"}:   # separator row
                continue
            names.update(_expand_braces(tok))
    if not names:
        raise SpeclintError(
            f"{readme_path}: no Observability name table found")
    return names


def _lint_obs_names(root: str) -> list[str]:
    """Rule 3: every emitted event name is documented in the README."""
    emitted = collect_emitted_names(root)
    documented = documented_names(os.path.join(root, "README.md"))
    problems = []
    for name in sorted(set(emitted) - documented):
        problems.append(
            f"{emitted[name]}: obs event {name!r} is not documented in "
            "the README § Observability table")
    return problems


def repo_lint(root: str = REPO) -> dict[str, Any]:
    problems = (_lint_pallas_containment(root)
                + _lint_specs_layout(root)
                + _lint_obs_names(root))
    return {"repo": root, "problems": problems}


# ---------------------------------------------------------------- CLI

def main(argv: Optional[list[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="static analysis over the codegen registry + repo "
                    "lint (repro.analysis front end)")
    ap.add_argument("--kernel", action="append", default=None,
                    metavar="NAME",
                    help="restrict the sweep to named variants")
    ap.add_argument("--fixture", default=None, metavar="NAME",
                    help="run one adversarial fixture "
                         "(race, redsplit, halo, vmem, reassoc); the "
                         "expected rule firing exits 1, missing it 2")
    ap.add_argument("--repo-lint", action="store_true",
                    help="AST lint: pallas containment, specs.py layout, "
                         "README-documented obs names")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the structured report")
    args = ap.parse_args(argv)

    try:
        if args.repo_lint:
            report = repo_lint()
            for p in report["problems"]:
                print(p)
            print(f"# repo-lint: {len(report['problems'])} problem(s)")
            rc = 1 if report["problems"] else 0
        elif args.fixture:
            report = run_fixture(args.fixture)
            for f in report["findings"]:
                print(f"{f['rule']}({f['severity']}) @{f['locus']}: "
                      f"{f['message']}")
            if not report["flagged"]:
                print(f"speclint: fixture {args.fixture!r} expected "
                      f"{report['expected_rule']} but it did not fire",
                      file=sys.stderr)
                rc = 2
            else:
                print(f"# fixture {args.fixture}: "
                      f"{report['expected_rule']} flagged as expected")
                rc = 1
        else:
            report = sweep(args.kernel)
            print(format_sweep(report))
            rc = 1 if report["errors"] else 0
    except SpeclintError as e:
        print(f"speclint: {e}", file=sys.stderr)
        return 2
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1)
        print(f"# wrote {args.json}", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
