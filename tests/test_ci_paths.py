"""Every module, script and test file that CI names exists."""
import importlib.util
import pathlib
import re

ROOT = pathlib.Path(__file__).resolve().parent.parent
CI = ROOT / ".github" / "workflows" / "ci.yml"


def test_ci_names_only_paths_that_exist(monkeypatch):
    text = CI.read_text()
    modules = set(re.findall(r"python -m ([\w.]+)", text))
    scripts = set(re.findall(r"python ([\w./-]+\.py)", text))
    tests = set(re.findall(r"\b(tests/[\w./-]+)", text))
    assert modules and scripts and tests
    monkeypatch.syspath_prepend(str(ROOT / "src"))
    monkeypatch.syspath_prepend(str(ROOT))
    missing = [m for m in sorted(modules)
               if importlib.util.find_spec(m) is None]
    missing += [p for p in sorted(scripts | tests) if not (ROOT / p).exists()]
    assert not missing, f"{CI.relative_to(ROOT)} names: {missing}"
