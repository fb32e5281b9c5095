"""Each example runs to its end on the CPU.

The examples are the README's entry points; nothing else runs them.
Each runs in its own process, as a reader would start it.
``train_lm.py`` runs for minutes and is left out.
"""
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.mark.parametrize("example", ["quickstart.py", "multistride_kernels.py",
                                     "codegen_kernel.py", "serve_lm.py"])
def test_example_runs(example):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    env.pop("REPRO_KERNEL_MODE", None)      # each example's own default
    res = subprocess.run([sys.executable, str(ROOT / "examples" / example)],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=120)
    assert res.returncode == 0, res.stderr[-4000:]
