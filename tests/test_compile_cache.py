"""Where the launchers' persistent compile cache lands.

Each case runs in a fresh CPU-only interpreter: JAX initializes its
persistent cache once per process, so the directory must be decided
before the first compile.
"""
import os
import pathlib
import subprocess
import sys

import pytest

from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]

_PROBE = """
import pathlib, sys
import jax, jax.numpy as jnp
from repro.launch import compile_cache
compile_cache.CHECKOUT_CACHE_DIR = pathlib.Path(sys.argv[1])
print(compile_cache.setup_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.arange(7.0)).block_until_ready()
"""


def _probe(tmp_path, env_dir):
    checkout = tmp_path / "checkout_cache"
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _PROBE, str(checkout)],
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()[-1], checkout


def _files(d: pathlib.Path):
    return [p for p in d.rglob("*") if p.is_file()] if d.exists() else []


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_compile_cache_lands_in_one_place(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is written elsewhere;
    unset, the cache goes to the fixed in-checkout directory."""
    env_dir = tmp_path / "env_cache" if from_env else None
    used, checkout = _probe(tmp_path, env_dir)
    want = env_dir if from_env else checkout
    assert used == str(want)
    assert _files(want), f"no cache entries written to {want}"
    if from_env:
        assert not _files(checkout)


def test_checkout_cache_dir_is_fixed_and_ignored():
    """A fixed path inside the checkout (never a temp or per-run name)
    that git ignores."""
    assert compile_cache.CHECKOUT_CACHE_DIR == ROOT / ".jax_cache"
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
