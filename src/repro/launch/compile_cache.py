"""Persistent compilation cache placement shared by the launchers.

JAX keys its compile cache by the directory as well as the program, so a
directory that moves between runs never hits.  ``JAX_COMPILATION_CACHE_DIR``
wins when it is set (JAX reads it itself, and nothing here overrides it);
otherwise the cache lives at a fixed path inside the checkout,
``.jax_cache/`` (git-ignored).
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "setup_compile_cache"]

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn the persistent cache on and return the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
