"""One persistent compilation cache for every entry point.

Each launcher (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.chaos``, ``examples/*.py``, ``benchmarks/run.py``) calls
:func:`enable_compile_cache` before its first compile. The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this helper
  sets nothing else.
* unset: the cache goes to ``<repo>/.jax_cache`` (git-ignored). The path
  is fixed because it is part of the cache key: a directory that moves
  never hits.

The helper exports the variable, so child processes the entry point
starts use the same directory. It imports no JAX, so a parent that must
stay off the chip (the chaos harness) can call it.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at one directory; returns it."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    os.environ[ENV_VAR] = REPO_CACHE_DIR
    jax = sys.modules.get("jax")
    if jax is not None:  # imported already: its config read the env before
        jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
