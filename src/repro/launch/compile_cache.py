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

It also puts the programs' metadata into the cache key
(``JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY``, unless set): the
named scopes that profiles attribute device time by are op metadata, and
without it an executable cached by another version of the program would
carry that version's scopes (or none) into the trace.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
METADATA_VAR = "JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY"
REPO_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at one directory; returns it."""
    jax = sys.modules.get("jax")  # imported already: it read the env before
    if METADATA_VAR not in os.environ:
        os.environ[METADATA_VAR] = "true"
        if jax is not None:
            jax.config.update(
                "jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    os.environ[ENV_VAR] = REPO_CACHE_DIR
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
