"""Where the entry points keep JAX's persistent compilation cache.

A cache entry is keyed by the directory it lives in, so the directory has
to stay put from one run to the next: it is either the one the
environment names, or a fixed directory inside the repository — never one
made from a temporary name, a pid or the time. Library code and tests do
not call this; `chip_smoke.py`, the launch drivers and the benchmark
mains call it once before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: used when JAX_COMPILATION_CACHE_DIR is not set (listed in .gitignore)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Keep compiled programs across runs; returns the directory used.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and
    nothing is changed; otherwise the cache goes to `REPO_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
