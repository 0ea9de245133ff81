"""Where JAX keeps its persistent compilation cache.

Entry points (`chip_smoke.py`, `repro.launch.serve`, `benchmarks.run`)
call `use_compile_cache()` once, before their first compile; importing
`repro` never does.  The cache key includes the directory, so the
directory must not move between runs: an operator's
`JAX_COMPILATION_CACHE_DIR` wins (JAX reads it itself), and otherwise the
cache lives at the fixed `<repo>/.jax_cache`.
"""

from __future__ import annotations

import os
import pathlib

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
