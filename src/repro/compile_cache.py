"""Where the entry points keep JAX's persistent compilation cache.

Every entry point that may run on an accelerator (chip_smoke.py,
benchmarks/run.py, the examples) calls `enable_compile_cache()` once,
before its first compile. The rule:

* `JAX_COMPILATION_CACHE_DIR` set: JAX keeps its cache in that directory,
  and no other directory is set here;
* unset: the cache goes to `<checkout>/.jax-cache` (listed in .gitignore).

The path is fixed on purpose. It is part of what a later run must find
again, so a temporary, per-process or per-run directory would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax-cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at the directory the rule
    above picks; returns that directory."""
    import jax

    path = os.environ.get(CACHE_ENV) or str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
