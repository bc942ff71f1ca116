"""Persistent XLA compilation cache for the program's entry points.

The fleet and single-hall programs take tens of seconds to compile on a
TPU, and a fresh process compiles them again.  JAX's persistent cache
keeps the compiled executables on disk and keys them, among other
things, by the cache directory, so the directory must not move between
runs: a path made from a temporary directory, a pid or the time never
hits.

Entry points (`chip_smoke.py`, `benchmarks.run`, the `examples/`
scripts) call `enable_compile_cache()` once, before their first
compile.  Library code and tests never call it, so importing the
package leaves JAX's configuration untouched.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/src/repro/runtime/compile_cache.py → <checkout>/.jax_cache
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and
    nothing is changed here.  Otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache``, which `.gitignore` lists."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
