"""JAX's persistent compilation cache: where it lives, decided once.

Every backend that compiles (jax filter, llm filter, fused segment,
trainer) calls :func:`ensure_compile_cache` before its first compile.
This is the ONLY place the repo writes ``jax_compilation_cache_dir``:

* ``JAX_COMPILATION_CACHE_DIR`` set -> JAX already read it at import;
  the program sets nothing, so whoever runs the process places the
  cache (a CI volume, a chip tool's carried-over directory).
* unset -> a fixed, git-ignored directory inside the checkout. The path
  is part of every cache key, so it must be the same on every run: never
  a temporary name, a pid or a clock.

The fleet's signature registry (``NNS_COMPILE_CACHE``, fleet/cache.py)
is a different thing — it remembers WHICH shapes to replay at open, not
the compiled programs — and never names this directory.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def ensure_compile_cache() -> str:
    """Make sure compiles from here on hit a persistent cache; returns
    the directory in use. Idempotent and cheap (no backend is touched,
    nothing is created on disk until JAX writes its first entry)."""
    import jax
    if not (os.environ.get(ENV_VAR)
            or jax.config.jax_compilation_cache_dir):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return jax.config.jax_compilation_cache_dir
