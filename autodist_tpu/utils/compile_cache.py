"""Where the persistent XLA compile cache lives.

Entry points (``chip_smoke.py``, ``bench.py``, ``examples/benchmark/
train.py``, ``python -m autodist_tpu.serve`` server mode) call
:func:`enable_compile_cache` once, before their first compile — never at
package import. The directory is part of the cache key, so it must not move
between runs: it is either what the operator exported in
``JAX_COMPILATION_CACHE_DIR`` (jax reads that itself; nothing is set in
code) or ``<checkout>/.jax_cache`` — a fixed, git-ignored path that every
process started from the same checkout resolves to the same directory.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Make sure jax has a persistent compile cache and return its
    directory: the environment's if it names one (nothing is set in code),
    else ``<checkout>/.jax_cache``."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
