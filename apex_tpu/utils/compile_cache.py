"""Persistent XLA compilation cache for the entry scripts.

Called by the scripts a user runs (`chip_smoke.py`, `bench.py`, the
examples through `examples/_bootstrap.py`) — never on `import apex_tpu`,
which must not change process-wide JAX configuration.

The cache directory is part of the cache key, so it must not move
between runs: where `JAX_COMPILATION_CACHE_DIR` is set JAX reads it
itself and nothing is set here; otherwise the cache lives at the fixed
path `<checkout>/.jax_cache` (listed in `.gitignore`).
"""

from __future__ import annotations

import os

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV_CACHE_DIR)
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
