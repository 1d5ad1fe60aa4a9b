"""Device programs of the store (compaction merge) and their Pallas kernels."""
from __future__ import annotations

from pathlib import Path

# <checkout>/.jax_cache: a fixed path, since the path is part of the cache key
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Keep JAX's persistent compile cache in ``<checkout>/.jax_cache``.

    Does nothing when a cache directory is already configured, as it is when
    ``JAX_COMPILATION_CACHE_DIR`` is set: the environment wins.
    """
    import jax

    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
