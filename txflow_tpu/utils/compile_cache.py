"""Where JAX's persistent compilation cache lives.

One decision, made in one place: ``JAX_COMPILATION_CACHE_DIR`` wins when
it is set — JAX reads it itself and nothing here overrides it — and
otherwise the cache is ``<checkout>/.jax_cache``. Never a temporary,
pid- or time-named directory: the path is part of the cache key, so a
directory that moves never hits.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def use_compile_cache() -> str:
    """Place the persistent compile cache and return its directory.

    Call before the first compile (JAX opens the cache lazily at the
    first one and keeps what it opened). Idempotent."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    return path
