"""Device-backend questions the rest of the code asks in one place:
is this a TPU, and where does JAX's persistent compile cache live."""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def is_tpu_backend() -> bool:
    """True when the default JAX backend is a TPU."""
    import jax

    return jax.default_backend() == "tpu"


def place_compilation_cache(configured: str = "") -> str:
    """Place JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing here touches the setting: whoever runs the process placed
    the cache from outside. Otherwise the operator's
    ``tpu_compilation_cache_dir``, and failing that, on a TPU, the fixed
    path ``<checkout>/.jax_cache`` — the path is part of the cache key,
    so a directory that moves (a temp name, a pid, a time) never hits.
    Off the TPU nothing is cached unless asked for: XLA:CPU programs
    compile in seconds and its loader warns on every cached one.
    Returns "" when no cache is in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = configured
    if not path and is_tpu_backend():
        path = os.path.join(_CHECKOUT, ".jax_cache")
    if path:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
