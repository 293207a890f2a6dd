"""Where a checkout keeps what it builds at run time: JAX's persistent
compilation cache and the compiled ``_native`` libraries.

Everything goes under ONE fixed, git-ignored directory at the root of
the checkout. Fixed matters: the directory is part of the compilation
cache's key, so a path made from a temporary name, a pid or the time
would never hit.
"""

from __future__ import annotations

import os

CACHE_DIRNAME = ".ray-tpu-cache"

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def checkout_cache_dir(*parts: str) -> str:
    """``<checkout>/.ray-tpu-cache/<parts...>``, created on demand."""
    path = os.path.join(_REPO_ROOT, CACHE_DIRNAME, *parts)
    os.makedirs(path, exist_ok=True)
    return path


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read
    it already and nothing here sets another; otherwise the cache lives
    in ``checkout_cache_dir("jax")``. Call before the first compile."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax

    path = checkout_cache_dir("jax")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
