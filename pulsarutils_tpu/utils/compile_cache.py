"""Where this checkout keeps what it caches between processes.

Two caches outlive a process: JAX's persistent compilation cache (a
1,024 x 2^20 chunk's programs take minutes to compile cold) and the
kernel autotuner's tune cache (:mod:`pulsarutils_tpu.tuning.cache`).
Both live in one fixed, git-ignored directory at the root of the
checkout — never under ``$HOME`` — so a run's behaviour depends only on
the tree it runs from, and the compile-cache path (part of JAX's cache
key) never moves.
"""

from __future__ import annotations

import os

__all__ = ["CACHE_DIRNAME", "checkout_cache_dir", "enable_compile_cache"]

#: directory (relative to the checkout root) listed in ``.gitignore``
CACHE_DIRNAME = ".pulsarutils_tpu_cache"


def checkout_cache_dir(*parts):
    """``<checkout>/.pulsarutils_tpu_cache[/parts...]`` (not created here)."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, CACHE_DIRNAME, *parts)


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache for this process.

    THE one place the cache directory is chosen; every entry point that
    compiles (the CLI mains, the fleet worker, ``chip_smoke.py``) calls
    it before its first compile.  ``JAX_COMPILATION_CACHE_DIR`` wins: when it is set JAX has
    already read it and nothing is set in code; otherwise the cache goes
    to ``<checkout>/.pulsarutils_tpu_cache/jax``.  Returns the directory
    in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = checkout_cache_dir("jax")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
