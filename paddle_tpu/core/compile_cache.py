"""Where compiled programs persist between processes.

A cold engine or train step compiles for minutes on the chip; JAX's
persistent cache turns the second process's set-up into a disk read.  The
directory is part of the cache key, so it must be the same path every time:
never the working directory, a temporary name, a pid or a time.
"""
import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache():
    """Call once, before the first compilation, from every entry point that
    touches the chip.  Returns the cache directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    function sets nothing — whoever runs the program has placed the cache.
    Otherwise the cache goes to ``<checkout>/.jax_cache`` (git-ignored),
    resolved from this file's location so every working directory agrees.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
