"""Where the persistent XLA compile cache lives.

Entry scripts (chip_smoke.py, benchmark/run.py) call
:func:`enable_compile_cache` once, before first backend use; nothing
calls it at import. The directory is placed from OUTSIDE when
``JAX_COMPILATION_CACHE_DIR`` is set (jax reads that variable itself, so
no directory is set in code); unset, it is the fixed
``<checkout>/.jax_cache`` — the path is part of the cache key, so it
never carries a temp dir, a pid or a time.
"""
import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir():
    """The directory the cache uses: the environment's if set, else the
    fixed in-checkout one. Pure (touches no backend, no filesystem)."""
    return os.environ.get(ENV_VAR) or os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache():
    """Turn the persistent compile cache on; returns its directory."""
    import jax
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
