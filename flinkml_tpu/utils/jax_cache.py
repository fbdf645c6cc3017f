"""The one module that touches JAX's persistent compilation cache.

Rule: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and this module sets no directory; where it is not, the cache lives at
``<checkout>/.jax_cache`` (git-ignored, resolved from this package's own
path — the directory is part of the cache key, so it must not move
between runs). Entry points (``tests/conftest.py``,
``benchmark/drivers/program.py``, ``chip_smoke.py``, the probes) call
:func:`enable` before their first compile; library code never does.
"""

from __future__ import annotations

import contextlib
import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def cache_dir() -> str:
    """The directory :func:`enable` selects (without touching JAX)."""
    package = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.environ.get(ENV_VAR) or os.path.join(
        os.path.dirname(package), ".jax_cache")


def aot_dir() -> str:
    """Where the repo's own AOT artifact store goes when
    ``FLINKML_TPU_COMPILE_CACHE`` names no directory: ``aot/`` inside
    the cache directory in use, so it shares that directory's fate."""
    return os.path.join(cache_dir(), "aot")


def in_use():
    """The directory this process's JAX keeps compiled programs in
    (:func:`enable`'s, or the variable's), None where the persistent
    cache is off: for what else is kept beside the programs."""
    import jax

    return jax.config.jax_compilation_cache_dir


def enable() -> str:
    """Turn the persistent cache on for this process; return its
    directory. Every compile is cached (the default floor of one second
    would skip most of this repo's programs)."""
    import jax

    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir()


@contextlib.contextmanager
def suspended():
    """Compile with the persistent cache off, then restore it.

    JAX latches the cache on first use, so un-setting the directory is
    not enough: the cache object is reset on the way in and out.
    """
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_compilation_cache_dir
    if prev is None:
        yield
        return
    jax.config.update("jax_compilation_cache_dir", None)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        cc.reset_cache()
