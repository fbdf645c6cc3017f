"""The few things every driver takes from the program besides its entry
points: the one compile cache and the counters it already keeps."""

from __future__ import annotations


def enable_compile_cache() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` if set, else the fixed directory
    inside the checkout (``flinkml_tpu.utils.jax_cache`` is the program's
    one place for this)."""
    from flinkml_tpu.utils import jax_cache

    return jax_cache.enable()


def counters() -> dict:
    """Every metric group of the program, as ``default_registry()``
    snapshots it."""
    from flinkml_tpu.utils.metrics import default_registry

    return default_registry().snapshot()
