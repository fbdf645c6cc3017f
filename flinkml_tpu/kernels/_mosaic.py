"""What the Mosaic kernels of this package share: whether a
``pallas_call`` runs under the interpreter (:func:`interpret_mode`: on
every backend but a TPU), the import of Pallas beside a fit's host work
(:func:`import_beside_host_work`), an output's declaration inside
``jax.shard_map`` (:func:`out_struct`), and the error a kernel called
directly raises on operands it cannot run
(:class:`KernelUnsupportedError`). Where a kernel applies is read by its
caller off the kernel's own ``unsupported_reason`` (the backend, the
dtype, the shapes); nothing here chooses between lowerings.
"""

from __future__ import annotations

import os

#: Force/forbid interpreter-mode ``pallas_call`` (default: interpret on
#: every non-TPU backend so CPU CI runs the kernels device-free).
ENV_INTERPRET_VAR = "FLINKML_TPU_KERNELS_INTERPRET"


class KernelUnsupportedError(ValueError):
    """A Pallas kernel called by name cannot run this dtype/shape.

    Raised INSTEAD of silently falling back: the caller asked for the
    kernel itself, so degrading quietly would misreport what was
    measured. The message names the kernel, the offending dtype/shape,
    and the supported set.
    """


def interpret_mode() -> bool:
    """Whether ``pallas_call`` should run under the interpreter: yes on
    every non-TPU backend (CPU CI stays device-free), overridable with
    ``FLINKML_TPU_KERNELS_INTERPRET=0/1`` (device runs can force the
    interpreter for a parity bisect)."""
    forced = os.environ.get(ENV_INTERPRET_VAR)
    if forced is not None:
        if forced not in ("0", "1"):
            raise ValueError(
                f"{ENV_INTERPRET_VAR}={forced!r}: expected '0' or '1'"
            )
        return forced == "1"
    import jax

    return jax.default_backend() != "tpu"


#: What tracing a Mosaic kernel imports: Pallas, and the module its TPU
#: lowering pulls in at the first ``pallas_call`` lowered (private: left
#: to that moment where this version of JAX has none of the name).
_TRACING_IMPORTS = ("jax.experimental.pallas", "jax.experimental.pallas.tpu",
                    "jax._src.pallas.mosaic.pallas_call_registration")


def _import_keeping_bytecode(names=_TRACING_IMPORTS) -> None:
    """Import ``names``, their bytecode kept beside the programs this
    process keeps: where JAX's persistent compilation cache is on (the
    entry point's or ``JAX_COMPILATION_CACHE_DIR``'s directory), the
    interpreter reads and writes the modules' compiled code under its
    ``pycache/`` while the imports run (``sys.pycache_prefix``, put back
    after). On a host whose site-packages keep no bytecode the import of
    Pallas is 1.09 s of ``compile()`` in 1.15 (PERF.md section 5), each
    process anew, where the program it traces comes from the cache; a
    process with no cache directory imports as it always did."""
    import importlib
    import os
    import sys

    from flinkml_tpu.utils import jax_cache

    kept = jax_cache.in_use()
    before = sys.pycache_prefix, sys.dont_write_bytecode
    if kept:
        sys.pycache_prefix = os.path.join(kept, "pycache")
        sys.dont_write_bytecode = False
    try:
        for name in names:
            try:
                importlib.import_module(name)
            except ImportError:
                if not name.startswith("jax._src."):
                    raise
    finally:
        sys.pycache_prefix, sys.dont_write_bytecode = before


def import_beside_host_work() -> None:
    """Start importing Pallas on a thread of its own, on a TPU, where it
    is not imported yet: what a fit whose step may hold a Mosaic kernel
    (``sparse_blocks``, ``dense_step``, ``row_update``) calls where it
    starts. A fit's first dispatch traces the kernels, and before it comes
    host work
    that is NumPy's (the plan's pass, the seeded permutation and gather:
    0.8 s at ``lr-criteo``'s 16.8 M rows, 0.7 s at ``lr-a9a``'s 9.4 M);
    the import otherwise stands in the fit between its placement's first
    round and its first step (:func:`_import_keeping_bytecode` has what
    it costs). The import's own lock makes the tracing thread wait for
    what is left of it."""
    import sys
    import threading

    if interpret_mode() or "jax.experimental.pallas" in sys.modules:
        return
    threading.Thread(target=_import_keeping_bytecode, daemon=True).start()


def out_struct(shape, dtype, *operands):
    """The ``out_shape`` entry for a ``pallas_call`` whose output varies
    over the same manual mesh axes as ``operands``: inside
    ``jax.shard_map`` (``check_vma=True``, the default) an output must
    declare its ``vma``; outside one the set is empty."""
    import jax

    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(tuple(shape), dtype, vma=vma)
