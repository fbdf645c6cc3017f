"""Many small symmetric positive definite systems solved at once, a
system a LANE: the solve of ALS's normal equations on a TPU.

XLA's batched Cholesky walks a ``[batch, k, k]`` array column by column
and reads and writes all of it from HBM at every column; at ``k`` 100 and
a million systems that is terabytes a half-step. Here a block of 128
systems lies in fast memory as ``[k, width, 128]`` (row, column, system),
so every step of the elimination is plain elementwise arithmetic over
whole vregs, a system a lane, and HBM is read once and written once.

The method is Gaussian elimination without pivoting on the augmented
``[A | b]`` (for a symmetric positive definite ``A`` it is ``L D Lᵀ``, as
stable as Cholesky, and takes no root), then the back substitution:
float32 throughout. ``tests/test_als_blocked.py`` bounds its residual
against NumPy's float64 solve.
"""

from __future__ import annotations

import functools
from typing import Optional

#: Systems a block: a vreg's lanes.
LANES = 128
#: Rows of a vreg: columns are cut at multiples of it.
SUBLANES = 8
#: Three blocks of ``[100, 104, 128]`` float32 (two input buffers and the
#: one eliminated in place) are 16 MB, a v5e's default limit.
VMEM_LIMIT_BYTES = 40 << 20


def augmented_width(k: int) -> int:
    """Columns of ``[A | b]`` for rank ``k``, in whole vreg rows."""
    return -(-(k + 1) // SUBLANES) * SUBLANES


def _solve_body(aug_ref, x_ref, work_ref, *, k: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    width = work_ref.shape[1]
    work_ref[...] = aug_ref[...]
    # The pivots a vreg's rows at a time: columns below a pivot are
    # eliminated already, so a row is read from the vreg that holds the
    # block's first column on (a static cut; the pivot inside it is not).
    for j0 in range(0, k - 1, SUBLANES):
        cols = pl.ds(j0, width - j0)

        def pivot(j, carry, cols=cols):
            at = pl.ds(j, 1)
            scaled = work_ref[j, cols, :] / work_ref[j, at, :]

            def eliminate(i, carry):
                work_ref[i, cols, :] = (
                    work_ref[i, cols, :] - work_ref[i, at, :] * scaled)
                return carry

            return jax.lax.fori_loop(j + 1, k, eliminate, carry)

        jax.lax.fori_loop(j0, min(j0 + SUBLANES, k - 1), pivot, 0)
    # Back substitution into the output block, a row of it a solved
    # unknown; the rows not yet solved are 0 and add nothing.
    x_ref[...] = jnp.zeros(x_ref.shape, x_ref.dtype)

    def unknown(step, carry):
        j = k - 1 - step
        row = work_ref[j]
        known = jnp.sum(row * x_ref[...], axis=0, keepdims=True)
        x_ref[pl.ds(j, 1), :] = (row[k:k + 1, :] - known) / work_ref[j, pl.ds(j, 1), :]
        return carry

    jax.lax.fori_loop(0, k, unknown, 0)


@functools.lru_cache(maxsize=8)
def _call(k: int, width: int, batch: int, interpret: bool, vma):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        functools.partial(_solve_body, k=k),
        grid=(batch // LANES,),
        in_specs=[pl.BlockSpec((k, width, LANES), lambda g: (0, 0, g))],
        out_specs=pl.BlockSpec((width, LANES), lambda g: (0, g)),
        out_shape=jax.ShapeDtypeStruct((width, batch), jnp.float32, vma=vma),
        scratch_shapes=[pltpu.VMEM((k, width, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )


def solve_lanes(aug, k: int, interpret: Optional[bool] = None):
    """``x [k, batch]`` with ``A_n x_n = b_n`` for every system ``n``:
    ``aug [k, width, batch]`` float32 holds ``A_n[i, m]`` at ``[i, m,
    n]`` for ``m < k`` and ``b_n[i]`` at ``[i, k, n]`` (``width`` is
    :func:`augmented_width`, the columns past ``k`` anything finite;
    ``batch`` whole blocks of :data:`LANES`). Every ``A_n`` symmetric
    positive definite."""
    import jax

    from flinkml_tpu.kernels import _mosaic

    if interpret is None:
        interpret = _mosaic.interpret_mode()
    rows, width, batch = aug.shape
    if rows != k or width != augmented_width(k) or batch % LANES:
        raise ValueError(
            f"solve_lanes wants [{k}, {augmented_width(k)}, whole blocks of "
            f"{LANES}], got {aug.shape}")
    # Traced in 32-bit mode whatever the caller's: Mosaic lowers no
    # 64-bit index or constant.
    with jax.enable_x64(False):
        x = _call(k, width, batch, bool(interpret), jax.typeof(aug).vma)(aug)
    return x[:k]
