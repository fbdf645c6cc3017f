"""Pallas bucketed top-k — KNN voting and LSH candidate ranking.

``jax.lax.top_k`` over a ``[nq, n]`` distance matrix sorts every row's
full n-vector to keep k of it. For the small k the neighbor queries use
(k ≪ n), k passes of a masked row-max over a VMEM-resident tile do the
same work as k sweeps of the VPU with no sort network: the kernel tiles
the query rows (grid over ``rows / TILE``), keeps each ``[TILE, n]``
block resident, and per pass records the row max + its first index, then
masks exactly that column out. Selected values are exact copies of input
elements and ``argmax`` takes the FIRST maximum, so values AND indices
are bit-identical to ``lax.top_k`` (both break ties toward the lower
index).

The KNN search's tiled path calls :func:`pallas_top_k` for the tiles of
its distance matrix (``models.knn._tile_top_k``): on a
v5e it is that search's fastest exact top-k (a call of 10,000 queries
against 2,025,000 rows: 1.589 s with it, 1.791 with ``lax.top_k`` over
the same tiles; PERF.md §5, PR 30). Operands it cannot rank are refused
by name (:class:`~flinkml_tpu.kernels._mosaic.KernelUnsupportedError`).
"""

from __future__ import annotations

from typing import Optional, Tuple

#: Query-row tile (grid unit). 8 = f32 sublane count; rows pad up to a
#: multiple with -inf rows that are sliced off after the call.
ROW_TILE = 8

#: k passes unroll into the kernel body; beyond this the unrolled body
#: stops being the cheap path and a sort is the right tool — refuse.
MAX_K = 128


def unsupported_reason(x, k: int, interpret: bool) -> Optional[str]:
    """Why the Pallas kernel cannot rank these operands (None = it can)."""
    import jax.numpy as jnp

    if x.ndim not in (1, 2):
        return f"operand must be [n] or [rows, n], got rank {x.ndim}"
    n = x.shape[-1]
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return (f"operand dtype {x.dtype} is not floating (the mask "
                "sentinel is -inf; integer ranking has no Pallas path)")
    if not 1 <= k <= n:
        return f"k={k} outside [1, n={n}]"
    if k > MAX_K:
        return f"k={k} exceeds the unrolled-pass ceiling of {MAX_K}"
    if not interpret and x.dtype != jnp.float32:
        return (f"operand dtype {x.dtype}: Mosaic's argmax lowers "
                "float32 only; other float widths are interpreter-only")
    return None


def _topk_body(x_ref, val_ref, idx_ref, *, k: int):
    import jax
    import jax.numpy as jnp

    work = x_ref[...]
    col = jax.lax.broadcasted_iota(jnp.int32, work.shape, 1)
    neg_inf = jnp.full_like(work, -jnp.inf)
    # Selected columns are excluded via a taken-mask, NOT by overwriting
    # the value with -inf: a row whose remaining entries ARE -inf would
    # then re-select column 0 forever instead of walking the untaken
    # -inf entries in ascending index order the way lax.top_k does.
    taken = jnp.zeros(work.shape, jnp.bool_)
    for j in range(k):
        cand = jnp.where(taken, neg_inf, work)
        m = jnp.max(cand, axis=1)
        # lax.argmax with its index dtype said: under x64 jnp.argmax asks
        # for int64 indices, which Mosaic does not lower.
        a = jax.lax.argmax(cand, 1, jnp.int32)
        # All untaken entries at -inf: the masked and unmasked values
        # tie, so argmax must not land on an already-taken column —
        # take the first UNTAKEN index instead.
        # f32 operand: Mosaic's argmax lowers float32 only (not bool).
        first_untaken = jax.lax.argmax(
            jnp.where(taken, jnp.float32(0.0), jnp.float32(1.0)), 1, jnp.int32)
        a = jnp.where(jnp.isneginf(m), first_untaken, a)
        val_ref[:, j] = m
        idx_ref[:, j] = a
        taken = taken | (col == a[:, None])


def pallas_top_k(x, k: int, *, interpret: Optional[bool] = None) -> Tuple:
    """``(values, indices)`` of the k largest entries of each row of
    ``x`` — bit-compatible with ``jax.lax.top_k(x, k)`` (descending
    values, ties toward the lower index, int32 indices)."""
    import contextlib
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from flinkml_tpu.kernels import _mosaic

    if interpret is None:
        interpret = _mosaic.interpret_mode()
    reason = unsupported_reason(x, k, interpret)
    if reason is not None:
        raise _mosaic.KernelUnsupportedError(
            f"kernels.topk: the kernel cannot rank these operands: {reason}")
    squeeze = x.ndim == 1
    x2 = x[None, :] if squeeze else x
    rows, n = x2.shape
    pad = (-rows) % ROW_TILE
    if pad:
        x2 = jnp.concatenate(
            [x2, jnp.full((pad, n), -jnp.inf, x2.dtype)]
        )
    grid = (x2.shape[0] // ROW_TILE,)
    # For Mosaic, traced in 32-bit mode whatever the caller's: under x64
    # the block index maps' literal 0 and the body's Python constants come
    # out 64 bits wide, and Mosaic does not lower those (a float64 block
    # aborts the process in its layout pass). Its operand is float32
    # (unsupported_reason); the interpreter keeps the caller's mode, and
    # with it the float64 operands it alone ranks.
    with contextlib.nullcontext() if interpret else jax.enable_x64(False):
        vals, idxs = pl.pallas_call(
            functools.partial(_topk_body, k=k),
            grid=grid,
            in_specs=[pl.BlockSpec((ROW_TILE, n), lambda i: (i, 0))],
            out_specs=(
                pl.BlockSpec((ROW_TILE, k), lambda i: (i, 0)),
                pl.BlockSpec((ROW_TILE, k), lambda i: (i, 0)),
            ),
            out_shape=(
                _mosaic.out_struct((x2.shape[0], k), x2.dtype, x2),
                _mosaic.out_struct((x2.shape[0], k), jnp.int32, x2),
            ),
            interpret=interpret,
        )(x2)
    if pad:
        vals, idxs = vals[:rows], idxs[:rows]
    if squeeze:
        vals, idxs = vals[0], idxs[0]
    return vals, idxs
