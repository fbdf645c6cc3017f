"""Pallas dense linear step — a window's margins, multipliers and
gradient from ONE read of its rows.

``models._linear_sgd.make_dense_step`` is, through XLA, two fusions:
``xb @ coef`` and ``xb.T @ mult``, each a pass over the window's rows
(134 MB at ``lr-a9a``'s 262,144 x 123 float32) at nine tenths of the
HBM rate, for the one pass the algorithm needs: a row's multiplier
depends on that row's own dot product and nothing else. Here a tile of
the window's rows (:func:`tile_rows`) comes from HBM once, and its
margins, its multipliers, its share of the gradient, of the loss and of
the weight sum are all made while it is in VMEM.

*The table as it lies.* ``xl [n_local, dim]`` is the kept placement, a
row's features along the lanes (a v5e's layout for a float32 ``[n,
123]``: ``{1,0:T(8,128)}``, read off the compiled ``lr_dense_loop``).
The kernel takes it whole; the window is found by the grid's index map
from the window's first tile, a scalar in SMEM before the grid runs. No
``dynamic_slice`` of the window, no second array.

*Rows to lanes and back through the MXU's weights, nothing multiplied
by hand.* The margins have to come out a row a LANE (dense vregs for
``margin_terms``) and the multipliers go back in a row a SUBLANE; with
the features along the lanes that is a turn of every vreg, and the
shuffle unit turns one in ten cycles and more: a chunk turned in VMEM
read 0.51 ms a step on a v5e, a lane sum and a lane broadcast a vreg
0.27, XLA's two passes 0.38 (PERF.md section 5). The MXU takes a
``[128 rows, 128 features]`` block as its WEIGHTS either way up for the
price of loading it. So a chunk of 128 rows is split once into its
three bfloat16 parts (:func:`flinkml_tpu.kernels._split.disjoint_parts`:
disjoint bit fields, their sum the float32 bit for bit) and is the
weights of both products: forward, the
coefficients' three parts ``[16, dim]`` against the chunk's transpose
(``[16, 128]``: nine exact products a feature, summed in float32; the
three rows added are the chunk's margins, a row a lane); backward, the
multipliers' three parts ``[16, 128]`` against the chunk as it lies
(``[16, dim]``, the chunk's share of the gradient). A matrix-vector
product that STREAMED the rows (``[tile, dim] @ [dim, 1]``, what the
Pallas step this repo once had did, and lost by) pays six passes of 128
columns for one; here 16 rows are streamed against each block of weights
and the cost is the blocks' loading, which hides beside the read: 0.200
ms a step where the read alone is 0.193.

*Float32 throughout, one fixed order.* Every product is bfloat16 x
bfloat16, exact in float32; all nine of a float32 pair's are taken (XLA's
and Mosaic's ``HIGHEST`` take six), summed in float32 by the MXU along
the contraction, chunk after chunk, tile after tile (the grid's one axis
is sequential, the sums stay in VMEM over it). No atomics: the same bits
every run.

One traced body a loss and shape. Traced in 32-bit mode whatever the
caller's (PR 30: a float64 block aborts the process in Mosaic).
"""

from __future__ import annotations

import functools
from typing import Optional

from flinkml_tpu.kernels._split import disjoint_parts

#: Lanes of a vreg: the rows of a chunk, the MXU's block of weights.
LANES = 128
#: Sublanes of a float32 vreg.
SUBLANES = 8
#: Rows of a streamed operand: a bfloat16 vreg's sixteen, of which three
#: hold a vector's parts.
STREAMED = 16
#: Rows a grid step holds, the most (:func:`tile_rows`).
TILE = 4096
#: Rows a grid step holds, the least: a tile's labels are ``[tile / 128,
#: 128]``, whole float32 vregs.
MIN_TILE = 1024
#: Floats of a tile split into their parts at a time (:func:`chunks`):
#: sixteen chunks at up to 128 features, so that one chunk's products
#: run beside the next one's split.
SPLIT_FLOATS = 2048 * 128
#: Features the kernel takes: above it the products are matrix-sized and
#: a tile of :data:`MIN_TILE` rows in two buffers outgrows its share of
#: fast memory.
MAX_DIM = 2048
#: Bytes one buffer of a tile's rows may take (there are two).
_TILE_BYTES = 8 * 1024 * 1024
#: Fast memory the kernel may use (a v5e has 128 MiB, the compiler's own
#: limit is 16).
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _padded(dim: int) -> int:
    """``dim`` in whole rows of lanes, as a tile's rows lie in VMEM."""
    return -(-dim // LANES) * LANES


def features_along_lanes(dim: int) -> bool:
    """Whether a TPU holds a float32 ``[n, dim]`` table (``n`` whole
    vregs of rows) with a row's features along the lanes,
    ``{1,0:T(8,128)}``, which is how the kernel reads it. The chip's
    compiler lays an array the way that pads it least and row-major at a
    tie: the features' last row of lanes has to be filled to within a
    sublane's eight (123, 128, 250, 256; not 100, not 784), or the rows
    go along the lanes (read off programs compiled for a described v5e,
    every width here up to 2,048: ``tests/test_chip_compile.py``) and the
    program that hands the kernel the table re-lays all of it first."""
    return _padded(dim) == -(-dim // SUBLANES) * SUBLANES


def tile_rows(local_bs: int, dim: int) -> Optional[int]:
    """Rows a grid step: the most, of :data:`TILE` halved down to
    :data:`MIN_TILE`, that divide the window and whose buffer stays
    inside :data:`_TILE_BYTES`; None where none does."""
    tile = TILE
    while tile >= MIN_TILE:
        if local_bs % tile == 0 and 4 * tile * _padded(dim) <= _TILE_BYTES:
            return tile
        tile //= 2
    return None


def unsupported_reason(dtype, n_local: int, local_bs: int,
                       dim: int) -> Optional[str]:
    """Why the kernel does not take this step (None = it does): read off
    the backend and what the step is handed, nothing else."""
    import jax.numpy as jnp

    from flinkml_tpu.kernels import _mosaic

    if _mosaic.interpret_mode():
        return "not a TPU: Mosaic's kernel would run interpreted"
    if jnp.dtype(dtype) != jnp.float32:
        return f"features {jnp.dtype(dtype).name}: the sums are float32's"
    if dim > MAX_DIM:
        return f"{dim} features: over {MAX_DIM} the products are the MXU's"
    if not features_along_lanes(dim):
        return (f"{dim} features: the chip lays such a table with its rows "
                "along the lanes, and a kernel handed it would copy it first")
    if tile_rows(local_bs, dim) is None:
        return (f"a window of {local_bs} rows a device is not whole tiles "
                f"of {MIN_TILE}")
    if n_local % local_bs:
        return (f"{n_local} rows a device are not whole windows of "
                f"{local_bs}: the last window starts off a tile")
    return None


def chunks(dim: int, tile: int = TILE) -> int:
    """Chunks of 128 rows whose parts are made at a time: 16 at up to
    128 features, halved as the rows get wider (:data:`SPLIT_FLOATS`) or
    the tile shorter, at least one. A power of two: a tile is whole
    runs of them."""
    n = 16
    while n > 1 and (n * LANES * _padded(dim) > SPLIT_FLOATS
                     or n * LANES > tile):
        n //= 2
    return n


def _streamed(parts, rows: int):
    """``parts`` (three ``[..., 1, n]`` float32 rows) as the MXU's
    streamed operand: ``[..., rows, n]`` bfloat16, the parts its first
    three rows."""
    import jax
    import jax.numpy as jnp

    shape = parts[0].shape[:-2] + (rows, parts[0].shape[-1])
    at = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 2)
    hi, mid, lo = (jnp.broadcast_to(p, shape) for p in parts)
    return jnp.where(at == 0, hi, jnp.where(
        at == 1, mid, jnp.where(at == 2, lo, 0.0))).astype(jnp.bfloat16)


def _body(first_ref, x_ref, y_ref, w_ref, coef_ref, grad_ref, loss_ref,
          wsum_ref, *, loss: str, dim: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from flinkml_tpu.ops.losses import margin_terms

    del first_ref  # the index maps' (which tile the window starts at)

    @pl.when(pl.program_id(0) == 0)
    def _():
        grad_ref[...] = jnp.zeros(grad_ref.shape, jnp.float32)
        loss_ref[...] = jnp.zeros(loss_ref.shape, jnp.float32)
        wsum_ref[...] = jnp.zeros(wsum_ref.shape, jnp.float32)

    tile, padded = x_ref.shape
    n_chunks = chunks(dim, tile)
    rows = n_chunks * LANES
    coef = coef_ref[...]                    # [16, padded] bfloat16 parts
    # a chunk a batch: contract both along the lanes; rows with lanes
    transposed = (((2,), (2,)), ((0,), (0,)))
    as_it_lies = (((2,), (1,)), ((0,), (0,)))

    def several_chunks(j, carry):
        x = x_ref[pl.ds(pl.multiple_of(j * rows, rows), rows), :]
        if padded != dim:
            # The block's last lanes lie past the table's: whatever the
            # copy left there.
            lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
            x = jnp.where(lane < dim, x, 0.0)
        # Each chunk's three parts, the MXU's weights for both products.
        weights = [p.astype(jnp.bfloat16).reshape(n_chunks, LANES, padded)
                   for p in disjoint_parts(x)]
        # Forward: a chunk's margins, a row a lane.
        coefs = jnp.broadcast_to(coef, (n_chunks,) + coef.shape)
        dot = jnp.sum(sum(jax.lax.dot_general(
            coefs, part, transposed, preferred_element_type=jnp.float32)
            for part in weights), axis=1)
        labels = pl.ds(j * n_chunks, n_chunks)
        w = w_ref[labels, :]
        mult, per_ex = margin_terms(loss, dot, y_ref[labels, :], w)
        loss_ref[...] += jnp.sum(per_ex, axis=0, keepdims=True)
        wsum_ref[...] += jnp.sum(w, axis=0, keepdims=True)
        # Backward: the chunks' shares of the gradient.
        streamed = _streamed(
            [p[:, None, :] for p in disjoint_parts(mult)], STREAMED)
        grad_ref[...] += jnp.sum(sum(jax.lax.dot_general(
            streamed, part, as_it_lies, preferred_element_type=jnp.float32)
            for part in weights), axis=0)
        return carry

    jax.lax.fori_loop(0, tile // rows, several_chunks, 0)


def margin_grad(loss: str, xl, yl, wl, coef, start, local_bs: int, *,
                interpret: Optional[bool] = None):
    """``(grad [dim], loss_sum, wsum)`` of the window of ``local_bs``
    rows that starts at row ``start`` (a traced int32, a multiple of the
    tile) of ``xl [n_local, dim]``, ``yl`` and ``wl [n_local]``, float32:
    ``xb.T @ mult``, ``sum(per_ex)`` and ``sum(wb)`` for ``mult, per_ex =
    margin_terms(loss, xb @ coef, yb, wb)``, what
    ``_linear_sgd.make_dense_step`` makes between its windows and its
    ``psum``s. The table is read in place, each of the window's rows
    once. Float32 products and sums in one fixed order: the same bits
    every run. ``n_local`` is whole windows and the window whole tiles
    (:func:`unsupported_reason`)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from flinkml_tpu.kernels import _mosaic

    if interpret is None:
        interpret = _mosaic.interpret_mode()
    n_local, dim = xl.shape
    tile, padded = tile_rows(local_bs, dim), _padded(dim)
    with jax.enable_x64(False):
        first = (jnp.asarray(start, jnp.int32) // tile).reshape(1)
        # The coefficients as the MXU streams them: their parts a row
        # each, zeros past the last feature. Made once a step.
        coef_parts = _streamed(disjoint_parts(jnp.pad(
            coef.astype(jnp.float32), (0, padded - dim))[None, :]), STREAMED)
        # The labels as the chip holds a vector: 128 rows a row.
        y2 = yl.reshape(n_local // LANES, LANES)
        w2 = wl.reshape(n_local // LANES, LANES)
        # Whole rows of lanes: the last features' block ends past the
        # table where ``dim`` is no multiple of 128 (the body masks it).
        rows_of = pl.BlockSpec((tile, padded), lambda t, at: (at[0] + t, 0))
        labels_of = pl.BlockSpec((tile // LANES, LANES),
                                 lambda t, at: (at[0] + t, 0))

        def whole(*shape):
            return pl.BlockSpec(shape, lambda t, at: (0,) * len(shape))

        operands = (xl, y2, w2, coef_parts)
        grad, loss_sum, wsum = pl.pallas_call(
            functools.partial(_body, loss=loss, dim=dim),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(local_bs // tile,),
                in_specs=[rows_of, labels_of, labels_of,
                          whole(STREAMED, padded)],
                out_specs=[whole(STREAMED, padded), whole(1, LANES),
                           whole(1, LANES)]),
            out_shape=[
                _mosaic.out_struct((STREAMED, padded), jnp.float32, *operands),
                _mosaic.out_struct((1, LANES), jnp.float32, *operands),
                _mosaic.out_struct((1, LANES), jnp.float32, *operands)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(first, *operands)
        return (jnp.sum(grad, axis=0)[:dim].astype(coef.dtype),
                jnp.sum(loss_sum), jnp.sum(wsum))
