"""Pallas padded-ELL segment-sum — the sparse gradient scatter-accumulate.

The sparse trainers' dominant op at Criteo scale is one flat
``segment_sum`` per step: ``contrib [cells]`` (or ``[cells, k]`` for the
row-payload W2V accumulator) scatter-added into ``[num_segments]`` by
``ids [cells]``. XLA lowers the unsorted case as an element-serial
scatter-add; this kernel streams the cells once instead, accumulating
into the VMEM-resident output block:

- **unsorted**: one sequential pass, ``out[ids[j]] += v[j]`` — addition
  order equals XLA's CPU scatter order (element order), so the f32
  result is bit-identical to ``jax.ops.segment_sum``.
- **``indices_are_sorted=True``**: run-flush specialization — a carried
  ``(current id, accumulator)`` pair flushes to ``out`` only at run
  boundaries, turning ``cells`` read-modify-writes of the output into
  ``runs`` predicated stores. Left-to-right addition within a run keeps
  bit-parity with the sorted XLA scatter.

The CELL axis streams through a grid: up to ``BLOCK_CELLS`` cells per
grid step, with the output block revisited (constant index map) so the
accumulator persists across steps — TPU grids iterate sequentially, so
element-order addition is preserved and parity stays bitwise at any
cell count. The sorted run-flush carry rides two tiny extra output refs
(current id + accumulator row) between grid steps, so a run spanning a
block boundary is still added left-to-right and flushed exactly once.
The remaining supported-shape ceiling (``MAX_COMPILED_CELLS``) is the
OUTPUT block, which must stay VMEM-resident for the whole pass; the
compiled path refuses sizes past it rather than compiling something
that spills.

Placement on the TPU (what Mosaic on a v5e accepts): the ids are read
one scalar at a time, so they live in SMEM (a scalar read from a VMEM
vector does not lower), as does the sorted variant's carried id; a
``[rows, k]`` float32 VMEM block tiles to ``(8, 128)``, so every row
costs 128 lanes whatever ``k`` is — the ceilings below count PADDED
cells. At the sparse trainers' own shape (``k = 1``, one segment per
feature) that is a 128x blow-up: ``dim = 1e6`` segments would need
512 MB of VMEM, so the compiled path refuses it by name and XLA's
scatter keeps that site. The gate (:mod:`flinkml_tpu.kernels._gate`)
keeps XLA the default everywhere until a measured win is committed.
"""

from __future__ import annotations

import functools
from typing import Optional

#: Supported-shape ceiling for the COMPILED (non-interpret) path, in
#: PADDED cells of the OUTPUT block (:func:`padded_cells` of
#: ``[num_segments, k]``; 8 MiB of float32): the segment axis must fit
#: one VMEM block; the cell axis streams through the grid and has no
#: ceiling.
MAX_COMPILED_CELLS = 1 << 21

#: Cells per grid step: one ``[BLOCK_CELLS, k]`` value block is 2 MiB of
#: VMEM at k <= 128 (double-buffered), its ids 16 KiB of SMEM. Inputs
#: up to here run as one block; larger ones grid over
#: ``ceil(cells / BLOCK_CELLS)`` steps.
BLOCK_CELLS = 1 << 12

_FLOAT_KINDS = "f"  # jnp dtype.kind for floating


def padded_cells(rows: int, k: int) -> int:
    """float32 cells a ``[rows, k]`` VMEM block occupies once tiled to
    ``(8, 128)``."""
    return (-(-rows // 8) * 8) * (-(-k // 128) * 128)


def unsupported_reason(values, ids, num_segments: int,
                       interpret: bool) -> Optional[str]:
    """Why the Pallas kernel cannot run these operands (None = it can).
    The wording lands verbatim in :class:`KernelUnsupportedError`."""
    import jax.numpy as jnp

    v = jnp.asarray(values) if not hasattr(values, "dtype") else values
    i = jnp.asarray(ids) if not hasattr(ids, "dtype") else ids
    if v.ndim not in (1, 2):
        return f"values must be [cells] or [cells, k], got rank {v.ndim}"
    if i.ndim != 1:
        return f"ids must be [cells], got rank {i.ndim}"
    if v.shape[0] != i.shape[0]:
        return f"values rows {v.shape[0]} != ids rows {i.shape[0]}"
    if not jnp.issubdtype(v.dtype, jnp.floating):
        return (f"values dtype {v.dtype} is not floating (supported: "
                "bfloat16/float32, + float64 under the interpreter)")
    if not jnp.issubdtype(i.dtype, jnp.integer):
        return f"ids dtype {i.dtype} is not integer"
    if num_segments < 1:
        return f"num_segments must be >= 1, got {num_segments}"
    if not interpret:
        if v.dtype != jnp.float32:
            return (f"values dtype {v.dtype}: the compiled kernel "
                    "updates one float32 row at a dynamic sublane; "
                    "packed (bfloat16) and float64 rows are "
                    "interpreter-only")
        k = 1 if v.ndim == 1 else v.shape[1]
        padded = padded_cells(num_segments, k)
        if padded > MAX_COMPILED_CELLS:
            return (f"output block [{num_segments}, {k}] tiles to "
                    f"{padded} padded cells ({padded * 4 >> 20} MiB of "
                    f"VMEM at 128 lanes per row), above the one-block "
                    f"compiled ceiling of {MAX_COMPILED_CELLS} "
                    "(MAX_COMPILED_CELLS); the grid streams the cell "
                    "axis, but the segment axis must fit one "
                    "VMEM-resident block")
    return None


def _unsorted_body(ids_ref, val_ref, out_ref):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    out_ref[...] = jnp.zeros_like(out_ref)
    cells = val_ref.shape[0]

    def body(j, carry):
        idx = ids_ref[j]
        out_ref[pl.ds(idx, 1), :] = (
            out_ref[pl.ds(idx, 1), :] + val_ref[pl.ds(j, 1), :]
        )
        return carry

    jax.lax.fori_loop(0, cells, body, 0)


def _sorted_body(ids_ref, val_ref, out_ref):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    out_ref[...] = jnp.zeros_like(out_ref)
    cells = val_ref.shape[0]

    # The accumulator stays a [1, k] row throughout: Mosaic has no
    # layout for the rank-1 [k] vector a squeezed row would be.
    def body(j, carry):
        cur, acc = carry
        idx = ids_ref[j]
        v = val_ref[pl.ds(j, 1), :]
        flush = idx != cur

        @pl.when(flush)
        def _():
            out_ref[pl.ds(cur, 1), :] = out_ref[pl.ds(cur, 1), :] + acc

        return idx, jnp.where(flush, v, acc + v)

    cur, acc = jax.lax.fori_loop(
        0, cells, body,
        (ids_ref[0], jnp.zeros_like(val_ref[pl.ds(0, 1), :])),
    )
    out_ref[pl.ds(cur, 1), :] = out_ref[pl.ds(cur, 1), :] + acc


def _unsorted_grid_body(ids_ref, val_ref, out_ref, *, total_cells: int):
    # Multi-block variant: the output block has a constant index map, so
    # it stays resident while the grid walks cell blocks sequentially —
    # addition order is still element order, parity stays bitwise. The
    # padded tail cells (last block only) are predicated off entirely
    # instead of relying on id-0/value-0 no-op adds, which could flip a
    # -0.0 accumulator to +0.0.
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    block = val_ref.shape[0]

    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    def body(j, carry):
        @pl.when(i * block + j < total_cells)
        def _():
            idx = ids_ref[j]
            out_ref[pl.ds(idx, 1), :] = (
                out_ref[pl.ds(idx, 1), :] + val_ref[pl.ds(j, 1), :]
            )
        return carry

    jax.lax.fori_loop(0, block, body, 0)


def _sorted_grid_body(ids_ref, val_ref, out_ref, carry_id_ref,
                      carry_acc_ref, *, total_cells: int):
    # Multi-block run-flush: the (current id, accumulator) carry lives in
    # two tiny revisited output refs between grid steps, so a run that
    # spans a block boundary keeps accumulating left-to-right and is
    # flushed exactly once — the per-cell op tree is identical to the
    # single-block body, which keeps parity with the sorted XLA scatter
    # bitwise. The last block does the final flush; earlier blocks park
    # the carry instead.
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    last = pl.num_programs(0) - 1
    block = val_ref.shape[0]

    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)
        carry_id_ref[0, 0] = ids_ref[0]
        carry_acc_ref[...] = jnp.zeros_like(carry_acc_ref)

    def body(j, carry):
        cur, acc = carry
        valid = i * block + j < total_cells
        idx = ids_ref[j]
        v = val_ref[pl.ds(j, 1), :]
        flush = (idx != cur) & valid

        @pl.when(flush)
        def _():
            out_ref[pl.ds(cur, 1), :] = out_ref[pl.ds(cur, 1), :] + acc

        ncur = jnp.where(valid, idx, cur)
        nacc = jnp.where(valid, jnp.where(flush, v, acc + v), acc)
        return ncur, nacc

    cur, acc = jax.lax.fori_loop(
        0, block, body, (carry_id_ref[0, 0], carry_acc_ref[...])
    )

    @pl.when(i == last)
    def _():
        out_ref[pl.ds(cur, 1), :] = out_ref[pl.ds(cur, 1), :] + acc

    @pl.when(i != last)
    def _():
        carry_id_ref[0, 0] = cur
        carry_acc_ref[...] = acc


def pallas_segment_sum(values, ids, num_segments: int, *,
                       indices_are_sorted: bool = False,
                       interpret: Optional[bool] = None):
    """The Pallas scatter-accumulate (module docstring). Same contract
    as ``jax.ops.segment_sum(values, ids, num_segments,
    indices_are_sorted=...)`` for in-range ids; out-of-range ids are the
    caller's bug on both backends (padding rides the ELL convention:
    index 0 / value 0 is a no-op add). Unsupported operands raise
    :class:`KernelUnsupportedError` — direct callers get the same typed
    refusal as the gated dispatcher, with the same wording."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from flinkml_tpu.kernels import _gate

    if interpret is None:
        interpret = _gate.interpret_mode()
    reason = unsupported_reason(values, ids, num_segments, interpret)
    if reason is not None:
        raise _gate.KernelUnsupportedError(
            f"kernels[segment_sum]: pallas_segment_sum cannot run these "
            f"operands: {reason}"
        )
    flat = values.ndim == 1
    v2 = values[:, None] if flat else values
    cells, k = v2.shape
    ids32 = ids.astype(jnp.int32)
    if cells <= BLOCK_CELLS:
        body = _sorted_body if indices_are_sorted else _unsorted_body
        out = pl.pallas_call(
            body,
            in_specs=[
                pl.BlockSpec((cells,), lambda: (0,),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((cells, k), lambda: (0, 0)),
            ],
            out_specs=pl.BlockSpec((num_segments, k), lambda: (0, 0)),
            out_shape=_gate.out_struct((num_segments, k), v2.dtype, ids32, v2),
            interpret=interpret,
        )(ids32, v2)
        return out[:, 0] if flat else out
    grid = pl.cdiv(cells, BLOCK_CELLS)
    pad = grid * BLOCK_CELLS - cells
    if pad:
        # Padding is predicated off inside the bodies (total_cells);
        # zeros here only square up the block shape.
        ids32 = jnp.concatenate([ids32, jnp.zeros((pad,), jnp.int32)])
        v2 = jnp.concatenate([v2, jnp.zeros((pad, k), v2.dtype)])
    in_specs = [
        pl.BlockSpec((BLOCK_CELLS,), lambda i: (i,),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((BLOCK_CELLS, k), lambda i: (i, 0)),
    ]
    out_spec = pl.BlockSpec((num_segments, k), lambda i: (0, 0))
    if indices_are_sorted:
        out, _, _ = pl.pallas_call(
            functools.partial(_sorted_grid_body, total_cells=cells),
            grid=(grid,),
            in_specs=in_specs,
            out_specs=(
                out_spec,
                pl.BlockSpec((1, 1), lambda i: (0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((1, k), lambda i: (0, 0)),
            ),
            out_shape=(
                _gate.out_struct((num_segments, k), v2.dtype, ids32, v2),
                _gate.out_struct((1, 1), jnp.int32, ids32, v2),
                _gate.out_struct((1, k), v2.dtype, ids32, v2),
            ),
            interpret=interpret,
        )(ids32, v2)
    else:
        out = pl.pallas_call(
            functools.partial(_unsorted_grid_body, total_cells=cells),
            grid=(grid,),
            in_specs=in_specs,
            out_specs=out_spec,
            out_shape=_gate.out_struct((num_segments, k), v2.dtype, ids32, v2),
            interpret=interpret,
        )(ids32, v2)
    return out[:, 0] if flat else out


def segment_sum(values, ids, num_segments: int, *,
                indices_are_sorted: bool = False,
                backend: Optional[str] = None):
    """The gated dispatcher: ``jax.ops.segment_sum`` under ``"xla"``,
    :func:`pallas_segment_sum` under ``"pallas"``. ``backend=None``
    resolves the gate (env > autotune table > xla); passing a backend
    is an explicit request and refuses unsupported operands loudly.
    Zero-cell and zero-segment inputs always take the XLA path (nothing
    to measure, and the kernel needs >= 1 of each)."""
    import jax
    import jax.numpy as jnp

    from flinkml_tpu.kernels import _gate

    values = jnp.asarray(values)
    ids = jnp.asarray(ids)
    if values.shape[0] == 0 or num_segments == 0:
        return jax.ops.segment_sum(
            values, ids, num_segments=num_segments,
            indices_are_sorted=indices_are_sorted,
        )
    interpret = _gate.interpret_mode()
    chosen = _gate.resolve_checked(
        "segment_sum",
        unsupported_reason(values, ids, num_segments, interpret),
        backend,
    )
    if chosen == "pallas":
        return pallas_segment_sum(
            values, ids, num_segments,
            indices_are_sorted=indices_are_sorted, interpret=interpret,
        )
    return jax.ops.segment_sum(
        values, ids, num_segments=num_segments,
        indices_are_sorted=indices_are_sorted,
    )


def factory_backend() -> str:
    """The segment-sum backend for a trainer FACTORY to bake into its
    ``functools.lru_cache`` key (the established layout-gate idiom:
    resolve once at fit time, thread down as a static argument, so a
    gate flip re-keys the jitted trainer instead of silently reusing
    the old program)."""
    from flinkml_tpu.kernels import _gate

    return _gate.backend_for("segment_sum")
