"""Operations and bytes that the sparse trainer's step *needs*, from its
shapes (``flops_bytes.py`` is the dense kernels' and is not edited;
``readers/roofline_in_program_span.py`` looks here). As there: what the
algorithm needs, not what the program happens to move, so a share cannot
pass 100 %.
"""

from __future__ import annotations


def sparse_lr_step(batch: int, nnz: int, dim: int, itemsize: int = 4,
                   index_itemsize: int = 4) -> dict:
    """One mini-batch step of binomial LR over rows of ``nnz`` cells.

    Flops: a multiply and an add per cell for the row's dot product, a
    multiply and an add per cell for its share of the gradient: 4 * B *
    nnz (the O(B) margin terms and the O(dim) update are left out).
    Bytes: every cell's index and value read ONCE (a row's multiplier
    needs only that row's own dot product, so one pass over the cells
    can do both the gather-dot and the scatter-add): B * nnz *
    (index_itemsize + itemsize); labels and weights, 2 * B * itemsize;
    the coefficient read, the gradient written, the coefficient written:
    3 * dim * itemsize. The gathers from and the scatter-adds into the
    ``[dim]`` arrays are counted as those three dense passes and no more:
    4 MB stays in fast memory on any chip of peaks.json.
    0.5 flop/byte at most: bound by bytes."""
    cells = batch * nnz
    return {
        "flops": 4.0 * cells,
        "bytes": float(cells * (index_itemsize + itemsize)
                       + 2 * batch * itemsize + 3 * dim * itemsize),
    }
