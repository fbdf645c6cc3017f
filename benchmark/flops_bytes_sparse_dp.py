"""Operations and bytes that ONE CHIP of a data-parallel sparse step
*needs*, from its shapes (``flops_bytes_sparse.py`` counts the one-worker
step and is not edited; ``readers/roofline_of_program.py`` looks here).
As there: what the algorithm needs, not what the program happens to
move, so a share cannot pass 100 %.
"""

from __future__ import annotations


def sparse_lr_dp_step(global_batch: int, chips: int, nnz: int, dim: int,
                      itemsize: int = 4, index_itemsize: int = 4) -> dict:
    """One mini-batch step of binomial LR over rows of ``nnz`` cells, as
    ONE of ``chips`` data-parallel workers pays for it: its share of the
    batch, ``ceil(global_batch / chips)`` rows, against ONE chip's peak
    and one chip's device time.

    Flops: ``flops_bytes_sparse.sparse_lr_step``'s 4 a cell over the
    chip's own cells. Bytes: its cells' indices and values read once,
    its labels and weights; and FIVE passes over ``[dim]`` where the
    one-worker step has three, whatever the chip's share of the rows: the
    coefficient read, the local gradient written, read again by the
    all-reduce, the reduced gradient read, the coefficient written. What
    the all-reduce moves over the interconnect is not HBM traffic and is
    not counted (``sharding.psum_bus_bytes_per_s`` reads it)."""
    batch = -(-int(global_batch) // int(chips))
    cells = batch * nnz
    return {
        "flops": 4.0 * cells,
        "bytes": float(cells * (index_itemsize + itemsize)
                       + 2 * batch * itemsize + 5 * dim * itemsize),
    }
