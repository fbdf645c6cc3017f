"""Operations and bytes that a step of the sparse factorization machine
*needs*, from its shapes (``flops_bytes.py`` is the dense kernels' and is
not edited; ``readers/roofline_of_program.py`` looks here). As there:
what the algorithm needs, whatever implements the step, so a share
cannot pass 100 %.
"""

from __future__ import annotations


def step(batch: int, nnz: int, dim: int, factor_size: int, itemsize: int = 4,
         index_itemsize: int = 4) -> dict:
    """One Adam step of a second-order FM with ``factor_size`` factors
    over ``batch`` rows of ``nnz`` cells, its parameters ``[dim, 1 +
    factor_size]``.

    Bytes, each read once: the batch's cells, an index and a value each,
    ``batch * nnz * (index_itemsize + itemsize)``; labels and weights, ``2
    * batch * itemsize``; a looked-up parameter row a cell, ``batch * nnz
    * (1 + k) * itemsize``, once forward (the row's sums) and once back
    (a cell's gradient needs the row's sums, which need every cell of the
    row first: the rows cannot stay on the chip, 174 MB a step); Adam's
    seven passes over the ``[dim, 1 + k]`` table (the parameters, both
    moments and the gradient read, the parameters and both moments
    written; dense moments: every column moves every step).
    Flops: a multiply and an add a looked-up float for the sums, the same
    for the squares of the ``k`` factors, four a float for the cell's
    gradient and its accumulation, twelve a parameter for Adam.
    ~ 0.7 flop/byte: bound by bytes on every chip of peaks.json."""
    cells, width = batch * nnz, 1 + factor_size
    return {
        "flops": float(cells * (2 * width + 2 * factor_size + 4 * width)
                       + 12 * dim * width),
        "bytes": float(cells * (index_itemsize + itemsize) + 2 * batch * itemsize
                       + 2 * cells * width * itemsize
                       + 7 * dim * width * itemsize),
    }
