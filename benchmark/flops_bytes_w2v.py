"""Operations and bytes that a step of skip-gram with negative sampling
*needs*, from its shapes (``flops_bytes.py`` is the dense kernels' and is
not edited; ``readers/roofline_of_program.py`` looks here). As there: what
NO implementation can avoid, so a share cannot pass 100 %.
"""

from __future__ import annotations


def step(batch: int, negatives: int, dim: int, itemsize: int = 4,
         index_itemsize: int = 4) -> dict:
    """One step on ``batch`` pairs, ``negatives`` negatives a pair, vectors
    of ``dim`` floats.

    Bytes: a pair names ``2 + negatives`` rows (its centre's word vector,
    its context's and its negatives' output vectors); each is read once
    for the scores and, updated, read and written once more (every
    gradient is taken at the step's start, so the update cannot ride the
    first read), ``3 * dim * itemsize`` a row; and the rows' ids. Rows
    that several pairs name are counted a pair (a step of the cell names
    114,688 rows of 1,115,011 and the hottest some hundreds of times:
    what could be saved is not what paces it). The draw's own reads (the
    corpus positions it looks at) are the program's, not the step's need.
    Flops: a multiply and an add a float for each of a pair's ``1 +
    negatives`` scores, the same again for the centre's gradient and for
    the output rows' gradients, two more for the scaled update.
    ~ 1.4 flop/byte: bound by bytes on every chip of peaks.json."""
    rows = batch * (2 + negatives)
    return {
        "flops": float(batch * (1 + negatives) * dim * 6 + rows * dim * 2),
        "bytes": float(rows * (3 * dim * itemsize + index_itemsize)),
    }
