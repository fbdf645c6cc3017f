"""Operations and bytes that a round of Lloyd's KMeans *needs*, from its
shapes (``flops_bytes.py`` is the dense kernels' and is not edited;
``readers/roofline_in_program_span.py`` looks here). As there: what the
algorithm needs, not what the program happens to move, so a share cannot
pass 100 %.
"""

from __future__ import annotations


def lloyd_round(rows: int, dim: int, k: int, itemsize: int = 4) -> dict:
    """One round over ``rows`` resident rows of ``dim`` features against
    ``k`` centroids: every row to its nearest centroid, then each
    cluster's mean.

    Flops: a multiply and an add per (row, centroid, feature) for the
    products ``x . c``, 2 * N * d * k, and as many for the per-cluster
    sums written as the product ``onehot.T @ x`` (what a chip with a
    matrix unit does; d additions a row would do on a scalar machine):
    4 * N * d * k; the three additions that make a distance of a
    product, 3 * N * k. Counted ONCE, as float32 arithmetic: the six
    bfloat16 passes a TPU's MXU makes of a float32 product at
    ``Precision.HIGHEST`` are how the chip does it. The rows' squared
    norms are the table's, computed once a table, not a round's; the
    argmin's comparisons (k a row on the vector unit) are left out.
    Bytes: the table read ONCE (a row can be assigned and added to its
    cluster's sum while it is on the chip), its norms and mask with it;
    the centroids in and out. No [rows, k] array needs to leave the
    chip's fast memory. A program that reads the table twice a round
    cannot pass 50 %.
    ~ k flop/byte: bound by bytes on every chip of peaks.json."""
    return {
        "flops": float(4.0 * rows * dim * k + 3.0 * rows * k),
        "bytes": float(rows * (dim + 2) * itemsize + 2 * k * dim * itemsize),
    }
