"""Operations and bytes that the KNN search *needs*, from its shapes
(``flops_bytes.py`` is the dense kernels' and is not edited;
``readers/roofline_in_program_span.py`` looks here). As there: what the
algorithm needs, not what the program happens to move, so a share cannot
pass 100 %.
"""

from __future__ import annotations


def knn_search(query_rows: int, train_rows: int, dim: int, chunk: int = 4096,
               itemsize: int = 4) -> dict:
    """One call: ``query_rows`` queries ranked against ``train_rows``
    resident rows of ``dim`` features.

    Flops: a multiply and an add per (query, row, feature) for the
    products ``q . x``: 2 * Q * N * d; each query's and each row's
    squared norm once, 2 * (Q + N) * d; the three additions that make a
    distance of them, 3 * Q * N. Counted ONCE, as float32 arithmetic:
    the six bfloat16 passes a TPU's MXU makes of a float32 product at
    ``Precision.HIGHEST`` are how the chip does it, not what the
    algorithm needs, so against ``peaks.json``'s bfloat16 rate the share
    cannot pass 1/6 = 16.7 % while the product runs in six passes (33 %
    in three). The top-k's comparisons (a few per distance on the vector
    unit) are left out.
    Bytes: the train rows and their norms read once for every chunk of
    ``chunk`` queries that is held on the chip while they stream by
    (``ceil(Q / chunk)`` times), the queries read once, ``k`` results a
    query written (left out). No distance needs to leave the chip's
    fast memory.
    ~ 2 * chunk / itemsize flop/byte: bound by flops on every chip of
    peaks.json."""
    passes = -(-query_rows // chunk)
    return {
        "flops": float(2.0 * query_rows * train_rows * dim
                       + 2.0 * (query_rows + train_rows) * dim
                       + 3.0 * query_rows * train_rows),
        "bytes": float(passes * train_rows * (dim + 1) * itemsize
                       + query_rows * dim * itemsize),
    }
