"""Exact brute-force k-nearest-neighbours classification, NumPy float64,
the benchmark's own copy (it imports nothing of the program or of
``tests/``). Flink ML's ``KnnModel``: Euclidean distance, the ``k``
nearest train rows, a majority vote.

    d2[q, r]  = sum_j (queries[q, j] - train[r, j]) ** 2       (float64)
    nearest   = the k rows of smallest (d2, r): ties to the LOWER row
    predicted = the class most of them carry; ties to the SMALLER class

:func:`k_nearest` returns ``k + 1`` neighbours where the train set has
them, so that :func:`unstable` can see how close the first row left out
came.

At a cell's size (512 queries against 2,025,000 rows of 784) the direct
sum over every pair is 1.6e12 subtractions through ``[rows, 784]``
temporaries; ``shortlist=S`` first ranks every row by the expansion
``|x|^2 - 2 x.y + |y|^2`` in a blocked float64 ``dgemm``, keeps each
query's ``S`` best, and takes the direct sum over those alone. *The
margin.* The expansion in float64 is off the direct sum by at most a
few ``d * 2**-53 * (|x|^2 + |y|^2)``: under 1e-10 for rows of squared
length below 1,000, where neighbouring distances differ by 1e-3 and
more. A row can be lost from the shortlist only if ``S - k`` other rows
lie within that error of it, so any ``S`` well above ``k + 1`` (64 is
used) loses none; the direct sum then orders the shortlist itself.
"""

from __future__ import annotations

import concurrent.futures as cf
import os

import numpy as np
import threadpoolctl

#: Blocks ranked at a time, each block's ``dgemm`` on one thread (four
#: at a time on all of BLAS's threads took twice as long).
_THREADS = max(1, min(12, os.cpu_count() or 1))
#: Train rows a block of the ranking holds: ``[queries, block]`` float64
#: distances and the block's ``[block, dim]`` float64 copy.
BLOCK_ROWS = 65_536


def squared_distances(queries: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """``[q, r]`` float64, each the direct sum of squared differences
    (no expansion, so nothing cancels); ``rows`` is ``[r, d]`` shared by
    the queries or ``[q, r, d]``, a list of its own for each."""
    q = np.asarray(queries, np.float64)
    r = np.asarray(rows, np.float64)
    if r.ndim == 2:
        r = r[None, :, :]
    out = np.empty((q.shape[0], r.shape[1]), np.float64)
    step = max(1, (1 << 24) // max(1, r.shape[1] * r.shape[2]))
    for lo in range(0, q.shape[0], step):
        part = r[lo:lo + step] if r.shape[0] > 1 else r
        diff = q[lo:lo + step, None, :] - part
        out[lo:lo + step] = np.einsum("qrd,qrd->qr", diff, diff)
    return out


def _order(d2: np.ndarray, rows: np.ndarray, keep: int):
    """``(rows, d2)`` of each query's ``keep`` first candidates by
    ``(d2, row)``."""
    order = np.lexsort((rows, d2), axis=-1)[:, :keep]
    return np.take_along_axis(rows, order, 1), np.take_along_axis(d2, order, 1)


def _block_best(q: np.ndarray, train: np.ndarray, lo: int, size: int):
    """``(d2, rows)``, ``[q, <=size]``: the ``size`` best rows of block
    ``[lo, lo + BLOCK_ROWS)`` for each query by the float64 expansion
    (one ``dgemm``); ``|query|^2``, the same for every row, is left out."""
    x = np.asarray(train[lo:lo + BLOCK_ROWS], np.float64)
    d2 = q @ x.T
    d2 *= -2.0
    d2 += np.einsum("rd,rd->r", x, x)[None, :]
    rows = np.arange(lo, lo + x.shape[0])
    if x.shape[0] <= size:
        return d2, np.broadcast_to(rows, d2.shape)
    part = np.argpartition(d2, size - 1, axis=1)[:, :size]
    return np.take_along_axis(d2, part, 1), rows[part]


def _shortlist(queries: np.ndarray, train: np.ndarray, size: int) -> np.ndarray:
    """``[q, size]`` row numbers: each query's ``size`` best rows by the
    float64 expansion: every block's best (several blocks at a time: the
    cast, the ``dgemm`` and the partition release the interpreter lock),
    then the best of those."""
    q = np.asarray(queries, np.float64)
    with threadpoolctl.threadpool_limits(1, "blas"), \
            cf.ThreadPoolExecutor(_THREADS) as pool:
        parts = list(pool.map(lambda lo: _block_best(q, train, lo, size),
                              range(0, train.shape[0], BLOCK_ROWS)))
    d2 = np.concatenate([p[0] for p in parts], axis=1)
    rows = np.concatenate([p[1] for p in parts], axis=1)
    if d2.shape[1] <= size:
        return rows
    part = np.argpartition(d2, size - 1, axis=1)[:, :size]
    return np.take_along_axis(rows, part, 1)


def k_nearest(queries: np.ndarray, train: np.ndarray, k: int, shortlist=None):
    """``(rows, d2)``, both ``[q, min(k + 1, n)]``: each query's nearest
    train rows in order of ``(d2, row)`` and their float64 squared
    distances. The first ``k`` columns are the neighbours; the last is
    the first row left out (absent where ``k >= n``).

    ``shortlist`` None ranks every row by the direct sum; a number ranks
    by the expansion first (module docstring)."""
    n = train.shape[0]
    keep = min(k + 1, n)
    if shortlist is None or shortlist >= n:
        rows = np.broadcast_to(np.arange(n), (queries.shape[0], n))
        return _order(squared_distances(queries, train), rows, keep)
    if shortlist < keep:
        raise ValueError(f"a shortlist of {shortlist} cannot hold {keep} neighbours")
    cand = np.sort(_shortlist(queries, train, shortlist), axis=1)
    d2 = squared_distances(queries, np.asarray(train)[cand])
    return _order(d2, cand, keep)


def vote(labels: np.ndarray, rows: np.ndarray, k: int) -> np.ndarray:
    """The predicted label of each query: the class most of its first
    ``k`` ``rows`` carry, ties to the smaller class."""
    classes, ids = np.unique(np.asarray(labels), return_inverse=True)
    near = ids[rows[:, :k]]
    counts = (near[:, :, None] == np.arange(classes.size)).sum(axis=1)
    return classes[np.argmax(counts, axis=1)]


def unstable(d2: np.ndarray, k: int, tol) -> np.ndarray:
    """``[q]`` bool: queries whose answer a perturbation of every squared
    distance by at most ``tol`` (a number, or one a query) could change:
    the ``k``-th and the ``(k + 1)``-th of ``d2`` (as :func:`k_nearest`
    returns them) lie within ``2 * tol``, so which of the two rows is a
    neighbour, and with it the vote, is not decided. Where there is no
    ``(k + 1)``-th row every row votes and nothing can swap."""
    if d2.shape[1] <= k:
        return np.zeros(d2.shape[0], bool)
    return d2[:, k] - d2[:, k - 1] <= 2.0 * np.asarray(tol)


def unordered(d2: np.ndarray, k: int, tol) -> np.ndarray:
    """``[q]`` bool: queries whose neighbours' ORDER the same perturbation
    could change: some two consecutive entries of ``d2`` (the ``k``
    neighbours and the first row left out) lie within ``2 * tol``. Every
    :func:`unstable` query is one."""
    if d2.shape[1] < 2:
        return np.zeros(d2.shape[0], bool)
    return np.diff(d2, axis=1).min(axis=1) <= 2.0 * np.asarray(tol)


def merge_shares(rows, d2, k: int):
    """What a host holding the train set by rows does with its chips'
    answers: ``rows`` and ``d2`` are lists, one entry a share, of
    ``[q, <=k]`` GLOBAL row numbers and distances; the result is the
    ``k`` best of all of them by ``(d2, row)``."""
    return _order(np.concatenate(d2, axis=1), np.concatenate(rows, axis=1), k)
