"""Lloyd's KMeans, NumPy float64, the benchmark's own copy (it imports
nothing of the program or of ``tests/``). Flink ML's ``KMeans`` at its
documented parameters: Euclidean distance, ``initMode`` random,
``maxIter`` rounds and no tolerance (``TerminateOnMaxIter``).

    start     = rows[default_rng(seed).choice(n, size=k, replace=False)]
    a round   : assign[i] = argmin_j |rows[i] - centroid[j]|^2, ties to the LOWER j
                centroid[j] = mean of the rows assigned to j; a cluster with
                no row keeps the centroid it had

:func:`start_rows` is the documented rule for the start (the program
states the same one; the numbers it draws are NumPy's, so the two need
share nothing but the seed).

*A departure, noted.* At a cell's size (2,025,000 rows of 784, k 10, 20
rounds) the direct sum over every (row, centroid) pair is 3.2e11
subtractions through ``[rows, 784]`` temporaries, minutes a run. The
distances are therefore the expansion ``|x|^2 - 2 x.c + |c|^2`` in
float64, one ``dgemm`` a block of rows; its error, a few ``784 * 2**-53
* (|x|^2 + |c|^2)`` (under 1e-11 for rows of squared length below 1,000),
is a hundred thousand times below what float32 rounding moves a distance
by, which is what the comparison has to resolve.
``squared_distances(..., direct=True)`` is the direct sum;
``tests/test_reference.py``-style checks hold the expansion to it at a
small size. The per-cluster sums are ``onehot.T @ block`` in float64.
"""

from __future__ import annotations

import concurrent.futures as cf
import os

import numpy as np
import threadpoolctl

#: Blocks handled at a time, each block's ``dgemm`` on one thread.
_THREADS = max(1, min(12, os.cpu_count() or 1))
#: Rows a block holds: its ``[block, dim]`` float64 copy is 206 MB at 784.
BLOCK_ROWS = 32_768


def start_rows(seed: int, rows: int, k: int) -> np.ndarray:
    """``[k]`` distinct row numbers: ``initMode`` random by the seed."""
    return np.random.default_rng(seed).choice(rows, size=k, replace=False)


def squared_distances(block: np.ndarray, centroids: np.ndarray,
                      direct: bool = False) -> np.ndarray:
    """``[b, k]`` float64 squared distances of a block of rows to the
    centroids: the float64 expansion, or (``direct``) the sum of squared
    differences, in which nothing cancels."""
    x = np.asarray(block, np.float64)
    c = np.asarray(centroids, np.float64)
    if direct:
        return np.stack([np.einsum("bd,bd->b", x - cj, x - cj) for cj in c], axis=1)
    d2 = x @ c.T
    d2 *= -2.0
    d2 += np.einsum("bd,bd->b", x, x)[:, None]
    d2 += np.einsum("kd,kd->k", c, c)[None, :]
    return d2


def scratch(rows: np.ndarray) -> list:
    """One ``[BLOCK_ROWS, dim]`` float64 buffer a worker, for a caller to
    make once and hand to every call over ``rows``: a block's float64
    copy is 206 MB, and twenty rounds of fresh ones are 250 GB of pages
    touched for the first time (on the chip's host, whose sandbox hands
    freed pages back late, that ran a 40 GiB machine out of memory)."""
    return [np.empty((min(BLOCK_ROWS, rows.shape[0]), rows.shape[1]), np.float64)
            for _ in range(_THREADS)]


def _block_round(rows, lo, centroids, tol, buffer):
    """One block's part of a round: per-cluster ``(sums [k, d], counts
    [k], cost, close)``: the float64 sums and counts of the rows
    assigned to each centroid, their squared distances to it added up,
    and how many of them have a runner-up within ``2 * tol`` of their
    nearest centroid (``tol`` None: not counted)."""
    block = rows[lo:lo + BLOCK_ROWS]
    x = buffer[:block.shape[0]]
    np.copyto(x, block)
    d2 = squared_distances(x, centroids)
    assign = np.argmin(d2, axis=1)          # the first of equals: the lower
    k = centroids.shape[0]
    onehot = np.zeros((x.shape[0], k))
    onehot[np.arange(x.shape[0]), assign] = 1.0
    best = d2[np.arange(x.shape[0]), assign]
    close = 0
    if tol is not None:
        two = np.partition(d2, 1, axis=1)[:, :2]
        sq = np.einsum("bd,bd->b", x, x)
        close = int(np.sum(two[:, 1] - two[:, 0] <= 2.0 * tol(sq, centroids)))
    return onehot.T @ x, onehot.sum(axis=0), float(np.maximum(best, 0.0).sum()), close


def _over_blocks(rows, centroids, tol=None, buffers=None):
    """:func:`_block_round` over all blocks, worker ``w`` taking blocks
    ``w, w + T, ...`` through its own buffer (the cast, the ``dgemm`` and
    the partition release the interpreter lock), added up in block
    order, so the sums do not depend on the thread count."""
    c = np.asarray(centroids, np.float64)
    buffers = buffers if buffers is not None else scratch(rows)
    starts = range(0, rows.shape[0], BLOCK_ROWS)

    def work(w):
        return [_block_round(rows, lo, c, tol, buffers[w])
                for lo in starts[w::len(buffers)]]

    with threadpoolctl.threadpool_limits(1, "blas"), \
            cf.ThreadPoolExecutor(len(buffers)) as pool:
        by_worker = list(pool.map(work, range(len(buffers))))
    parts = [by_worker[i % len(buffers)][i // len(buffers)]
             for i in range(len(starts))]
    sums = np.sum([p[0] for p in parts], axis=0)
    counts = np.sum([p[1] for p in parts], axis=0)
    return sums, counts, float(sum(p[2] for p in parts)), int(sum(p[3] for p in parts))


def lloyd_round(rows: np.ndarray, centroids: np.ndarray, tol=None, buffers=None):
    """``(centroids' [k, d] float64, counts [k], close)``: one round from
    ``centroids`` over all ``rows``. ``close`` counts the rows whose two
    nearest centroids lie within ``2 * tol(|x|^2, centroids)`` of each
    other (a function giving, a row, what the compared arithmetic may
    move a squared distance by): rows a sound program may assign either
    way. None: not counted, 0. ``buffers`` here and below: :func:`scratch`'s,
    where the caller keeps them over several calls."""
    c = np.asarray(centroids, np.float64)
    sums, counts, _, close = _over_blocks(rows, c, tol, buffers)
    moved = np.where(counts[:, None] > 0, sums / np.maximum(counts, 1.0)[:, None], c)
    return moved, counts, close


def lloyd(rows: np.ndarray, start: np.ndarray, rounds: int, tol=None, buffers=None):
    """``(centroids [k, d] float64, counts [k] of the last round, close
    [rounds])`` after exactly ``rounds`` rounds from the centroids
    ``start``; ``close`` as :func:`lloyd_round` counts it, a round."""
    c = np.asarray(start, np.float64)
    counts = np.zeros(c.shape[0])
    close = []
    buffers = buffers if buffers is not None else scratch(rows)
    for _ in range(rounds):
        c, counts, n_close = lloyd_round(rows, c, tol, buffers)
        close.append(n_close)
    return c, counts, close


def assignments(rows: np.ndarray, centroids: np.ndarray) -> np.ndarray:
    """``[n]`` the nearest centroid of every row, ties to the lower."""
    c = np.asarray(centroids, np.float64)
    return np.concatenate([
        np.argmin(squared_distances(rows[lo:lo + BLOCK_ROWS], c), axis=1)
        for lo in range(0, rows.shape[0], BLOCK_ROWS)])


def cost(rows: np.ndarray, centroids: np.ndarray, buffers=None) -> float:
    """The within-cluster sum of squares at ``centroids``, float64: every
    row's squared distance to its nearest centroid, added up."""
    return _over_blocks(rows, centroids, None, buffers)[2]
