"""Planted labels with a pairwise term, for the configuration
``fm-criteo``: the rows are ``datagen_criteo``'s (imported, not edited);
the labels are made here so that a factorization machine's factors have
something to learn. NumPy only; streams from ``datagen.rng`` under tags
of their own.

A row's signal is a seeded linear coefficient over its columns (standard
deviation :data:`LINEAR` a row, as ``datagen_criteo``'s) PLUS a seeded
rank-:data:`RANK` pairwise term ``sum_f sum_{s < s'} x_s x_s' U[i_s, f]
U[i_s', f]`` of the same standard deviation, plus logistic noise; the
label is ``signal + offset > 0`` with the offset the ``1 -
POSITIVE_SHARE`` quantile of block 0's signal, negated.
"""

from __future__ import annotations

import concurrent.futures as cf

import numpy as np

from benchmark import datagen

# Stream tags (datagen.py holds 1-4, datagen_criteo.py 11-13, datagen_mnist.py 21-23).
TAG_LINEAR, TAG_PAIRS, TAG_NOISE = 31, 32, 33

_THREADS = 8
_BLOCK_ROWS = 65_536
#: A row's linear signal and its pairwise signal each have this standard
#: deviation (logistic noise has pi / sqrt(3) = 1.81); the pairwise
#: term's rank; the share of positive labels the offset is set for.
LINEAR, PAIRWISE, RANK, POSITIVE_SHARE = 2.0, 2.0, 4, 0.25


def pair_scale(nnz: int) -> float:
    """The scale ``a`` of ``U = a N(0, 1)`` that gives a row of ``nnz``
    cells of value ``1 / sqrt(nnz)`` a pairwise signal of standard
    deviation :data:`PAIRWISE`: the term is a sum of ``RANK * nnz (nnz -
    1) / 2`` products of variance ``a**4 / nnz**2``."""
    terms = RANK * nnz * (nnz - 1) / 2.0
    return float((PAIRWISE ** 2 * nnz ** 2 / terms) ** 0.25)


def planted_labels(seed: int, indices: np.ndarray, dim: int) -> np.ndarray:
    """Float32 0/1 labels for rows ``indices [rows, nnz]`` whose every
    value is ``float32(1 / sqrt(nnz))``. Block ``i`` of 65,536 rows takes
    its noise from stream ``(seed, TAG_NOISE, i)``, so the labels do not
    depend on the thread count."""
    rows, nnz = indices.shape
    value = float(np.float32(1.0 / np.sqrt(nnz)))
    linear = (LINEAR * datagen.rng(seed, TAG_LINEAR).standard_normal(dim)
              ).astype(np.float32)
    pairs = (pair_scale(nnz) * datagen.rng(seed, TAG_PAIRS).standard_normal(
        (RANK, dim))).astype(np.float32)
    signal = np.empty(rows, np.float64)
    blocks = list(enumerate(range(0, rows, _BLOCK_ROWS)))

    def work(mine) -> None:
        took = np.empty((_BLOCK_ROWS, nnz), np.float32)
        for block, lo in mine:
            hi = min(lo + _BLOCK_ROWS, rows)
            cols, t = indices[lo:hi], took[:hi - lo]
            np.take(linear, cols, out=t)
            total = t.sum(axis=1, dtype=np.float64) * value
            for f in range(RANK):
                np.take(pairs[f], cols, out=t)
                s = t.sum(axis=1, dtype=np.float64)
                np.multiply(t, t, out=t)
                total += 0.5 * value * value * (s * s - t.sum(axis=1, dtype=np.float64))
            signal[lo:hi] = total + datagen.rng(seed, TAG_NOISE, block).logistic(
                size=hi - lo)

    with cf.ThreadPoolExecutor(_THREADS) as pool:
        # list(): an executor keeps a task's exception until it is read.
        list(pool.map(work, [blocks[t::_THREADS] for t in range(_THREADS)]))
    offset = -np.quantile(signal[:_BLOCK_ROWS], 1.0 - POSITIVE_SHARE)
    return (signal + offset > 0).astype(np.float32)
