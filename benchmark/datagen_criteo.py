"""Criteo-profile sparse rows from ``--seed``: the field sampler of the
configuration ``lr-criteo`` and its planted labels. NumPy only; the
streams come from ``datagen.rng`` (``datagen.py`` is imported, not
edited) under tags of their own.

A row holds one cell per field, so ``len(cardinalities)`` non-zeros,
sorted and distinct by construction: field ``f`` owns the columns
``[f * stratum, (f + 1) * stratum)`` and its cell is column
``f * stratum + rank mod stratum`` with ``rank = floor(c_f * u**3)``,
``u`` uniform on [0, 1) (float32) — the source's hashing folds a field of
ten million values into its stratum the same way, and the cube puts
``(1 / c_f) ** (1 / 3)`` of all rows on the field's first value (69 % for
a three-valued field, 0.5 % for a ten-million-valued one). Every value
is ``float32(1 / sqrt(nnz))``: rows have unit length.

Rows are filled block by block on a few threads; block ``i`` always comes
from stream ``(seed, tag, i)``, so the bytes do not depend on the thread
count.
"""

from __future__ import annotations

import concurrent.futures as cf

import numpy as np

from benchmark import datagen

# Stream tags (datagen.py holds 1-4).
TAG_CELLS, TAG_TRUE, TAG_NOISE = 11, 12, 13

_THREADS = 8
_BLOCK_ROWS = 65_536
#: Scale of the planted coefficient (a row's signal then has this
#: standard deviation, against logistic noise of pi / sqrt(3) = 1.81) and
#: the share of positive labels the offset is set for.
SIGNAL, POSITIVE_SHARE = 2.0, 0.25


class _Scratch:
    """One worker's buffers, reused over its blocks: page faults of fresh
    temporaries, taken on several threads at once, were most of the time."""

    def __init__(self, fields: int):
        self.u = np.empty((_BLOCK_ROWS, fields), np.float32)
        self.sq = np.empty((_BLOCK_ROWS, fields), np.float32)


def _fill_block(seed: int, block: int, cols: np.ndarray, signal: np.ndarray,
                card: np.ndarray, stratum: int, true: np.ndarray,
                value: float, scratch: _Scratch) -> None:
    """Columns (``cols``: ``[rows, fields]`` int32, written in place) and
    planted margins before the offset (``signal``) of one block."""
    rows = cols.shape[0]
    u, sq = scratch.u[:rows], scratch.sq[:rows]
    datagen.rng(seed, TAG_CELLS, block).random(out=u, dtype=np.float32)
    np.multiply(u, u, out=sq)
    np.multiply(sq, u, out=u)
    np.multiply(u, card.astype(np.float32), out=u)
    np.copyto(cols, u, casting="unsafe")            # floor: u >= 0
    np.minimum(cols, (card - 1).astype(np.int32), out=cols)
    for f in np.flatnonzero(card > stratum):        # hashing folds these
        cols[:, f] %= np.int32(stratum)
    cols += np.arange(card.size, dtype=np.int32) * np.int32(stratum)
    np.take(true, cols, out=sq)
    noise = datagen.rng(seed, TAG_NOISE, block).logistic(size=rows)
    np.add(sq.sum(axis=1, dtype=np.float64) * value, noise, out=signal)


def criteo_rows(seed: int, rows: int, dim: int, cardinalities, stratum: int):
    """``(indptr, indices, values, labels)``: CSR of ``rows`` rows (int64
    pointers, int32 indices, float32 values) and float32 0/1 labels.

    The label of a row is ``signal + offset > 0``: the seeded coefficient
    over the row's columns plus logistic noise, and an offset that is the
    ``1 - POSITIVE_SHARE`` quantile of block 0's signal, negated, so
    about a quarter of the rows are positive whatever the seed."""
    card = np.asarray(cardinalities, np.int64)
    nnz = card.size
    if nnz * stratum > dim:
        raise ValueError(f"{nnz} strata of {stratum} columns pass dim {dim}")
    value = np.float32(1.0 / np.sqrt(nnz))
    true = (SIGNAL * datagen.rng(seed, TAG_TRUE).standard_normal(dim)
            ).astype(np.float32)
    indices = np.empty((rows, nnz), np.int32)
    signal = np.empty(rows, np.float64)
    blocks = list(enumerate(range(0, rows, _BLOCK_ROWS)))

    def work(mine) -> None:
        scratch = _Scratch(nnz)
        for block, lo in mine:
            hi = min(lo + _BLOCK_ROWS, rows)
            _fill_block(seed, block, indices[lo:hi], signal[lo:hi], card,
                        stratum, true, float(value), scratch)

    with cf.ThreadPoolExecutor(_THREADS) as pool:
        # list(): an executor keeps a task's exception until it is read.
        list(pool.map(work, [blocks[t::_THREADS] for t in range(_THREADS)]))
    offset = -np.quantile(signal[:_BLOCK_ROWS], 1.0 - POSITIVE_SHARE)
    labels = (signal + offset > 0).astype(np.float32)
    indptr = np.arange(rows + 1, dtype=np.int64) * nnz
    values = np.full(rows * nnz, value, np.float32)
    return indptr, indices.reshape(-1), values, labels
