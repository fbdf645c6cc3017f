"""ALS with weighted-lambda regularisation (Zhou, Wilkinson, Schreiber,
Pan, AAIM 2008), NumPy float64, the benchmark's own copy: it imports
nothing of the program or of ``tests/``.

A half-step fixes one side's factors ``Y`` and gives every target ``t``
of the other side, over ALL its ratings ``r_j`` of fixed rows ``y_j``
(a pair that comes twice counts twice) ::

    explicit   (sum_j y_j y_j' + lam I) x = sum_j r_j y_j
    implicit   (Y'Y + sum_j alpha r_j y_j y_j' + lam I) x = sum_j (1 + alpha r_j) y_j
    lam = max(reg * max(n_t, 1), 1e-4)         n_t the target's ratings

(Hu, Koren, Volinsky, ICDM 2008, for the implicit form: confidence ``1 +
alpha r``, preference 1). A target with no rating solves ``lam x = 0``:
0. The floor of ``lam`` is the program's, stated by its configuration:
at ``reg`` 0 a target with fewer ratings than the rank has a singular
system. An iteration is the users' half-step from the item factors, then
the items' from the users' new ones; the start is the item factors
handed in.

Every system is solved by ``numpy.linalg.solve`` (LAPACK ``dgesv``) from
sums formed as ``Y_t' Y_t`` (``dgemm``) over the target's own rows, a
block of :data:`BLOCK_ROWS` at a time (a target of the cell's size holds
600,000 ratings: its rows in float64 at once are half a gigabyte of fresh
pages, the sums over blocks the same to the last bits); the targets of a
sample on a few threads, each target's ``dgemm`` on one.
"""

from __future__ import annotations

import concurrent.futures as cf
import os

import numpy as np
import threadpoolctl

LAM_FLOOR = 1e-4
#: Rows of a target summed at a time: 26 MB of float64 at rank 100.
BLOCK_ROWS = 32_768
_THREADS = max(1, min(12, os.cpu_count() or 1))


def solve_target(fixed: np.ndarray, index: np.ndarray, ratings: np.ndarray,
                 reg: float, implicit: bool = False, alpha: float = 1.0,
                 gram: np.ndarray = None) -> np.ndarray:
    """One target's factor ``[k]`` from its ratings: positions ``index
    [n]`` of the fixed side's factors ``fixed [m, k]`` and their values
    ``ratings [n]`` (``gram``: the fixed side's ``Y'Y``, implicit mode)."""
    index = np.asarray(index)
    k = fixed.shape[1]
    a, b = np.zeros((k, k)), np.zeros(k)
    for lo in range(0, index.size, BLOCK_ROWS):
        y = fixed[index[lo:lo + BLOCK_ROWS]].astype(np.float64)
        r = np.asarray(ratings[lo:lo + BLOCK_ROWS], np.float64)
        if implicit:
            a += (y * (alpha * r)[:, None]).T @ y
            b += (1.0 + alpha * r) @ y
        else:
            a += y.T @ y
            b += r @ y
    if implicit:
        a += gram
    lam = max(reg * max(index.size, 1), LAM_FLOOR)
    return np.linalg.solve(a + lam * np.eye(k), b)


def solve_targets(rows, fixed: np.ndarray, reg: float, implicit: bool = False,
                  alpha: float = 1.0) -> np.ndarray:
    """``[len(rows), k]``: the factors of a sample of targets. ``rows`` is
    a sequence of ``(index [n], rating [n])`` pairs, a target's ratings
    as positions of ``fixed [m, k]`` and their values."""
    fixed = np.asarray(fixed)
    gram = None
    if implicit:
        wide = fixed.astype(np.float64)
        gram = wide.T @ wide

    def one(row):
        return solve_target(fixed, row[0], row[1], reg, implicit, alpha, gram)

    with threadpoolctl.threadpool_limits(limits=1):
        with cf.ThreadPoolExecutor(_THREADS) as pool:
            return np.stack(list(pool.map(one, rows)))


def half_step(target: np.ndarray, other: np.ndarray, ratings: np.ndarray,
              targets: int, fixed: np.ndarray, reg: float,
              implicit: bool = False, alpha: float = 1.0) -> np.ndarray:
    """``[targets, k]``: every target's factor from the COO ``(target,
    other, ratings)`` and the other side's factors ``fixed``."""
    order = np.argsort(target, kind="stable")
    bounds = np.searchsorted(target[order], np.arange(targets + 1))
    rows = [(other[order[lo:hi]], ratings[order[lo:hi]])
            for lo, hi in zip(bounds[:-1], bounds[1:])]
    return solve_targets(rows, fixed, reg, implicit, alpha)


def fit(users: np.ndarray, items: np.ndarray, ratings: np.ndarray,
        start_item_factors: np.ndarray, max_iter: int, reg: float,
        implicit: bool = False, alpha: float = 1.0):
    """``(user_factors, item_factors)`` after ``max_iter`` iterations;
    ``users`` and ``items`` are positions ``0 .. n - 1`` of their sides."""
    item_f = np.asarray(start_item_factors, np.float64)
    n_users = int(users.max()) + 1 if users.size else 0
    for _ in range(max_iter):
        user_f = half_step(users, items, ratings, n_users, item_f, reg, implicit, alpha)
        item_f = half_step(items, users, ratings, item_f.shape[0], user_f, reg,
                           implicit, alpha)
    return user_f, item_f


def rmse(users, items, ratings, user_f, item_f) -> float:
    """Root mean square error of ``user_f[u] . item_f[i]`` over the
    ratings given, a block at a time."""
    total = 0.0
    for lo in range(0, len(ratings), BLOCK_ROWS):
        at = slice(lo, lo + BLOCK_ROWS)
        pred = np.sum(user_f[users[at]].astype(np.float64)
                      * item_f[items[at]].astype(np.float64), axis=1)
        total += float(np.sum((pred - ratings[at]) ** 2))
    return float(np.sqrt(total / len(ratings)))
