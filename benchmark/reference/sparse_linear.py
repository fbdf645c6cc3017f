"""Binomial logistic regression by mini-batch gradient descent over
sparse rows, NumPy float64, the benchmark's own copy (it imports nothing
of the program or of ``tests/``). The update is ``reference/linear.py``'s
(Flink ML's ``LogisticGradient``), the rows are uniform CSR: row ``r``
holds columns ``i[r, :]`` with values ``v[r, :]``. With labels y in
{0, 1}, s = 2y - 1, over the batch B of step k

    dot_r = sum_j v[r, j] * c[i[r, j]]
    m_r   = w_r * (-s_r * sigmoid(-s_r * dot_r))
    g     = bincount(i, weights = v * m, minlength = dim)
    c    <- c - rate / sum_{r in B} w_r * g          (reg 0)

Step ``k`` takes window ``k mod ceil(rows / batch)`` of the seeded order
(``seeded_order``), pulled back so that a last, short window still holds
``batch`` rows: float64 SGD replays the very fit that was timed.
"""

from __future__ import annotations

import concurrent.futures as cf

import numpy as np

# The dense replay's own pieces: one sigmoid, one seeded order (the same
# guarantee), one bfloat16 rounding for both references.
from benchmark.reference.linear import _sigmoid, seeded_order, to_bfloat16  # noqa: F401


def minibatch_sgd(indices: np.ndarray, values: np.ndarray, dim: int,
                  y: np.ndarray, steps: int, rate: float, batch: int,
                  order: np.ndarray, weights=None, round_to=None,
                  threads: int = 8) -> np.ndarray:
    """``steps`` updates from zero coefficients over ``[rows, nnz]``
    ``indices`` / ``values``; returns float64 ``[dim]``.

    Each step gathers its own rows (ascending, which a sum does not
    notice) and computes their cells' contributions in ``threads`` parts;
    one ``bincount`` over all of them is the gradient.

    ``round_to`` is the control's hook: a function applied to the values,
    to the coefficient before each product and to the per-row
    multipliers, so the same arithmetic can be run at a lower precision
    (``to_bfloat16``), its sums kept in float32 between steps (``bincount``
    itself adds in float64: no worse than float32 accumulation). ``None``
    is float64 throughout."""
    wide = round_to is None
    acc = np.float64 if wide else np.float32
    rnd = (lambda a: a) if wide else round_to
    n = indices.shape[0]
    batch = min(int(batch), n)
    windows = -(-n // batch)
    c = np.zeros(dim, acc)

    def part(rows: np.ndarray, cr: np.ndarray):
        ib = indices[rows]
        vb = rnd(values[rows].astype(acc))
        s = 2.0 * y[rows].astype(acc) - 1.0
        dot = (vb * cr[ib]).sum(axis=1, dtype=acc)
        mult = -s * _sigmoid(-s * dot)
        if weights is not None:
            mult = mult * weights[rows].astype(acc)
        mult = rnd(mult.astype(acc))
        return ib.reshape(-1), (vb * mult[:, None]).reshape(-1)

    with cf.ThreadPoolExecutor(threads) as pool:
        for k in range(steps):
            lo = min((k % windows) * batch, n - batch)
            rows = np.sort(order[lo:lo + batch])
            cr = rnd(c)
            cells, contrib = zip(*pool.map(lambda r: part(r, cr),
                                           np.array_split(rows, threads)))
            grad = np.bincount(np.concatenate(cells),
                               weights=np.concatenate(contrib),
                               minlength=dim).astype(acc)
            wsum = acc(batch) if weights is None else \
                weights[rows].astype(acc).sum(dtype=acc)
            c = (c - acc(rate) / wsum * grad).astype(acc)
    return c.astype(np.float64)


def densified(indices: np.ndarray, values: np.ndarray, dim: int) -> np.ndarray:
    """``[rows, dim]`` float64 of the same rows (small sizes only): what
    the benchmark's tests hand ``reference/linear.py`` to check this
    replay against the dense one."""
    out = np.zeros((indices.shape[0], dim))
    np.add.at(out, (np.arange(indices.shape[0])[:, None], indices), values)
    return out
