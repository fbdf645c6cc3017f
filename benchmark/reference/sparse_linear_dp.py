"""``reference/sparse_linear.py``'s replay for a table sharded over ``p``
data-parallel workers, NumPy float64, written from the WORDS of the
configuration ``lr-criteo-dp4`` (its ``guarantees``), not from the
program's placement: it imports nothing of the program or of ``tests/``.

The words. The seeded order is ``numpy.random.default_rng(seed).
permutation(rows)``. With ``p`` workers a worker holds ``n_local =
ceil(rows / p)`` positions of it: worker ``d`` the positions ``[d *
n_local, (d + 1) * n_local)``; a position at or past ``rows`` is a
padding row of weight 0 (the last worker alone has any, ``p * n_local -
rows`` of them). A worker's batch is ``b = ceil(global_batch_size / p)``
rows (at most ``n_local``), its local window ``k`` the local positions
``[lo, lo + b)`` with ``lo = min((k mod W) * b, n_local - b)``, ``W =
ceil(n_local / b)``: a last, short window is pulled back so that it holds
a whole local batch. Step ``k``'s batch is the union of the ``p``
workers' ``k``-th local windows, and the update is
``sparse_linear.minibatch_sgd``'s over that batch: the gradient summed
over all of its rows, the step ``rate / (sum of their weights)``. One
worker is that module's own replay, to the bit.
"""

from __future__ import annotations

import concurrent.futures as cf

import numpy as np

from benchmark.reference.linear import _sigmoid, seeded_order, to_bfloat16  # noqa: F401


def shard_layout(rows: int, workers: int, batch: int):
    """``(n_local, local batch, local windows)`` of ``rows`` rows over
    ``workers`` workers at the global batch ``batch``."""
    n_local = -(-int(rows) // int(workers))
    local = min(max(1, -(-int(batch) // int(workers))), n_local)
    return n_local, local, -(-n_local // local)


def step_positions(rows: int, workers: int, batch: int, k: int) -> np.ndarray:
    """The positions of the seeded order that step ``k`` reads, worker by
    worker, padding rows left out."""
    n_local, local, windows = shard_layout(rows, workers, batch)
    lo = min((k % windows) * local, n_local - local)
    at = (np.arange(workers, dtype=np.int64)[:, None] * n_local
          + np.arange(lo, lo + local, dtype=np.int64)[None, :]).reshape(-1)
    return at[at < rows]


def minibatch_sgd(indices: np.ndarray, values: np.ndarray, dim: int,
                  y: np.ndarray, steps: int, rate: float, batch: int,
                  order: np.ndarray, workers: int, weights=None,
                  round_to=None, threads: int = 8) -> np.ndarray:
    """``steps`` updates from zero coefficients over ``[rows, nnz]``
    ``indices`` / ``values`` sharded over ``workers`` workers as the
    module's words have it; returns float64 ``[dim]``. ``round_to`` is
    ``sparse_linear.minibatch_sgd``'s hook for the control (the values,
    the coefficient before each product and the per-row multipliers
    rounded, the sums kept in float32)."""
    wide = round_to is None
    acc = np.float64 if wide else np.float32
    rnd = (lambda a: a) if wide else round_to
    n = indices.shape[0]
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
            # Ascending, which a sum does not notice and a gather likes.
            rows = np.sort(order[step_positions(n, workers, batch, k)])
            cr = rnd(c)
            cells, contrib = zip(*pool.map(lambda r: part(r, cr),
                                           np.array_split(rows, threads)))
            grad = np.bincount(np.concatenate(cells),
                               weights=np.concatenate(contrib),
                               minlength=dim).astype(acc)
            wsum = acc(rows.size) if weights is None else \
                weights[rows].astype(acc).sum(dtype=acc)
            c = (c - acc(rate) / wsum * grad).astype(acc)
    return c.astype(np.float64)
