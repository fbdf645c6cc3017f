"""A second-order factorization machine trained by Adam over sparse rows,
NumPy float64: the benchmark's own copy (it imports nothing of the
program or of ``tests/``). Plain fancy-index gathers a factor at a time, and a
factor's gradient one sum over the cells of each column; a few threads
share out the rows and the factors, which changes no sum.

The model is Rendle's (ICDM 2010, eq. 1 and its O(k n) form, eq. 5).
Rows are ELL: row ``r`` holds columns ``i[r, :]`` with values ``x[r, :]``
(a ragged row pads with value 0, which adds nothing anywhere)::

    S[r, f] = sum_s x[r, s] V[i[r, s], f]
    y^[r]   = w0 + sum_s x[r, s] w[i[r, s]]
              + 1/2 sum_f (S[r, f]**2 - sum_s x[r, s]**2 V[i[r, s], f]**2)

With row weights ``c`` and ``W = sum_r c_r`` over the batch, the loss is
``sum_r c_r l(y^[r], y[r]) / W + reg (|w|**2 + |V|**2)``, ``l`` the
logistic loss ``log(1 + exp(y^)) - y y^`` for labels in {0, 1} or the
squared loss ``(y^ - y)**2 / 2``, and with ``m_r = c_r dl/dy^``::

    dw0        = sum_r m_r / W
    dw[j]      = sum_{(r, s): i = j} m_r x[r, s] / W              + 2 reg w[j]
    dV[j, f]   = sum_{(r, s): i = j} m_r (x S[r, f] - x**2 V[j, f]) / W + 2 reg V[j, f]

Departures from the paper, both the program's (``models/fm.py``,
``models/_adam.py``) and stated in the configuration's file:

- **Adam, not SGD**: rates 0.9 and 0.999, epsilon 1e-8 added outside the
  root, both moments bias-corrected, dense moments over every parameter
  (a column no row of the batch names still decays its moments and moves).
- **L2 scaled per batch**: the program adds ``reg (|w|**2 + |V|**2) sum_r
  c_r`` to the batch's weighted loss SUM before dividing by ``W``, so the
  penalty's gradient is ``2 reg`` times the parameter every step, whatever
  the batch; the paper regularises per observed cell. ``w0`` has none.

Step ``t`` reads window ``t mod ceil(rows / batch)`` of the seeded order
(``seeded_order``), pulled back so that a last, short window still holds
``batch`` rows; over ``shards`` data-parallel workers each reads that
window of its own contiguous share of the order (:func:`step_rows`).
"""

from __future__ import annotations

import concurrent.futures as cf

import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8


def seeded_order(seed: int, rows: int) -> np.ndarray:
    """The row order a seed fixes."""
    return np.random.default_rng(int(seed)).permutation(rows)


def step_rows(order: np.ndarray, batch: int, t: int, shards: int = 1) -> np.ndarray:
    """The rows step ``t`` reads: positions of ``order``, its rows split
    into ``shards`` contiguous shares of ``ceil(rows / shards)`` (the
    last one short), each share windowed on its own."""
    n = order.shape[0]
    n_local = -(-n // shards)
    local = min(max(1, -(-int(batch) // shards)), n_local)
    windows = max(-(-n_local // local), 1)
    lo = min((t % windows) * local, n_local - local)
    at = (np.arange(shards)[:, None] * n_local + lo + np.arange(local)).reshape(-1)
    return order[at[at < n]]


def margin(w0: float, w: np.ndarray, v: np.ndarray, idx: np.ndarray,
           x: np.ndarray):
    """``(y^ [rows], S [k, rows], x V[i] [k, rows, width])`` of ELL rows
    ``idx``, ``x``; ``v`` is ``[dim, k]``, or ``[k, dim]`` where the
    caller has turned it (``factor_major``). A factor at a time, so that
    every array a product reads is contiguous."""
    return _margin(w0, w, np.ascontiguousarray(v.T), idx, x)


def _margin(w0, w, factors, idx, x):
    """:func:`margin` with the factors ``[k, dim]``."""
    k = factors.shape[0]
    xv = np.empty((k,) + idx.shape)
    squares = np.zeros(idx.shape[0])
    for f in range(k):
        np.multiply(factors[f][idx], x, out=xv[f])
        squares += np.einsum("rs,rs->r", xv[f], xv[f])
    s = xv.sum(axis=2)
    return (w0 + (x * w[idx]).sum(axis=1)
            + 0.5 * ((s * s).sum(axis=0) - squares)), s, xv


def _rows_part(w0, w, factors, idx, x, y, c, logistic: bool):
    """One share of a batch's rows: its weighted loss sum, ``sum m``, and
    per cell ``m x`` ``[cells]`` and ``m (x S_f - x**2 V[i, f])`` ``[k,
    cells]``."""
    x, y, c = x.astype(np.float64), y.astype(np.float64), c.astype(np.float64)
    y_hat, s, xv = _margin(w0, w, factors, idx, x)
    if logistic:
        per_row = np.logaddexp(0.0, y_hat) - y * y_hat
        m = (0.5 * (1.0 + np.tanh(0.5 * y_hat)) - y) * c
    else:
        per_row = 0.5 * (y_hat - y) ** 2
        m = (y_hat - y) * c
    mx = m[:, None] * x
    np.subtract(s[:, :, None], xv, out=xv)
    xv *= mx
    return (per_row * c).sum(), m.sum(), mx.reshape(-1), xv.reshape(len(xv), -1)


def loss_and_gradients(w0, w, v, idx, x, y, c, reg: float, logistic: bool):
    """``(loss, (dw0, dw [dim], dV [dim, k]))`` of one batch, as the
    module docstring states them."""
    loss, (g0, gw, gf) = _gradients(w0, w, np.ascontiguousarray(v.T), idx, x,
                                    y, c, reg, logistic)
    return loss, (g0, gw, gf.T)


def _gradients(w0, w, factors, idx, x, y, c, reg, logistic, pool=None,
               threads: int = 1):
    """:func:`loss_and_gradients` with the factors and their gradient
    ``[k, dim]``. ``pool`` (a pool of ``threads`` threads) only shares out work that is
    the same whatever the split: the rows' gathers and products, and then
    a factor's sum over the cells of each column (``np.add.reduceat``
    over the cells sorted by column once: what ``np.bincount(cells,
    weights)`` gives, on threads)."""
    k, dim = factors.shape
    run = map if pool is None else pool.map
    shares = [r for r in np.array_split(np.arange(idx.shape[0]), threads)
              if r.size]
    parts = list(run(
        lambda r: _rows_part(w0, w, factors, idx[r], x[r], y[r], c[r], logistic),
        shares))
    weight = float(np.sum(c, dtype=np.float64))
    total = max(weight, 1e-12)
    l2 = reg * weight / total
    flat = factors.reshape(-1)
    loss = sum(p[0] for p in parts) / total + l2 * (np.dot(w, w) + np.dot(flat, flat))
    cells = idx.reshape(-1)          # the shares are consecutive rows
    by_column = np.argsort(cells, kind="stable")
    sorted_cells = cells[by_column]
    first = np.flatnonzero(np.r_[True, sorted_cells[1:] != sorted_cells[:-1]])
    columns = sorted_cells[first]
    grads = np.zeros((k + 1, dim))

    def column_sums(f):
        per_cell = np.concatenate([p[2] if f == k else p[3][f] for p in parts])
        grads[f, columns] = np.add.reduceat(per_cell[by_column], first)
        grads[f] /= total
        grads[f] += 2.0 * l2 * (w if f == k else factors[f])

    list(run(column_sums, range(k + 1)))
    return loss, (sum(p[1] for p in parts) / total, grads[k], grads[:k])


def _adam(p, m, v, g, t: int, rate: float):
    """Adam's update of one array from the gradient of 0-based step ``t``,
    in place."""
    m *= B1
    m += (1 - B1) * g
    v *= B2
    v += (1 - B2) * g * g
    p -= rate * (m / (1 - B1 ** (t + 1))) / (np.sqrt(v / (1 - B2 ** (t + 1))) + EPS)


def adam_fit(idx: np.ndarray, x: np.ndarray, dim: int, y: np.ndarray,
             v_start: np.ndarray, steps: int, rate: float, reg: float,
             batch: int, order: np.ndarray, weights=None, logistic: bool = True,
             tol: float = 0.0, shards: int = 1, threads: int = 8):
    """``steps`` Adam updates from ``w0 = 0``, ``w = 0``, ``V = v_start``
    over ELL rows ``idx`` / ``x`` ``[rows, width]``: ``(w0, w [dim], V
    [dim, k], losses)`` in float64. With ``tol > 0`` it stops once two
    successive losses lie within ``tol``; at 0 it runs every step."""
    n = idx.shape[0]
    c_all = np.ones(n) if weights is None else np.asarray(weights, np.float64)
    # The factors [k, dim] from start to end: a factor's arrays contiguous.
    params = [np.zeros(1), np.zeros(dim),
              np.ascontiguousarray(np.asarray(v_start, np.float64).T)]
    m = [np.zeros_like(p) for p in params]
    v = [np.zeros_like(p) for p in params]
    losses = []
    with cf.ThreadPoolExecutor(threads) as pool:
        for t in range(steps):
            if tol > 0 and len(losses) > 1 and abs(losses[-2] - losses[-1]) <= tol:
                break
            rows = np.sort(step_rows(order, batch, t, shards))
            loss, (g0, gw, gf) = _gradients(
                params[0][0], params[1], params[2], idx[rows], x[rows], y[rows],
                c_all[rows], reg, logistic, pool, threads)
            losses.append(loss)
            _adam(params[0], m[0], v[0], g0, t, rate)
            _adam(params[1], m[1], v[1], gw, t, rate)
            list(pool.map(lambda f: _adam(params[2][f], m[2][f], v[2][f], gf[f],
                                          t, rate), range(len(gf))))
    return float(params[0][0]), params[1], params[2].T.copy(), losses


def densified(idx: np.ndarray, x: np.ndarray, dim: int) -> np.ndarray:
    """``[rows, dim]`` float64 of the same rows (small sizes only)."""
    out = np.zeros((idx.shape[0], dim))
    np.add.at(out, (np.arange(idx.shape[0])[:, None], idx), x)
    return out


def dense_margin(w0, w, v, dense: np.ndarray) -> np.ndarray:
    """Rendle's eq. 5 over a dense matrix: what :func:`margin` must equal
    on :func:`densified` rows."""
    xv = dense @ v
    return w0 + dense @ w + 0.5 * ((xv * xv).sum(axis=1)
                                   - (dense * dense) @ (v * v).sum(axis=1))
