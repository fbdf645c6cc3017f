"""Mini-batch SGD over sparse rows in NumPy float64: the plain reference
the sparse trainers are tested against (``tests/test_csr_column.py``). It
imports nothing of the program.

Rows are uniform CSR: row ``r`` holds columns ``i[r, :]`` with values
``v[r, :]`` (ragged rows pad with value 0, which adds nothing). Step
``k`` takes window ``k mod ceil(rows / batch)`` of the seeded order
(``numpy.random.default_rng(seed).permutation(rows)``, pulled back so a
last, short window still holds ``batch`` rows), and with labels y in
{0, 1}, s = 2y - 1:

    dot_r = sum_j v[r, j] * c[i[r, j]]
    logistic: m_r = w_r * (-s_r * sigmoid(-s_r * dot_r))
    hinge:    m_r = w_r * (-s_r if s_r * dot_r < 1 else 0)
    squared:  m_r = w_r * (dot_r - y_r)
    g  = bincount(i, weights = v * m, minlength = dim)
    c <- c - rate / sum_r w_r * g                      (reg 0)
"""

import numpy as np


def seeded_order(seed, rows):
    return np.random.default_rng(int(seed)).permutation(rows)


def _multiplier(loss, dot, y, w):
    s = 2.0 * y - 1.0
    if loss == "logistic":
        return w * (-s * 0.5 * (1.0 + np.tanh(0.5 * (-s * dot))))
    if loss == "hinge":
        return w * np.where(s * dot < 1.0, -s, 0.0)
    if loss == "squared":
        return w * (dot - y)
    raise ValueError(loss)


def sparse_sgd(indices, values, dim, y, w, loss, steps, rate, batch, order):
    """``steps`` updates from zero coefficients; float64 ``[dim]``."""
    indices = np.asarray(indices)
    values = np.asarray(values, np.float64)
    y, w = np.asarray(y, np.float64), np.asarray(w, np.float64)
    n = indices.shape[0]
    batch = min(int(batch), n)
    windows = -(-n // batch)
    c = np.zeros(dim)
    for k in range(steps):
        lo = min((k % windows) * batch, n - batch)
        rows = order[lo:lo + batch]
        i, v = indices[rows], values[rows]
        dot = (v * c[i]).sum(axis=1)
        m = _multiplier(loss, dot, y[rows], w[rows])
        g = np.bincount(i.reshape(-1), weights=(v * m[:, None]).reshape(-1),
                        minlength=dim)
        c = c - rate / w[rows].sum() * g
    return c
