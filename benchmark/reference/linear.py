"""Binomial logistic regression by (mini-batch) gradient descent in NumPy
float64, written from Flink ML's ``LogisticGradient``/``LogisticRegression``
description: with labels y in {0, 1} and s = 2y - 1, over a batch B

    loss_i = log(1 + exp(-s_i * x_i . c))
    grad   = sum_{i in B} w_i * (-s_i * sigmoid(-s_i * x_i . c)) * x_i
    c     <- c - rate / sum_{i in B} w_i * grad        (reg 0, weights 1)

The batches follow the row order the configuration states as a guarantee
(``seeded_order``): step k takes window ``k mod ceil(n / batch)`` of the
rows in that order, so float64 SGD replays the very fit that was timed.
A full-batch run (batch = all rows) does not depend on the order at all.
"""

from __future__ import annotations

import concurrent.futures as cf

import numpy as np


def _sigmoid(z: np.ndarray) -> np.ndarray:
    return 0.5 * (1.0 + np.tanh(0.5 * z))


def seeded_order(seed: int, n: int) -> np.ndarray:
    """The order in which a fit with ``set_seed(seed)`` visits ``n`` rows,
    as the configuration's guarantees state it."""
    return np.random.default_rng(int(seed)).permutation(n)


def minibatch_sgd(x: np.ndarray, y: np.ndarray, steps: int, rate: float,
                  batch: int, order: np.ndarray, round_to=None,
                  threads: int = 8) -> np.ndarray:
    """``steps`` updates from zero coefficients; step ``k`` sums over rows
    ``order[lo:lo + batch]`` with ``lo = (k mod ceil(n / batch)) * batch``,
    pulled back so that a last, short window still holds ``batch`` rows.

    ``x`` stays as it is given (float32, gigabytes): each step gathers its
    own rows, widens them and sums them in ``threads`` parts. Within a step
    the rows are taken in ascending order, which a sum does not notice.

    ``round_to`` is the control's hook: a function applied to the
    features, to the coefficient before each product and to the
    per-row multipliers, so the same arithmetic can be run at a lower
    precision (``to_bfloat16``) with float32 accumulation. ``None`` is
    float64 throughout."""
    wide = round_to is None
    acc = np.float64 if wide else np.float32
    rnd = (lambda a: a) if wide else round_to
    n, d = x.shape
    batch = min(int(batch), n)
    windows = -(-n // batch)
    c = np.zeros(d, acc)

    def part(idx: np.ndarray, cr: np.ndarray) -> np.ndarray:
        xb = rnd(x[idx].astype(acc))
        s = 2.0 * y[idx].astype(acc) - 1.0
        mult = rnd((-s * _sigmoid(-s * (xb @ cr))).astype(acc))
        return xb.T @ mult

    with cf.ThreadPoolExecutor(threads) as pool:
        for k in range(steps):
            lo = min((k % windows) * batch, n - batch)
            idx = np.sort(order[lo:lo + batch])
            cr = rnd(c)
            grads = pool.map(lambda ix: part(ix, cr), np.array_split(idx, threads))
            grad = np.sum(list(grads), axis=0, dtype=acc)
            c = (c - acc(rate) / acc(batch) * grad).astype(acc)
    return c.astype(np.float64)


def full_batch_gd(x: np.ndarray, y: np.ndarray, steps: int, rate: float,
                  round_to=None) -> np.ndarray:
    """``steps`` updates over all rows at once: order-independent."""
    return minibatch_sgd(x, y, steps, rate, x.shape[0], np.arange(x.shape[0]),
                         round_to=round_to)


def to_bfloat16(a: np.ndarray) -> np.ndarray:
    """Round float32 values to the nearest bfloat16 (ties to even),
    returned as float32."""
    a = np.ascontiguousarray(a, np.float32)
    bits = a.view(np.uint32)
    rounded = (bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return rounded.view(np.float32)


def log_loss(margins: np.ndarray, y: np.ndarray) -> float:
    """Mean logistic loss at float64."""
    s = 2.0 * np.asarray(y, np.float64) - 1.0
    return float(np.mean(np.logaddexp(0.0, -np.asarray(margins, np.float64) * s)))
