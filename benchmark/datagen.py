"""Inputs and model data from ``--seed``: the same seed gives the same
bytes. Everything here is NumPy; nothing of the program is imported.

Every stream is drawn from ``np.random.SeedSequence([seed, tag])`` so a
seed a little over 2**31 is as good as 0, and so two streams of one run
never share state.
"""

from __future__ import annotations

import concurrent.futures as cf

import numpy as np

# Stream tags: one per thing drawn, never reused.
TAG_FEATURES, TAG_MODEL, TAG_LABELS, TAG_SAMPLE = 1, 2, 3, 4

_FILL_THREADS = 8
_FILL_BLOCK_ROWS = 65_536


def rng(seed: int, tag: int, *more: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), tag, *more]))


def normal_matrix(seed: int, tag: int, rows: int, cols: int) -> np.ndarray:
    """``[rows, cols]`` float32 standard normals, filled block by block on
    a few threads (the generators release the GIL). Block ``i`` always
    comes from stream ``(seed, tag, i)``, so the bytes do not depend on
    the thread count."""
    out = np.empty((rows, cols), np.float32)
    starts = range(0, rows, _FILL_BLOCK_ROWS)

    def fill(i: int, lo: int) -> None:
        hi = min(lo + _FILL_BLOCK_ROWS, rows)
        rng(seed, tag, i).standard_normal(out=out[lo:hi], dtype=np.float32)

    with cf.ThreadPoolExecutor(_FILL_THREADS) as pool:
        for f in [pool.submit(fill, i, lo) for i, lo in enumerate(starts)]:
            f.result()
    return out


def chain_model_data(seed: int, d: int) -> dict:
    """Model data of the five-stage chain, float64, drawn so that every
    stage does work and the margins straddle 0: features are standard
    normal, the scalers keep them O(1), and the coefficient sums to zero
    (the scaled features share an offset, which a zero-sum coefficient
    cancels, so probabilities do not saturate and every stage's rounding
    shows in them)."""
    g = rng(seed, TAG_MODEL)
    coef = g.standard_normal(d)
    coef -= coef.mean()
    return {
        "mean": 0.1 * g.standard_normal(d),
        "std": g.uniform(0.8, 1.2, d),
        "dataMin": -5.0 - g.uniform(0.0, 1.0, d),
        "dataMax": 5.0 + g.uniform(0.0, 1.0, d),
        "maxAbs": g.uniform(0.9, 1.1, d),
        "median": 0.5 + 0.05 * g.standard_normal(d),
        "range": g.uniform(0.18, 0.22, d),
        "coefficient": 2.0 * coef / np.linalg.norm(coef),
    }


def planted_labels(seed: int, x: np.ndarray) -> np.ndarray:
    """Binary labels planted by a seeded linear model over ``x``
    (float32 0/1), computed in row blocks so no float64 copy of the
    whole matrix is made."""
    true = rng(seed, TAG_LABELS).standard_normal(x.shape[1]).astype(np.float32)
    y = np.empty(x.shape[0], np.float32)
    step = 1 << 20
    for lo in range(0, x.shape[0], step):
        y[lo:lo + step] = x[lo:lo + step] @ true > 0
    return y


def sample_rows(seed: int, n: int, k: int, *more: int) -> np.ndarray:
    """``k`` distinct row numbers below ``n``, sorted, from the seed."""
    return np.sort(rng(seed, TAG_SAMPLE, *more).choice(n, size=min(k, n), replace=False))
