"""The five-stage scoring chain in NumPy float64, written from the
operators' documented semantics (Flink ML StandardScaler, MinMaxScaler,
MaxAbsScaler, RobustScaler, LogisticRegressionModel):

    standard:  (x - mean) / std            (std 0 -> divide by 1)
    min-max:   (x - min) / (max - min) * (hi - lo) + lo, constant
               features to the middle of [lo, hi]; here lo, hi = 0, 1
    max-abs:   x / maxAbs                  (maxAbs 0 -> divide by 1)
    robust:    x / range                   (the operator's defaults: scaling
               on, centering off; range 0 -> divide by 1)
    logistic:  dot = x . coefficient; prediction = [dot >= 0];
               rawPrediction = [1 - p, p], p = 1 / (1 + exp(-dot))

``md`` is ``datagen.chain_model_data``'s dict.
"""

from __future__ import annotations

import numpy as np


def _safe(v: np.ndarray) -> np.ndarray:
    return np.where(v > 0, v, 1.0)


def chain(md: dict, x: np.ndarray):
    """Returns ``(dot, prediction, rawPrediction)`` for rows ``x``."""
    out = np.asarray(x, np.float64)
    out = (out - md["mean"]) / _safe(md["std"])
    span = md["dataMax"] - md["dataMin"]
    out = np.where(span > 0, (out - md["dataMin"]) / _safe(span), 0.5)
    out = out / _safe(md["maxAbs"])
    out = out / _safe(md["range"])
    dot = out @ md["coefficient"]
    p = 1.0 / (1.0 + np.exp(-dot))
    return dot, (dot >= 0).astype(np.float64), np.stack([1.0 - p, p], -1)


def compare(md: dict, x: np.ndarray, prediction, raw=None) -> dict:
    """The numbers ``correct`` is decided on, for the rows ``x`` and the
    program's outputs for them: the widest absolute gap of a
    probability (``None`` when ``raw`` was not read), and how many
    predictions differ from the reference's where the reference margin
    is too far from 0 for float32 rounding to have crossed it
    (``|dp/dm| <= 1/4``, so ``4 * 1e-4`` is generous at any limit this
    benchmark sets)."""
    dot, ref_pred, ref_raw = chain(md, x)
    pred = np.asarray(prediction, np.float64).reshape(-1)
    away = np.abs(dot) > 4e-4
    out = {
        "rows": int(dot.shape[0]),
        "pred_mismatch_away": int(np.sum(pred[away] != ref_pred[away])),
        "raw_max_abs_err": None,
    }
    if raw is not None:
        r = np.asarray(raw, np.float64)
        out["raw_max_abs_err"] = (
            float(np.max(np.abs(r - ref_raw))) if np.isfinite(r).all()
            else float("inf"))
    return out
