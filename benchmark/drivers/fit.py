"""``driver: fit`` — whole ``LogisticRegression().fit(Table)`` calls on a
dense host table, back to back; a new fit starts while the window is
open and the one in flight always finishes.

The configuration's file gives ``dim``, ``rows``, ``global_batch_size``,
``reg``, ``tol`` and the row order a seed fixes; the cell's file
``max_iter``, ``learning_rate`` and ``limits``. The coefficients the timed
fits return are compared with NumPy float64 SGD replayed over the same
rows in that order.
"""

from __future__ import annotations

import time
import types

import numpy as np

from benchmark import datagen
from benchmark.reference import linear as reference


def _fit(ctx, table, batch, max_iter):
    from flinkml_tpu.models import LogisticRegression

    est = (LogisticRegression()
           .set_global_batch_size(int(batch))
           .set_max_iter(int(max_iter))
           .set_learning_rate(float(ctx.cell["learning_rate"]))
           .set_reg(float(ctx.config["reg"]))
           .set_tol(float(ctx.config["tol"]))
           .set_seed(ctx.seed % (1 << 31)))
    model = est.fit(table)
    return np.asarray(model.coefficient, np.float64)


def setup(ctx):
    from flinkml_tpu.table import Table

    s = types.SimpleNamespace()
    s.rows, s.dim = int(ctx.size("rows")), int(ctx.config["dim"])
    s.batch = int(ctx.size("global_batch_size"))
    s.max_iter = int(ctx.size("max_iter"))
    s.x = datagen.normal_matrix(ctx.seed, datagen.TAG_FEATURES, s.rows, s.dim)
    s.y = datagen.planted_labels(ctx.seed, s.x)
    s.table = Table({"features": s.x, "label": s.y})
    # The timed call itself, once: it warms the one program the window
    # runs (the window's zero-compile count checks that it did), and its
    # coefficients are the first of those every timed fit has to equal.
    s.coefs = [_fit(ctx, s.table, s.batch, s.max_iter)]
    return s


def window(ctx, s):
    walls = []
    t_open = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        with ctx.unit("fit", fits=1, steps=s.max_iter,
                      samples=s.max_iter * s.batch):
            s.coefs.append(_fit(ctx, s.table, s.batch, s.max_iter))
        now = time.perf_counter()
        walls.append(now - t0)
        if now - t_open >= ctx.seconds:
            break
    return {"work": len(walls) * s.max_iter * s.batch, "wall_s": now - t_open,
            "attempted": len(walls), "failed": 0, "unit_walls_s": walls}


def check(ctx, s, result, counters):
    limits = ctx.size("limits")
    out = []
    finite = all(np.isfinite(c).all() and c.shape == (s.dim,) for c in s.coefs)
    out.append({"what": "fits with a non-finite coefficient",
                "value": 0 if finite else 1, "limit": 0})
    spread = max(float(np.max(np.abs(c - s.coefs[0]))) for c in s.coefs[1:])
    out.append({"what": f"coefficients of the {len(s.coefs) - 1} timed fit(s), widest "
                        "difference from set-up's fit (same seed, same table)",
                "value": spread if finite else None, "limit": 0.0})
    # The timed fit itself, replayed: float64 SGD over the same rows in
    # the order the configuration states for the seed.
    order = reference.seeded_order(ctx.seed % (1 << 31), s.rows)
    want = reference.minibatch_sgd(s.x, s.y, s.max_iter,
                                   float(ctx.cell["learning_rate"]), s.batch, order)
    gap = float(np.max(np.abs(s.coefs[-1] - want))) if finite else None
    out.append({"what": f"last timed fit ({s.rows} rows, batch {s.batch}, "
                        f"{s.max_iter} steps): widest coefficient gap to float64 SGD "
                        f"over the same row order (largest |coefficient| "
                        f"{float(np.max(np.abs(want))):.4f})",
                "value": gap, "limit": limits["coef_gap"]})
    return out
