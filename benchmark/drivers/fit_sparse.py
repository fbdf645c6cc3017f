"""``driver: fit_sparse`` — whole ``LogisticRegression().fit(Table)``
calls on a resident table whose features column is a ``CsrColumn``
(Criteo-profile rows from ``datagen_criteo``), back to back; a new fit
starts while the window is open and the one in flight always finishes.

The configuration's file gives ``dim``, ``rows``, the field table,
``global_batch_size``, ``reg``, ``tol`` and the row order a seed fixes;
the cell's file ``max_iter``, ``learning_rate`` and ``limits``. The fit
call and the window are ``drivers/fit.py``'s own (imported, not edited);
set-up and the checks are this cell's: the coefficients the timed fits
return are compared with NumPy float64 SGD replayed over the same rows in
that order (``reference/sparse_linear.py``), and the window must have
built no ``SparseVector``.
"""

from __future__ import annotations

import types

import numpy as np

from benchmark import datagen_criteo
from benchmark.drivers import fit as dense
from benchmark.reference import sparse_linear as reference

window = dense.window


def setup(ctx):
    from flinkml_tpu.table import CsrColumn, Table

    s = types.SimpleNamespace()
    s.rows, s.dim = int(ctx.size("rows")), int(ctx.config["dim"])
    s.nnz = int(ctx.config["nnz"])
    s.batch = int(ctx.size("global_batch_size"))
    s.max_iter = int(ctx.size("max_iter"))
    indptr, s.indices, s.values, s.y = datagen_criteo.criteo_rows(
        ctx.seed, s.rows, s.dim, ctx.config["field_cardinalities"],
        int(ctx.config["field_stratum"]))
    # What a loader hands over: the column validates its rows once, here.
    column = CsrColumn(indptr, s.indices, s.values, s.dim)
    s.table = Table({"features": column, "label": s.y})
    # The timed call itself, once: it warms the one program the window
    # runs, and its coefficients are those every timed fit has to equal.
    s.coefs = [dense._fit(ctx, s.table, s.batch, s.max_iter)]
    return s


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
    out.append({"what": "SparseVector rows built from the CsrColumn inside the "
                        "window (table.csr_rows_materialized)",
                "value": counters.get("table.csr_rows_materialized"), "limit": 0})
    # The timed fit itself, replayed: float64 SGD over the same rows in
    # the order the configuration states for the seed.
    order = reference.seeded_order(ctx.seed % (1 << 31), s.rows)
    want = reference.minibatch_sgd(
        s.indices.reshape(s.rows, s.nnz), s.values.reshape(s.rows, s.nnz),
        s.dim, s.y, s.max_iter, float(ctx.cell["learning_rate"]), s.batch, order)
    gap = float(np.max(np.abs(s.coefs[-1] - want))) if finite else None
    out.append({"what": f"last timed fit ({s.rows} rows of {s.nnz} cells, batch "
                        f"{s.batch}, {s.max_iter} steps): widest coefficient gap to "
                        f"float64 SGD over the same row order (largest |coefficient| "
                        f"{float(np.max(np.abs(want))):.4f}, "
                        f"{int(np.count_nonzero(want))} columns touched)",
                "value": gap, "limit": limits["coef_gap"]})
    return out
