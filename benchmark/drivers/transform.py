"""``driver: transform`` — back-to-back ``PipelineModel.transform(Table)``
calls over a host table, a new ``Table`` for every call, each call ended
by reading ``prediction`` back to the host.

The cell's file gives ``rows`` (rows a table), ``tables`` and
``table_stride_rows`` (table ``j`` is rows ``[j*stride, j*stride+rows)``
of one seeded matrix, used in rotation: every table is different bytes
at no extra set-up), ``warmup_calls``, ``sample_rows`` and ``limits``.
"""

from __future__ import annotations

import time
import types

import numpy as np

from benchmark import datagen
from benchmark.drivers import chain_model
from benchmark.reference import chain as reference


def _table(state, j):
    lo = (j % state.tables) * state.stride
    return state.base[lo:lo + state.rows]


def _call(state, x):
    """One unit: a new Table over host rows ``x``, the fused transform,
    ``prediction`` read back. Returns the output table too, so the
    caller decides how long its device columns live."""
    from flinkml_tpu.table import Table

    (out,) = state.model.transform(Table({"features": x}))
    return out, np.asarray(out.column("prediction"))


def setup(ctx):
    s = types.SimpleNamespace()
    s.rows, s.dim = int(ctx.size("rows")), int(ctx.config["dim"])
    s.tables, s.stride = int(ctx.cell["tables"]), int(ctx.cell["table_stride_rows"])
    s.md = datagen.chain_model_data(ctx.seed, s.dim)
    s.model = chain_model.build(s.md)
    s.base = datagen.normal_matrix(
        ctx.seed, datagen.TAG_FEATURES, s.rows + (s.tables - 1) * s.stride, s.dim)
    for j in range(int(ctx.cell["warmup_calls"])):
        out, _ = _call(s, _table(s, j))
        del out
    return s


def window(ctx, s):
    preds, walls = [], []
    out = None
    t_open = time.perf_counter()
    j = 0
    while True:
        # The last call's output table is kept for the check; every
        # earlier one is dropped before the next upload, as a scorer
        # that holds one table at a time does.
        out = None
        t0 = time.perf_counter()
        with ctx.unit("transform-call", calls=1, rows=s.rows):
            out, pred = _call(s, _table(s, j))
        now = time.perf_counter()
        walls.append(now - t0)
        preds.append(pred)
        j += 1
        if now - t_open >= ctx.seconds:
            break
    s.last_out, s.preds = out, preds
    return {"work": j * s.rows, "wall_s": now - t_open, "attempted": j,
            "failed": 0, "unit_walls_s": walls}


def check(ctx, s, result, counters):
    """Every call's predictions on a seeded sample of its rows, and the
    last call's probabilities on its sample, against the NumPy float64
    chain; the program's own host-to-device byte count against the
    bytes a new table must cost."""
    k = int(ctx.cell["sample_rows"])
    limits = ctx.size("limits")
    mismatches, worst_raw = 0, None
    for j, pred in enumerate(s.preds):
        idx = datagen.sample_rows(ctx.seed, s.rows, k, j)
        x = _table(s, j)[idx]
        raw = None
        if j == len(s.preds) - 1:
            raw = np.asarray(s.last_out.column("rawPrediction"))[idx]
        cmp = reference.compare(s.md, x, pred[idx], raw)
        mismatches += cmp["pred_mismatch_away"]
        if raw is not None:
            worst_raw = cmp["raw_max_abs_err"]
    s.last_out = None
    h2d = counters.get("pipeline.fusion.host_to_device_bytes", 0.0) / result["work"]
    row_bytes = s.dim * np.dtype(ctx.config["feature_dtype"]).itemsize
    return [
        {"what": "rawPrediction, widest absolute gap to the float64 chain "
                 f"({k} sampled rows of the last call)",
         "value": worst_raw, "limit": limits["raw_max_abs_err"]},
        {"what": "predictions that differ from the float64 chain away from "
                 f"the boundary ({k} sampled rows of each of {len(s.preds)} calls)",
         "value": mismatches, "limit": limits["pred_mismatch_away"]},
        {"what": "host-to-device bytes a row, off the bytes of a new table "
                 f"({row_bytes} B/row; the program's counter)",
         "value": abs(h2d - row_bytes), "limit": 0},
    ]
