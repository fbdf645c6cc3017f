"""``driver: fm`` — whole ``FMClassifier().fit(Table)`` calls, back to
back, on ONE host ``Table`` whose features column is a ``CsrColumn``
(Criteo-profile rows from ``datagen_criteo``, labels with a pairwise term
from ``datagen_fm``) and whose cells set-up's first fit placed on the
chip: a learning rate and a regularisation swept over the day's logs
before the full run. The cell's ``sweep`` lists the (rate, ``reg``)
pairs, taken in turn; the parameters are read back every fit. A closed
loop: a new fit starts while the window is open and the one in flight
always finishes.

Set-up makes the table and fits each pair once: the first fit places the
table and warms the one program (rate and ``reg`` are operands of it),
and the window may upload nothing of the table again. The
configuration's file gives ``dim``, ``nnz``, the field table,
``factor_size``, ``global_batch_size``, ``tol``, ``rows`` and
``max_iter``; the cell's file ``sweep`` and ``limits``.

``correct`` is decided after the window, on what the timed fits
themselves returned: the LAST timed fit's ``w0``, ``w [dim]`` and ``V
[dim, k]`` against ``reference/fm.py`` (NumPy float64 Adam replayed over
the same rows in the seeded order from the program's own start factors),
by the widest gap of any parameter and by the root mean square of the gap
over all of them, both in units of the learning rate; every timed fit equal to set-up's fit
of its pair to the bit; the table's upload counter unmoved; the steps the
program counted ``max_iter`` a fit; every cell blocked; no
``SparseVector`` built; parameters finite.

A program whose ``models.fm`` states no ``LOOKUP_PRECISION`` (the parent
of PR 36, whose fit densifies the column to ``[rows, dim]`` float64, or
builds one ``SparseVector`` a row on the way) stops at set-up's import,
before any data is made.

``flops_bytes_fm.step`` is the roofline's count and
``tests/chip_controls_fm.py`` the one-bfloat16-pass control, for a builder
on the chip. Rehearse the cell on a CPU (rows 16,384, batch 2,048, 8
steps; a minute)::

    JAX_PLATFORMS=cpu python benchmark/run.py --workload fm-criteo.fit \
        --seed 2147493104 --seconds 1 --trace 1 --rehearse
"""

from __future__ import annotations

import json
import time
import types

import numpy as np

from benchmark import datagen_criteo, datagen_fm
from benchmark.reference import fm as reference


def _fit(s, pair, precision=None):
    """One unit: a whole fit on the one table at ``pair`` = (rate, reg),
    its parameters read back: ``(w0 [1], w [dim], V [dim, k])``.
    ``precision`` is a control's (None: the program's own)."""
    from flinkml_tpu.models import FMClassifier, _fm_sparse

    est = (FMClassifier().set_factor_size(s.k).set_max_iter(s.max_iter)
           .set_global_batch_size(s.batch).set_tol(s.tol)
           .set_learning_rate(float(pair[0])).set_reg(float(pair[1]))
           .set_seed(s.seed))
    if precision is not None:
        return tuple(np.asarray(a, np.float64) for a in
                     _fm_sparse.fit_csr(est, s.table, True, precision=precision))
    # The model's own arrays (float64 copies of the float32 the chip
    # returned): views, nothing converted inside the window.
    data = est.fit(s.table).get_model_data()[0]
    return (np.asarray(data.column("w0")).reshape(1), data.column("w")[0],
            data.column("v")[0])


def setup(ctx):
    from flinkml_tpu.models.fm import LOOKUP_PRECISION  # noqa: F401 — see the docstring
    from flinkml_tpu.table import CsrColumn, Table

    s = types.SimpleNamespace()
    s.rows, s.dim = int(ctx.size("rows")), int(ctx.config["dim"])
    s.nnz, s.k = int(ctx.config["nnz"]), int(ctx.config["factor_size"])
    s.batch = int(ctx.size("global_batch_size"))
    s.max_iter, s.tol = int(ctx.size("max_iter")), float(ctx.config["tol"])
    s.seed = ctx.seed % (1 << 31)
    s.pairs = [tuple(p) for p in ctx.cell["sweep"]]
    t0 = time.perf_counter()
    indptr, s.indices, s.values, _ = datagen_criteo.criteo_rows(
        ctx.seed, s.rows, s.dim, ctx.config["field_cardinalities"],
        int(ctx.config["field_stratum"]))
    s.y = datagen_fm.planted_labels(ctx.seed, s.indices.reshape(s.rows, s.nnz), s.dim)
    print(json.dumps({"phase": "data", "seconds": time.perf_counter() - t0,
                      "positive_share": float(s.y.mean())}), flush=True)
    # What a loader hands over: the column validates its rows once, here.
    s.table = Table({"features": CsrColumn(indptr, s.indices, s.values, s.dim),
                     "label": s.y})
    # Each pair's fit once: the first places the table and warms the one
    # program (the window's zero-compile count checks that it did), and
    # each is what every timed fit of its pair has to equal.
    s.first = []
    for pair in s.pairs:
        t0 = time.perf_counter()
        s.first.append(_fit(s, pair))
        print(json.dumps({"phase": "warm-fit", "pair": pair,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return s


def window(ctx, s):
    walls, s.timed = [], []
    t_open = time.perf_counter()
    while True:
        which = len(walls) % len(s.pairs)
        t0 = time.perf_counter()
        with ctx.unit("fit", fits=1, steps=s.max_iter,
                      samples=s.max_iter * s.batch):
            s.timed.append((which, _fit(s, s.pairs[which])))
        now = time.perf_counter()
        walls.append(now - t0)
        if now - t_open >= ctx.seconds:
            break
    return {"work": len(walls) * s.max_iter * s.batch, "wall_s": now - t_open,
            "attempted": len(walls), "failed": 0, "unit_walls_s": walls}


def reference_fit(s, pair) -> dict:
    """Float64 Adam over the same rows in the seeded order, from the
    program's own start factors: what a fit at ``pair`` is held to."""
    from flinkml_tpu.models.fm import start_factors

    start = np.asarray(start_factors(s.dim, s.k, s.seed))
    w0, w, v, losses = reference.adam_fit(
        s.indices.reshape(s.rows, s.nnz), s.values.reshape(s.rows, s.nnz),
        s.dim, s.y, start, s.max_iter, float(pair[0]), float(pair[1]), s.batch,
        reference.seeded_order(s.seed, s.rows), tol=s.tol)
    return {"pair": pair, "w0": w0, "w": w, "v": v, "losses": losses,
            "moved": float(max(abs(w0), np.abs(w).max(), np.abs(v - start).max())),
            "touched": int(np.count_nonzero(w))}


def compare(ref: dict, fit) -> dict:
    """What a fit at ``ref``'s pair returned against :func:`reference_fit`."""
    w0, w, v = fit
    out = {"pair": ref["pair"], "moved_by_the_fit": ref["moved"],
           "columns_touched": ref["touched"], "steps": len(ref["losses"]),
           "loss_first": ref["losses"][0], "loss_last": ref["losses"][-1]}
    if (w.shape != ref["w"].shape or v.shape != ref["v"].shape
            or not all(np.isfinite(a).all() for a in fit)):
        return {**out, "gap": None, "rms_gap_in_rates": None}
    off = np.abs(v - ref["v"])
    gaps = {"w0": float(abs(float(w0[0]) - ref["w0"])),
            "w": float(np.abs(w - ref["w"]).max()), "v": float(off.max())}
    # Over all 1 + dim * (1 + k) parameters, in units of the rate (a step
    # of Adam moves a parameter by about one rate).
    squares = (gaps["w0"] ** 2 + float(np.sum((w - ref["w"]) ** 2))
               + float(np.sum(off * off)))
    rms = float(np.sqrt(squares / (1 + w.size + v.size))) / float(ref["pair"][0])
    # Where the factors are furthest off, for whoever reads a failure:
    # the column, and how many parameters are within a tenth of it.
    column, factor = np.unravel_index(int(off.argmax()), off.shape)
    return {**out, "gaps": gaps, "gap": max(gaps.values()), "rms_gap_in_rates": rms,
            "worst_factor": {"column": int(column), "factor": int(factor),
                             "got": float(v[column, factor]),
                             "want": float(ref["v"][column, factor]),
                             "within_a_tenth": int((off > 0.1 * off.max()).sum())},
            "worst_weight_column": int(np.abs(w - ref["w"]).argmax())}


def check(ctx, s, result, counters):
    t0 = time.perf_counter()
    which, last = s.timed[-1]
    cmp = compare(reference_fit(s, s.pairs[which]), last)
    print(json.dumps({"phase": "reference", "seconds": time.perf_counter() - t0,
                      **cmp}), flush=True)
    return verdicts(ctx, s, cmp, counters)


def verdicts(ctx, s, cmp: dict, counters: dict) -> list:
    """The cell's own checks of one fit's :func:`compare` and of the
    window's fits and counters, each a value beside its limit."""
    limits = ctx.size("limits")
    fits = len(s.timed)
    apart = sum(1 for which, fit in s.timed
                if any(a.shape != b.shape or not np.array_equal(a, b)
                       for a, b in zip(fit, s.first[which])))
    strange = sum(1 for _, (w0, w, v) in s.timed
                  if w.shape != (s.dim,) or v.shape != (s.dim, s.k)
                  or not all(np.isfinite(a).all() for a in (w0, w, v)))
    steps, counted = counters.get("fm.steps"), counters.get("fm.fits", 0)
    cells = counters.get("fm.cells")
    return [
        {"what": f"last timed fit (rate {cmp['pair'][0]}, reg {cmp['pair'][1]}; "
                 f"{s.rows} rows of {s.nnz} cells, batch {s.batch}, {s.max_iter} "
                 f"steps, {s.k} factors): widest gap of w0, w [{s.dim}] and V "
                 f"[{s.dim}, {s.k}] to float64 Adam over the same rows in the seeded "
                 "order, IN RATES (a step of Adam moves a parameter by about one "
                 f"rate; the gaps themselves {cmp.get('gaps')}; the fit moved a "
                 f"parameter by up to {cmp['moved_by_the_fit']:.4f}, "
                 f"{cmp['columns_touched']} columns have a weight, the loss went "
                 f"{cmp['loss_first']:.4f} -> {cmp['loss_last']:.4f})",
         "value": None if cmp["gap"] is None else cmp["gap"] / float(cmp["pair"][0]),
         "limit": limits["parameter_gap_in_rates"]},
        {"what": "the same fit: root mean square of that gap over all "
                 f"{1 + s.dim * (1 + s.k)} parameters, in rates (the widest gap is one "
                 "parameter's, whose gradient happened to cancel to within Adam's "
                 "epsilon; this is every parameter's)",
         "value": cmp["rms_gap_in_rates"], "limit": limits["rms_gap_in_rates"]},
        {"what": f"timed fits ({fits}) that differ in any bit from set-up's fit of "
                 "the same (rate, reg) pair",
         "value": apart, "limit": 0},
        {"what": "table bytes uploaded inside the window (fm.table_h2d_bytes)",
         "value": counters.get("fm.table_h2d_bytes"), "limit": 0},
        {"what": f"timed fits ({fits}) whose parameters are not [{s.dim}] and "
                 f"[{s.dim}, {s.k}] or not finite",
         "value": strange, "limit": 0},
        {"what": f"steps the program counted, off {s.max_iter} a timed fit "
                 f"(fm.steps {steps}, fm.fits {counted})",
         "value": None if steps is None else
         abs(steps - s.max_iter * fits) + abs(counted - fits),
         "limit": 0},
        {"what": "cells of the timed fits outside a blocked slot (fm.cells less "
                 f"fm.blocked_cells; fm.cells {cells})",
         "value": None if cells is None else
         cells - counters.get("fm.blocked_cells", 0.0),
         "limit": 0},
        {"what": "SparseVector rows built from the CsrColumn inside the window "
                 "(table.csr_rows_materialized)",
         "value": counters.get("table.csr_rows_materialized"), "limit": 0},
    ]
