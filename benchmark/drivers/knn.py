"""``driver: knn`` — back-to-back ``KnnModel.transform(Table)`` calls
against a train set the model holds on the chip, a new host ``Table`` of
queries for every call, each call ended by reading ``prediction`` back
to the host. A closed loop: the next table goes in when the last one's
labels are out, as a batch scorer that labels incoming tables against a
labelled archive does.

Set-up makes the train table (``datagen_mnist``, from the seed),
``Knn().set_k(k).fit(table)``, and ``warmup_calls`` calls: the first one
places the model data on the chip, and the window may upload none of it
again. The configuration's file gives ``dim``, ``k``, ``train_rows`` and
``query_rows``; the cell's file ``tables`` (the query tables used in
rotation, each of its own streams), ``warmup_calls``, the sample and
``limits``.

``correct`` is decided from the LAST timed call's own ``prediction``, on
a sample of its queries: ``sample_near_cut`` of them the queries nearest
the cut between the generator's twin classes (their neighbourhoods hold
both classes, so a neighbour that changes places can change the vote;
a uniform sample would hold some twenty such queries and a control that
computes its products in one bfloat16 pass would slip through) and
``sample_others`` seeded others. Each is ranked against ALL resident
rows by ``reference/knn.py`` (float64; a shortlist by ``dgemm``, then
direct sums). The prediction must equal the reference's vote on every
query the reference calls stable at the tolerance :func:`tolerance`
gives. The vote bites only where a neighbourhood is mixed, so the
neighbours themselves are held to the reference too: after the window
the program's own search (``models.knn.nearest``, at the model's
precision, over the rows the model holds on the chip) ranks the sampled
queries, and its ROWS must be the reference's, as a set on every stable
query and index for index on every query whose order is decided. The
model data's upload count must not move, and every prediction must be a
label of the train set.

A program whose ``models.knn`` has no ``nearest`` (the parent of PR 30,
whose ``KnnModel`` uploads the train set in every call) stops at
set-up's import, before any data is made.
"""

from __future__ import annotations

import functools
import json
import time
import types

import numpy as np

from benchmark import datagen, datagen_mnist
from benchmark.reference import knn as reference


def tolerance(query_sq: np.ndarray, rows_sq: np.ndarray) -> np.ndarray:
    """What float32 arithmetic may move a squared distance by, a query:
    ``8 * 2**-24 * (|q| + max |x|)**2``. The program forms ``|q|^2 - 2
    q.x + |x|^2`` in float32: the two norms (sums of 784 squares, each
    good to a few units in the last place of a number up to ``|.|^2``),
    the product at float32 accuracy (the same of ``|q||x|``), two
    additions; ``(|q| + |x|)^2`` bounds the sum of those magnitudes and 8
    units in the last place their count. Read on the chip: PERF.md §2."""
    return 8.0 * 2.0 ** -24 * (np.sqrt(query_sq) + np.sqrt(rows_sq.max(axis=1))) ** 2


def sample_tolerance(queries: np.ndarray, neighbours: np.ndarray) -> np.ndarray:
    """:func:`tolerance` of each query ([q, d]) against its own
    neighbours ([q, r, d]), lengths in float64."""
    q = queries.astype(np.float64)
    x = neighbours.astype(np.float64)
    return tolerance(np.einsum("qd,qd->q", q, q), np.einsum("qrd,qrd->qr", x, x))


def _call(state, j):
    """One unit: a new Table over query table ``j``'s host rows, the
    search, ``prediction`` read back."""
    from flinkml_tpu.table import Table

    lo = (j % state.tables) * state.query_rows
    (out,) = state.model.transform(
        Table({"features": state.queries[lo:lo + state.query_rows]}))
    return np.asarray(out.column("prediction"))


def setup(ctx):
    from flinkml_tpu.models import Knn
    from flinkml_tpu.models.knn import nearest  # noqa: F401 — see the docstring
    from flinkml_tpu.table import Table

    s = types.SimpleNamespace()
    s.k = int(ctx.config["k"])
    s.train_rows, s.query_rows = int(ctx.size("train_rows")), int(ctx.size("query_rows"))
    s.tables = int(ctx.cell["tables"])
    prof = datagen_mnist.profile(ctx.seed)
    t0 = time.perf_counter()
    s.x, s.y, _ = datagen_mnist.images(
        ctx.seed, datagen_mnist.TAG_TRAIN, s.train_rows, prof)
    s.queries, _, s.margins = datagen_mnist.images(
        ctx.seed, datagen_mnist.TAG_QUERIES, s.tables * s.query_rows, prof)
    print(json.dumps({"phase": "data", "seconds": time.perf_counter() - t0}),
          flush=True)
    s.model = Knn().set_k(s.k).fit(Table({"features": s.x, "label": s.y}))
    for j in range(int(ctx.cell["warmup_calls"])):
        _call(s, j)
    return s


def window(ctx, s):
    walls = []
    t_open = time.perf_counter()
    j = 0
    while True:
        t0 = time.perf_counter()
        with ctx.unit("transform-call", calls=1, rows=s.query_rows):
            pred = _call(s, j)
        now = time.perf_counter()
        walls.append(now - t0)
        j += 1
        if now - t_open >= ctx.seconds:
            break
    s.last_pred, s.last_table = pred, (j - 1) % s.tables
    return {"work": j * s.query_rows, "wall_s": now - t_open, "attempted": j,
            "failed": 0, "unit_walls_s": walls}


def sample(ctx, s, table: int) -> np.ndarray:
    """Row numbers, within query table ``table``, of the checked queries:
    the ``sample_near_cut`` of smallest margin and ``sample_others``
    seeded others."""
    lo = table * s.query_rows
    by_margin = np.argsort(s.margins[lo:lo + s.query_rows], kind="stable")
    near = by_margin[:int(ctx.cell["sample_near_cut"])]
    rest = by_margin[near.size:]
    others = rest[datagen.sample_rows(
        ctx.seed, rest.size, int(ctx.cell["sample_others"]), table)]
    return np.sort(np.concatenate([near, others]))


def searched(s, q: np.ndarray, precision=None):
    """``(d2, rows)``, both ``[q, k]``: the program's search over the
    rows the model holds on the chip (placed long since: nothing is
    uploaded), at the model's precision unless a control gives another."""
    import jax

    from flinkml_tpu.models import knn as program

    resident = s.model._on_device()
    search = jax.jit(functools.partial(
        program.nearest, k=s.k, chunk=-(-q.shape[0] // 8) * 8,
        tile=program._tile_rows(s.train_rows, s.k),
        precision=program.PRODUCT_PRECISION if precision is None else precision))
    d2, rows = search(jax.numpy.asarray(q), resident.features, resident.norms)
    return np.asarray(d2), np.asarray(rows)


def compare(ctx, s, table: int, pred: np.ndarray, precision=None) -> dict:
    """The sampled queries of ``table`` against the float64 reference
    over all resident rows: ``pred`` their call's predictions, and the
    rows :func:`searched` finds for them."""
    rows = sample(ctx, s, table)
    q = s.queries[table * s.query_rows + rows]
    near, d2 = reference.k_nearest(q, s.x, s.k, shortlist=int(ctx.cell["shortlist"]))
    want = reference.vote(s.y, near, s.k)
    tol = sample_tolerance(q, s.x[near])
    unstable = reference.unstable(d2, s.k, tol)
    unordered = reference.unordered(d2, s.k, tol)
    _, got = searched(s, q, precision)
    other_set = (np.sort(got, axis=1) != np.sort(near[:, :s.k], axis=1)).any(axis=1)
    other_order = (got != near[:, :s.k]).any(axis=1)
    labels = s.y[near[:, :s.k]]
    return {
        "sampled": int(rows.size),
        "mismatch_stable": int(np.sum((pred[rows] != want) & ~unstable)),
        "mismatch_all": int(np.sum(pred[rows] != want)),
        "rows_mismatch": int(np.sum((other_set & ~unstable) | (other_order & ~unordered))),
        "rows_mismatch_all": int(np.sum(other_order)),
        "unstable_share": float(unstable.mean()),
        "unordered_share": float(unordered.mean()),
        "mixed_share": float((labels != labels[:, :1]).any(axis=1).mean()),
        "nearest_d2_median": float(np.median(d2[:, 0])),
        "gap_median": float(np.median(d2[:, -1] - d2[:, -2])),
        "tolerance_median": float(np.median(tol)),
    }


def check(ctx, s, result, counters):
    t0 = time.perf_counter()
    cmp = compare(ctx, s, s.last_table, s.last_pred)
    print(json.dumps({"phase": "reference", "seconds": time.perf_counter() - t0,
                      **cmp}), flush=True)
    return verdicts(ctx, s, cmp, s.last_pred, counters)


def verdicts(ctx, s, cmp: dict, pred: np.ndarray, counters: dict) -> list:
    """The cell's own checks of one call's ``pred`` and :func:`compare`'s
    counts for it, each a value beside its limit."""
    limits = ctx.size("limits")
    strange = int(np.sum(~np.isfinite(pred) | ~np.isin(pred, np.unique(s.y))))
    return [
        {"what": f"predictions of the last call that differ from the float64 "
                 f"reference's vote over all {s.train_rows} rows, on the queries it "
                 f"calls stable ({cmp['sampled']} sampled; {cmp['mismatch_all']} "
                 "differ counting the unstable)",
         "value": cmp["mismatch_stable"], "limit": limits["vote_mismatch_stable"]},
        {"what": "sampled queries whose neighbour ROWS, searched on the resident "
                 "model, are not the reference's: as a set where it calls the query "
                 "stable, index for index where it calls the order decided (all but "
                 f"{cmp['unordered_share']:.4f}; {cmp['rows_mismatch_all']} differ "
                 "counting the undecided)",
         "value": cmp["rows_mismatch"], "limit": limits["rows_mismatch"]},
        {"what": "sampled queries the reference calls unstable (fifth and sixth "
                 f"neighbour within twice the float32 tolerance, median "
                 f"{cmp['tolerance_median']:.3g}; their gap's median {cmp['gap_median']:.3g})",
         "value": cmp["unstable_share"], "limit": limits["unstable_share"]},
        {"what": "sampled queries with a mixed neighbourhood, short of the share the "
                 f"comparison needs to bite (found {cmp['mixed_share']:.4f})",
         "value": max(0.0, limits["mixed_share_short_of"] - cmp["mixed_share"]),
         "limit": 0.0},
        {"what": "model data bytes uploaded inside the window (knn.model_h2d_bytes)",
         "value": counters.get("knn.model_h2d_bytes"), "limit": 0},
        {"what": f"predictions of the last call ({pred.size}) that are not finite or "
                 "not a label of the train set",
         "value": strange + abs(pred.size - s.query_rows), "limit": 0},
    ]
