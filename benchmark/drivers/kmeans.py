"""``driver: kmeans`` — whole ``KMeans().fit(Table)`` calls, back to back,
on ONE host ``Table`` whose features column the first fit placed on the
chip: Lloyd restarted from another seed on the same archive, the
centroids read back every fit (an analyst's ``n_init`` restarts, the best
objective kept). A closed loop: a new fit starts while the window is
open and the one in flight always finishes.

Set-up makes the table (``datagen_mnist``'s rows from the seed; the
labels are not used) and fits each of the cell's ``seeds`` seeds once
(``(--seed + i) mod 2**31``): the first fit places the column and warms
the one program, and the window may upload nothing of the table again.
The configuration's file gives ``dim``, ``k``, ``max_iter``,
``init_mode`` and ``train_rows``; the cell's file ``seeds`` and
``limits``.

``correct`` is decided after the window, on what the LAST timed fit
itself returned: its ``[k, dim]`` centroids against
``reference/kmeans.py`` (NumPy float64) after the same rounds from the
same start rows, by the widest coordinate gap. Twenty rounds carry a
rounding forward, and on a seed whose clusters are still moving they
enlarge it (PERF.md §2), so that limit has room; the ARITHMETIC is held
to a tighter one over a single round, which nothing enlarges: after the
window the program's own trainer runs ONE round over the rows the table
holds on the chip, from the reference's centroids before its last round,
and must land on the reference's round from those same float32 numbers.
The relative gap of the float64 within-cluster sum of squares at the two
sets of centroids is printed and decides nothing (it does not tell the
program from its one-pass control: PERF.md §2). Every timed fit must
equal set-up's fit of its seed to the bit; the table's upload counter
must not move; the rounds the program counted must be ``max_iter`` a fit.

A program whose ``models.kmeans`` states no ``PRODUCT_PRECISION`` (the
parent of PR 32, whose fit widens the column to float64 on the host,
sends it in one ``device_put`` every fit and rounds both products to
bfloat16) stops at set-up's import, before any data is made.
"""

from __future__ import annotations

import json
import time
import types

import numpy as np

from benchmark import datagen_mnist
from benchmark.reference import kmeans as reference


def tolerance(rows_sq: np.ndarray, centroids: np.ndarray) -> np.ndarray:
    """What float32 arithmetic may move a row's squared distance to a
    centroid by: ``8 * 2**-24 * (|x| + max |c|)**2`` (``drivers/knn.py``'s
    bound, for the same expansion). The reference counts the rows whose
    two nearest centroids lie within twice this: rows a sound program
    may assign either way, printed beside the gaps they explain."""
    c_sq = np.einsum("kd,kd->k", centroids, centroids).max()
    return 8.0 * 2.0 ** -24 * (np.sqrt(rows_sq) + np.sqrt(c_sq)) ** 2


def _fit(s, seed: int) -> np.ndarray:
    """One unit: a whole fit on the one table, its centroids read back."""
    from flinkml_tpu.models import KMeans

    model = (KMeans().set_k(s.k).set_max_iter(s.max_iter)
             .set_init_mode(s.init_mode).set_seed(seed).fit(s.table))
    return np.asarray(model.centroids)


def setup(ctx):
    from flinkml_tpu.models.kmeans import PRODUCT_PRECISION  # noqa: F401 — see the docstring
    from flinkml_tpu.table import Table

    s = types.SimpleNamespace()
    s.k, s.max_iter = int(ctx.config["k"]), int(ctx.config["max_iter"])
    s.init_mode = ctx.config["init_mode"]
    s.rows = int(ctx.size("train_rows"))
    s.seeds = [(ctx.seed + i) % (1 << 31) for i in range(int(ctx.cell["seeds"]))]
    t0 = time.perf_counter()
    s.x, _, _ = datagen_mnist.images(ctx.seed, datagen_mnist.TAG_TRAIN, s.rows)
    print(json.dumps({"phase": "data", "seconds": time.perf_counter() - t0}),
          flush=True)
    s.table = Table({"features": s.x})
    # Each seed's fit once: the first places the column and warms the
    # one program (the window's zero-compile count checks that it did),
    # and each is what every timed fit of its seed has to equal.
    s.first = {}
    for seed in s.seeds:
        t0 = time.perf_counter()
        s.first[seed] = _fit(s, seed)
        print(json.dumps({"phase": "warm-fit", "seed": seed,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return s


def window(ctx, s):
    walls, s.timed = [], []
    t_open = time.perf_counter()
    while True:
        seed = s.seeds[len(walls) % len(s.seeds)]
        t0 = time.perf_counter()
        with ctx.unit("fit", fits=1, rounds=s.max_iter,
                      samples=s.max_iter * s.rows):
            s.timed.append((seed, _fit(s, seed)))
        now = time.perf_counter()
        walls.append(now - t0)
        if now - t_open >= ctx.seconds:
            break
    return {"work": len(walls) * s.max_iter * s.rows, "wall_s": now - t_open,
            "attempted": len(walls), "failed": 0, "unit_walls_s": walls}


def reference_fit(s, seed: int) -> dict:
    """Float64 Lloyd after the cell's rounds from the start rows of
    ``seed``: what a fit of that seed is held to. ``before_last`` is its
    centroids before the last round, rounded to the rows' float32 (what
    the program can be handed), and ``last_round`` the reference's round
    from exactly those numbers."""
    start = s.x[reference.start_rows(seed, s.rows, s.k)]
    buffers = reference.scratch(s.x)
    before, _, close = reference.lloyd(s.x, start, s.max_iter - 1, tolerance, buffers)
    want, counts, n_close = reference.lloyd_round(s.x, before, tolerance, buffers)
    handed = before.astype(s.x.dtype)
    return {"seed": seed, "centroids": want, "counts": counts,
            "close": close + [n_close], "buffers": buffers,
            "cost": reference.cost(s.x, want, buffers), "before_last": handed,
            "last_round": reference.lloyd_round(s.x, handed, None, buffers)[0],
            "moved": float(np.abs(want - start).max())}


def one_round(s, centroids: np.ndarray, precision=None) -> np.ndarray:
    """The program's own trainer, ONE round from ``centroids`` over the
    rows the table holds on the chip (placed long since: nothing is
    uploaded), at the program's precision unless a control gives another."""
    from flinkml_tpu.models import kmeans as program
    from flinkml_tpu.parallel import DeviceMesh

    mesh = DeviceMesh()
    placed = program._rows_on_mesh(s.table, "features", s.x, mesh)
    return program._lloyd(
        placed, centroids, mesh, s.k, 1,
        precision=program.PRODUCT_PRECISION if precision is None else precision)


def compare(s, ref: dict, centroids: np.ndarray, precision=None) -> dict:
    """``centroids``, what a fit of ``ref``'s seed returned, against
    :func:`reference_fit`; and :func:`one_round` against its last round."""
    want, got = ref["centroids"], np.asarray(centroids, np.float64)
    out = {"seed": ref["seed"], "cost_reference": ref["cost"],
           "counts_min": float(ref["counts"].min()),
           "counts_max": float(ref["counts"].max()),
           "close_rows_a_round_max": int(max(ref["close"])),
           "close_rows_all_rounds": int(sum(ref["close"])),
           "moved_by_the_fit": ref["moved"],
           "moved_by_the_last_round": float(np.abs(
               ref["last_round"] - ref["before_last"]).max()),
           "round_gap": float(np.abs(
               one_round(s, ref["before_last"], precision) - ref["last_round"]).max())}
    if got.shape != want.shape or not np.isfinite(got).all():
        return {**out, "gap": None, "cost_gap": None}
    return {**out, "gap": float(np.abs(got - want).max()),
            "cost_gap": abs(reference.cost(s.x, got, ref["buffers"]) - ref["cost"])
            / ref["cost"]}


def check(ctx, s, result, counters):
    t0 = time.perf_counter()
    seed, last = s.timed[-1]
    cmp = compare(s, reference_fit(s, seed), last)
    print(json.dumps({"phase": "reference", "seconds": time.perf_counter() - t0,
                      **cmp}), flush=True)
    return verdicts(ctx, s, cmp, counters)


def verdicts(ctx, s, cmp: dict, counters: dict) -> list:
    """The cell's own checks of one fit's :func:`compare` and of the
    window's fits and counters, each a value beside its limit."""
    limits = ctx.size("limits")
    fits = len(s.timed)
    apart = sum(1 for seed, c in s.timed
                if c.shape != s.first[seed].shape
                or c.tobytes() != s.first[seed].tobytes())
    strange = sum(1 for _, c in s.timed
                  if c.shape != (s.k, s.x.shape[1]) or not np.isfinite(c).all())
    rounds = counters.get("kmeans.rounds")
    return [
        {"what": f"last timed fit (seed {cmp['seed']}, {s.rows} rows, k {s.k}, "
                 f"{s.max_iter} rounds): widest centroid coordinate gap to float64 "
                 "Lloyd from the same start rows (the fit moved a coordinate by up "
                 f"to {cmp['moved_by_the_fit']:.3f}; clusters hold "
                 f"{cmp['counts_min']:.0f}-{cmp['counts_max']:.0f} rows; "
                 f"{cmp['close_rows_all_rounds']} rows over the rounds, at most "
                 f"{cmp['close_rows_a_round_max']} a round, have a runner-up inside "
                 "twice the float32 tolerance)",
         "value": cmp["gap"], "limit": limits["centroid_gap"]},
        {"what": "ONE round of the program's trainer over the resident rows, from the "
                 "reference's centroids before its last round: widest coordinate gap "
                 "to the reference's round from the same numbers (which moves a "
                 f"coordinate by up to {cmp['moved_by_the_last_round']:.2g}; the fit's "
                 "within-cluster sum of squares is off the reference's "
                 f"{cmp['cost_reference']:.6g} by {cmp['cost_gap']} of it, which decides "
                 "nothing)",
         "value": cmp["round_gap"], "limit": limits["round_gap"]},
        {"what": f"timed fits ({fits}) that differ in any bit from set-up's fit of "
                 "the same seed",
         "value": apart, "limit": 0},
        {"what": "table bytes uploaded inside the window (kmeans.table_h2d_bytes)",
         "value": counters.get("kmeans.table_h2d_bytes"), "limit": 0},
        {"what": f"timed fits ({fits}) whose centroids are not [{s.k}, "
                 f"{s.x.shape[1]}] or not finite",
         "value": strange, "limit": 0},
        {"what": f"rounds the program counted, off {s.max_iter} a timed fit "
                 f"(kmeans.rounds {rounds}, kmeans.fits {counters.get('kmeans.fits')})",
         "value": None if rounds is None else
         abs(rounds - s.max_iter * fits) + abs(counters.get("kmeans.fits", 0) - fits),
         "limit": 0},
    ]
