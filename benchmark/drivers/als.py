"""``driver: als`` — whole ``ALS(rank, maxIter 1).fit(Table)`` calls, back
to back, on ONE host ``Table`` of (user, item, rating) rows
(``datagen_ratings``: the source's counts, synthesised skews) whose two
orders set-up's first fit placed on the chip: ALS-WR's regularisation
swept over the month's ratings before the full run. The cell's ``sweep``
lists the ``regParam`` values, taken in turn; both factor tables are read
back every fit. A closed loop: a new fit starts while the window is open
and the one in flight always finishes.

Set-up makes the table and fits each value once: the first fit ingests
and places the table and warms the two programs (``regParam`` is an
operand of them), and the window may upload nothing of the table again.
The configuration's file gives ``users``, ``items``, ``ratings``,
``rank`` and ``max_iter``; the cell's file ``sweep`` and ``limits``.

``correct`` is decided after the window, on what the timed fits
themselves returned. The fit is ONE iteration, so its whole output can be
followed: for 512 sampled users (the 64 with most ratings, the 64 with
fewest, 384 by the seed) ``reference/als.py`` solves each one's system in
float64 over ALL its ratings from the program's own start item factors,
against the LAST timed fit's user rows; for 512 items sampled the same
way it solves from that fit's OWN returned user factors, against its item
rows. The number is the widest ``|x - x_ref|_inf / |x_ref|_inf`` of a
side. Every timed fit equal to set-up's fit of its value to the bit; the
table's upload counter unmoved; two half-steps a fit counted; shapes and
finiteness. The train RMSE over a million sampled ratings, before and
after, is printed and decides nothing.

A program whose ``models.als`` states no ``GRAM_PRECISION`` (the parent of
PR 38, whose fit scatters ``[ratings, k, k]`` outer products into ``[users,
k, k]``: 40 GB) stops at set-up's import, before any data is made.

``flops_bytes_als.half_step`` is the roofline's count and
``tests/chip_controls_als.py`` the one-bfloat16-pass control, for a
builder on the chip. Rehearse the cell on a CPU (6,000 users, 4,000
items, 200,000 ratings, rank 100; a minute)::

    JAX_PLATFORMS=cpu python benchmark/run.py --workload als-yahoomusic.fit \
        --seed 2147493104 --seconds 1 --trace 1 --rehearse
"""

from __future__ import annotations

import json
import time
import types

import numpy as np

from benchmark import datagen, datagen_ratings
from benchmark.drivers import program
from benchmark.reference import als as reference

# Stream tag (datagen.py holds 1-4, the other generators 11-45).
TAG_CHECK = 51
SAMPLE_ENDS, SAMPLE_SEEDED = 64, 384


def _fit(s, reg: float, precision=None):
    """One unit: a whole fit on the one table at ``regParam`` ``reg``,
    both factor tables read back: ``(users [users, rank], items [items,
    rank])`` float32, as the chip returned them. ``precision`` is a
    control's (None: the program's own)."""
    from flinkml_tpu.models import ALS, _als_blocked

    est = (ALS().set_rank(s.rank).set_max_iter(s.max_iter)
           .set_reg_param(float(reg)).set_seed(s.seed))
    if precision is not None:
        _, user_f, _, item_f = _als_blocked.fit_table(est, s.table, precision=precision)
        return user_f, item_f
    return est.fit(s.table).factors()


def setup(ctx):
    from flinkml_tpu.models.als import GRAM_PRECISION  # noqa: F401 — see the docstring
    from flinkml_tpu.table import Table

    s = types.SimpleNamespace()
    s.users, s.items = int(ctx.size("users")), int(ctx.size("items"))
    s.ratings, s.rank = int(ctx.size("ratings")), int(ctx.config["rank"])
    s.max_iter = int(ctx.size("max_iter"))
    s.seed = ctx.seed % (1 << 31)
    s.sweep = [float(v) for v in ctx.cell["sweep"]]
    t0 = time.perf_counter()
    s.user, s.item, s.rating = datagen_ratings.rating_table(
        ctx.seed, s.users, s.items, s.ratings)
    print(json.dumps({"phase": "data", "seconds": time.perf_counter() - t0}),
          flush=True)
    s.table = Table({"user": s.user, "item": s.item, "rating": s.rating})
    # Each value's fit once: the first ingests and places the table and
    # warms both programs (the window's zero-compile count checks that it
    # did), and each is what every timed fit of its value has to equal.
    s.first = []
    for reg in s.sweep:
        t0 = time.perf_counter()
        s.first.append(_fit(s, reg))
        print(json.dumps({"phase": "warm-fit", "reg": reg,
                          "seconds": time.perf_counter() - t0}), flush=True)
    spans = program.counters().get("span", {}).get("counters", {})
    print(json.dumps({"phase": "set-up's spans", **{
        name: spans.get(f"{name}.seconds") for name in (
            "als.ingest", "als.table_to_device", "als.init", "als.loop",
            "als.readback")}}), flush=True)
    return s


def window(ctx, s):
    walls, s.timed = [], []
    half_steps = 2 * s.max_iter
    t_open = time.perf_counter()
    while True:
        which = len(walls) % len(s.sweep)
        t0 = time.perf_counter()
        with ctx.unit("fit", fits=1, half_steps=half_steps,
                      samples=half_steps * s.ratings):
            s.timed.append((which, _fit(s, s.sweep[which])))
        now = time.perf_counter()
        walls.append(now - t0)
        if now - t_open >= ctx.seconds:
            break
    return {"work": len(walls) * half_steps * s.ratings, "wall_s": now - t_open,
            "attempted": len(walls), "failed": 0, "unit_walls_s": walls}


def sample(seed: int, degrees: np.ndarray, tag: int) -> np.ndarray:
    """The targets a side is checked on: the ``SAMPLE_ENDS`` with most
    ratings, the ``SAMPLE_ENDS`` with fewest, ``SAMPLE_SEEDED`` by the
    seed; distinct, sorted."""
    by_degree = np.argsort(degrees, kind="stable")
    ends = np.concatenate([by_degree[:SAMPLE_ENDS], by_degree[-SAMPLE_ENDS:]])
    seeded = datagen.rng(seed, TAG_CHECK, tag).choice(
        degrees.size, size=min(SAMPLE_SEEDED, degrees.size), replace=False)
    return np.unique(np.concatenate([ends, seeded]))


def rows_of(target: np.ndarray, other: np.ndarray, rating: np.ndarray,
            wanted: np.ndarray):
    """For each target of ``wanted`` (sorted), ALL its ratings in the
    table: ``(other's position [n], rating [n])``."""
    listed = np.zeros(int(wanted[-1]) + 2, bool)
    listed[wanted] = True
    at = np.flatnonzero(listed[np.minimum(target, wanted[-1] + 1)])
    order = at[np.argsort(target[at], kind="stable")]
    bounds = np.searchsorted(target[order], np.append(wanted, wanted[-1] + 1))
    return [(other[order[lo:hi]], rating[order[lo:hi]])
            for lo, hi in zip(bounds[:-1], bounds[1:])]


def widest_gap(got: np.ndarray, want: np.ndarray) -> float:
    """The widest ``|x - x_ref|_inf / |x_ref|_inf`` over the rows."""
    scale = np.abs(want).max(axis=1)
    return float((np.abs(got - want).max(axis=1) / scale).max())


def compare(s, reg: float, fit) -> dict:
    """What a fit at ``regParam`` ``reg`` returned against the float64
    solves of the sampled users (from the program's start item factors)
    and items (from the fit's own user factors). One iteration."""
    from flinkml_tpu.models.als import start_factors

    user_f, item_f = fit
    out = {"reg": reg}
    if (user_f.shape != (s.users, s.rank) or item_f.shape != (s.items, s.rank)
            or not (np.isfinite(user_f).all() and np.isfinite(item_f).all())):
        return {**out, "user_gap": None, "item_gap": None}
    start = np.asarray(start_factors(s.seed, s.items, s.rank))
    users = sample(s.seed, np.bincount(s.user, minlength=s.users), 0)
    items = sample(s.seed, np.bincount(s.item, minlength=s.items), 1)
    of_items = rows_of(s.item, s.user, s.rating, items)
    want_users = reference.solve_targets(
        rows_of(s.user, s.item, s.rating, users), start, reg)
    want_items = reference.solve_targets(of_items, user_f, reg)
    at = datagen.rng(s.seed, TAG_CHECK, 2).integers(0, s.ratings, 1_000_000)
    u, i, r = s.user[at], s.item[at], s.rating[at].astype(np.float64)
    return {**out,
            "user_gap": widest_gap(user_f[users], want_users),
            "item_gap": widest_gap(item_f[items], want_items),
            "users_checked": int(users.size), "items_checked": int(items.size),
            "ratings_of_checked_items": int(sum(r.size for _, r in of_items)),
            # The start has no user factors: the mean rating's error.
            "train_rmse_before": float(np.sqrt(np.mean((r - r.mean()) ** 2))),
            "train_rmse_after": reference.rmse(u, i, r, user_f, item_f)}


def check(ctx, s, result, counters):
    if s.max_iter != 1:
        raise ValueError("the cell's comparison follows ONE iteration")
    t0 = time.perf_counter()
    which, last = s.timed[-1]
    cmp = compare(s, s.sweep[which], last)
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
    strange = sum(1 for _, (user_f, item_f) in s.timed
                  if user_f.shape != (s.users, s.rank)
                  or item_f.shape != (s.items, s.rank)
                  or not (np.isfinite(user_f).all() and np.isfinite(item_f).all()))
    halves, counted = counters.get("als.half_steps"), counters.get("als.fits", 0)
    of = (f"last timed fit (regParam {cmp['reg']}; {s.ratings} ratings of "
          f"{s.users} users and {s.items} items, rank {s.rank}, one iteration)")
    return [
        {"what": f"{of}: widest |x - x_ref|_inf / |x_ref|_inf over "
                 f"{cmp.get('users_checked')} sampled users, each solved in float64 "
                 "over ALL its ratings from the program's own start item factors",
         "value": cmp["user_gap"], "limit": limits["factor_gap"]},
        {"what": f"the same fit: that gap over {cmp.get('items_checked')} sampled "
                 f"items ({cmp.get('ratings_of_checked_items')} ratings), each solved "
                 "in float64 from the fit's own returned user factors",
         "value": cmp["item_gap"], "limit": limits["factor_gap"]},
        {"what": f"timed fits ({fits}) that differ in any bit from set-up's fit of "
                 "the same regParam",
         "value": apart, "limit": 0},
        {"what": "table bytes uploaded inside the window (als.table_h2d_bytes)",
         "value": counters.get("als.table_h2d_bytes"), "limit": 0},
        {"what": f"timed fits ({fits}) whose factors are not [{s.users}, {s.rank}] "
                 f"and [{s.items}, {s.rank}] or not finite",
         "value": strange, "limit": 0},
        {"what": f"half-steps the program counted, off {2 * s.max_iter} a timed fit "
                 f"(als.half_steps {halves}, als.fits {counted})",
         "value": None if halves is None else
         abs(halves - 2 * s.max_iter * fits) + abs(counted - fits),
         "limit": 0},
    ]
