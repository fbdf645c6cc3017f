"""``driver: gbt`` — whole ``GBTClassifier(numTrees, maxDepth, maxBins,
regLambda, subsample, learningRate).fit(Table)`` calls, back to back, on
ONE host ``Table`` of ``rows x features`` float32 (``datagen_airline``: the
source's columns and cardinalities, the rows synthesised) whose binned
table set-up's first fit placed on the chip: the learning rate of a team's
boosted trees swept over the table before the full run. The cell's
``sweep`` lists the rates, taken in turn from the one the seed names (a
window holds two fits, so the seed decides which rate the last one, the
one ``correct`` follows, has); the forest (a tree's 63 splits, 63 gains
and 64 leaves at depth 6) is read back every fit. A closed loop: a new
fit starts while the window is open and the one in flight always
finishes.

Set-up makes the table and fits each rate once: the first fit takes the
bin edges over its seeded row sample, bins the table one byte a cell,
places it and warms the one program (rate, ``regLambda``, ``subsample``,
base score and the sampling key are operands of it), and the window may
upload nothing of the table again. The configuration's file gives
``rows``, ``features``, ``max_bins``, ``max_depth``, ``reg_lambda``,
``subsample``, ``num_trees`` and ``bin_sample_rows``; the cell's file
``sweep`` and ``limits``.

``correct`` is decided after the window, on what the LAST timed fit
returned. ``reference/gbt.py`` is handed the table, the labels, the seed
and the forest. It takes the bin edges and the base score ITSELF
(``edges_of``: the configuration's quantile rule over its own draw of the
seeded sample; ``base_of``: the training log-odds), bins and starts by
them, and the program's are held to them: ``gbt.bin_edges`` (the
program's public function of (table, seed)) equal entry for entry, every
threshold of the forest one of the reference's edges of its feature, the
model's base score within a float64 rounding. Then it **follows the
program's own trees** in float64 over all rows, so that a near-tie
between two splits can never fail a sound fit. Three numbers, each
beside its limit (the cell's ``limits_from``): ``leaf_gap``, the
widest ``|leaf - leaf_ref|`` over all the leaves in units of the widest
``|leaf_ref|``; ``gain_gap``, the widest ``|gain - gain_ref|`` over the
inner nodes, the program's reported gain against the float64 gain of its
own split, in units of its tree's root gain; ``split_regret``, the widest
(best float64 gain of the node less the float64 gain of the program's
split) in the same units. Exactly: every timed fit equal to set-up's fit
of its rate to the bit; the table's upload counter unmoved; trees, levels
and (on a TPU) product levels as counted; splits and leaves in range and
finite. The reference's mean logistic loss before the first tree and
after the last is printed and decides nothing.

A program without ``gbt.bin_edges`` (the parent of PR 47, whose fit
widens the column to float64 and sorts every feature) stops at this
module's import, before any data is made.

``flops_bytes_gbt.level`` is the roofline's count and
``tests/chip_controls_gbt.py`` the one-bfloat16-part control and the
planted wrong splits, for a builder on the chip. Rehearse the cell on a CPU (20,000 rows; 13
features, 256 bins and depth 6 kept; half a minute)::

    JAX_PLATFORMS=cpu python benchmark/run.py --workload gbt-airline.fit \
        --seed 2147493104 --seconds 1 --trace 1 --rehearse
"""

from __future__ import annotations

import json
import os
import time
import types

import numpy as np

from benchmark import datagen_airline
from benchmark.drivers import program
from benchmark.reference import gbt as reference
from flinkml_tpu.models.gbt import bin_edges  # see the docstring
from flinkml_tpu.table import Table

FEATURES, LABEL = "features", "label"
#: Where the reference's children map the binned table from.
SCRATCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "out")


def estimator(s, rate: float):
    from flinkml_tpu.models import GBTClassifier

    return (GBTClassifier().set_features_col(FEATURES).set_label_col(LABEL)
            .set_num_trees(s.trees).set_max_depth(s.depth).set_max_bins(s.bins)
            .set_reg_lambda(s.lam).set_subsample(s.subsample)
            .set_learning_rate(float(rate)).set_seed(s.seed))


def one_part_fit(s, rate: float) -> dict:
    """The control's unit: a whole fit on the one table at ``rate`` with
    ``g`` and ``h`` rounded to ONE bfloat16 part before a tree's
    histograms (the program's own keeps three), the forest as the chip
    returned it."""
    from flinkml_tpu.models import _gbt_table
    from flinkml_tpu.models.gbt import _thresholds

    feats, cuts, gains, leaves, base, edges = _gbt_table.fit_table(
        estimator(s, rate), s.table, one_part=True)
    return {"rate": rate, "feats": feats, "gains": gains, "leaves": leaves,
            "thresholds": _thresholds(edges, feats, cuts), "base": base}


def _public_fit(s, rate: float) -> dict:
    """The same unit through ``Estimator.fit``: the model's arrays."""
    model = estimator(s, rate).fit(s.table)
    (data,) = model.get_model_data()
    return {"rate": rate, "feats": data.column("feat"),
            "thresholds": data.column("threshold"), "gains": data.column("gain"),
            "leaves": data.column("leaf"), "base": float(data.column("base")[0])}


def setup(ctx):
    s = types.SimpleNamespace()
    s.rows, s.features = int(ctx.size("rows")), int(ctx.config["features"])
    s.bins, s.depth = int(ctx.config["max_bins"]), int(ctx.config["max_depth"])
    s.lam, s.subsample = float(ctx.config["reg_lambda"]), float(ctx.config["subsample"])
    s.trees = int(ctx.size("num_trees"))
    s.sample_rows = int(ctx.config["bin_sample_rows"])
    s.seed = ctx.seed % (1 << 31)
    s.sweep = [float(v) for v in ctx.cell["sweep"]]
    t0 = time.perf_counter()
    s.x, s.y = datagen_airline.table(ctx.seed, s.rows)
    s.table = Table({FEATURES: s.x, LABEL: s.y})
    print(json.dumps({"phase": "data", "seconds": time.perf_counter() - t0,
                      "positive_share": float(s.y.mean())}), flush=True)
    # Each rate's fit once: the first bins and places the table and warms
    # the program (the window's zero-compile count checks that it did),
    # and each is what every timed fit of its rate has to equal.
    s.first = []
    for rate in s.sweep:
        t0 = time.perf_counter()
        s.first.append(_public_fit(s, rate))
        print(json.dumps({"phase": "warm-fit", "rate": rate,
                          "seconds": time.perf_counter() - t0}), flush=True)
    spans = program.counters().get("span", {}).get("counters", {})
    print(json.dumps({"phase": "set-up's spans", **{
        name: spans.get(f"{name}.seconds") for name in (
            "gbt.ingest", "gbt.table_to_device", "gbt.loop", "gbt.readback")}}),
        flush=True)
    return s


def window(ctx, s):
    walls, s.timed = [], []
    levels = s.trees * s.depth
    t_open = time.perf_counter()
    while True:
        which = (s.seed + len(walls)) % len(s.sweep)
        t0 = time.perf_counter()
        with ctx.unit("fit", fits=1, trees=s.trees, levels=levels,
                      samples=s.rows * s.trees):
            s.timed.append((which, _public_fit(s, s.sweep[which])))
        now = time.perf_counter()
        walls.append(now - t0)
        if now - t_open >= ctx.seconds:
            break
    return {"work": len(walls) * s.rows * s.trees, "wall_s": now - t_open,
            "attempted": len(walls), "failed": 0, "unit_walls_s": walls}


def cuts_of(edges: np.ndarray, feats: np.ndarray, thresholds: np.ndarray):
    """The bin ``b`` of every split ``bin <= b`` from the model's raw
    thresholds: where ``edges[f, b]`` is the threshold, the last bin for
    +inf (everything goes left); and how many thresholds are neither (the
    nearest edge above stands in for one)."""
    last = edges.shape[1]
    padded = np.concatenate([edges, np.full((edges.shape[0], 1), np.inf)], axis=1)
    cuts = np.array([[min(int(np.searchsorted(padded[f], t, side="left")), last)
                      for f, t in zip(fs, ts)] for fs, ts in zip(feats, thresholds)])
    return cuts, int(np.sum(padded[feats, cuts] != thresholds))


def compare(s, fit: dict) -> dict:
    """What a fit returned (``feats``, ``thresholds``, ``gains``,
    ``leaves``, ``base``, ``rate``) against the reference's own edges and
    base score and the float64 fit that follows its trees from them."""
    edges = reference.edges_of(s.x, s.bins, s.seed, s.sample_rows)
    base = reference.base_of(s.y)
    # The program's own sample size: the configuration's, or they differ.
    theirs = bin_edges(s.table, FEATURES, s.bins, s.seed)
    inner, leaves = (1 << s.depth) - 1, 1 << s.depth
    out = {"rate": fit["rate"], "leaf_gap": None, "gain_gap": None,
           "split_regret": None, "strangers": None,
           "real_edges": int(np.isfinite(edges).sum()),
           "edges_apart": int(np.sum(theirs != edges)) if theirs.shape == edges.shape
           else int(edges.size),
           "base_ref": base,
           "base_gap": float(abs(fit["base"] - base) / np.spacing(abs(base)))}
    feats, thresholds = np.asarray(fit["feats"]), np.asarray(fit["thresholds"])
    if (feats.shape != (s.trees, inner) or np.shape(fit["leaves"]) != (s.trees, leaves)
            or thresholds.shape != feats.shape or np.isnan(thresholds).any()
            or feats.min() < 0 or feats.max() >= s.features
            or not np.isfinite(fit["leaves"]).all()
            or not np.isfinite(fit["gains"]).all()):
        return out
    cuts, out["strangers"] = cuts_of(edges, feats, thresholds)
    ref = reference.follow(s.x, s.y, edges, feats, cuts, base=base,
                           rate=fit["rate"], lam=s.lam, n_bins=s.bins,
                           workers=s.workers, scratch=SCRATCH)
    root = ref["root_gain"][:, None]
    regret = (ref["best_gain"] - ref["split_gain"]) / root
    by_level = [regret[:, (1 << level) - 1:(2 << level) - 1] for level in range(s.depth)]
    return {**out, "root_gain": ref["root_gain"].tolist(),
            # Where the regret lies, and the quartiles over the nodes of the
            # last tree's last level (what ONE of chip_controls_gbt.py's
            # planted splits reads).
            "regret_by_level": [float(level.max()) for level in by_level],
            "last_level_regrets": [float(q) for q in np.quantile(
                by_level[-1][-1], [0.0, 0.25, 0.5, 0.75, 1.0])],
            "widest_leaf": float(np.abs(ref["leaves"]).max()),
            "loss_before": ref["loss_before"], "loss_after": ref["loss_after"],
            "leaf_gap": float(np.abs(fit["leaves"] - ref["leaves"]).max()
                              / np.abs(ref["leaves"]).max()),
            "gain_gap": float((np.abs(fit["gains"] - ref["split_gain"]) / root).max()),
            "split_regret": float(regret.max())}


def check(ctx, s, result, counters):
    t0 = time.perf_counter()
    s.workers = 0 if s.rows < (1 << 22) else reference.WORKERS
    os.makedirs(SCRATCH, exist_ok=True)
    which, last = s.timed[-1]
    cmp = compare(s, last)
    print(json.dumps({"phase": "reference", "seconds": time.perf_counter() - t0,
                      "workers": s.workers, **cmp}), flush=True)
    return verdicts(ctx, s, cmp, counters)


def verdicts(ctx, s, cmp: dict, counters: dict) -> list:
    """The cell's own checks of one fit's :func:`compare` and of the
    window's fits and counters, each a value beside its limit."""
    import jax

    limits = ctx.size("limits")
    fits = len(s.timed)
    inner, leaves = (1 << s.depth) - 1, 1 << s.depth
    keys = ("feats", "thresholds", "gains", "leaves")
    apart = sum(1 for which, fit in s.timed
                if any(not np.array_equal(fit[k], s.first[which][k]) for k in keys)
                or fit["base"] != s.first[which]["base"])
    strange = sum(1 for _, fit in s.timed
                  if np.shape(fit["feats"]) != (s.trees, inner)
                  or np.shape(fit["leaves"]) != (s.trees, leaves)
                  or fit["feats"].min() < 0 or fit["feats"].max() >= s.features
                  or not np.isfinite(fit["leaves"]).all())
    counted = counters.get("gbt.fits", 0)
    trees, levels = counters.get("gbt.trees"), counters.get("gbt.levels")
    product = counters.get("gbt.product_levels")
    of = (f"last timed fit (rate {cmp['rate']}; {s.rows} rows x {s.features}, "
          f"{s.bins} bins, {s.trees} trees of depth {s.depth})")
    rows = [
        {"what": f"entries of the program's bin edges (gbt.bin_edges at seed {s.seed}) "
                 f"off the reference's own [{s.features}, {s.bins - 1}] "
                 f"({cmp['real_edges']} real ones: the quantile rule in float64 over "
                 f"its own draw of {s.sample_rows} rows by default_rng(seed))",
         "value": cmp["edges_apart"], "limit": 0},
        {"what": f"{of}: thresholds of its {s.trees * inner} splits that are neither "
                 "one of the reference's edges of their feature nor +inf",
         "value": cmp["strangers"], "limit": 0},
        {"what": f"{of}: its base score off the reference's training log-odds "
                 f"({cmp['base_ref']}), in float64 spacings",
         "value": cmp["base_gap"], "limit": 4},
        {"what": f"{of}: leaf_gap, the widest |leaf - leaf_ref| over all "
                 f"{s.trees * leaves} leaves in units of the widest |leaf_ref| "
                 f"({cmp.get('widest_leaf')}); leaf_ref the float64 leaf values over "
                 "the program's own partition binned by the reference's edges, every "
                 "tree's g and h from the reference's own float64 prediction and "
                 "base score",
         "value": cmp["leaf_gap"], "limit": limits["leaf_gap"]},
        {"what": f"{of}: gain_gap, the widest |gain - gain_ref| over all "
                 f"{s.trees * inner} inner nodes, the program's reported gain against "
                 "the float64 gain of its own split, in units of its tree's root gain "
                 f"({cmp.get('root_gain')})",
         "value": cmp["gain_gap"], "limit": limits["gain_gap"]},
        {"what": f"{of}: split_regret, the widest (best float64 gain of the node "
                 "over every (feature, bin) less the float64 gain of the program's "
                 "split) in units of its tree's root gain",
         "value": cmp["split_regret"], "limit": limits["split_regret"]},
        {"what": f"timed fits ({fits}) that differ in any bit from set-up's fit of "
                 "the same rate",
         "value": apart, "limit": 0},
        {"what": "table bytes uploaded inside the window (gbt.table_h2d_bytes)",
         "value": counters.get("gbt.table_h2d_bytes"), "limit": 0},
        {"what": f"trees the program counted, off {s.trees} a timed fit "
                 f"(gbt.trees {trees}, gbt.fits {counted})",
         "value": None if trees is None else
         abs(trees - s.trees * counted) + abs(counted - fits),
         "limit": 0},
        {"what": f"levels the program counted, off {s.depth} a tree (gbt.levels {levels})",
         "value": None if levels is None or trees is None
         else abs(levels - s.depth * trees),
         "limit": 0},
        {"what": f"timed fits ({fits}) whose forest is not {s.trees} trees of {inner} "
                 f"splits on features in [0, {s.features}) and {leaves} finite leaves",
         "value": strange, "limit": 0},
    ]
    if jax.default_backend() == "tpu":
        rows.append({"what": "levels whose histograms were not the Mosaic product "
                             f"(gbt.levels {levels} less gbt.product_levels {product})",
                     "value": None if product is None or levels is None
                     else levels - product,
                     "limit": 0})
    return rows
