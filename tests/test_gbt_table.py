"""``GBTClassifier.fit(Table)`` on a dense column (PR 47,
``models/_gbt_table.py``): the binned table one byte a cell kept with the
``Table``, a level's histograms as one-hot products, the whole forest one
program. Held to ``benchmark/reference/gbt.py`` (NumPy float64, following
the program's own trees) on Airline-profile tables at the cell's 13
features, 256 bins and depth 6, to ``segment_sum``'s histograms level by
level, and to the parent's builder (``gbt._forest_builder``) forest for
forest.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import datagen_airline
from benchmark.reference import gbt as reference
from flinkml_tpu.kernels import gbt_hist
from flinkml_tpu.models import (
    GBTClassifier,
    GBTRegressor,
    RandomForestClassifier,
    RandomForestRegressor,
    _gbt_table,
)
from flinkml_tpu.models import gbt as gbt_module
from flinkml_tpu.parallel import DeviceMesh
from flinkml_tpu.table import Table
from flinkml_tpu.utils.metrics import metrics

ROWS, SEED = 6_000, 5
#: Float32's rounding of a level's sums reads 1e-6 to 2e-5 of the widest
#: leaf here, one bfloat16 part of g and h 2e-3 and more.
LEAF_TOL, GAIN_TOL = 2e-4, 2e-4


def _mesh(p=1):
    return DeviceMesh(devices=jax.devices()[:p])


def _airline(seed=11, rows=ROWS):
    x, y = datagen_airline.table(seed, rows)
    return x, y, Table({"features": x, "label": y})


def _estimator(rate=0.1, p=1, cls=GBTClassifier, trees=2, depth=6, bins=256, **knobs):
    return (cls(mesh=_mesh(p), **knobs).set_num_trees(trees).set_max_depth(depth)
            .set_max_bins(bins).set_learning_rate(rate).set_seed(SEED))


def _followed(x, y, fit, rate):
    """The gaps of what ``_gbt_table.fit_table`` returned to the float64
    fit that follows its trees from the reference's OWN edges and base
    score, which the program's have to equal."""
    feats, cuts, gains, leaves, base, edges = fit
    own = reference.edges_of(x, 256, SEED, _gbt_table.BIN_SAMPLE_ROWS)
    assert np.array_equal(edges, own)
    assert base == pytest.approx(reference.base_of(y), rel=1e-15)
    ref = reference.follow(x, y, own, feats, cuts, base=reference.base_of(y), rate=rate,
                           lam=1.0, n_bins=256, workers=0)
    root = ref["root_gain"][:, None]
    return {"leaf_gap": np.abs(leaves - ref["leaves"]).max() / np.abs(ref["leaves"]).max(),
            "gain_gap": (np.abs(gains - ref["split_gain"]) / root).max(),
            "split_regret": ((ref["best_gain"] - ref["split_gain"]) / root).max(),
            "loss": (ref["loss_before"], ref["loss_after"])}


@pytest.mark.parametrize("seed", [11, 3_000_000_019])
@pytest.mark.parametrize("rate", [0.1, 0.3])
def test_a_fit_follows_the_reference_at_both_rates(rate, seed):
    x, y, table = _airline(seed)
    got = _followed(x, y, _gbt_table.fit_table(_estimator(rate), table), rate)
    assert got["leaf_gap"] < LEAF_TOL and got["gain_gap"] < GAIN_TOL
    assert got["split_regret"] < GAIN_TOL
    assert got["loss"][1] < got["loss"][0] - 0.005     # two trees learn


@pytest.mark.parametrize("rate", [0.1, 0.3])
def test_one_bfloat16_part_of_g_and_h_fails_the_tolerance(rate):
    x, y, table = _airline()
    got = _followed(
        x, y, _gbt_table.fit_table(_estimator(rate), table, one_part=True), rate)
    assert got["leaf_gap"] > 5 * LEAF_TOL and got["gain_gap"] > 5 * GAIN_TOL


def test_the_model_holds_what_the_chip_returned_and_raw_thresholds():
    x, y, table = _airline()
    est = _estimator()
    feats, cuts, gains, leaves, base, edges = _gbt_table.fit_table(est, table)
    model = est.fit(table)
    (data,) = model.get_model_data()
    assert np.array_equal(data.column("feat"), feats)
    assert np.array_equal(data.column("leaf"), leaves.astype(np.float64))
    assert np.array_equal(data.column("gain"), gains.astype(np.float64))
    padded = np.concatenate([edges, np.full((13, 1), np.inf)], axis=1)
    assert np.array_equal(data.column("threshold"), padded[feats, cuts])
    assert np.array_equal(edges, gbt_module.bin_edges(table, "features", 256, SEED))
    # inference needs no binning: x <= threshold is bin <= cut
    (out,) = model.transform(table)
    assert float(np.mean(out["prediction"] == y)) > 0.55


# -- the bins -----------------------------------------------------------------

@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_uint8_bins_are_bin_features_int32_ones(dtype):
    x, _, _ = _airline(rows=4_000)
    x = x.astype(dtype)
    if dtype == np.float64:
        x += np.random.default_rng(0).normal(size=x.shape) * 1e-3
    edges = gbt_module.quantile_bin_edges(x.astype(np.float64), 256)
    # edges that no float32 holds, and ones that rows sit exactly on
    edges[4, :8] += 1e-9
    bins = _gbt_table.bin_rows(x, edges, 4_096)
    assert bins.dtype == np.uint8 and bins.shape == (13, 4_096)
    want = gbt_module.bin_features(x.astype(np.float64), edges)
    assert np.array_equal(bins[:, :4_000], want.T)
    assert not bins[:, 4_000:].any()
    assert np.array_equal(reference.bins_of(x, edges), want.T)


def test_the_edges_are_a_seeded_sample_above_the_sample_size_and_exact_below():
    x, _, table = _airline(rows=5_000)
    exact = gbt_module.quantile_bin_edges(x.astype(np.float64), 64)
    assert np.array_equal(gbt_module.bin_edges(table, "features", 64, 1), exact)
    assert np.array_equal(gbt_module.bin_edges(table, "features", 64, 2), exact)
    one = gbt_module.bin_edges(table, "features", 64, 1, sample_rows=1_000)
    assert np.array_equal(one, gbt_module.bin_edges(table, "features", 64, 1, 1_000))
    assert not np.array_equal(one, gbt_module.bin_edges(table, "features", 64, 2, 1_000))
    rows = np.sort(np.random.default_rng(1).choice(5_000, 1_000, replace=False))
    assert np.array_equal(
        one, gbt_module.quantile_bin_edges(x[rows].astype(np.float64), 64))
    # the reference's own restatement of the rule, sampled and not
    assert np.array_equal(one, reference.edges_of(x, 64, 1, 1_000))
    assert np.array_equal(exact, reference.edges_of(x, 64, 1, 5_000))
    assert not np.array_equal(one, reference.edges_of(x[::-1], 64, 1, 1_000))
    assert reference.edges_of(x, 64, 1, 1_000).dtype == np.float64


def test_the_references_base_score_is_the_weighted_log_odds():
    y = np.array([1.0, 0.0, 0.0, 1.0, 1.0], np.float32)
    assert reference.base_of(y) == pytest.approx(np.log(3 / 2), rel=1e-15)
    w = np.array([1.0, 2.0, 3.0, 4.0, 5.0], np.float32)
    assert reference.base_of(y, w) == pytest.approx(np.log(10 / 5), rel=1e-15)
    assert reference.base_of(np.ones(4)) == pytest.approx(np.log(4 / 1e-12))


# -- a level's histograms -----------------------------------------------------

def _level(seed, n, nodes, d=13):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, 256, (d, n)).astype(np.uint8)
    bins[d - 1] = rng.random(n) < 0.002            # one bin holds nearly all
    g = (rng.random(n) - 0.47).astype(np.float32)
    h = np.maximum(0.25 - g * g, 1e-6).astype(np.float32)
    g[::7] = h[::7] = 0.0                          # rows a subsample left out
    node = rng.integers(0, nodes, n).astype(np.int32)
    return bins, g, h, node


def _parts_added(sums, nodes):
    """``[hg, hh]`` of ``[features, 256, used]`` sums (``gbt_hist.
    unfolded``'s): the three parts' columns of each statistic added."""
    group = max(nodes, 8)
    return [sum(np.asarray(sums[:, :, (s * 3 + p) * group:(s * 3 + p) * group + nodes])
                for p in range(3)).transpose(2, 0, 1) for s in range(2)]


def _segment_sums(bins, g, h, node, nodes):
    d, n = bins.shape
    ids = ((node[None, :] * d + np.arange(d)[:, None]) * 256 + bins).reshape(-1)
    return [np.asarray(jax.ops.segment_sum(
        jnp.asarray(np.tile(s, d)), jnp.asarray(ids), num_segments=nodes * d * 256)
    ).reshape(nodes, d, 256) for s in (g, h)]


@pytest.mark.parametrize("form", ["xla", "kernel"])
@pytest.mark.parametrize("level", range(6))
def test_the_product_histogram_is_segment_sums_at_every_level(level, form):
    nodes = 1 << level
    bins, g, h, node = _level(level, 2_048, nodes)
    want = _segment_sums(bins, g, h, node, nodes)
    make = (_gbt_table.xla_level_histograms if form == "xla" else
            lambda *a, **k: gbt_hist.level_histograms(*a, interpret=True, **k))
    got = jax.jit(make, static_argnums=4)(*map(jnp.asarray, (bins, g, h, node)), nodes)
    for ours, theirs, stat in zip(got, want, (g, h)):
        assert ours.shape == (nodes, 13, 256) and ours.dtype == jnp.float32
        # float32's rounding of a sum of up to 2,048 terms
        assert np.abs(np.asarray(ours) - theirs).max() <= 2e-6 * np.abs(stat).sum() / nodes ** 0.5
    # the benchmark's control: g rounded to one bfloat16 part beforehand
    rounded = jax.lax.reduce_precision(jnp.asarray(g), exponent_bits=8, mantissa_bits=7)
    one_part = make(jnp.asarray(bins), rounded, jnp.asarray(h), jnp.asarray(node), nodes)
    assert np.abs(np.asarray(one_part[0]) - want[0]).max() > 1e-3


def test_a_splits_two_sides_are_each_their_own_sum():
    """A leaf's sums are the last level's histograms' two sides of the
    chosen split. One value of a rare flag (``Diverted``: 0.2 % of the
    rows) sends few rows right; as the node's total less the left side
    their sum would carry the whole node's float32 rounding (here: all of
    it), as its own sum from the last bin down it is exact."""
    hg = jnp.asarray([[[2.0 ** 24, 1.0, 0.0, 0.0]]], jnp.float32)
    hh = jnp.asarray([[[1e6, 2.0, 0.0, 0.0]]], jnp.float32)
    assert float(hg.sum()) - 2.0 ** 24 == 0.0          # what total - left reads
    feat, cut, gain, lg, lh, rg, rh = _gbt_table.best_splits(
        hg, hh, jnp.float32(1.0), jnp.ones(1, jnp.float32))
    assert (int(feat[0]), int(cut[0])) == (0, 0) and float(gain[0]) > 0
    assert (float(lg[0]), float(lh[0])) == (2.0 ** 24, 1e6)
    assert (float(rg[0]), float(rh[0])) == (1.0, 2.0)
    # the last bin's "split" has nothing on its right
    last = _gbt_table.best_splits(jnp.zeros((1, 1, 4), jnp.float32) + 1.0,
                                  jnp.zeros((1, 1, 4), jnp.float32),
                                  jnp.float32(1.0), jnp.ones(1, jnp.float32))
    assert int(last[1][0]) == 0 and float(last[5][0]) == 3.0


def test_the_kernel_says_why_it_does_not_take_a_level():
    f32, u8 = jnp.float32, jnp.uint8
    reason = gbt_hist.unsupported_reason
    assert "backend" in reason(f32, u8, 13, 4_096, 32)            # a CPU here
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        assert reason(f32, u8, 13, 115_343_360, 32) is None
        assert reason(f32, u8, 13, 128, 1) is None
        assert "float32" in reason(jnp.bfloat16, u8, 13, 4_096, 32)
        assert "byte" in reason(f32, jnp.int32, 13, 4_096, 32)
        assert "bins" in reason(f32, u8, 13, 4_096, 32, 512)
        assert "tiles" in reason(f32, u8, 13, 4_000, 32)
        # the level's sums, three times, stay in fast memory: 13 features
        # to maxDepth 8's last level, 100 to maxDepth 5's
        assert reason(f32, u8, 13, 4_096, 128) is None
        assert "nodes" in reason(f32, u8, 13, 4_096, 256)
        assert reason(f32, u8, 100, 4_096, 16) is None
        assert "100 features" in reason(f32, u8, 100, 4_096, 32)
        assert "nodes" in reason(f32, u8, 1_000, 4_096, 1)
    assert gbt_hist.vmem_bytes(13, 128, 4_096) < gbt_hist.VMEM_LIMIT_BYTES
    assert gbt_hist.tile_rows(115_343_360) == gbt_hist.TILE
    # a folded level's sums are no larger: 128 x 2 used for 256 x used
    assert gbt_hist.columns(1) == 128 and gbt_hist.columns(32) == 384
    assert gbt_hist.vmem_bytes(13, 1, 4_096) < gbt_hist.vmem_bytes(13, 16, 4_096)
    with pytest.MonkeyPatch.context() as patch:
        folded = [gbt_hist.vmem_bytes(13, n, 4_096) for n in (1, 8, 32)]
        patch.setattr(gbt_hist, "fold", lambda nodes: False)
        assert gbt_hist.columns(1) == 128 and gbt_hist.columns(32) == 256
        plain = [gbt_hist.vmem_bytes(13, n, 4_096) for n in (1, 8, 32)]
    assert all(a < b for a, b in zip(folded, plain))
    assert _gbt_table.padded_rows(6_000, 4) == 4 * 1_536
    assert _gbt_table.padded_rows(115_343_360, 1) == 115_343_360


# -- against the parent's builder --------------------------------------------

def _parents(monkeypatch, est, table):
    """The same estimator's forest by the parent's path (``_fit_forest``:
    int32 bins row-major, ``segment_sum``)."""
    monkeypatch.delenv("FLINKML_TPU_GBT_HISTOGRAM", raising=False)
    return est._fit_forest(table)


@pytest.mark.parametrize("cls,knobs", [
    (GBTClassifier, {"weights": True}),
    (GBTClassifier, {"subsample": 0.7}),
    (GBTRegressor, {"subsample": 0.8, "weights": True}),
    (RandomForestClassifier, {}),
    (RandomForestRegressor, {"subsample": 0.6, "fraction": 0.5}),
    (GBTClassifier, {"holdout": 0.25, "weights": True}),
    (GBTRegressor, {"holdout": 0.2}),
])
def test_a_forest_is_the_parents_with_weights_subsampling_and_feature_subsets(
        monkeypatch, cls, knobs):
    """2,048 rows on one device: the parent pads nothing and neither does
    the table fit, so both draw the same rows and features. Continuous
    features and shallow trees: no two splits of a node tie. A holdout's
    rows, which the parent takes out of the table, stay in it at weight 0
    and out of the edges: the same forest, cut at the same prefix."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=(2_048, 6))
    signal = x[:, 0] * x[:, 1] + np.sin(2 * x[:, 2]) + 0.5 * x[:, 3]
    columns = {"features": x,
               "label": (signal + rng.logistic(size=2_048) > 0).astype(np.float64)
               if cls._LOGISTIC else signal + 0.1 * rng.normal(size=2_048)}
    est = _estimator(0.2, cls=cls, trees=3, depth=3, bins=32)
    if knobs.get("weights"):
        columns["w"] = rng.integers(1, 4, 2_048).astype(np.float64)
        est.set_weight_col("w")
    if "subsample" in knobs:
        est.set_subsample(knobs["subsample"])
    if "fraction" in knobs:
        est.set_feature_subset_fraction(knobs["fraction"])
    if "holdout" in knobs:
        est.set_validation_fraction(knobs["holdout"])
    table = Table(columns)
    feats, thrs, gains, leaves, base, depth, n_features, hashed = est._fit_table(table)
    want = _parents(monkeypatch, est, table)
    assert np.array_equal(feats, want[0]) and np.array_equal(thrs, want[1])
    np.testing.assert_allclose(leaves, want[3], rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(gains, want[2], rtol=2e-4, atol=2e-4 * np.abs(want[2]).max())
    assert base == pytest.approx(want[4], rel=1e-12)
    assert (depth, n_features, hashed) == want[5:]


def test_a_holdouts_fit_is_the_fit_of_the_other_rows_alone():
    """The held rows at weight 0: the edges, the base score and the forest
    of a table that holds the training rows alone (in the table's order),
    and the holdout in the key of what the ``Table`` keeps."""
    rng = np.random.default_rng(5)
    x = rng.normal(size=(3_000, 5)).astype(np.float32)
    y = (x[:, 0] * x[:, 1] + x[:, 2] + rng.logistic(size=3_000) > 0).astype(np.float32)
    w = rng.integers(1, 4, 3_000).astype(np.float32)
    table = Table({"features": x, "label": y, "w": w})
    est = lambda: (_estimator(0.3, trees=3, depth=3, bins=32).set_weight_col("w")
                   .set_validation_fraction(0.3))
    held, train = (np.sort(rows) for rows in est()._holdout_rows(3_000))
    assert held.shape == (900,) and np.intersect1d(held, train).size == 0
    uploads = lambda: metrics.group("gbt").snapshot()["counters"].get("table_uploads", 0)
    before = uploads()
    got = _gbt_table.fit_table(est(), table, held=held)
    want = _gbt_table.fit_table(
        _estimator(0.3, trees=3, depth=3, bins=32).set_weight_col("w"),
        Table({"features": x[train], "label": y[train], "w": w[train]}))
    assert np.array_equal(got[5], want[5]) and got[4] == pytest.approx(want[4], rel=1e-12)
    assert np.array_equal(got[0], want[0]) and np.array_equal(got[1], want[1])
    np.testing.assert_allclose(got[3], want[3], rtol=2e-4, atol=1e-6)
    # kept with the table under the holdout's fraction and seed
    assert uploads() - before == 2
    model = est().fit(table)
    assert uploads() - before == 2
    assert 1 <= model.get_model_data()[0].column("feat").shape[0] <= 3
    est().set_seed(SEED + 1).fit(table)
    _estimator(trees=1, depth=2, bins=32).set_weight_col("w").fit(table)
    assert uploads() - before == 4
    with pytest.raises(ValueError, match="boosted estimators only"):
        RandomForestClassifier().set_validation_fraction(0.2).fit(table)


@pytest.mark.parametrize("p", [4, 8])
def test_a_mesh_joins_its_shares_to_the_one_device_forest(p):
    """The rows sharded, every device its share's histograms, the real
    ``psum``: float32's order of summation apart."""
    x, y, table = _airline()
    one = _gbt_table.fit_table(_estimator(), table)
    many = _gbt_table.fit_table(_estimator(p=p), table.select("features", "label"))
    # the top of the first tree, where no two splits are a rounding apart
    assert np.array_equal(one[0][0, :7], many[0][0, :7])
    assert np.array_equal(one[1][0, :7], many[1][0, :7])
    np.testing.assert_allclose(many[2][:, 0], one[2][:, 0], rtol=1e-4)
    got = _followed(x, y, many, 0.1)
    assert got["leaf_gap"] < LEAF_TOL and got["gain_gap"] < GAIN_TOL
    assert got["split_regret"] < GAIN_TOL


# -- what is kept with the table ----------------------------------------------

def test_a_second_fit_uploads_nothing_and_a_second_rate_compiles_nothing():
    _, _, table = _airline(seed=12)
    counters = lambda: dict(metrics.group("gbt").snapshot()["counters"])
    facts = lambda: dict(metrics.group("hostdata").snapshot()["counters"])
    forest = lambda m: [np.asarray(m.get_model_data()[0].column(c))
                        for c in ("feat", "threshold", "gain", "leaf")]
    before, facts_before = counters(), facts()
    first = forest(_estimator().fit(table))
    after = counters()
    assert after["table_uploads"] - before.get("table_uploads", 0) == 1
    padded = _gbt_table.padded_rows(ROWS, 1)
    assert (after["table_h2d_bytes"] - before.get("table_h2d_bytes", 0)
            == padded * 13 + 2 * 4 * padded)
    lowered = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_, **__: lowered.append(name)
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration" else None)
    again = forest(_estimator().fit(table))
    faster = forest(_estimator(rate=0.3).fit(table))
    heavier = forest(_estimator().set_reg_lambda(3.0).fit(table))
    sampled = forest(_estimator().set_subsample(0.5).fit(table))
    assert lowered == []      # rate, lambda and subsample are operands
    last = counters()
    assert last["table_uploads"] == after["table_uploads"]
    assert last["table_h2d_bytes"] == after["table_h2d_bytes"]
    assert last["fits"] - after["fits"] == 4 and last["trees"] - after["trees"] == 8
    assert last["levels"] - after["levels"] == 48
    assert last["product_levels"] == after["product_levels"]      # a CPU
    assert last["rows"] - after["rows"] == 4 * ROWS
    assert last["hist_cells"] - after["hist_cells"] == 48 * padded * 13
    assert all(np.array_equal(a, b) for a, b in zip(first, again))    # to the bit
    for other in (faster, heavier, sampled):
        assert not all(np.array_equal(a, b) for a, b in zip(first, other))
    assert np.array_equal(first[0][0], faster[0][0])   # the first tree's splits
    # the label checks ran at every fit and read the column once
    assert facts()["label_facts_made"] - facts_before.get("label_facts_made", 0) == 1
    assert facts()["label_facts_kept"] - facts_before.get("label_facts_kept", 0) == 4
    # another seed takes the same edges where every row is in the sample,
    # and other ones, placed again, where the table has more rows
    _estimator().set_seed(SEED + 1).fit(table)
    assert counters()["table_uploads"] == last["table_uploads"]
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(_gbt_table, "BIN_SAMPLE_ROWS", 1_000)
        sampled_table = table.select("features", "label")
        _estimator().fit(sampled_table)
        _estimator().set_seed(SEED + 1).fit(sampled_table)
        _estimator().set_learning_rate(0.3).fit(sampled_table)
    assert counters()["table_uploads"] == last["table_uploads"] + 2
    # another bin count takes other bins
    _estimator().set_max_bins(64).fit(table)
    assert counters()["table_uploads"] == last["table_uploads"] + 3
    last = counters()
    last["table_uploads"] -= 1
    # a new Table over the same arrays bins and uploads again
    _estimator().fit(table.select("features", "label"))
    assert counters()["table_uploads"] == last["table_uploads"] + 2


def test_the_fit_reads_no_gate_and_no_autotune_entry(monkeypatch):
    """``FLINKML_TPU_GBT_HISTOGRAM`` and the tuning table's
    ``gbt_histogram`` choose the parent's layouts; the table fit asks for
    neither."""
    import flinkml_tpu.autotune as autotune

    asked = []
    monkeypatch.setattr(autotune, "tuned_default",
                        lambda *a, **k: asked.append(a) or a[1])
    monkeypatch.setenv("FLINKML_TPU_GBT_HISTOGRAM", "no-such-layout")
    _, _, table = _airline(rows=1_000)
    _estimator(trees=1, depth=2, bins=16).fit(table)
    assert asked == []


def test_labels_outside_0_and_1_are_refused_from_the_kept_facts():
    x, y, _ = _airline(rows=1_000)
    with pytest.raises(ValueError, match="labels in"):
        _estimator().fit(Table({"features": x, "label": y * 2}))


def test_the_kernels_runs_of_tiles_add_up_to_the_level():
    """300 tiles of 128 rows: two whole runs of ``RUN_TILES`` tiles and a
    short one, each added to the level's sums once."""
    nodes, n = 4, 128 * 300
    assert 2 * gbt_hist.RUN_TILES < 300 < 3 * gbt_hist.RUN_TILES
    bins, g, h, node = _level(9, n, nodes, d=2)
    sums = gbt_hist.unfolded(gbt_hist.level_sums(
        *map(jnp.asarray, (bins, g, h, node)), nodes, tile=128, interpret=True), nodes)
    for ours, stat in zip(_parts_added(sums, nodes), (g, h)):
        want = np.zeros((nodes, 2, 256))
        for f in range(2):
            np.add.at(want, (node, f, bins[f]), stat.astype(np.float64))
        assert np.abs(ours - want).max() <= 1e-6 * np.abs(want).max()


@pytest.mark.parametrize("nodes,folds,hot,width", [
    (1, True, 128, 128), (2, True, 128, 128), (4, True, 128, 128), (8, True, 128, 128),
    (16, False, 256, 128), (32, True, 128, 384), (64, False, 256, 384),
    (128, False, 256, 768), (256, False, 256, 1_536)])
def test_the_fold_is_chosen_from_the_node_count_alone(nodes, folds, hot, width):
    """A one-hot of 128 rows against twice the used columns where that is
    fewer MXU passes than 256 rows against them once (PR 48): at 1 to 8
    nodes (48 used columns) and at 32 (192: three tiles for four); the
    level's 16 nodes and ``maxDepth`` 8's 64 and 128 keep the product
    they had."""
    used = 6 * max(nodes, 8)
    assert gbt_hist.used_columns(nodes) == used
    assert gbt_hist.fold(nodes) is folds
    assert folds == (128 * -(-2 * used // 128) < 256 * -(-used // 128))
    assert (gbt_hist.one_hot_rows(nodes), gbt_hist.columns(nodes)) == (hot, width)
    assert gbt_hist.vmem_bytes(13, nodes, 4_096) == (
        3 * 13 * hot * width * 4 + 4_096 * (hot + width) * 6 + 13 * 4_096 * 6)


@pytest.mark.parametrize("tiles", ["one run", "300 tiles of 128"])
@pytest.mark.parametrize("nodes", [1, 2, 4, 8, 16, 32, 128])
def test_a_folded_levels_sums_are_the_unfolded_kernels_to_the_bit(nodes, tiles):
    """The fold moves a cell's sum to another cell of the MXU's output and
    adds the same products in the same order: interpreted, the level's sums
    un-folded equal the kernel's with the rule patched to "never" bit for
    bit (this CPU's product does not order a contraction's additions by the
    operands' shapes), over one run of tiles and over three, and the
    histograms XLA's product and the segment sums as before."""
    rows, tile, d = (2_048, None, 13) if tiles == "one run" else (128 * 300, 128, 3)
    bins, g, h, node = _level(40 + nodes, rows, nodes, d=d)
    operands = tuple(map(jnp.asarray, (bins, g, h, node)))
    sums = gbt_hist.level_sums(*operands, nodes, tile=tile, interpret=True)
    assert sums.shape == (d, gbt_hist.one_hot_rows(nodes), gbt_hist.columns(nodes))
    ours = np.asarray(gbt_hist.unfolded(sums, nodes))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(gbt_hist, "fold", lambda nodes: False)
        plain = gbt_hist.level_sums(*operands, nodes, tile=tile, interpret=True)
        assert plain.shape == (d, 256, -(-6 * max(nodes, 8) // 128) * 128)
        plain = np.asarray(gbt_hist.unfolded(plain, nodes))
    assert ours.shape == plain.shape == (d, 256, 6 * max(nodes, 8))
    assert np.array_equal(ours, plain)                                  # to the bit
    if tile is None:
        with pytest.MonkeyPatch.context() as patch:
            got = gbt_hist.level_histograms(*operands, nodes, interpret=True)
            patch.setattr(gbt_hist, "fold", lambda nodes: False)
            want = gbt_hist.level_histograms(*operands, nodes, interpret=True)
        assert all(np.array_equal(a, b) for a, b in zip(got, want))
        others = (_gbt_table.xla_level_histograms(*operands, nodes),
                  _segment_sums(bins, g, h, node, nodes))
    else:
        got = _parts_added(ours, nodes)
        others = (_segment_sums(bins, g, h, node, nodes),)
    for other in others:
        for a, b, stat in zip(got, other, (g, h)):
            assert a.shape == (nodes, d, 256)
            assert (np.abs(np.asarray(a) - np.asarray(b)).max()
                    <= 2e-6 * np.abs(stat).sum() / nodes ** 0.5)


#: What the parent commit (0231a88, before PR 48) returned for
#: ``fit_table(_estimator(), _airline()[2])``: the splits' features and
#: bins weighted by their place, the gains' sum, the leaves against a
#: seeded probe, the base score.
PARENT_FOREST = (40_801, 590_093, "0x1.21a8fd2a00000p+10", "0x1.c309fadaf2f5cp+3",
                 "-0x1.0bfcb03eac582p-3")


def _digest(fit):
    feats, cuts, gains, leaves, base, _ = fit
    place = np.arange(1, feats.size + 1).reshape(feats.shape)
    probe = np.random.default_rng(5).normal(size=leaves.shape)
    return (int((feats * place).sum()), int((cuts * place).sum()),
            float(gains.astype(np.float64).sum()).hex(),
            float((leaves.astype(np.float64) * probe).sum()).hex(), float(base).hex())


def test_a_cpus_forest_is_the_parents_to_the_bit_and_folded_levels_are_counted(monkeypatch):
    """A CPU keeps XLA's product at every level: the forest is the parent
    commit's bit for bit and no level counts as the kernel's or as folded.
    Where the kernel takes every level (here: said to, XLA's product
    standing in its place, because an interpreted kernel's values carry no
    mesh axes inside the trainer's ``shard_map``) a tree of depth 6 counts
    six product levels and the five folded ones: all but the 16 nodes'."""
    _, _, table = _airline()
    counters = lambda: dict(metrics.group("gbt").snapshot()["counters"])
    before = counters()
    assert _digest(_gbt_table.fit_table(_estimator(), table)) == PARENT_FOREST
    after = counters()
    assert after["levels"] - before.get("levels", 0) == 12
    assert after.get("product_levels", 0) == before.get("product_levels", 0)
    assert after.get("folded_levels", 0) == before.get("folded_levels", 0)
    monkeypatch.setattr(gbt_hist, "unsupported_reason", lambda *a, **k: None)
    monkeypatch.setattr(gbt_hist, "level_histograms", _gbt_table.xla_level_histograms)
    try:
        assert _digest(_gbt_table.fit_table(_estimator(), table)) == PARENT_FOREST
        shallow = _estimator(trees=3, depth=4)
        _gbt_table.fit_table(shallow, table)
    finally:
        _gbt_table._program.cache_clear()     # programs traced around the stand-in
    last = counters()
    assert last["levels"] - after["levels"] == 12 + 12
    assert last["product_levels"] - after.get("product_levels", 0) == 12 + 12
    assert last["folded_levels"] - after.get("folded_levels", 0) == 2 * 5 + 3 * 4


# -- what the cell's ``correct`` sees -----------------------------------------

@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    """``gbt-airline.fit``'s own set-up at its rehearsal's rows, with the
    edges' sample cut to 4,000 of them (so that the seeded draw is on the
    path, on both sides)."""
    import os

    from benchmark import run
    from benchmark.drivers import gbt as driver

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = run.load_spec(root, "gbt-airline.fit")
    spec["config"] = {**spec["config"], "bin_sample_rows": 4_000}
    ctx = run.Context(spec, 2_147_493_105, 0.0, False, True,
                      str(tmp_path_factory.mktemp("out")))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(_gbt_table, "BIN_SAMPLE_ROWS", 4_000)
        s = driver.setup(ctx)
    s.workers = 0
    return ctx, s, driver


def _failed(cell, fit, program_samples=4_000):
    """The rows of the cell's verdict that ``fit`` fails, by their first
    words."""
    ctx, s, driver = cell
    s.timed = [(1, s.first[1])]
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(_gbt_table, "BIN_SAMPLE_ROWS", program_samples)
        cmp = driver.compare(s, fit)
    levels = float(s.trees * s.depth)
    checks = driver.verdicts(ctx, s, cmp, {
        "gbt.table_h2d_bytes": 0.0, "gbt.fits": 1.0, "gbt.trees": float(s.trees),
        "gbt.levels": levels, "gbt.product_levels": levels})
    return {c["what"].split("): ")[-1][:18] for c in checks
            if c["value"] is None or c["value"] > c["limit"]}


def _tampered(cell, patch, edges_of):
    """Set-up's second fit again on a new ``Table`` by a program whose
    edges are ``edges_of``'s."""
    ctx, s, driver = cell
    patch.setattr(_gbt_table, "BIN_SAMPLE_ROWS", 4_000)
    patch.setattr(_gbt_table, "_edges_of", edges_of)
    patch.setattr(s, "table", Table({"features": s.x, "label": s.y}))
    return driver._public_fit(s, s.sweep[1])


def test_the_cells_verdict_takes_its_edges_and_base_score_from_the_reference(cell):
    ctx, s, driver = cell
    sound = s.first[1]
    assert s.rows > s.sample_rows == 4_000          # the seeded draw is on the path
    assert _failed(cell, sound) == set()
    # a program that starts from another score
    assert _failed(cell, {**sound, "base": 0.0}) == {"its base score off"}
    # a threshold that is no edge of the reference's
    moved = {**sound, "thresholds": sound["thresholds"] + 0.25}
    assert "thresholds of its " in _failed(cell, moved)
    # a program whose sample is not the configuration's
    assert "entries of the pro" in _failed(cell, sound, program_samples=1 << 20)


@pytest.mark.parametrize("fault", ["first rows", "another seed", "half the bins"])
def test_a_program_with_other_edges_is_not_correct(cell, fault):
    """The table's first rows instead of the seeded draw, another draw,
    fewer bins: each moves the edges, and the verdict says so whatever
    the gaps read."""
    ctx, s, driver = cell
    sound = _gbt_table._edges_of

    def edges_of(x, max_bins, seed, sample_rows, among=None):
        if fault == "first rows":
            return _gbt_table.quantile_bin_edges(
                np.asarray(x[:sample_rows], np.float64), max_bins)
        if fault == "another seed":
            return sound(x, max_bins, seed + 1, sample_rows, among)
        edges = np.full((x.shape[1], max_bins - 1), np.inf)
        half = sound(x, max_bins // 2, seed, sample_rows, among)
        edges[:, :half.shape[1]] = half
        return edges

    with pytest.MonkeyPatch.context() as patch:
        fit = _tampered(cell, patch, edges_of)
        failed = _failed(cell, fit)
    assert "entries of the pro" in failed, failed
    assert len(failed) >= 2, failed          # the bins moved, so a gap or a threshold too
