"""What PR 47 added for ``gbt-airline.fit``, on the CPU: the float64
reference against a tree worked by hand and against ``np.add.at``, its
children against its own process, the level's count against its own
arithmetic, the generator's table, the configuration and the entries'
form, and a rehearsal of the cell, traced and not, and of the builder's
control script (whose control is ``correct`` false here too: the rounding
is written out, so a CPU shows it). The metric sets are held as SUBSETS:
the next metric a cell gains must not break them."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import datagen_airline, flops_bytes, flops_bytes_gbt
from benchmark.reference import gbt as reference

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL_NAME = "gbt-airline.fit"

with open(os.path.join(BENCH, "configs", "gbt-airline.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(BENCH, "workloads", f"{CELL_NAME}.json")) as f:
    CELL = json.load(f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)

COUNTED = ["compile.cache_misses.setup", "gbt.product_level_share",
           "hostdata.label_facts_kept_share"]
TRACED = ["gbt.level_device_ms", "gbt_level_roofline", "device.idle_share.fit",
          "device.idle_outside_spans.fit"]
SPANS = ["gbt.dispatch_s_per_fit", "gbt.readback_s_per_fit",
         "api.fit_own_traced_s_per_fit"]


def test_the_reference_on_a_tree_worked_by_hand():
    """Four rows, one feature with the edges 0.5 and 1.5 (bins 0, 1, 2),
    one tree of depth 1 split at ``bin <= 0``, base 0, lambda 1: p = 1/2,
    g = (1/2, -1/2, -1/2, 1/2), h = 1/4 a row; the left leaf holds row 0
    (G 1/2, H 1/4: -0.4), the right the rest (G -1/2, H 3/4: 2/7); the
    split's gain 0.25/1.25 + 0.25/1.75 - 0; the cut at bin 1 gains 0."""
    x = np.array([[0.0], [1.0], [2.0], [1.0]])
    y = np.array([0.0, 1.0, 1.0, 0.0])
    edges = np.array([[0.5, 1.5]])
    out = reference.follow(x, y, edges, np.array([[0]]), np.array([[0]]), base=0.0,
                           rate=0.5, lam=1.0, n_bins=3, workers=0)
    np.testing.assert_allclose(out["leaves"], [[-0.4, 2 / 7]], atol=1e-15)
    gain = 0.25 / 1.25 + 0.25 / 1.75
    np.testing.assert_allclose(out["split_gain"], [[gain]], atol=1e-15)
    np.testing.assert_allclose(out["best_gain"], [[gain]], atol=1e-15)
    assert out["loss_before"] == pytest.approx(np.log(2.0))
    pred = 0.5 * np.array([-0.4, 2 / 7, 2 / 7, 2 / 7])
    assert out["loss_after"] == pytest.approx(
        np.mean(np.log1p(np.exp(pred)) - y * pred))
    # the program's split at the last bin: everything left, gain 0, regret all
    last = reference.follow(x, y, edges, np.array([[0]]), np.array([[2]]), base=0.0,
                            rate=0.5, lam=1.0, n_bins=3, workers=0)
    assert last["split_gain"][0, 0] == 0.0 and last["best_gain"][0, 0] == pytest.approx(gain)
    np.testing.assert_allclose(last["leaves"], [[0.0, 0.0]], atol=1e-15)


def _followed(workers, weights=None, rows=5_000):
    x, y = datagen_airline.table(3, rows)
    rng = np.random.default_rng(0)
    edges = np.sort(rng.choice(np.unique(x), (13, 40)), axis=1).astype(np.float64)
    feats = rng.integers(0, 13, (2, 7))
    cuts = rng.integers(0, 41, (2, 7))
    out = reference.follow(x, y, edges, feats, cuts, base=-0.1, rate=0.3, lam=1.0,
                           n_bins=41, weights=weights, workers=workers)
    return x, y, edges, feats, cuts, out


@pytest.mark.parametrize("weighted", [False, True])
def test_a_followed_trees_sums_are_np_add_ats(weighted):
    w = np.random.default_rng(1).integers(1, 4, 5_000).astype(np.float32) if weighted else None
    x, y, edges, feats, cuts, out = _followed(0, w)
    bins = np.stack([np.searchsorted(edges[f], x[:, f].astype(np.float64), side="left")
                     for f in range(13)])
    assert np.array_equal(bins, reference.bins_of(x, edges))
    pred = np.full(5_000, -0.1)
    for t in range(2):
        prob = 1 / (1 + np.exp(-pred))
        g, h = (prob - y) * (1 if w is None else w), np.maximum(
            prob * (1 - prob), 1e-6) * (1 if w is None else w)
        node = np.zeros(5_000, int)
        for level in range(3):
            first = (1 << level) - 1
            G, H = np.zeros((1 << level, 13, 41)), np.zeros((1 << level, 13, 41))
            for f in range(13):
                np.add.at(G, (node, f, bins[f]), g)
                np.add.at(H, (node, f, bins[f]), h)
            gl, hl = np.cumsum(G, axis=2), np.cumsum(H, axis=2)
            gt, ht = gl[:, :, -1:], hl[:, :, -1:]
            with np.errstate(all="ignore"):
                gain = (gl ** 2 / (hl + 1) + (gt - gl) ** 2 / (ht - hl + 1)
                        - gt ** 2 / (ht + 1))
            gain = np.where((hl > 0) & (ht - hl > 0), gain, 0.0)
            gain[:, :, -1] = 0
            for w_ in range(1 << level):
                f, b = feats[t, first + w_], cuts[t, first + w_]
                assert out["split_gain"][t, first + w_] == pytest.approx(
                    max(gain[w_, f, b], 0), abs=1e-9)
                assert out["best_gain"][t, first + w_] == pytest.approx(
                    max(gain[w_].max(), 0), abs=1e-9)
            node = 2 * node + (bins[feats[t, first + node], np.arange(5_000)]
                               > cuts[t, first + node])
        lg, lh = np.bincount(node, g, 8), np.bincount(node, h, 8)
        np.testing.assert_allclose(out["leaves"][t], -lg / (lh + 1), atol=1e-12)
        pred = pred + 0.3 * out["leaves"][t][node]


def test_the_children_add_up_to_the_one_process():
    *_, here = _followed(0)
    *_, apart = _followed(3)
    for key in ("split_gain", "best_gain", "leaves"):
        np.testing.assert_allclose(apart[key], here[key], rtol=1e-12, atol=1e-12)
    assert apart["loss_after"] == pytest.approx(here["loss_after"], rel=1e-12)


def test_the_levels_count_is_its_own_arithmetic():
    c = flops_bytes_gbt.level(CONFIG["rows"], CONFIG["features"], CONFIG["max_bins"],
                              CONFIG["max_depth"])
    rows = 115_343_360
    assert c["bytes"] == rows * 13 + rows * 16 + 2 * 10.5 * 13 * 256 * 4
    assert c["flops"] == 2 * rows * 13
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = flops_bytes.least_seconds(c, peaks)
    assert bound == "bytes" and 0.0040 < least < 0.0042        # four milliseconds


def test_the_generators_table():
    x, y = datagen_airline.table(11, 200_000)
    assert x.dtype == np.float32 and x.shape == (200_000, 13) and y.dtype == np.float32
    distinct = [len(np.unique(x[:, j])) for j in range(13)]
    assert distinct[:4] == [22, 12, 31, 7] and distinct[6] == 29 and distinct[12] == 2
    assert 1_300 < distinct[4] <= 1_440 and 300 <= distinct[9] <= 340
    assert 4_000 < distinct[7] <= 8_000 and 500 < distinct[8] <= 701
    assert 1_400 < distinct[11] <= 1_600
    assert set(np.unique(y)) == {0.0, 1.0} and 0.45 < y.mean() < 0.50
    assert 0.001 < x[:, 12].mean() < 0.003
    # a Zipf head: the largest carrier flies a fifth of the rows and more
    assert np.bincount(x[:, 6].astype(int)).max() > 0.15 * 200_000
    # the labels follow the planted effects: late flights arrive late
    late = x[:, 8] > 25 + x[:, 11] / 7.5
    assert y[late].mean() > y[~late].mean() + 0.1
    again = datagen_airline.table(11, 200_000)
    assert np.array_equal(x, again[0]) and np.array_equal(y, again[1])
    assert not np.array_equal(x, datagen_airline.table(12, 200_000)[0])


def test_the_configuration_and_the_entries():
    assert CONFIG["architecture"] is None and CONFIG["reduced"] == ["num_trees"]
    assert (CONFIG["rows"], CONFIG["features"], CONFIG["max_bins"], CONFIG["max_depth"],
            CONFIG["reg_lambda"], CONFIG["subsample"]) == (110 << 20, 13, 256, 6, 1.0, 1.0)
    assert CONFIG["num_trees"] == 2 and CONFIG["num_trees_source"] == 500
    assert CONFIG["bin_sample_rows"] == 1 << 20 and CONFIG["bin_dtype"] == "uint8"
    assert len(CONFIG["source"]) <= 200 and len(CONFIG["guarantees"]) == 7
    assert len(CONFIG["departures"]) >= 4 and "table" in CONFIG["assumed"]
    (entry,) = [c for c in BENCHMARK["configs"] if c["name"] == "gbt-airline"]
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == ["num_trees"]
    assert entry["file"] == "benchmark/configs/gbt-airline.json"
    (cell,) = [w for w in BENCHMARK["workloads"] if w["name"] == CELL_NAME]
    assert cell["chips"] == CELL["chips"] == 1 and cell["why"] == CELL["why"]
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert CELL["sweep"] == [0.1, 0.3] and CELL["driver"] == "gbt"
    assert set(CELL["limits"]) == {"leaf_gap", "gain_gap", "split_regret"}
    # a rehearsal overrides the rows, never a width
    assert set(CELL["rehearse"]) == {"rows"}
    mine = {m["name"] for m in BENCHMARK["per_layer"] if CELL_NAME in m.get("workloads", [])}
    assert set(COUNTED + TRACED + SPANS) <= mine
    assert all(m["layer"] in ("GBT trainer", "Kernels") for m in BENCHMARK["per_layer"]
               if m["name"].startswith("gbt"))
    rate = next(m for m in BENCHMARK["end_to_end"] if m["name"] == "fit_samples_per_s")
    assert CELL_NAME in rate["workloads"]      # not "the last": the next cell's goes after
    for name in mine:
        assert os.path.exists(os.path.join(BENCH, "metrics", f"{name}.json")), name


def _run(*extra):
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL_NAME,
         "--seed", "2147493104", "--seconds", "1", "--rehearse", *extra],
        capture_output=True, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(line) for line in out.stdout.splitlines()
             if line.startswith("{")]
    return lines[-1], lines


@pytest.mark.parametrize("trace", [0, 1])
def test_a_rehearsal_of_the_cell(trace):
    line, lines = _run("--trace", str(trace))
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    checks = [c for c in lines if c.get("phase") == "check"]
    assert len(checks) == 12 and all(c["ok"] for c in checks)   # no product level: a CPU
    # the edges, the thresholds and the base score are the reference's own
    assert [c["value"] for c in checks[:3]] == [0, 0, 0.0]
    assert [c["limit"] for c in checks[:3]] == [0, 0, 4]
    assert 0 < checks[3]["value"] < CELL["limits"]["leaf_gap"]
    assert 0 < checks[4]["value"] < CELL["limits"]["gain_gap"]
    assert checks[5]["value"] <= CELL["limits"]["split_regret"]
    # held by the check, not by a per-layer metric (PR 54): a miss is not correct
    assert [(c["value"], c["limit"]) for c in checks
            if "bytes uploaded inside the window" in c["what"]] == [(0.0, 0)]
    if trace:
        assert set(COUNTED + SPANS) <= set(line["metrics"])
        assert line["metrics"]["gbt.product_level_share"]["value"] == 0.0
        assert line["metrics"]["hostdata.label_facts_kept_share"]["value"] == 1.0
    else:
        assert set(line["metrics"]) == {"fit_samples_per_s", "setup_s"}


def test_the_builders_control_script_rehearses_and_its_control_is_not_correct():
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "chip_controls_gbt.py"), "--seeds", "1",
         "--rehearse"], capture_output=True, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines() if ln.startswith("{")]
    rates = [ln for ln in lines if "sound_correct" in ln]
    assert [ln["rate"] for ln in rates] == CELL["sweep"]
    for line in rates:
        assert line["sound_correct"] is True and line["sound_failed_checks"] == []
        assert line["sound_leaf_gap"] < CELL["limits"]["leaf_gap"] / 3
        assert line["sound_gain_gap"] < CELL["limits"]["gain_gap"] / 3
        # the rounding is written out, so a CPU shows it too
        assert line["control_correct"] is False
        assert line["control_leaf_gap"] > CELL["limits"]["leaf_gap"]
        assert line["control_gain_gap"] > CELL["limits"]["gain_gap"]
        # wrong splits at the last level: split_regret alone has to see them
        assert line["planted_correct"] is False
        assert line["planted_split_regret"] > 3 * CELL["limits"]["split_regret"]
        assert line["sound_edges_apart"] == 0 and line["sound_strangers"] == 0
