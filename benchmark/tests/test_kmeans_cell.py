"""What PR 32 added for ``kmeans-mnist8m.fit``, on the CPU: the float64
reference (its expansion against direct sums, its rounds against a
loop written out, ties and empty clusters), the round's count, the
configuration and the entries' form, the bfloat16 control, and a
rehearsal of the cell, traced and not, and of the builder's control
script."""

import json
import os
import re

import numpy as np
import pytest

from benchmark import datagen_mnist, flops_bytes, flops_bytes_kmeans
from benchmark.reference import kmeans as reference

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL_NAME = "kmeans-mnist8m.fit"

with open(os.path.join(BENCH, "configs", "kmeans-mnist8m.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(BENCH, "workloads", f"{CELL_NAME}.json")) as f:
    CELL = json.load(f)
with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)

COUNTED = ["compile.cache_misses.setup", "kmeans.init_s_per_fit",
           "kmeans.dispatch_s_per_fit", "kmeans.readback_s_per_fit"]
TRACED = ["kmeans.round_device_ms", "kmeans_lloyd_roofline",
          "device.idle_share.fit", "device.idle_outside_spans.fit"]


def _plain_lloyd(x, start, rounds):
    """Lloyd written out row by row in float64, direct differences."""
    x, c = x.astype(np.float64), start.astype(np.float64).copy()
    for _ in range(rounds):
        d2 = ((x[:, None, :] - c[None, :, :]) ** 2).sum(-1)
        assign = d2.argmin(1)
        for j in range(c.shape[0]):
            if (assign == j).any():
                c[j] = x[assign == j].mean(0)
    return c


def test_reference_is_lloyd_written_out():
    x, _, _ = datagen_mnist.images(3, datagen_mnist.TAG_TRAIN, 700)
    start = x[reference.start_rows(3, 700, 6)]
    got, counts, close = reference.lloyd(x, start, 7)
    np.testing.assert_allclose(got, _plain_lloyd(x, start, 7), rtol=0, atol=1e-12)
    assert counts.sum() == 700 and close == [0] * 7
    np.testing.assert_array_equal(
        reference.assignments(x, got),
        ((x[:, None, :].astype(np.float64) - got[None]) ** 2).sum(-1).argmin(1))
    direct = ((x.astype(np.float64) - got[reference.assignments(x, got)]) ** 2).sum()
    assert abs(reference.cost(x, got) - direct) <= 1e-9 * direct


def test_reference_blocks_threads_ties_and_empty_clusters(monkeypatch):
    # blocks that do not divide the rows, more workers than blocks
    monkeypatch.setattr(reference, "BLOCK_ROWS", 96)
    x, _, _ = datagen_mnist.images(4, datagen_mnist.TAG_TRAIN, 500)
    start = x[reference.start_rows(4, 500, 5)]
    np.testing.assert_allclose(reference.lloyd(x, start, 5)[0],
                               _plain_lloyd(x, start, 5), rtol=0, atol=1e-12)
    # the start rule: k distinct rows by the seed, whatever the seed's size
    rows = reference.start_rows(2 ** 31 - 1, 500, 10)
    assert len(set(rows.tolist())) == 10 and rows.max() < 500
    np.testing.assert_array_equal(rows, reference.start_rows(2 ** 31 - 1, 500, 10))
    # a tie goes to the lower centroid; a centroid no row is nearest to stays
    pts = np.repeat(np.array([[0.0], [2.0], [4.0]], np.float32), 4, axis=0)
    c, counts, _ = reference.lloyd(pts, np.array([[1.0], [3.0], [50.0]]), 1)
    np.testing.assert_array_equal(counts, [8, 4, 0])
    np.testing.assert_array_equal(c, [[1.0], [4.0], [50.0]])
    # the rows a tolerance cannot decide are counted
    tol = lambda sq, cc: np.full(sq.shape, 0.5)
    assert reference.lloyd_round(pts, np.array([[0.9], [3.0]]), tol)[2] == 4


def test_round_count():
    c = flops_bytes_kmeans.lloyd_round(2_025_000, 784, 10)
    assert c["flops"] == 4 * 2_025_000 * 784 * 10 + 3 * 2_025_000 * 10
    assert c["bytes"] == 2_025_000 * 786 * 4 + 2 * 10 * 784 * 4
    for kind, peaks in json.load(open(os.path.join(BENCH, "peaks.json")))["devices"].items():
        seconds, bound = flops_bytes.least_seconds(c, peaks)
        assert bound == "bytes", kind
    assert 0.0077 < seconds < 0.0078          # a v5e: 6.37 GB at 819 GB/s


def test_the_configuration_is_the_issues():
    assert (CONFIG["dim"], CONFIG["k"], CONFIG["max_iter"]) == (784, 10, 20)
    assert CONFIG["init_mode"] == "random" and CONFIG["distance"] == "euclidean"
    assert CONFIG["feature_dtype"] == "float32" and CONFIG["architecture"] is None
    assert CONFIG["train_rows"] * 4 == CONFIG["train_rows_source"] == 8_100_000
    assert CONFIG["train_rows"] * CONFIG["dim"] * 4 == 6_350_400_000
    assert CONFIG["reduced"] == ["train_rows"] and len(CONFIG["guarantees"]) == 6
    assert (CELL["driver"], CELL["rate_metric"], CELL["chips"]) == (
        "kmeans", "fit_samples_per_s", 1)
    assert CELL["seeds"] == 4 and CELL["trace_units"] == 2
    assert set(CELL["rehearse"]) == {"train_rows"}            # rows, never widths
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == "kmeans-mnist8m")
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == CONFIG["reduced"]
    sources = [c["source"] for c in BENCHMARK["configs"]]
    assert len(set(sources)) == len(sources)     # knn-mnist8m's is another
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL_NAME)
    assert cell == {"name": CELL_NAME, "config": "kmeans-mnist8m", "traffic": "fit",
                    "chips": 1, "why": CELL["why"]}
    listed = {m["name"] for m in BENCHMARK["per_layer"]
              if CELL_NAME in m.get("workloads", [])}
    assert listed >= set(COUNTED) | set(TRACED)
    rate = next(m for m in BENCHMARK["end_to_end"] if m["name"] == "fit_samples_per_s")
    assert CELL_NAME in rate["workloads"]
    for name in listed:
        assert os.path.exists(os.path.join(BENCH, "metrics", f"{name}.json")), name


def test_the_entries_keep_to_the_form_of_benchmark_json():
    """The driver refuses the file for one string over 200 characters (PR
    30's ``why`` at 230), so every entry this cell brought is held to the
    limits here."""
    mine = ([c for c in BENCHMARK["configs"] if c["name"] == "kmeans-mnist8m"]
            + [w for w in BENCHMARK["workloads"] if w["config"] == "kmeans-mnist8m"]
            + [m for m in BENCHMARK["per_layer"] if "kmeans" in m["name"]])
    # the configuration, the cell and PR 32's entries that stay; later PRs
    # appended their own (PR 49's two phases)
    assert len(mine) >= 2 + len([n for n in COUNTED + TRACED if "kmeans" in n])
    for entry in mine:
        assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}", entry["name"]), entry["name"]
        for key in {"why", "source", "layer"} & set(entry):
            line = entry[key]
            assert 1 <= len(line) <= 200 and line.isascii() and line.isprintable(), (
                entry["name"], key, len(line))
        if "unit" in entry:
            assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", entry["unit"]), entry["unit"]
            assert set(entry) == {"name", "unit", "better", "source", "layer", "moves",
                                  "workloads"}
    assert len(CELL["why"]) <= 200 and len(CONFIG["source"]) <= 200
    assert len(json.dumps(BENCHMARK, indent=2)) < 64 * 1024


@pytest.mark.parametrize("trace", [0, 1])
def test_a_rehearsal_of_the_cell(trace, capsys):
    from benchmark import run

    rc = run.main(["--workload", CELL_NAME, "--seed", str(2 ** 31 + 30),
                   "--seconds", "0.5", "--trace", str(trace), "--rehearse"])
    lines = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    line = lines[-1]
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    checks = [c for c in lines if c.get("phase") == "check"]
    assert len(checks) == 7 and all(c["ok"] for c in checks)
    warm = [c for c in lines if c.get("phase") == "warm-fit"]
    assert [c["seed"] for c in warm] == [30, 31, 32, 33]
    found = next(c for c in lines if c.get("phase") == "reference")
    assert found["moved_by_the_fit"] > 0.1 and found["gap"] < 1e-5
    assert found["round_gap"] < 1e-6
    units = next(c for c in lines if c.get("phase") == "window")["units"]
    assert units["rounds"] == 20 * units["fits"]
    assert units["samples"] == 20_000 * units["rounds"]
    # held by the check, not by a per-layer metric (PR 54): a miss is not correct
    assert [(c["value"], c["limit"]) for c in checks
            if "bytes uploaded inside the window" in c["what"]] == [(0.0, 0)]
    metrics = line["metrics"]
    if not trace:
        assert set(metrics) == {"fit_samples_per_s", "setup_s"}
        return
    assert set(metrics) >= set(COUNTED)        # a rehearsal has no device number
    assert not set(metrics) & set(TRACED)
    assert metrics["kmeans.dispatch_s_per_fit"]["value"] > 0.0


def test_one_bfloat16_pass_fails_the_cells_comparison(monkeypatch):
    """The control, rehearsed: on a CPU a product's precision changes
    nothing, so both products' operands are rounded to bfloat16 instead
    (what one pass of the MXU does to them). The cell's own verdicts
    then fail, and pass for the program as it is."""
    import jax.numpy as jnp

    from benchmark import run
    from benchmark.drivers import kmeans as driver
    from flinkml_tpu.models import kmeans as program_kmeans

    spec = run.load_spec(os.path.dirname(BENCH), CELL_NAME)
    ctx = run.Context(spec, 2 ** 31 + 5, 0.0, False, True, os.path.join(BENCH, "out"))
    s = driver.setup(ctx)
    seed = s.seeds[0]
    ref = driver.reference_fit(s, seed)
    s.timed = [(seed, s.first[seed])]
    window = {"kmeans.table_h2d_bytes": 0.0, "kmeans.rounds": 20.0, "kmeans.fits": 1.0}
    sound = driver.verdicts(ctx, s, driver.compare(s, ref, s.first[seed]), window)
    assert all(c["value"] <= c["limit"] for c in sound)

    real = jnp.matmul

    def one_pass(a, b, precision=None):
        low = lambda m: m.astype(jnp.bfloat16).astype(jnp.float32)
        return real(low(a), low(b))

    monkeypatch.setattr(jnp, "matmul", one_pass)
    program_kmeans._kmeans_trainer.cache_clear()
    try:
        cmp = driver.compare(s, ref, driver._fit(s, seed))
    finally:
        monkeypatch.setattr(jnp, "matmul", real)
        program_kmeans._kmeans_trainer.cache_clear()
    control = driver.verdicts(ctx, s, cmp, window)
    # Not correct, by the one round and by the twenty.
    assert [c["value"] > c["limit"] for c in control] == [True, True] + [False] * 4
    assert cmp["round_gap"] > 5 * CELL["limits"]["round_gap"]
    assert cmp["round_gap"] > 1000 * sound[1]["value"]


def test_the_control_script_rehearses(monkeypatch, capsys):
    """``chip_controls_kmeans.py`` end to end at the rehearsal's rows (on
    a CPU its one-pass control computes in float32, so both come out
    correct: what it reads on the chip is PERF.md's)."""
    import importlib.util
    import sys

    spec = importlib.util.spec_from_file_location(
        "chip_controls_kmeans", os.path.join(HERE, "chip_controls_kmeans.py"))
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    monkeypatch.setattr(sys, "argv", ["chip_controls_kmeans.py", "--rehearse", "--seeds",
                                      "1", "--first-seed", str(2 ** 31 + 41), "--split"])
    script.main()
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["sound_correct"] is True and line["sound_failed_checks"] == []
    assert line["sound_round_gap"] < 1e-6 and line["control_round_gap"] < 1e-6
    assert line["control_correct"] is True and line["control_moved_centroids_by"] == 0.0
    assert line["distances_in_one_pass_correct"] and line["sums_in_one_pass_correct"]
