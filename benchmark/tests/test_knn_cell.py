"""What PR 30 added for ``knn-mnist8m.transform``, on the CPU: the
float64 reference's shortlist against its direct sums, the MNIST-profile
generator's invariants, the search's count, the driver's tolerance and
sample, the bfloat16 control, and a rehearsal of the cell, traced and
not, and of the builder's control script."""

import json
import os
import re

import numpy as np
import pytest

from benchmark import datagen_mnist, flops_bytes, flops_bytes_knn
from benchmark.reference import knn as reference

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)

with open(os.path.join(BENCH, "configs", "knn-mnist8m.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(BENCH, "workloads", "knn-mnist8m.transform.json")) as f:
    CELL = json.load(f)


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 77])
def test_generator_invariants(seed):
    rows = 70_000                       # three blocks, the last one short
    x, y, margins = datagen_mnist.images(seed, datagen_mnist.TAG_TRAIN, rows)
    assert x.shape == (rows, 784) and x.dtype == np.float32 and y.dtype == np.float32
    levels = x * np.float32(255.0)
    assert np.array_equal(levels, np.rint(levels)) and levels.min() == 0 and levels.max() <= 255
    assert np.array_equal(x, (levels / np.float32(255.0)).astype(np.float32))
    lit = x > 0
    assert abs(lit.mean() - datagen_mnist.LIT_SHARE) < 0.02
    image = lit.mean(axis=0).reshape(28, 28)
    assert image[:4].max() == 0 and image[-4:].max() == 0          # the border is dark
    assert image[:, :4].max() == 0 and image[:, -4:].max() == 0
    assert image[8:20, 8:20].mean() > 0.4
    assert set(np.unique(y)) == set(range(10))
    assert np.abs(np.bincount(y.astype(int)) / rows - 0.1).max() < 0.01
    # the twins alone have a margin; it is |z0|, half-normal
    twins = np.isin(y, (datagen_mnist.TWIN, datagen_mnist.TWIN_OF))
    assert np.isinf(margins[~twins]).all() and np.isfinite(margins[twins]).all()
    assert abs(np.median(margins[twins]) - 0.6745) < 0.03
    # the same seed the same bytes, whatever the length; another seed, others
    again = datagen_mnist.images(seed, datagen_mnist.TAG_TRAIN, 40_000)
    assert again[0].tobytes() == x[:40_000].tobytes()
    assert again[1].tobytes() == y[:40_000].tobytes()
    other = datagen_mnist.images(seed + 1, datagen_mnist.TAG_TRAIN, 1000)
    queries = datagen_mnist.images(seed, datagen_mnist.TAG_QUERIES, 1000)
    assert other[0].tobytes() != x[:1000].tobytes()
    assert queries[0].tobytes() != x[:1000].tobytes()


def test_neighbourhoods_are_mixed_along_the_cut_and_dense():
    """What the check bites on: among the queries nearest the twins' cut
    most neighbourhoods hold both classes; elsewhere nearly none does;
    and an archive's nearest rows are a small fraction of a row's own
    squared length away."""
    seed = 2 ** 31 + 9
    prof = datagen_mnist.profile(seed)
    x, y, _ = datagen_mnist.images(seed, datagen_mnist.TAG_TRAIN, 60_000, prof)
    q, qy, margins = datagen_mnist.images(seed, datagen_mnist.TAG_QUERIES, 4_000, prof)
    order = np.argsort(margins, kind="stable")
    near, far = order[:150], order[-150:]
    rows, d2 = reference.k_nearest(q[near], x, 5, shortlist=64)
    labels = y[rows[:, :5]]
    assert (labels != labels[:, :1]).any(axis=1).mean() > 0.4
    assert set(np.unique(labels)) == {datagen_mnist.TWIN, datagen_mnist.TWIN_OF}
    rows_far, d2_far = reference.k_nearest(q[far], x, 5, shortlist=64)
    labels_far = y[rows_far[:, :5]]
    assert (labels_far != labels_far[:, :1]).any(axis=1).mean() < 0.05
    assert (reference.vote(y, rows_far, 5) == qy[far]).mean() > 0.97
    assert np.median(d2[:, 0]) < 0.05 * np.median((q.astype(np.float64) ** 2).sum(axis=1))


def test_shortlist_is_the_direct_ranking_and_shares_merge():
    rng = np.random.default_rng(3)
    train = (rng.integers(0, 256, (5000, 40)) / 255).astype(np.float32)
    train[3000:3500] = train[:500]              # exact ties, broken by the row
    queries = np.concatenate([train[:20], (rng.integers(0, 256, (30, 40)) / 255)
                              .astype(np.float32)])
    direct = reference.k_nearest(queries, train, 5)
    block = reference.BLOCK_ROWS
    reference.BLOCK_ROWS = 900
    try:
        short = reference.k_nearest(queries, train, 5, shortlist=64)
    finally:
        reference.BLOCK_ROWS = block
    np.testing.assert_array_equal(short[0], direct[0])
    np.testing.assert_allclose(short[1], direct[1], rtol=1e-12, atol=1e-15)
    assert (direct[0][:20, 0] == np.arange(20)).all()           # itself, the lower twin
    assert (direct[0][:20, 1] == np.arange(20) + 3000).all()
    with pytest.raises(ValueError, match="cannot hold"):
        reference.k_nearest(queries, train, 5, shortlist=4)
    # four shares merged by (distance, row) are the whole
    parts = [reference.k_nearest(queries, train[lo:lo + 1250], 5) for lo in range(0, 5000, 1250)]
    merged = reference.merge_shares([p[0][:, :5] + lo for p, lo in zip(parts, range(0, 5000, 1250))],
                                    [p[1][:, :5] for p in parts], 5)
    np.testing.assert_array_equal(merged[0], direct[0][:, :5])


def test_search_count():
    c = flops_bytes_knn.knn_search(10_000, 2_025_000, 784)
    assert c["flops"] == 2 * 10_000 * 2_025_000 * 784 + 2 * 2_035_000 * 784 + 3 * 10_000 * 2_025_000
    assert c["bytes"] == 3 * 2_025_000 * 785 * 4 + 10_000 * 784 * 4
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    seconds, bound = flops_bytes.least_seconds(c, peaks)
    assert bound == "flops" and 0.16 < seconds < 0.17
    # one chunk reads the rows once
    assert flops_bytes_knn.knn_search(100, 1000, 8)["bytes"] == 1000 * 9 * 4 + 100 * 8 * 4


def test_the_configuration_is_the_issues():
    assert (CONFIG["dim"], CONFIG["classes"], CONFIG["k"]) == (784, 10, 5)
    assert CONFIG["distance"] == "euclidean" and CONFIG["feature_dtype"] == "float32"
    assert CONFIG["train_rows"] * 4 == CONFIG["train_rows_source"] == 8_100_000
    assert CONFIG["query_rows"] == 10_000 and CONFIG["reduced"] == ["train_rows"]
    assert CONFIG["architecture"] is None and len(CONFIG["source"]) <= 200
    assert CONFIG["train_rows"] * CONFIG["dim"] * 4 == 6_350_400_000
    assert (CELL["driver"], CELL["rate_metric"], CELL["chips"]) == ("knn", "transform_rows_per_s", 1)
    assert CELL["sample_near_cut"] + CELL["sample_others"] >= 512
    assert CELL["limits"]["vote_mismatch_stable"] == CELL["limits"]["rows_mismatch"] == 0
    assert set(CELL["rehearse"]) <= {"train_rows", "query_rows", "sample_near_cut",
                                     "sample_others"}          # rows, never widths
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for m in bench["per_layer"]
              if "knn-mnist8m.transform" in m.get("workloads", [])}
    assert listed >= set(COUNTED) | set(TRACED)
    rate = next(m for m in bench["end_to_end"] if m["name"] == "transform_rows_per_s")
    assert "knn-mnist8m.transform" in rate["workloads"]


def test_the_entries_keep_to_the_form_of_benchmark_json():
    """The driver refuses the file for one string over 200 characters (it did:
    the cell's ``why`` at 230), so every entry this cell brought is held to
    the limits here: a line of 1 to 200 printable ASCII characters, a name of
    at most 64 from the name's alphabet, a unit of at most 16."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = ([c for c in bench["configs"] if c["name"] == "knn-mnist8m"]
            + [w for w in bench["workloads"] if w["config"] == "knn-mnist8m"]
            + [m for m in bench["per_layer"] if m["name"].startswith("knn")])
    # the configuration, the cell and PR 30's entries that stay (later PRs
    # added their own; PR 54 retired knn.model_h2d_bytes_per_call, which
    # the cell's check holds at 0)
    assert len(mine) >= 2 + len([n for n in COUNTED + TRACED if n.startswith("knn")])
    for entry in mine:
        assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}", entry["name"]), entry["name"]
        for key in {"why", "source", "layer"} & set(entry):
            line = entry[key]
            assert 1 <= len(line) <= 200 and line.isascii() and line.isprintable(), (
                entry["name"], key, len(line))
        if "unit" in entry:
            assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", entry["unit"]), entry["unit"]
    assert len(CELL["why"]) <= 200


COUNTED = ["compile.cache_misses.setup", "knn.dispatch_s_per_call",
           "knn.readback_s_per_call"]
TRACED = ["knn.search_device_ms_per_call", "knn_search_roofline",
          "device.idle_share.transform", "device.idle_outside_spans.transform"]


@pytest.mark.parametrize("trace", [0, 1])
def test_a_rehearsal_of_the_cell(trace, capsys):
    from benchmark import run

    rc = run.main(["--workload", "knn-mnist8m.transform", "--seed", str(2 ** 31 + 30),
                   "--seconds", "0.5", "--trace", str(trace), "--rehearse"])
    lines = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    line = lines[-1]
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    checks = [c for c in lines if c.get("phase") == "check"]
    assert len(checks) == 7 and all(c["ok"] for c in checks)
    found = next(c for c in lines if c.get("phase") == "reference")
    assert found["sampled"] == 128 and found["mixed_share"] > 0.25
    assert found["rows_mismatch"] == 0 and found["unordered_share"] < 0.5
    uploaded = next(c for c in checks if c["what"].startswith("model data bytes"))
    assert uploaded["value"] == 0.0
    metrics = line["metrics"]
    if not trace:
        assert set(metrics) == {"transform_rows_per_s", "setup_s"}
        return
    # a rehearsal has no device number
    assert set(COUNTED) <= set(metrics) and not set(TRACED) & set(metrics)
    assert metrics["knn.dispatch_s_per_call"]["value"] > 0.0


def test_one_bfloat16_pass_fails_the_cells_comparison(monkeypatch):
    """The control, rehearsed: on a CPU a product's precision changes
    nothing, so the search's operands are rounded to bfloat16 instead
    (what one pass of the MXU does to them). The cell's own comparison
    then counts mismatches on stable queries, and none for the program
    as it is."""
    import jax.numpy as jnp

    from benchmark import run
    from benchmark.drivers import knn as driver
    from flinkml_tpu.models import knn as program_knn

    spec = run.load_spec(os.path.dirname(BENCH), "knn-mnist8m.transform")
    spec["cell"]["rehearse"] = {**spec["cell"]["rehearse"], "train_rows": 30_000,
                                "query_rows": 2_000, "sample_near_cut": 224,
                                "sample_others": 32}
    ctx = run.Context(spec, 2 ** 31 + 5, 0.0, False, True, os.path.join(BENCH, "out"))
    s = driver.setup(ctx)
    sound = driver.compare(ctx, s, 0, driver._call(s, 0))
    assert sound["mismatch_stable"] == 0 and sound["unstable_share"] < 0.06
    assert sound["rows_mismatch"] == 0

    real = jnp.matmul

    def one_pass(a, b, precision=None):
        low = lambda m: m.astype(jnp.bfloat16).astype(jnp.float32)
        return real(low(a), low(b))

    monkeypatch.setattr(jnp, "matmul", one_pass)
    program_knn._knn_vote.clear_cache()
    try:
        control_pred = driver._call(s, 0)
        control = driver.compare(ctx, s, 0, control_pred)
    finally:
        monkeypatch.setattr(jnp, "matmul", real)
        program_knn._knn_vote.clear_cache()
    assert control["mismatch_stable"] >= 3
    # the neighbours themselves, which need no mixed neighbourhood to tell
    assert control["rows_mismatch"] >= 50
    checks = driver.verdicts(ctx, s, control, control_pred, {"knn.model_h2d_bytes": 0.0})
    assert [c["value"] > c["limit"] for c in checks] == [True, True] + [False] * 4


def test_the_control_script_rehearses(monkeypatch, capsys):
    """``chip_controls_knn.py`` end to end at the rehearsal's rows (on a
    CPU its one-pass control computes in float32, so both come out
    correct: what it reads on the chip is PERF.md's)."""
    import importlib.util
    import sys

    spec = importlib.util.spec_from_file_location(
        "chip_controls_knn", os.path.join(HERE, "chip_controls_knn.py"))
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    monkeypatch.setattr(sys, "argv", ["chip_controls_knn.py", "--rehearse", "--seeds", "1",
                                      "--first-seed", str(2 ** 31 + 41), "--uniform"])
    script.main()
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["sound_correct"] is True and line["sound_rows_mismatch"] == 0
    assert line["control_correct"] is True and line["control_failed_checks"] == []
    assert line["d2_gap_over_tolerance_max"] < 1.0
    assert line["uniform_mixed_share"] < line["mixed_share"]
