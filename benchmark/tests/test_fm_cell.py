"""What PR 36 added for ``fm-criteo.fit``, on the CPU: the float64
reference against a 12-row case worked by hand and against a loop written
out, the step's count against its own arithmetic, the planted labels, the
configuration and the entries' form, the bfloat16 control, and a
rehearsal of the cell, traced and not, and of the builder's control
script. The metric sets are held as SUBSETS: the next metric a cell gains
must not break them."""

import json
import os
import re

import numpy as np
import pytest

from benchmark import datagen_criteo, datagen_fm, flops_bytes, flops_bytes_fm
from benchmark.reference import fm as reference

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL_NAME = "fm-criteo.fit"

with open(os.path.join(BENCH, "configs", "fm-criteo.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(BENCH, "workloads", f"{CELL_NAME}.json")) as f:
    CELL = json.load(f)
with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)

COUNTED = ["compile.cache_misses.setup", "fm.blocked_cell_share",
           "fm.fused_block_share"]
PLAIN = ["fm.dispatch_s_per_fit", "fm.readback_s_per_fit", "fm.init_s_per_fit",
         "api.fit_own_s_per_fit"]
TRACED = ["fm.step_device_ms", "fm_step_roofline", "device.idle_share.fit",
          "device.idle_outside_spans.fit"]


# Twelve rows of three cells over seven columns, two factors; every number
# below the table is worked from these by hand (see the test).
HAND_IDX = np.array([[0, 2, 5], [1, 2, 6], [0, 3, 4], [1, 3, 5], [0, 2, 6], [1, 4, 5],
                     [0, 3, 6], [1, 2, 4], [0, 4, 6], [1, 3, 4], [0, 2, 3], [1, 5, 6]])
HAND_X = np.tile(np.array([1.0, 2.0, -1.0]), (12, 1))
HAND_W = np.array([0.5, -0.5, 1.0, 0.0, 2.0, -1.0, 0.25])
HAND_V = np.array([[1.0, 0.0], [0.0, 1.0], [1.0, 1.0], [2.0, -1.0], [0.5, 0.5],
                   [-1.0, 2.0], [0.0, -2.0]])


def test_the_reference_on_a_case_worked_by_hand():
    """Row 0 holds columns (0, 2, 5) with values (1, 2, -1): S = 1 V[0] +
    2 V[2] - V[5] = (1 + 2 + 1, 0 + 2 - 2) = (4, 0); sum x^2 V^2 = (1 + 4
    + 1) + (0 + 4 + 4) = 14; y^ = w0 + (0.5 + 2 + 1) + (16 + 0 - 14) / 2 =
    0.1 + 3.5 + 1 = 4.6. Row 1, columns (1, 2, 6): S = (0 + 2 - 0, 1 + 2 +
    2) = (2, 5); squares (0 + 4 + 0) + (1 + 4 + 4) = 13; y^ = 0.1 + (-0.5
    + 2 - 0.25) + (4 + 25 - 13) / 2 = 9.35."""
    y_hat, s, _ = reference.margin(0.1, HAND_W, HAND_V, HAND_IDX, HAND_X)
    np.testing.assert_allclose(y_hat[:2], [4.6, 9.35], rtol=0, atol=1e-12)
    np.testing.assert_allclose(s[:, :2].T, [[4.0, 0.0], [2.0, 5.0]], atol=1e-12)
    dense = reference.densified(HAND_IDX, HAND_X, 7)
    np.testing.assert_allclose(y_hat, reference.dense_margin(0.1, HAND_W, HAND_V, dense),
                               rtol=0, atol=1e-12)
    # the squared loss's gradient over rows 0 and 1 alone, weights (1, 3),
    # labels (4.6 - 1, 9.35 + 1): m = (1 * 1, 3 * -1) = (1, -3), W = 4.
    # dw0 = (1 - 3) / 4 = -0.5. Column 2 is in both rows with x = 2:
    # dw[2] = (1 * 2 - 3 * 2) / 4 = -1; dV[2] = (m x (S - x V[2]) summed) / 4
    #       = (2 * ((4, 0) - (2, 2)) - 6 * ((2, 5) - (2, 2))) / 4
    #       = ((4, -4) - (0, 18)) / 4 = (1, -5.5).
    # Column 0 is in row 0 alone with x = 1: dV[0] = 1 * ((4, 0) - (1, 0)) / 4.
    loss, (g0, gw, gv) = reference.loss_and_gradients(
        0.1, HAND_W, HAND_V, HAND_IDX[:2], HAND_X[:2], np.array([3.6, 10.35]),
        np.array([1.0, 3.0]), 0.0, logistic=False)
    assert loss == pytest.approx((0.5 * 1 * 1 + 0.5 * 3 * 1) / 4)
    assert g0 == pytest.approx(-0.5)
    np.testing.assert_allclose(gw[[0, 2, 3]], [0.25, -1.0, 0.0], atol=1e-12)
    np.testing.assert_allclose(gv[2], [1.0, -5.5], atol=1e-12)
    np.testing.assert_allclose(gv[0], [0.75, 0.0], atol=1e-12)
    # L2 as the program scales it: 2 reg times the parameter, whatever the batch
    _, (_, gw2, gv2) = reference.loss_and_gradients(
        0.1, HAND_W, HAND_V, HAND_IDX[:2], HAND_X[:2], np.array([3.6, 10.35]),
        np.array([1.0, 3.0]), 0.5, logistic=False)
    np.testing.assert_allclose(gw2 - gw, HAND_W, atol=1e-12)
    np.testing.assert_allclose(gv2 - gv, HAND_V, atol=1e-12)


def _plain_adam(idx, x, dim, y, v_start, steps, rate, reg, batch, order, logistic):
    """Adam over autodiff-free finite sums, a row and a cell at a time."""
    w0, w, v = 0.0, np.zeros(dim), v_start.astype(np.float64).copy()
    k = v.shape[1]
    flat = lambda: np.concatenate([[w0], w, v.reshape(-1)])
    m, u = np.zeros(1 + dim + dim * k), np.zeros(1 + dim + dim * k)
    n = idx.shape[0]
    windows = -(-n // batch)
    for t in range(steps):
        lo = min((t % windows) * batch, n - batch)
        g0, gw, gv = 0.0, np.zeros(dim), np.zeros((dim, k))
        for r in order[lo:lo + batch]:
            s = sum(x[r, j] * v[idx[r, j]] for j in range(idx.shape[1]))
            y_hat = w0 + sum(x[r, j] * w[idx[r, j]] for j in range(idx.shape[1])) \
                + 0.5 * sum(s[f] ** 2 - sum((x[r, j] * v[idx[r, j], f]) ** 2
                                            for j in range(idx.shape[1]))
                            for f in range(k))
            mult = (1 / (1 + np.exp(-y_hat)) - y[r]) if logistic else y_hat - y[r]
            g0 += mult
            for j in range(idx.shape[1]):
                gw[idx[r, j]] += mult * x[r, j]
                gv[idx[r, j]] += mult * (x[r, j] * s - x[r, j] ** 2 * v[idx[r, j]])
        g = np.concatenate([[g0 / batch], gw / batch + 2 * reg * w,
                            (gv / batch + 2 * reg * v).reshape(-1)])
        m = 0.9 * m + 0.1 * g
        u = 0.999 * u + 0.001 * g * g
        p = flat() - rate * (m / (1 - 0.9 ** (t + 1))) / (
            np.sqrt(u / (1 - 0.999 ** (t + 1))) + 1e-8)
        w0, w, v = p[0], p[1:1 + dim], p[1 + dim:].reshape(dim, k)
    return w0, w, v


@pytest.mark.parametrize("logistic", [True, False])
def test_the_reference_is_adam_written_out(logistic):
    rng = np.random.default_rng(2)
    y = (rng.random(12) < 0.5).astype(np.float64)
    order = reference.seeded_order(5, 12)
    start = 0.1 * rng.standard_normal((7, 2))
    got = reference.adam_fit(HAND_IDX, HAND_X, 7, y, start, 7, 0.05, 0.01, 5, order,
                             logistic=logistic, threads=3)
    want = _plain_adam(HAND_IDX, HAND_X, 7, y, start, 7, 0.05, 0.01, 5, order, logistic)
    assert got[0] == pytest.approx(want[0], abs=1e-12)
    np.testing.assert_allclose(got[1], want[1], rtol=0, atol=1e-12)
    np.testing.assert_allclose(got[2], want[2], rtol=0, atol=1e-12)
    assert len(got[3]) == 7
    # the threads share out work, not sums; four shards window their own shares
    one = reference.adam_fit(HAND_IDX, HAND_X, 7, y, start, 7, 0.05, 0.01, 5, order,
                             logistic=logistic, threads=1)
    np.testing.assert_allclose(one[2], got[2], rtol=0, atol=1e-15)
    # twelve rows over four shards of three, two a shard a step: the
    # second window is pulled back to end at the share's end
    np.testing.assert_array_equal(
        reference.step_rows(np.arange(12), 8, 1, shards=4),
        [1, 2, 4, 5, 7, 8, 10, 11])
    np.testing.assert_array_equal(
        reference.step_rows(np.arange(10), 4, 2, shards=1), [6, 7, 8, 9])


def test_the_steps_count_is_its_own_arithmetic():
    c = flops_bytes_fm.step(65_536, 39, 1_000_000, 16)
    cells = 65_536 * 39
    assert c["bytes"] == (cells * 8 + 2 * 65_536 * 4 + 2 * cells * 17 * 4
                          + 7 * 1_000_000 * 17 * 4) == 844_574_464
    assert c["flops"] == cells * (34 + 32 + 68) + 12 * 17_000_000
    for kind, peaks in json.load(open(os.path.join(BENCH, "peaks.json")))["devices"].items():
        seconds, bound = flops_bytes.least_seconds(c, peaks)
        assert bound == "bytes", kind
    assert 0.00103 < seconds < 0.00104        # a v5e: 0.845 GB at 819 GB/s


def test_the_planted_labels_have_a_pairwise_term():
    rows = 20_000
    _, idx, _, _ = datagen_criteo.criteo_rows(
        9, rows, CONFIG["dim"], CONFIG["field_cardinalities"], CONFIG["field_stratum"])
    idx = idx.reshape(rows, 39)
    y = datagen_fm.planted_labels(9, idx, CONFIG["dim"])
    assert y.dtype == np.float32 and set(np.unique(y)) == {0.0, 1.0}
    assert 0.22 < y.mean() < 0.28
    np.testing.assert_array_equal(y, datagen_fm.planted_labels(9, idx, CONFIG["dim"]))
    assert datagen_fm.pair_scale(39) == pytest.approx(1.19695, abs=1e-4)
    # the pairwise signal's spread a row is the linear one's
    value = 1 / np.sqrt(39)
    from benchmark import datagen
    u = (datagen_fm.pair_scale(39) * datagen.rng(9, datagen_fm.TAG_PAIRS)
         .standard_normal((4, CONFIG["dim"]))).astype(np.float32)
    s = u[:, idx].sum(axis=2)
    pair = 0.5 * value ** 2 * (s * s - (u[:, idx] ** 2).sum(axis=2)).sum(axis=0)
    assert 1.5 < pair.std() < 2.6


def test_the_configuration_is_the_issues():
    assert (CONFIG["dim"], CONFIG["nnz"], CONFIG["factor_size"]) == (1_000_000, 39, 16)
    assert CONFIG["loss"] == "logistic" and CONFIG["architecture"] is None
    assert (CONFIG["global_batch_size"], CONFIG["tol"], CONFIG["max_iter"]) == (65_536, 0.0, 48)
    assert CONFIG["rows"] == 16_777_216 and CONFIG["rows_source"] == 45_840_617
    assert CONFIG["reduced"] == ["rows", "max_iter"] and len(CONFIG["guarantees"]) == 6
    assert {"rows", "max_iter", "factor_size", "optimizer", "global_batch_size",
            "start", "labels"} <= set(CONFIG["assumed"])
    with open(os.path.join(BENCH, "configs", "lr-criteo.json")) as f:
        lr = json.load(f)
    for key in ("dim", "nnz", "value", "rows", "field_stratum", "field_cardinalities"):
        assert CONFIG[key] == lr[key], key           # the same table
    assert (CELL["driver"], CELL["rate_metric"], CELL["chips"]) == (
        "fm", "fit_samples_per_s", 1)
    assert CELL["sweep"] == [[0.001, 1e-06], [0.003, 0.0]] and CELL["trace_units"] == 1
    assert CELL["rehearse"] == {"rows": 16384, "global_batch_size": 2048, "max_iter": 8}
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == "fm-criteo")
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == CONFIG["reduced"]
    sources = [c["source"] for c in BENCHMARK["configs"]]
    assert len(set(sources)) == len(sources)     # lr-criteo's is another
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL_NAME)
    assert cell == {"name": CELL_NAME, "config": "fm-criteo", "traffic": "fit",
                    "chips": 1, "why": CELL["why"]}
    listed = {m["name"] for m in BENCHMARK["per_layer"]
              if CELL_NAME in m.get("workloads", [])}
    assert listed >= set(COUNTED) | set(PLAIN) | set(TRACED)
    rate = next(m for m in BENCHMARK["end_to_end"] if m["name"] == "fit_samples_per_s")
    assert CELL_NAME in rate["workloads"]
    for name in listed:
        assert os.path.exists(os.path.join(BENCH, "metrics", f"{name}.json")), name
    # every per-layer metric that moves the rate still lists its cells
    assert all("workloads" in m for m in BENCHMARK["per_layer"]
               if m["moves"] == "fit_samples_per_s")


def test_the_entries_keep_to_the_form_of_benchmark_json():
    """The driver refuses the file for one string over 200 characters (PR
    30's ``why`` at 230)."""
    mine = ([c for c in BENCHMARK["configs"] if c["name"] == "fm-criteo"]
            + [w for w in BENCHMARK["workloads"] if w["config"] == "fm-criteo"]
            + [m for m in BENCHMARK["per_layer"] if m["name"].startswith(("fm.", "fm_"))])
    assert len(mine) >= 9
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
            assert entry["workloads"] == [CELL_NAME]
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
    assert len(checks) == 9 and all(c["ok"] for c in checks)
    warm = [c for c in lines if c.get("phase") == "warm-fit"]
    assert [c["pair"] for c in warm] == CELL["sweep"]
    found = next(c for c in lines if c.get("phase") == "reference")
    assert found["steps"] == 8 and found["moved_by_the_fit"] > 1e-3
    assert found["gap"] < 1e-5 and found["loss_last"] < found["loss_first"]
    assert found["rms_gap_in_rates"] < 1e-4
    units = next(c for c in lines if c.get("phase") == "window")["units"]
    assert units["steps"] == 8 * units["fits"]
    assert units["samples"] == 2048 * units["steps"]
    # held by the check, not by a per-layer metric (PR 54): a miss is not correct
    assert [(c["value"], c["limit"]) for c in checks
            if "bytes uploaded inside the window" in c["what"]] == [(0.0, 0)]
    metrics = line["metrics"]
    if not trace:
        assert set(metrics) == {"fit_samples_per_s", "setup_s"}
        return
    assert set(metrics) >= set(COUNTED)        # a rehearsal has no device number
    assert not set(metrics) & set(TRACED)
    assert metrics["fm.fused_block_share"]["value"] == 0.0   # XLA's walk: a CPU
    assert metrics["fm.blocked_cell_share"]["value"] == 1.0


def test_one_bfloat16_pass_fails_the_cells_comparison(monkeypatch):
    """The control, rehearsed: on a CPU a product's precision changes
    nothing, so the looked-up blocks and the accumulated gradients are
    rounded to bfloat16 instead (what one pass of the MXU does to them).
    The cell's own verdicts then fail, and pass for the program as it is."""
    import jax.numpy as jnp

    from benchmark import run
    from benchmark.drivers import fm as driver
    from flinkml_tpu.models import _fm_sparse
    from flinkml_tpu.ops import sparse

    spec = run.load_spec(os.path.dirname(BENCH), CELL_NAME)
    ctx = run.Context(spec, 2 ** 31 + 5, 0.0, False, True, os.path.join(BENCH, "out"))
    s = driver.setup(ctx)
    ref = driver.reference_fit(s, s.pairs[0])
    s.timed = [(0, s.first[0])]
    cells = float(s.rows * s.nnz)
    window = {"fm.table_h2d_bytes": 0.0, "fm.steps": 8.0, "fm.fits": 1.0,
              "fm.cells": cells, "fm.blocked_cells": cells,
              "table.csr_rows_materialized": 0.0}
    sound = driver.verdicts(ctx, s, driver.compare(ref, s.first[0]), window)
    assert all(c["value"] <= c["limit"] for c in sound)

    low = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    look, add = sparse.block_lookup, sparse.block_accumulate
    monkeypatch.setattr(sparse, "block_lookup", lambda b, i, p=None: look(low(b), i))
    monkeypatch.setattr(sparse, "block_accumulate",
                        lambda i, c, n, p=None: add(i, low(c), n))
    _fm_sparse._trainer.cache_clear()
    try:
        cmp = driver.compare(ref, driver._fit(s, s.pairs[0]))
    finally:
        monkeypatch.undo()
        _fm_sparse._trainer.cache_clear()
    control = driver.verdicts(ctx, s, cmp, window)
    assert [c["value"] > c["limit"] for c in control] == [True, True] + [False] * 6
    assert control[0]["value"] > 100 * sound[0]["value"]      # the widest gap
    assert control[1]["value"] > 100 * sound[1]["value"]      # every parameter's


def test_the_control_script_rehearses(monkeypatch, capsys):
    """``chip_controls_fm.py`` end to end at the rehearsal's rows (on a
    CPU its one-pass control computes in float32, so both come out
    correct and the lookup is exact at both: what it reads on the chip is
    PERF.md's)."""
    import importlib.util
    import sys

    spec = importlib.util.spec_from_file_location(
        "chip_controls_fm", os.path.join(HERE, "chip_controls_fm.py"))
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    monkeypatch.setattr(sys, "argv", ["chip_controls_fm.py", "--rehearse", "--seeds",
                                      "1", "--first-seed", str(2 ** 31 + 41)])
    script.main()
    out = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()
           if x.startswith("{")]
    line = out[-1]
    assert line["sound_correct"] is True and line["sound_failed_checks"] == []
    assert line["control_correct"] is True and line["control_moved_parameters_by"] == 0.0
    lookup = next(x for x in out if "lookup_floats_off_at_highest" in x)
    assert lookup["lookup_floats_off_at_highest"] == 0
