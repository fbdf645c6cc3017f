"""What PR 38 added for ``als-yahoomusic.fit``, on the CPU: the float64
reference against a case worked by hand and against the normal equations
written out, the half-step's count against its own arithmetic, the
generator's table, the configuration and the entries' form, the bfloat16
control through the cell's own verdicts, and a rehearsal of the cell,
traced and not, and of the builder's control script. The metric sets are
held as SUBSETS: the next metric a cell gains must not break them."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import datagen_ratings, flops_bytes, flops_bytes_als
from benchmark.reference import als as reference

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL_NAME = "als-yahoomusic.fit"

with open(os.path.join(BENCH, "configs", "als-yahoomusic.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(BENCH, "workloads", f"{CELL_NAME}.json")) as f:
    CELL = json.load(f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)

COUNTED = ["compile.cache_misses.setup", "als.padded_slot_share"]
#: Read off the PROFILED fit (one fit fills a traced window on the chip).
OF_THE_TRACED_FIT = ["als.dispatch_s_per_fit", "als.readback_s_per_fit",
                     "api.fit_own_traced_s_per_fit"]
TRACED = ["als.half_step_device_ms", "als_half_step_roofline",
          "device.idle_share.fit", "device.idle_outside_spans.fit"]


def test_the_reference_on_a_case_worked_by_hand():
    """A target with ratings (4, 2) of the fixed rows (1, 0) and (1, 1),
    reg 0.5: A = [[2, 1], [1, 1]] + 0.5 * 2 * I = [[3, 1], [1, 2]], b = 4
    (1, 0) + 2 (1, 1) = (6, 2); x = (10, 0) / 5 = (2, 0). A target with
    no rating: 0. Implicit, alpha 1, the same two rows and a third fixed
    row (0, 2): Y'Y = [[2, 1], [1, 5]]; A = Y'Y + 4 (1, 0)(1, 0)' + 2 (1,
    1)(1, 1)' + I = [[9, 3], [3, 8]]; b = 5 (1, 0) + 3 (1, 1) = (8, 3)."""
    fixed = np.array([[1.0, 0.0], [1.0, 1.0], [0.0, 2.0]])
    rows = [(np.array([0, 1]), np.array([4.0, 2.0])),
            (np.zeros(0, np.int64), np.zeros(0))]
    x = reference.solve_targets(rows, fixed, 0.5)
    np.testing.assert_allclose(x, [[2.0, 0.0], [0.0, 0.0]], atol=1e-12)
    x = reference.solve_targets(rows[:1], fixed, 0.5, implicit=True, alpha=1.0)
    np.testing.assert_allclose(
        x[0], np.linalg.solve([[9.0, 3.0], [3.0, 8.0]], [8.0, 3.0]), atol=1e-12)


def test_a_fit_satisfies_its_own_normal_equations_and_lowers_the_error():
    rng = np.random.default_rng(0)
    users, items, k = 30, 20, 3
    u = rng.integers(0, users, 600)
    i = rng.integers(0, items, 600)
    r = rng.uniform(0, 5, 600)
    start = rng.normal(size=(items, k)) / np.sqrt(k)
    user_f, item_f = reference.fit(u, i, r, start, 3, 0.05)
    # the last half-step solved the items from user_f: written out, a
    # rating at a time
    for t in (0, 7, 19):
        mine = np.flatnonzero(i == t)
        a = sum(np.outer(user_f[u[j]], user_f[u[j]]) for j in mine)
        b = sum(r[j] * user_f[u[j]] for j in mine)
        lam = 0.05 * max(mine.size, 1)
        np.testing.assert_allclose((a + lam * np.eye(k)) @ item_f[t], b, atol=1e-9)
    one = reference.fit(u, i, r, start, 1, 0.05)
    assert reference.rmse(u, i, r, user_f, item_f) < reference.rmse(u, i, r, *one)
    # blocks of rows change nothing but the order of a sum
    whole = reference.solve_target(start, i[:500] % items, r[:500], 0.05)
    old, reference.BLOCK_ROWS = reference.BLOCK_ROWS, 64
    try:
        blocked = reference.solve_target(start, i[:500] % items, r[:500], 0.05)
    finally:
        reference.BLOCK_ROWS = old
    np.testing.assert_allclose(blocked, whole, rtol=1e-12)


def test_the_half_steps_count_is_its_own_arithmetic():
    c = flops_bytes_als.half_step(CONFIG["ratings"], CONFIG["users"],
                                  CONFIG["items"], CONFIG["rank"])
    n, k = 252_800_275, 100
    targets = (1_000_990 + 624_961) / 2
    assert c["flops"] == pytest.approx(
        n * k * (k + 1) + 2 * n * k + targets * (k ** 3 / 3 + 2 * k ** 2))
    assert c["bytes"] == pytest.approx(8 * n + (1_000_990 + 624_961) * k * 4)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = flops_bytes.least_seconds(c, peaks)
    assert bound == "flops" and 0.012 < least < 0.016        # 14 ms a half-step


def test_the_generators_table():
    users, items, ratings = 3000, 2000, 150_000
    u, i, r = datagen_ratings.rating_table(11, users, items, ratings)
    assert u.dtype == i.dtype == np.int32 and r.dtype == np.float32
    assert u.size == i.size == r.size == ratings
    assert np.all(np.diff(u) >= 0)                              # grouped by user
    degrees = np.bincount(u, minlength=users)
    assert degrees.min() >= datagen_ratings.USER_FLOOR and degrees.sum() == ratings
    assert degrees.max() > 3 * np.median(degrees)               # a head (flat at this size)
    np.testing.assert_array_equal(np.unique(i), np.arange(items))   # every item
    counts = np.bincount(i, minlength=items)
    assert counts.max() > 10 * np.median(counts)                # a heavy head
    assert r.min() >= 0 and r.max() <= 100 and np.all(r == np.rint(r))
    again = datagen_ratings.rating_table(11, users, items, ratings)
    assert all(np.array_equal(a, b) for a, b in zip((u, i, r), again))
    other = datagen_ratings.rating_table(12, users, items, ratings)
    assert not np.array_equal(i, other[1])
    assert datagen_ratings.user_degrees(1_000_990, 252_800_275).sum() == 252_800_275


def test_the_configuration_and_the_entries():
    assert CONFIG["architecture"] is None and CONFIG["reduced"] == ["max_iter"]
    assert (CONFIG["users"], CONFIG["items"], CONFIG["ratings"]) == (
        1_000_990, 624_961, 252_800_275)
    assert CONFIG["rank"] == 100 and CONFIG["reg_param"] == 1.4
    assert CONFIG["implicit_prefs"] is False and CONFIG["max_iter"] == 1
    assert len(CONFIG["source"]) <= 200 and len(CONFIG["guarantees"]) == 5
    (entry,) = [c for c in BENCHMARK["configs"] if c["name"] == "als-yahoomusic"]
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == ["max_iter"]
    assert entry["file"] == "benchmark/configs/als-yahoomusic.json"
    (cell,) = [w for w in BENCHMARK["workloads"] if w["name"] == CELL_NAME]
    assert cell["chips"] == CELL["chips"] == 1 and cell["why"] == CELL["why"]
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert CELL["sweep"] == [1.4, 0.7] and CELL["driver"] == "als"
    assert set(CELL["rehearse"]) == {"users", "items", "ratings", "limits"}  # never the rank
    assert CELL["limits"]["factor_gap"] == 1e-4 < CELL["rehearse"]["limits"]["factor_gap"]
    mine = {m["name"] for m in BENCHMARK["per_layer"] if CELL_NAME in m.get("workloads", [])}
    assert set(COUNTED + OF_THE_TRACED_FIT + TRACED) <= mine
    rate = next(m for m in BENCHMARK["end_to_end"] if m["name"] == "fit_samples_per_s")
    assert CELL_NAME in rate["workloads"]
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
    assert len(checks) == 7 and all(c["ok"] for c in checks)
    gaps = [c["value"] for c in checks[:2]]
    assert all(0 < g < CELL["rehearse"]["limits"]["factor_gap"] for g in gaps)
    # held by the check, not by a per-layer metric (PR 54): a miss is not correct
    assert [(c["value"], c["limit"]) for c in checks
            if "bytes uploaded inside the window" in c["what"]] == [(0.0, 0)]
    if trace:
        assert set(COUNTED + OF_THE_TRACED_FIT) <= set(line["metrics"])
        assert 0.0 < line["metrics"]["als.padded_slot_share"]["value"] < 0.5
    else:
        assert set(line["metrics"]) == {"fit_samples_per_s", "setup_s"}


def test_factors_read_in_one_bfloat16_pass_fail_the_cells_own_verdicts():
    """The control through ``drivers/als.verdicts`` at the rehearsal's
    size: on the CPU a product's precision changes nothing, so the
    factors a solve reads are rounded to bfloat16 instead (what one pass
    of the MXU makes of them)."""
    import ml_dtypes

    from benchmark import run
    from benchmark.drivers import als as driver

    spec = run.load_spec(ROOT, CELL_NAME)
    ctx = run.Context(spec, 5, 0.0, False, True, os.path.join(BENCH, "out"))
    s = types.SimpleNamespace(
        users=ctx.size("users"), items=ctx.size("items"), ratings=ctx.size("ratings"),
        rank=CONFIG["rank"], max_iter=1, seed=5, sweep=CELL["sweep"])
    s.user, s.item, s.rating = datagen_ratings.rating_table(5, s.users, s.items, s.ratings)
    from flinkml_tpu.models.als import start_factors

    low = lambda a: np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)
    start = np.asarray(start_factors(s.seed, s.items, s.rank))
    fits = {}
    for name, read in (("sound", lambda a: a), ("control", low)):
        user_f = reference.half_step(s.user, s.item, s.rating, s.users, read(start), 0.7)
        item_f = reference.half_step(s.item, s.user, s.rating, s.items,
                                     read(user_f.astype(np.float32)), 0.7)
        fits[name] = (user_f.astype(np.float32), item_f.astype(np.float32))
    counters = {"als.table_h2d_bytes": 0.0, "als.half_steps": 2.0, "als.fits": 1.0}
    ok = {}
    for name, fit in fits.items():
        s.timed, s.first = [(1, fit)], [None, fit]
        checks = driver.verdicts(ctx, s, driver.compare(s, 0.7, fit), counters)
        ok[name] = [c["value"] is not None and c["value"] <= c["limit"] for c in checks]
    assert all(ok["sound"])
    assert not ok["control"][0] and not ok["control"][1] and all(ok["control"][2:])


def test_the_builders_control_script_rehearses():
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "chip_controls_als.py"), "--seeds", "1",
         "--rehearse"], capture_output=True, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads([ln for ln in out.stdout.splitlines() if ln.startswith("{")][-1])
    assert line["sound_correct"] is True and line["sound_failed_checks"] == []
    # a CPU computes every precision alike: its "one pass" is sound too
    assert line["control_correct"] is True
