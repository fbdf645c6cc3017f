"""What PR 55 added for ``lr-criteo-dp4.fit``, on the CPU: the cell's and
the configuration's files against the issue's table letter for letter,
the configuration against ``lr-criteo``'s shapes, the rehearsal end to end
on the devices it finds, the chip's count of a step's bytes against a
hand-reckoned step, the two new readers on made-up numbers, and the
driver's own checks biting."""

import json
import os
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL, CONFIG, ONE_CHIP = "lr-criteo-dp4.fit", "lr-criteo-dp4", "lr-criteo"


def _read(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


BENCHMARK = _read(os.path.dirname(BENCH), "BENCHMARK.json")


def test_the_cells_file_is_the_issues_table():
    cell = _read(BENCH, "workloads", f"{CELL}.json")
    assert {k: cell[k] for k in ("config", "driver", "chips", "rate_metric",
                                 "max_iter", "learning_rate", "trace_units")} == {
        "config": CONFIG, "driver": "fit_sparse_dp", "chips": 4,
        "rate_metric": "fit_samples_per_s", "max_iter": 700, "learning_rate": 1.0,
        "trace_units": 1}
    # one pass of the file: ceil(45,840,617 / 65,536)
    assert cell["max_iter"] == -(-45_840_617 // 65_536)
    assert set(cell["limits"]) == {"coef_gap"} and 0 < cell["limits"]["coef_gap"] < 1e-4
    # a rehearsal overrides rows, batch and steps only
    assert set(cell["rehearse"]) == {"rows", "global_batch_size", "max_iter"}
    (entry,) = [w for w in BENCHMARK["workloads"] if w["name"] == CELL]
    assert entry == {"name": CELL, "config": CONFIG, "traffic": "fit", "chips": 4,
                     "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert BENCHMARK["workloads"][-1] == entry                 # appended
    # the benchmark's only cell of more than one chip (the limit is a quarter)
    assert [w["name"] for w in BENCHMARK["workloads"] if w["chips"] != 1] == [CELL]
    (rate,) = [m for m in BENCHMARK["end_to_end"] if m["name"] == "fit_samples_per_s"]
    assert rate["workloads"][-1] == CELL


def test_the_configuration_is_the_whole_file_at_lr_criteos_shapes():
    config = _read(BENCH, "configs", f"{CONFIG}.json")
    one = _read(BENCH, "configs", f"{ONE_CHIP}.json")
    for key in ("dim", "nnz", "value", "feature_dtype", "index_dtype", "row_layout",
                "loss", "global_batch_size", "reg", "tol", "fields", "field_stratum",
                "field_cardinalities", "sparse_layout"):
        assert config[key] == one[key], key
    for key in ("field_cardinalities", "sampler", "labels", "learning_rate"):
        assert config["assumed"][key] == one["assumed"][key], key
    assert config["rows"] == config["rows_source"] == one["rows_source"] == 45_840_617
    assert config["reduced"] == [] and config["workers"] == 4
    assert "workers" in config["assumed"]
    (entry,) = [c for c in BENCHMARK["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == [] and entry["source"] == config["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert BENCHMARK["configs"][-1] == entry
    # the deployment and the sharding rule, in words
    assert "11,460,155" in config["deployment"] and "4 MB" in config["deployment"]
    words = " ".join(config["guarantees"])
    for said in ("n_local = ceil(rows / p)", "[d * n_local, (d + 1) * n_local)",
                 "lo = min((k mod W) * b, n_local - b)", "weight 0"):
        assert said in words, said


def test_the_words_give_the_cells_numbers():
    from benchmark.reference import sparse_linear_dp as ref

    config = _read(BENCH, "configs", f"{CONFIG}.json")
    n_local, local, windows = ref.shard_layout(
        config["rows"], config["workers"], config["global_batch_size"])
    assert (n_local, local, windows) == (11_460_155, 16_384, 700)
    assert config["workers"] * n_local - config["rows"] == 3
    # a chip's share before the coefficient: 22.9 % of 16 GB
    assert round(n_local * (39 * 8 + 8) / 16e9, 3) == 0.229


def test_the_new_entries_are_the_issues_and_list_this_cell_alone():
    new = BENCHMARK["per_layer"][-6:]
    assert [m["name"] for m in new] == [
        "sharding.psum_device_ms_per_step", "sharding.psum_step_share",
        "sharding.psum_bus_bytes_per_s", "sharding.chip_busy_skew",
        "sparse_lr_dp_step_roofline", "sharding.placement_hit_share"]
    assert [m["unit"] for m in new] == ["ms", "%", "B/s", "%", "%", "fits/fit"]
    assert [m["layer"] for m in new] == ["Sharding"] * 4 + ["Kernels", "Sharding"]
    assert [m["source"] for m in new] == ["device_trace"] * 5 + ["program_counter"]
    assert all(m["workloads"] == [CELL] and m["moves"] == "fit_samples_per_s"
               for m in new)
    assert len(BENCHMARK["per_layer"]) == 114          # 108 and the issue's six at most
    # the one-chip roofline counts the global batch as one chip's: not here
    (one,) = [m for m in BENCHMARK["per_layer"] if m["name"] == "sparse_lr_loop_roofline"]
    assert CELL not in one["workloads"]
    for m in BENCHMARK["per_layer"]:
        if CELL in m["workloads"] and m not in new:          # appended, nothing moved
            assert m["workloads"][-1] == CELL and len(m["workloads"]) > 1
    # the hit share reads hostdata.placement_hit_share's counter under a
    # name of this cell's own: that entry's list is held exactly by
    # test_lr_cold_cell.py, which a PR that adds a cell may not edit
    (pinned,) = [m for m in BENCHMARK["per_layer"]
                 if m["name"] == "hostdata.placement_hit_share"]
    assert CELL not in pinned["workloads"]
    assert (_read(BENCH, "metrics", "sharding.placement_hit_share.json")["params"]
            == _read(BENCH, "metrics", "hostdata.placement_hit_share.json")["params"])


def test_a_chips_bytes_a_step_reckoned_by_hand():
    from benchmark import flops_bytes, flops_bytes_sparse, flops_bytes_sparse_dp

    count = flops_bytes_sparse_dp.sparse_lr_dp_step(65_536, 4, 39, 1_000_000)
    # 16,384 rows' cells at 8 B, their labels and weights, five [dim] passes
    assert count["bytes"] == 16_384 * 39 * 8 + 2 * 16_384 * 4 + 5 * 4_000_000
    assert count["bytes"] == 25_242_880 and count["flops"] == 4 * 16_384 * 39
    # one worker's step but for the two passes the collective adds
    one = flops_bytes_sparse.sparse_lr_step(65_536, 39, 1_000_000)
    alone = flops_bytes_sparse_dp.sparse_lr_dp_step(65_536, 1, 39, 1_000_000)
    assert alone["bytes"] - one["bytes"] == 2 * 4_000_000
    assert alone["flops"] == one["flops"]
    peaks = _read(BENCH, "peaks.json")["devices"]["TPU v5 lite"]
    least, bound = flops_bytes.least_seconds(count, peaks)
    assert bound == "bytes" and least == pytest.approx(25_242_880 / 819e9)
    # a batch that does not divide: the chip's share is rounded up, as the fit's
    assert flops_bytes_sparse_dp.sparse_lr_dp_step(10, 4, 1, 0)["flops"] == 4 * 3


def test_the_skew_reader_on_a_made_up_busy_s():
    from benchmark.readers import trace_busy_skew

    def obs(*busy):
        return {"trace": {"busy_s": {f"/device:TPU:{i}": b for i, b in enumerate(busy)}}}

    assert trace_busy_skew.read({}, obs(2.0, 2.0, 2.0, 2.0)) == 0.0
    assert trace_busy_skew.read({}, obs(1.0, 1.0, 1.0, 1.4)) == pytest.approx(
        100 * 0.4 / 1.1)
    assert trace_busy_skew.read({}, obs(3.0)) is None            # one chip
    assert trace_busy_skew.read({}, obs(0.0, 0.0)) is None
    assert trace_busy_skew.read({}, {"trace": None}) is None     # a rehearsal


def test_the_bus_rate_reader_on_made_up_counters(monkeypatch):
    from benchmark.readers import collective_bus_rate, trace_phase_device_time

    params = _read(BENCH, "metrics", "sharding.psum_bus_bytes_per_s.json")["params"]
    seen = []

    def phase_ms(asked, obs):
        seen.append(asked)
        return 0.1                                   # ms a step, a chip's mean

    monkeypatch.setattr(trace_phase_device_time, "read", phase_ms)
    obs = {"counters": {"trainer.psum_bytes": 3 * 700 * 4_000_008.0,
                        "trainer.mesh_devices": 12.0},
           "units": {"fits": 3, "steps": 2100}}
    assert collective_bus_rate.read(params, obs) == pytest.approx(
        1.5 * 4_000_008 / 1e-4)
    assert [(a["programs"], a["phase"], a["unit"]) for a in seen] == [
        (["lr_sparse_loop"], "lr.psum", "steps")]
    # one worker, a program without the counters, a phase with no operation
    assert collective_bus_rate.read(params, {**obs, "counters": {
        "trainer.psum_bytes": 0.0, "trainer.mesh_devices": 3.0}}) is None
    assert collective_bus_rate.read(params, {**obs, "counters": {}}) is None
    monkeypatch.setattr(trace_phase_device_time, "read", lambda *_: None)
    assert collective_bus_rate.read(params, obs) is None


def test_the_driver_is_fit_sparses_but_for_what_four_chips_need():
    from benchmark.drivers import fit, fit_sparse, fit_sparse_dp

    assert fit_sparse_dp.window is fit.window
    assert fit_sparse_dp.dense._fit is fit._fit
    assert fit_sparse_dp.sparse.setup is fit_sparse.setup
    assert fit_sparse_dp.check is not fit_sparse.check


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rehearsal_runs_end_to_end_on_the_devices_it_finds(trace, capsys):
    import jax

    from benchmark import run

    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 55),
                   "--seconds", "0.3", "--trace", str(trace), "--rehearse"])
    lines = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]
    line = lines[-1]
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    (host,) = [l for l in lines if l.get("phase") == "host"]
    assert host["workers"] == jax.device_count() == line["device"]["count"]
    assert host["host_peak_rss_bytes"] > 0
    checks = {l["what"].split(" (")[0].split(":")[0]: l for l in lines
              if l.get("phase") == "check"}
    assert len(checks) == 7 and all(c["ok"] for c in checks.values())
    (gap,) = [c for w, c in checks.items() if w.startswith("last timed fit")]
    assert 0 < gap["value"] < 1e-6
    if trace:
        m = line["metrics"]
        assert m["sharding.placement_hit_share"]["value"] == 1.0
        assert m["hostdata.csr_materialized_rows"]["value"] == 0.0
        assert m["trainer.sparse_fused_block_share"]["value"] == 0.0   # no TPU here
        # a device's numbers are a chip's: a rehearsal prints none of them
        assert [k for k in m if k.startswith("sharding.") or "roofline" in k] == [
            "sharding.placement_hit_share"]
    else:
        assert set(line["metrics"]) == {"fit_samples_per_s", "setup_s"}


def _state(rows=1027, steps=6, batch=128, seed=5):
    """A fitted state as ``fit_sparse_dp.setup`` leaves it, at a test's
    size on every device the CPU shows (the driver's workers), with a
    window of one fit."""
    import jax

    from benchmark import datagen_criteo
    from flinkml_tpu.models import LogisticRegression
    from flinkml_tpu.parallel import DeviceMesh
    from flinkml_tpu.table import CsrColumn, Table

    config = _read(BENCH, "configs", f"{CONFIG}.json")
    dim, stratum = 20_000, 512
    indptr, indices, values, y = datagen_criteo.criteo_rows(
        seed, rows, dim, config["field_cardinalities"], stratum)
    table = Table({"features": CsrColumn(indptr, indices, values, dim), "label": y})

    workers = jax.device_count()

    def fit():
        est = (LogisticRegression(mesh=DeviceMesh())
               .set_max_iter(steps).set_global_batch_size(batch)
               .set_learning_rate(1.0).set_reg(0.0).set_tol(0.0).set_seed(seed))
        return np.asarray(est.fit(table).coefficient, np.float64)

    s = types.SimpleNamespace(
        rows=rows, dim=dim, nnz=39, batch=batch, max_iter=steps, workers=workers,
        indices=indices, values=values, y=y, table=table, coefs=[fit(), fit()])
    ctx = types.SimpleNamespace(
        seed=seed, cell={"learning_rate": 1.0},
        size=lambda key: {"limits": {"coef_gap": 1e-6}}[key])
    return ctx, s, {"unit_walls_s": [0.1]}


def test_the_checks_pass_on_a_sound_fit_and_each_bites():
    from benchmark.drivers import fit_sparse_dp

    ctx, s, result = _state()
    counters = {"table.csr_rows_materialized": 0.0,
                "trainer.mesh_devices": float(s.workers)}
    checks = fit_sparse_dp.check(ctx, s, result, counters)
    assert [c["value"] <= c["limit"] for c in checks] == [True] * 6
    # a program from before the counter says so and passes
    (said,) = [c for c in fit_sparse_dp.check(
        ctx, s, result, {"table.csr_rows_materialized": 0.0})
        if c["what"].endswith("the program has no such count")]
    assert said["value"] == 0.0
    # the replay of ANOTHER number of workers is another problem
    other = types.SimpleNamespace(**{**vars(s), "workers": 1 if s.workers > 1 else 2})
    bad = fit_sparse_dp.check(ctx, other, result, counters)
    assert [c["value"] <= c["limit"] for c in bad] == [
        True, True, True, False, False, False]
    # a timed fit that differs from set-up's
    off = types.SimpleNamespace(**{**vars(s), "coefs": [s.coefs[0], s.coefs[1] + 1e-9]})
    assert fit_sparse_dp.check(ctx, off, result, counters)[1]["value"] > 0.0


def test_the_bfloat16_replay_fails_the_gap_the_float64_one_sets():
    """The control of the cell's one limit, at a test's size: the sharded
    replay with values, coefficient and multipliers rounded to bfloat16 is
    far from the float64 one, the program's float32 fit close."""
    from benchmark.reference import sparse_linear_dp as ref

    ctx, s, _ = _state(steps=40)
    order = ref.seeded_order(ctx.seed, s.rows)
    args = (s.indices.reshape(s.rows, -1), s.values.reshape(s.rows, -1), s.dim, s.y,
            s.max_iter, 1.0, s.batch, order, s.workers)
    wide = ref.minibatch_sgd(*args)
    low = ref.minibatch_sgd(*args, round_to=ref.to_bfloat16)
    sound = float(np.max(np.abs(s.coefs[-1] - wide)))
    control = float(np.max(np.abs(low - wide)))
    assert sound < 1e-6 and control > 30 * sound
