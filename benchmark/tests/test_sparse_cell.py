"""What PR 26 added for ``lr-criteo.fit``, on the CPU: the float64 sparse
replay against the dense float64 replay of the same rows densified; the
bfloat16 control; the field sampler's invariants; the sparse step's
count; the two readers scoped to the program's own span, on a trace made
by hand; and a rehearsal of the cell, traced and not."""

import json
import os

import numpy as np
import pytest

from benchmark import datagen_criteo, flops_bytes, flops_bytes_sparse
from benchmark.readers import (_xplane_program as xp, roofline_in_program_span,
                               trace_busy_in_program_span)
from benchmark.reference import linear, sparse_linear

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
MS = 1e6  # ns

with open(os.path.join(BENCH, "configs", "lr-criteo.json")) as f:
    CONFIG = json.load(f)
CARD, STRATUM = CONFIG["field_cardinalities"], CONFIG["field_stratum"]


def _small_rows(seed, rows=2048, dim=390):
    """Criteo-shaped rows at a dim that can be densified: 39 fields of
    ten columns, the configuration's cardinalities folded into them."""
    indptr, indices, values, y = datagen_criteo.criteo_rows(
        seed, rows, dim, CARD, 10)
    return indices.reshape(rows, 39), values.reshape(rows, 39), y, dim


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_sparse_replay_equals_the_dense_replay_of_the_densified_rows(seed):
    indices, values, y, dim = _small_rows(seed)
    order = sparse_linear.seeded_order(seed % (1 << 31), y.size)
    assert sparse_linear.seeded_order is linear.seeded_order  # one guarantee
    got = sparse_linear.minibatch_sgd(indices, values, dim, y, 30, 1.0, 256, order)
    dense = sparse_linear.densified(indices, values, dim)
    assert np.count_nonzero(dense) == indices.size      # distinct within a row
    want = linear.minibatch_sgd(dense, y, 30, 1.0, 256, order)
    assert np.abs(want).max() > 0.05
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-13)
    # a weight column of ones is no weight column; halved weights on half
    # the rows move the result
    ones = sparse_linear.minibatch_sgd(indices, values, dim, y, 30, 1.0, 256, order,
                                       weights=np.ones(y.size))
    np.testing.assert_allclose(ones, got, rtol=0, atol=1e-15)
    w = np.where(np.arange(y.size) % 2, 0.5, 1.0)
    assert np.abs(sparse_linear.minibatch_sgd(
        indices, values, dim, y, 30, 1.0, 256, order, weights=w) - got).max() > 1e-4


def test_the_bfloat16_control_stands_apart_and_other_faults_show():
    indices, values, y, dim = _small_rows(11)
    order = sparse_linear.seeded_order(11, y.size)
    sound = sparse_linear.minibatch_sgd(indices, values, dim, y, 30, 1.0, 256, order)
    low = sparse_linear.minibatch_sgd(indices, values, dim, y, 30, 1.0, 256, order,
                                      round_to=sparse_linear.to_bfloat16)
    assert 1e-5 < np.abs(low - sound).max() < 1e-2
    other_order = sparse_linear.minibatch_sgd(indices, values, dim, y, 30, 1.0, 256,
                                              order[::-1])
    half_batch = sparse_linear.minibatch_sgd(indices, values, dim, y, 30, 1.0, 128, order)
    for wrong in (other_order, half_batch):
        assert np.abs(wrong - sound).max() > 1e-3


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 1234])
def test_field_sampler_invariants(seed):
    rows, dim = 200_000, CONFIG["dim"]
    indptr, indices, values, y = datagen_criteo.criteo_rows(seed, rows, dim, CARD, STRATUM)
    assert indptr.dtype == np.int64 and indices.dtype == np.int32
    assert values.dtype == np.float32 and y.dtype == np.float32
    np.testing.assert_array_equal(indptr, np.arange(rows + 1) * 39)
    cols = indices.reshape(rows, 39)
    assert (np.diff(cols, axis=1) > 0).all()            # sorted, distinct
    fields = np.arange(39) * STRATUM
    assert (cols >= fields).all() and (cols < fields + STRATUM).all()  # in stratum
    assert cols.max() < dim
    assert np.all(values == np.float32(1 / np.sqrt(39)))
    # a field of c values never leaves its first c columns
    for f in np.flatnonzero(np.asarray(CARD) < STRATUM):
        assert (cols[:, f] - fields[f]).max() < CARD[f]
    three = CARD.index(3)
    head = np.mean(cols[:, three] == fields[three])
    assert abs(head - (1 / 3) ** (1 / 3)) < 0.01         # 69 % on one column
    big = CARD.index(10131227)
    assert np.mean(cols[:, big] == fields[big]) < 0.01
    assert abs(y.mean() - datagen_criteo.POSITIVE_SHARE) < 0.02
    assert set(np.unique(y)) == {0.0, 1.0}
    again = datagen_criteo.criteo_rows(seed, rows, dim, CARD, STRATUM)
    assert again[1].tobytes() == indices.tobytes() and again[3].tobytes() == y.tobytes()
    shorter = datagen_criteo.criteo_rows(seed, 70_000, dim, CARD, STRATUM)
    assert shorter[1].tobytes() == indices[:70_000 * 39].tobytes()
    other = datagen_criteo.criteo_rows(seed + 1, rows, dim, CARD, STRATUM)
    assert other[1].tobytes() != indices.tobytes()
    with pytest.raises(ValueError, match="pass dim"):
        datagen_criteo.criteo_rows(seed, 10, 999_998, CARD, STRATUM)


def test_the_configuration_is_the_issues():
    assert len(CARD) == 39 and CARD[:13] == [64] * 13
    assert CONFIG["dim"] == 1_000_000 and CONFIG["nnz"] == 39
    assert 39 * STRATUM <= CONFIG["dim"] < 39 * (STRATUM + 1)
    assert CONFIG["rows"] == 256 * CONFIG["global_batch_size"] == 16_777_216
    assert CONFIG["reduced"] == ["rows"] and CONFIG["rows_source"] == 45_840_617
    with open(os.path.join(BENCH, "workloads", "lr-criteo.fit.json")) as f:
        cell = json.load(f)
    assert (cell["max_iter"], cell["learning_rate"], cell["trace_units"]) == (160, 1.0, 1)
    assert set(cell["rehearse"]) <= {"rows", "global_batch_size", "max_iter", "limits"}


def test_sparse_step_count():
    c = flops_bytes_sparse.sparse_lr_step(65_536, 39, 1_000_000)
    assert c["flops"] == 4 * 65_536 * 39
    assert c["bytes"] == 65_536 * 39 * 8 + 2 * 65_536 * 4 + 3 * 1_000_000 * 4
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    seconds, bound = flops_bytes.least_seconds(c, peaks)
    assert bound == "bytes" and seconds == pytest.approx(32_971_520 / 819e9)
    # one dense pass over the same cells, indices apart, is the dense count
    dense = flops_bytes.dense_lr_step(65_536, 39)
    assert c["flops"] == dense["flops"]


def _by_hand():
    """A 100 ms window, one fit. The chip runs a zero fill at 5-10 ms, two
    staging writes at 20-22 and 30-32 ms, and the loop's ``while`` at
    50-80 ms with a body operation inside it; the program is inside
    ``trainer.loop`` from 45 to 85 ms."""
    ops = [["broadcast.1", 5 * MS, 5 * MS], ["dynamic_update_slice.1", 20 * MS, 2 * MS],
           ["dynamic_update_slice.1", 30 * MS, 2 * MS], ["while.2", 50 * MS, 30 * MS],
           ["fusion.3", 51 * MS, 20 * MS]]
    host = [["bench:window", 0.0, 100 * MS], ["bench:fit", 0.0, 96 * MS],
            ["flinkml:fit", 0.0, 95 * MS], ["flinkml:trainer.loop", 45 * MS, 40 * MS]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]}]}


def _obs(monkeypatch, t, **more):
    monkeypatch.setattr(xp, "this_run", lambda obs: t if obs.get("trace") else None)
    return {"trace": {"window_s": 0.1}, "traced_units": {"fits": 1, "steps": 10},
            "units": {}, "counters": {}, **more}


def test_busy_in_program_span_leaves_the_upload_out(monkeypatch):
    loop = {"span": "trainer.loop", "unit": "steps"}
    obs = _obs(monkeypatch, _by_hand())
    # 30 ms of the while, none of the 9 ms before the span: 3 ms a step
    assert trace_busy_in_program_span.read(loop, obs) == pytest.approx(3.0)
    assert trace_busy_in_program_span.read({**loop, "unit": "fits"}, obs) == pytest.approx(30.0)
    # an operation that straddles the span's edge counts for the part inside
    t = _by_hand()
    t["planes"][0]["lines"][0]["events"].append(["copy.9", 84 * MS, 4 * MS])
    assert trace_busy_in_program_span.read(loop, _obs(monkeypatch, t)) == pytest.approx(3.1)
    # no such span (the parent, another cell), no such unit, a rehearsal
    assert trace_busy_in_program_span.read({**loop, "span": "absent"}, obs) is None
    assert trace_busy_in_program_span.read({**loop, "unit": "calls"}, obs) is None
    assert trace_busy_in_program_span.read(loop, {"trace": None, "traced_units": {"steps": 1}}) is None
    # a span in which the chip did nothing reads nothing, not 0
    idle = _by_hand()
    idle["planes"][1]["lines"][0]["events"][-1] = ["flinkml:trainer.loop", 82 * MS, 8 * MS]
    assert trace_busy_in_program_span.read(loop, _obs(monkeypatch, idle)) is None


def test_roofline_in_program_span(monkeypatch):
    params = {"span": "trainer.loop", "unit": "steps", "module": "flops_bytes_sparse",
              "count": "sparse_lr_step",
              "args": {"batch": "global_batch_size", "nnz": "nnz", "dim": "dim"}}
    obs = _obs(monkeypatch, _by_hand(), config=CONFIG, cell={"max_iter": 160},
               peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    least = 32_971_520 / 819e9
    assert roofline_in_program_span.read(params, obs) == pytest.approx(100 * least / 0.003)
    assert roofline_in_program_span.read({**params, "span": "absent"}, obs) is None


#: What PR 26 listed for the cell and the cell still feeds: every timed
#: fit finds its placement kept (PR 37), so the host data path's metrics
#: list ``lr-criteo.fit-cold`` alone (PR 54; ``test_lr_cold_cell.py``).
NEW_COUNTED = ["hostdata.ingest_s_per_fit", "trainer.readback_s_per_fit",
               "hostdata.csr_materialized_rows"]
NEW_TRACED = ["device.idle_share.fit", "device.idle_outside_spans.fit"]
COLD_ONLY = ["hostdata.shuffle_s_per_fit", "hostdata.stage_wait_s_per_fit",
             "hostdata.upload_s_per_fit", "hostdata.upload_bytes_per_s",
             "hostdata.sparse_pack_s_per_fit"]


@pytest.mark.parametrize("trace", [0, 1])
def test_a_rehearsal_of_the_cell(trace, capsys):
    from benchmark import run

    rc = run.main(["--workload", "lr-criteo.fit", "--seed", str(2 ** 31 + 26),
                   "--seconds", "0.3", "--trace", str(trace), "--rehearse"])
    lines = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    line = lines[-1]
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    checks = {c["what"].split(":")[0].split(" (")[0]: c for c in lines
              if c.get("phase") == "check"}
    assert len(checks) == 5 and all(c["ok"] for c in checks.values())
    built = next(c for w, c in checks.items() if w.startswith("SparseVector rows"))
    assert built["value"] == 0.0
    metrics = line["metrics"]
    if not trace:
        assert set(metrics) == {"fit_samples_per_s", "setup_s"}
        return
    # a rehearsal has no device number, and a hit none of the host data path's
    assert set(NEW_COUNTED) <= set(metrics)
    assert not (set(NEW_TRACED) | set(COLD_ONLY)) & set(metrics)
    assert metrics["hostdata.csr_materialized_rows"]["value"] == 0.0
    assert metrics["compile.cache_misses.setup"]["value"] >= 0.0
    # the profiled fit: the loop is most of it, the rest is the fit's own
    assert metrics["trainer.loop_own_traced_s_per_fit"]["value"] > 0.0
    assert metrics["api.fit_own_traced_s_per_fit"]["value"] >= 0.0


def test_the_cells_metrics_are_the_issues():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for m in bench["per_layer"]
              if "lr-criteo.fit" in m.get("workloads", [])}
    assert set(NEW_COUNTED) | set(NEW_TRACED) <= listed
    assert not set(COLD_ONLY) & listed
    assert all("workloads" in m for m in bench["per_layer"])
    misses = next(m for m in bench["per_layer"] if m["name"] == "compile.cache_misses.setup")
    assert {"chain-a9a.transform", "lr-a9a.fit", "lr-criteo.fit"} <= set(misses["workloads"])
