"""The sparse linear fit over a data axis of more than one device (PR 55,
``lr-criteo-dp4``): the fit on meshes of 1, 2 and 4 devices against the
benchmark's float64 replay of the SHARDED order, written from the
configuration's words (``benchmark/reference/sparse_linear_dp.py``); the
shards' gradients tied to the whole batch's; the collective's phase and
the two counters of ``_run_chunked``; and every per-layer metric of the
four-chip cell read off a made-up profile of four device planes as off
one plane of the same chip.
"""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import datagen_criteo, trace
from benchmark.reference import sparse_linear, sparse_linear_dp
from flinkml_tpu.models import LogisticRegression, _linear_sgd
from flinkml_tpu.parallel import DeviceMesh
from flinkml_tpu.table import CsrColumn, Table
from flinkml_tpu.utils import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "lr-criteo-dp4.fit"

#: A Criteo-profile table at a test's size: the configuration's 39 fields
#: and cardinalities on strata of 512 columns; 4,099 rows are no multiple
#: of two or four, nor (a worker's share) of a worker's batch.
ROWS, DIM, STRATUM, BATCH, STEPS, RATE, SEED = 4099, 20_000, 512, 512, 20, 1.0, 11


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs", "lr-criteo-dp4.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def rows():
    indptr, indices, values, y = datagen_criteo.criteo_rows(
        SEED, ROWS, DIM, _config()["field_cardinalities"], STRATUM)
    return indptr, indices, values, y


def _table(rows, take=None):
    indptr, indices, values, y = rows
    column = CsrColumn(indptr, indices, values, DIM)
    if take is not None:
        column, y = column.take(take), y[take]
    return Table({"features": column, "label": y})


def _fit(table, devices, steps=STEPS, batch=BATCH):
    est = (LogisticRegression(mesh=DeviceMesh(devices=jax.devices()[:devices]))
           .set_max_iter(steps).set_global_batch_size(batch)
           .set_learning_rate(RATE).set_reg(0.0).set_tol(0.0).set_seed(SEED))
    return np.asarray(est.fit(table).coefficient, np.float64)


def _trainer_counts():
    return dict(metrics.group("trainer").snapshot()["counters"])


# -- (a) the fit on p devices is the replay of the sharded order ----------------

@pytest.mark.parametrize("devices", [1, 2, 4])
def test_the_fit_on_p_devices_is_the_float64_replay_of_the_sharded_order(
        rows, devices):
    _, indices, values, y = rows
    n_local, local, windows = sparse_linear_dp.shard_layout(ROWS, devices, BATCH)
    # ragged every way the four-chip cell is: padding rows at the last
    # worker's end, a last window pulled back, steps that wrap
    assert devices * n_local >= ROWS and n_local % local and STEPS > windows
    assert devices == 1 or devices * n_local > ROWS
    got = _fit(_table(rows), devices)
    order = sparse_linear_dp.seeded_order(SEED, ROWS)
    want = sparse_linear_dp.minibatch_sgd(
        indices.reshape(ROWS, -1), values.reshape(ROWS, -1), DIM, y, STEPS, RATE,
        BATCH, order, devices)
    assert np.max(np.abs(want)) > 0.05
    assert np.max(np.abs(got - want)) < 2e-6
    if devices == 1:
        # one worker is the one-worker replay, to the bit
        assert np.array_equal(want, sparse_linear.minibatch_sgd(
            indices.reshape(ROWS, -1), values.reshape(ROWS, -1), DIM, y, STEPS,
            RATE, BATCH, order))
    else:
        # and another problem than the one-worker order's: the check bites
        other = sparse_linear_dp.minibatch_sgd(
            indices.reshape(ROWS, -1), values.reshape(ROWS, -1), DIM, y, STEPS,
            RATE, BATCH, order, 1)
        assert np.max(np.abs(got - other)) > 1e-3


def test_the_words_shard_every_row_once_and_pad_the_last_worker_alone():
    rows, p, batch = 45_840_617, 4, 65_536
    n_local, local, windows = sparse_linear_dp.shard_layout(rows, p, batch)
    assert (n_local, local, windows) == (11_460_155, 16_384, 700)
    assert p * n_local - rows == 3               # three padding rows, the last worker's
    first = sparse_linear_dp.step_positions(rows, p, batch, 0)
    last = sparse_linear_dp.step_positions(rows, p, batch, 699)
    assert first.size == batch and last.size == batch - 3
    # the last window is pulled back to end at the shard's end
    assert last[local - 1] == n_local - 1 and last[0] == n_local - local
    # a pass reads every row (the pulled-back window reads some twice)
    seen = np.zeros(rows, bool)
    for k in range(windows):
        seen[sparse_linear_dp.step_positions(rows, p, batch, k)] = True
    assert seen.all()


# -- (b) the shards' gradients add up to the whole batch's ----------------------

def test_four_shards_gradients_of_a_step_add_up_to_the_one_device_gradient(rows):
    """One step from zero coefficients: ``c = -rate / n * g``, so a fit of
    one step over a table gives its gradient sum ``g``. The four workers'
    windows of step 0, each fitted alone on one device, add up to the
    gradient of their union on one device, and to the four-device fit's."""
    order = sparse_linear_dp.seeded_order(SEED, ROWS)
    n_local, local, _ = sparse_linear_dp.shard_layout(ROWS, 4, BATCH)
    shards = [order[d * n_local:d * n_local + local] for d in range(4)]
    assert np.array_equal(np.concatenate(shards),
                          order[sparse_linear_dp.step_positions(ROWS, 4, BATCH, 0)])

    def gradient(take):
        return -_fit(_table(rows, take), 1, steps=1, batch=take.size) * take.size / RATE

    parts = [gradient(np.sort(s)) for s in shards]
    whole = gradient(np.sort(np.concatenate(shards)))
    scale = np.max(np.abs(whole))
    assert scale > 1.0
    assert np.max(np.abs(sum(parts) - whole)) < 1e-6 * scale
    on_four = -_fit(_table(rows), 4, steps=1) * BATCH / RATE
    assert np.max(np.abs(on_four - whole)) < 1e-6 * scale


# -- (c) the phase and the counters ---------------------------------------------

def test_the_collective_is_a_phase_of_the_sparse_loop():
    assert _linear_sgd.SPARSE_PHASES == (
        "lr.sparse_lookup", "lr.sparse_accumulate", "lr.psum")
    # tests/test_phases.py holds every declared phase to an instruction of
    # the compiled program (eight devices), tests/test_chip_compile.py to
    # the chip's: on four chips an all-reduce, on one nothing.


@pytest.mark.parametrize("devices", [1, 4])
def test_a_fit_counts_its_workers_and_the_bytes_it_reduces(rows, devices):
    before = _trainer_counts()
    _fit(_table(rows), devices)
    added = {k: v - before.get(k, 0.0) for k, v in _trainer_counts().items()}
    assert added["steps"] == STEPS
    assert added["mesh_devices"] == devices
    # a device hands the all-reduce the gradient and two sums a step; one
    # worker reduces nothing
    assert added["psum_bytes"] == (devices > 1) * STEPS * (DIM + 2) * 4


def test_the_dense_fit_counts_them_too():
    rng = np.random.default_rng(3)
    x = rng.normal(size=(1001, 7))            # float64, as the suite's x64 places it
    table = Table({"features": x, "label": (x[:, 0] > 0).astype(np.float64)})
    before = _trainer_counts()
    _fit(table, 2, steps=5, batch=100)
    added = {k: v - before.get(k, 0.0) for k, v in _trainer_counts().items()}
    assert (added["mesh_devices"], added["psum_bytes"]) == (2, 5 * (7 + 2) * 8)


# -- (d) four device planes read as one plane of the same chip -------------------

#: One chip's made-up fit: two runs of ``lr_sparse_loop`` in a window of
#: 10 ms, each a ``while`` over a lookup kernel, an accumulate kernel, an
#: all-reduce and an unphased update, and idle time between and after.
_PHASES = {"while.1": None, "lookup.2": "lr.sparse_lookup",
           "accumulate.3": "lr.sparse_accumulate", "all-reduce.4": "lr.psum",
           "fusion.5": None}


def _chip(shift: float = 0.0, stretch: float = 1.0):
    ops, runs = [], []
    for start in (1e6, 5e6):
        a = start + shift
        runs.append(["lr_sparse_loop", a, 3e6 * stretch])
        ops.append(["while.1", a, 3e6 * stretch])
        for name, at, dur in (("lookup.2", 0.1e6, 0.8e6), ("accumulate.3", 1.0e6, 0.9e6),
                              ("all-reduce.4", 2.0e6, 0.4e6), ("fusion.5", 2.5e6, 0.3e6)):
            ops.append([name, a + at * stretch, dur * stretch])
    return ops, runs


def _profile(chips):
    """``(ops form, modules form)`` of a profile whose device planes are
    ``chips`` (each ``_chip(...)``'s): ``trace.load``'s plain data."""
    host = {"name": trace.HOST_PLANE, "lines": [{"name": "main", "events": [
        ["bench:window", 0.0, 10e6], ["bench:fit", 0.5e6, 9e6],
        ["flinkml:fit", 0.6e6, 8.8e6], ["flinkml:trainer.loop", 0.7e6, 8e6],
        ["flinkml:trainer.readback", 8.8e6, 0.4e6]]}]}
    with_ops = {"planes": [host] + [
        {"name": f"/device:TPU:{i}", "lines": [{"name": trace.OP_LINE, "events": ops}]}
        for i, (ops, _) in enumerate(chips)]}
    with_modules = {"planes": [host] + [
        {"name": f"/device:TPU:{i}", "lines": [{"name": "XLA Modules", "events": runs}]}
        for i, (_, runs) in enumerate(chips)]}
    return with_ops, with_modules


def _observed(monkeypatch, chips):
    """``obs`` as ``benchmark/run.py`` builds it for a traced run whose
    profile is ``_profile(chips)``, the readers' own loaders pointed at
    it."""
    from benchmark.readers import _xplane_modules as xm
    from benchmark.readers import _xplane_phases as xph
    from benchmark.readers import _xplane_program as xp

    with_ops, with_modules = _profile(chips)
    by_chip = xm.programs(with_modules)
    phased = {"chips": len(by_chip), "programs": xph.attribute(
        trace.device_ops(with_ops), by_chip, xp.window(with_modules),
        {"lr_sparse_loop": [_PHASES]})}
    monkeypatch.setattr(xp, "_this_runs_file", lambda: with_ops)
    monkeypatch.setattr(xm, "_this_runs_file", lambda: with_modules)
    monkeypatch.setattr(xph, "_this_runs_phases", lambda: phased)
    with open(os.path.join(ROOT, "benchmark", "workloads", f"{CELL}.json")) as f:
        cell = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    p = float(len(chips))
    return {"trace": trace.reduce(with_ops),
            "counters": {"trainer.mesh_devices": 2 * p,
                         "trainer.psum_bytes": (p > 1) * 2 * 700 * 4_000_008.0,
                         "trainer.fused_block_fits": 2.0,
                         "hostdata.placement_hits": 2.0,
                         "hostdata.label_facts_kept": 2.0,
                         "table.csr_rows_materialized": 0.0,
                         "span.fit.traced_seconds": 8.8e-3,
                         "span.fit.traced_self_seconds": 0.4e-3,
                         "span.fit.traced_calls": 1.0,
                         "span.trainer.loop.traced_self_seconds": 8e-3,
                         "span.trainer.loop.traced_calls": 1.0,
                         "span.trainer.readback.seconds": 0.8e-3,
                         "span.trainer.readback.calls": 2.0},
            "setup_counters": {"jax.cache_misses": 0.0},
            "units": {"fits": 2, "steps": 1400}, "traced_units": {"fits": 2, "steps": 1400},
            "cell": cell, "config": _config(), "peaks": peaks}


def _cell_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [m["name"] for m in json.load(f)["per_layer"]
                if CELL in m.get("workloads", ())]


def _read(name, obs):
    import importlib

    with open(os.path.join(ROOT, "benchmark", "metrics", f"{name}.json")) as f:
        spec = json.load(f)
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    return reader.read(spec.get("params", {}), obs)


#: What a collective alone has: no reading on one chip.
_ONLY_ACROSS_CHIPS = {"sharding.chip_busy_skew", "sharding.psum_bus_bytes_per_s"}


@pytest.mark.parametrize("name", _cell_metrics())
def test_four_equal_planes_read_as_one_plane_of_the_same_chip(monkeypatch, name):
    with monkeypatch.context() as m:
        one = _read(name, _observed(m, [_chip()]))
    four = _read(name, _observed(monkeypatch, [_chip()] * 4))
    assert four is not None, name
    if name in _ONLY_ACROSS_CHIPS:
        assert one is None
    else:
        assert four == pytest.approx(one, rel=1e-12), name


def test_the_readings_of_the_made_up_chip_are_the_ones_reckoned_by_hand(monkeypatch):
    obs = _observed(monkeypatch, [_chip()] * 4)
    assert _cell_metrics()[-6:] == [
        "sharding.psum_device_ms_per_step", "sharding.psum_step_share",
        "sharding.psum_bus_bytes_per_s", "sharding.chip_busy_skew",
        "sparse_lr_dp_step_roofline", "sharding.placement_hit_share"]
    # two runs of 3 ms over 1,400 steps, a chip's mean
    assert _read("trainer.loop_device_ms_per_step", obs) == pytest.approx(6 / 1400)
    assert _read("sharding.psum_device_ms_per_step", obs) == pytest.approx(0.8 / 1400)
    assert _read("sharding.psum_step_share", obs) == pytest.approx(100 * 0.8 / 6)
    assert _read("trainer.sparse_lookup_device_ms", obs) == pytest.approx(1.6 / 1400)
    # a ring's traffic: 2 * 3 / 4 of 4,000,008 B a step in 0.8 ms / 1,400
    assert _read("sharding.psum_bus_bytes_per_s", obs) == pytest.approx(
        1.5 * 4_000_008 / (0.8e-3 / 1400))
    assert _read("sharding.chip_busy_skew", obs) == 0.0
    assert _read("device.idle_share.fit", obs) == pytest.approx(40.0)
    # a chip's bytes a step over one chip's peak and one chip's mean time
    least = (16_384 * 39 * 8 + 2 * 16_384 * 4 + 5 * 4_000_000) / 819e9
    assert _read("sparse_lr_dp_step_roofline", obs) == pytest.approx(
        100 * least / (6e-3 / 1400))


def test_a_chip_that_lags_shows_as_skew_and_not_in_the_mean_of_the_others(monkeypatch):
    # one chip's operations a tenth longer: busiest 6.6 ms, least 6, mean 6.15
    obs = _observed(monkeypatch, [_chip()] * 3 + [_chip(stretch=1.1)])
    assert _read("sharding.chip_busy_skew", obs) == pytest.approx(100 * 0.6 / 6.15)
    assert _read("trainer.loop_device_ms_per_step", obs) == pytest.approx(6.15 / 1400)
    assert _read("device.idle_share.fit", obs) == pytest.approx(100 * (1 - 0.615))


def test_the_new_readers_read_nothing_off_a_program_without_the_counters(monkeypatch):
    obs = _observed(monkeypatch, [_chip()] * 4)
    obs["counters"] = {k: v for k, v in obs["counters"].items()
                       if not k.startswith("trainer.")}
    assert _read("sharding.psum_bus_bytes_per_s", obs) is None
    assert _read("sharding.chip_busy_skew", {**obs, "trace": None}) is None


# -- the cell's rehearsal under the suite's eight devices ------------------------

def test_the_cells_rehearsal_runs_on_eight_devices(capsys):
    """``benchmark/run.py --rehearse`` of the four-chip cell, end to end:
    the driver takes the devices it finds as the workers (eight here, the
    benchmark's own tests have one), every check holds, and the replay is
    the eight workers'."""
    from benchmark import run

    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 55),
                   "--seconds", "0.2", "--trace", "1", "--rehearse"])
    lines = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]
    assert rc == 0 and lines[-1]["correct"] is True
    assert lines[-1]["device"]["count"] == 8
    checks = [l for l in lines if l.get("phase") == "check"]
    assert len(checks) == 7 and all(c["ok"] for c in checks)
    assert "over 8 workers, 2049 rows and 9 windows of 256 a worker" in checks[5]["what"]
    assert lines[-1]["metrics"]["sharding.placement_hit_share"]["value"] == 1.0
