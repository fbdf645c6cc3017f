"""The fit's placement follows its loop (PR 33): the columns of a
training table reach the mesh in lockstep rounds
(``DeviceMesh.stage_rows``), the device loop is dispatched from the carry
on the device for the windows that have landed while the next round is
gathered (``_linear_sgd._run_chunked``), and rows no step can read are not
sent (``_reach_rows``). The result is bit for bit the fit that places the
table whole and runs the loop in ONE dispatch, which these tests build
from the same trainer and the same placement run to its end."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flinkml_tpu.iteration import CheckpointManager
from flinkml_tpu.models import LogisticRegression, _linear_sgd
from flinkml_tpu.parallel import DeviceMesh, mesh as mesh_mod, pad_to_multiple
from flinkml_tpu.table import CsrColumn, Table
from flinkml_tpu.utils import metrics

ROWS, DIM = 1003, 5
#: Staging bytes that cut the 1003-row tables below into many rounds.
TINY_STAGE = 2048
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh(devices):
    return DeviceMesh(devices=jax.devices()[:devices])


def _counted(*groups):
    return {f"{g}.{k}": v for g in groups
            for k, v in metrics.group(g).snapshot()["counters"].items()}


class _Delta:
    """What a block added to the four counters of the mechanism."""

    def __enter__(self):
        self.before = _counted("trainer", "hostdata.stage")
        return self

    def __exit__(self, *exc):
        after = _counted("trainer", "hostdata.stage")
        self.added = {k: v - self.before.get(k, 0.0) for k, v in after.items()}
        return False

    def __getitem__(self, name):
        return self.added.get(name, 0.0)


def _one_dispatch(seen=None):
    """``_run_chunked`` as the fit before PR 33 ran: the table placed
    whole (every window in reach), then ONE dispatch of the whole loop."""

    def run(trainer, place, dim, dt, learning_rate, reg_l2, reg_l1, tol,
            max_iter, mesh, **kwargs):
        assert not kwargs.get("listeners") and kwargs.get(
            "checkpoint_manager") is None
        data = _linear_sgd._placed(place(0, 1 << 30))
        coef, epoch, _ = trainer(
            jnp.zeros(dim, dt), jnp.asarray(0, jnp.int32),
            jnp.asarray(np.inf, dt), *data,
            *(jnp.asarray(v, dt) for v in (learning_rate, reg_l2, reg_l1, tol)),
            jnp.asarray(max_iter, jnp.int32))
        if seen is not None:
            seen.append(int(epoch))
        return np.asarray(coef)

    return run


# -- the window arithmetic ------------------------------------------------------

def _window_end(n_local, local_bs, k):
    """The end of what ``_window`` slices for step ``k``: its own
    arithmetic, ``dynamic_slice``'s clamp included."""
    n_windows = max(-(-n_local // local_bs), 1)
    start = min((k % n_windows) * local_bs, n_local - local_bs)
    return start + local_bs


WINDOW_CASES = {
    "steps-below-the-rows": (1000, 100, 0, 6),
    "steps-equal-the-rows": (1000, 100, 0, 10),
    "steps-above-the-rows-wrap": (1000, 100, 0, 14),
    "last-window-cut-by-the-table": (1003, 100, 0, 11),
    "ends-before-the-cut-window": (1003, 100, 0, 10),
    "resumed-inside-a-pass": (1003, 100, 4, 9),
    "resumed-and-wrapping": (1003, 100, 8, 13),
    "resumed-in-a-later-pass": (1003, 100, 25, 28),
    "one-window": (64, 64, 0, 5),
    "batch-of-one-row": (7, 1, 2, 6),
    "nothing-left-to-run": (1000, 100, 12, 12),
}


@pytest.mark.parametrize("case", WINDOW_CASES)
def test_reach_is_the_last_row_a_step_reads(case):
    n_local, local_bs, first, last = WINDOW_CASES[case]
    ends = [_window_end(n_local, local_bs, k) for k in range(first, last)]
    assert _linear_sgd._reach_rows(n_local, local_bs, first, last) == max(
        ends, default=0)


@pytest.mark.parametrize("case", WINDOW_CASES)
def test_the_loop_runs_exactly_as_far_as_whole_windows_have_landed(case):
    n_local, local_bs, first, last = WINDOW_CASES[case]
    reach = _linear_sgd._reach_rows(n_local, local_bs, first, last)
    for complete in range(0, n_local + 1):
        want = first
        while want < last and _window_end(n_local, local_bs, want) <= complete:
            want += 1
        got = _linear_sgd._steps_ready(n_local, local_bs, first, last, complete)
        assert got == want, complete
        if complete >= reach:
            assert got == last


def test_window_reads_what_the_arithmetic_says():
    """The model above IS ``_window``: on an array of row numbers."""
    rows = jnp.arange(1003)
    for k in (0, 9, 10, 11, 21):
        got = np.asarray(_linear_sgd._window(rows, k, 100))
        assert got[-1] + 1 == _window_end(1003, 100, k) and len(got) == 100


# -- the lockstep placement -----------------------------------------------------

def _columns(rows=ROWS, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 1 << 20, (rows, 7)).astype(np.int32),
            rng.normal(size=(rows, 7)),
            rng.integers(0, 2, rows))


@pytest.mark.parametrize("reach", [None, 0, 1, 50, 126, 251, 10_000])
@pytest.mark.parametrize("devices", [1, 4, 8])
def test_placed_rows_below_the_reach_are_the_whole_pass_and_zero_above(
        monkeypatch, devices, reach):
    monkeypatch.setattr(mesh_mod, "_STAGE_BYTES", TINY_STAGE)
    mesh = _mesh(devices)
    idx, val, y = _columns()
    order = np.random.default_rng(4).permutation(ROWS)
    picked = np.random.default_rng(5).permutation(ROWS)
    n_local = -(-ROWS // devices)
    limit = n_local if reach is None else min(reach, n_local)
    with _Delta() as counted:
        rounds = list(mesh.stage_rows(
            [(idx, order, None), (val, order, np.float32),
             (y, picked, np.float32)], reach))
    completes = [complete for _, complete in rounds]
    assert completes == sorted(completes) and completes[-1] == limit
    assert len(set(completes)) == len(completes)
    placed = rounds[-1][0]
    for got, (x, o, dt) in zip(placed, ((idx, order, np.int32),
                                        (val, order, np.float32),
                                        (y, picked, np.float32))):
        want = mesh.shard_batch(pad_to_multiple(x.astype(dt)[o], devices)[0])
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.sharding == want.sharding
        got = np.asarray(got).reshape((devices, n_local) + x.shape[1:])
        want = np.asarray(want).reshape(got.shape)
        assert got[:, :limit].tobytes() == want[:, :limit].tobytes()
        assert not got[:, limit:].any()
    assert counted["hostdata.stage.rows"] == devices * n_local
    assert counted["hostdata.stage.rows_sent"] == devices * limit


def test_one_column_with_full_reach_is_shard_rows(monkeypatch):
    """``shard_rows`` is the lockstep placement of one column run to its
    last round: one implementation of a round."""
    monkeypatch.setattr(mesh_mod, "_STAGE_BYTES", TINY_STAGE)
    mesh, (idx, _, _) = _mesh(4), _columns()
    order = np.random.default_rng(4).permutation(ROWS)
    calls = []
    real = mesh.stage_rows

    def stage_rows(columns, reach_rows=None):
        calls.append((len(columns), reach_rows))
        return real(columns, reach_rows)

    monkeypatch.setattr(mesh, "stage_rows", stage_rows)
    got = mesh.shard_rows(idx, order)
    assert calls == [(1, None)]
    want = mesh.shard_batch(pad_to_multiple(idx[order], 4)[0])
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


# -- the pipelined fit equals the one-dispatch fit ------------------------------

def _dense(classes=2, rows=ROWS, seed=1):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(rows, DIM)).astype(np.float32)
    margin = x @ rng.normal(size=DIM)
    y = ((margin > 0) if classes == 2
         else np.digitize(margin, [-0.5, 0.5])).astype(np.float64)
    return Table({"features": x, "label": y, "w": rng.random(rows) + 0.5})


def _sparse(layout, rows=ROWS, seed=2):
    """``planned``: one cell a field, each field on a block of columns of
    its own; ``general``: one width, hashed over all of ``dim`` (the empty
    plan); ``ragged``: several padded buckets."""
    rng = np.random.default_rng(seed)
    if layout == "planned":
        dim, fields, stratum = 4096, 8, 512
        indices = (rng.integers(0, stratum, (rows, fields))
                   + np.arange(fields) * stratum).astype(np.int32).reshape(-1)
        nnz = np.full(rows, fields)
    else:
        dim = 1 << 20
        nnz = (np.full(rows, 9) if layout == "general"
               else rng.integers(0, 40, size=rows))
        indices = np.concatenate(
            [np.sort(rng.choice(dim, k, replace=False)) for k in nnz]
        ).astype(np.int32)
    indptr = np.concatenate([[0], np.cumsum(nnz)]).astype(np.int64)
    values = rng.normal(size=indices.size).astype(np.float32)
    y = rng.integers(0, 2, rows).astype(np.float32)
    return Table({"features": CsrColumn(indptr, indices, values, dim),
                  "label": y, "w": rng.random(rows) + 0.5})


def _table(kind):
    if kind == "dense":
        return _dense()
    if kind == "softmax":
        return _dense(classes=3)
    return _sparse(kind)


def _fit(table, devices, max_iter=20, batch=128, tol=0.0, weight_col=None,
         rate=0.5):
    est = (LogisticRegression(mesh=_mesh(devices)).set_max_iter(max_iter)
           .set_global_batch_size(batch).set_learning_rate(rate).set_tol(tol)
           .set_seed(7))
    if weight_col is not None:
        est.set_weight_col(weight_col)
    return np.asarray(est.fit(table).coefficient)


def _both(monkeypatch, table, devices, **fit):
    """The one-dispatch fit's coefficients and steps, then the pipelined
    fit's coefficients and counters."""
    steps = []
    with monkeypatch.context() as m:
        m.setattr(_linear_sgd, "_run_chunked", _one_dispatch(steps))
        want = _fit(table, devices, **fit)
    # A table of the same columns that keeps nothing yet: the table above
    # keeps what the one-dispatch fit placed, and would place nothing.
    with _Delta() as counted:
        got = _fit(table.select(*table.column_names), devices, **fit)
    return want, steps[0], got, counted


@pytest.mark.parametrize("weight_col", [None, "w"], ids=["unit", "weighted"])
@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("kind", ["dense", "planned", "general", "ragged",
                                  "softmax"])
def test_pipelined_fit_equals_the_one_dispatch_fit(
        monkeypatch, kind, devices, weight_col):
    monkeypatch.setattr(mesh_mod, "_STAGE_BYTES", TINY_STAGE)
    want, steps, got, counted = _both(
        monkeypatch, _table(kind), devices, weight_col=weight_col)
    assert got.tobytes() == want.tobytes() and np.abs(got).max() > 0
    assert steps == counted["trainer.steps"] == 20
    # 128 rows a step of 1003: eight windows, twenty steps read them all,
    # and all but the dispatch after the last round ran ahead of it.
    assert 0 < counted["trainer.pipelined_steps"] < 8
    assert counted["hostdata.stage.rows_sent"] == counted["hostdata.stage.rows"]


def test_the_plan_and_the_buckets_are_what_the_kinds_say():
    mesh = _mesh(1)
    for kind, planned, buckets in (("planned", True, 1), ("general", False, 1),
                                   ("ragged", False, None)):
        column = _table(kind).csr_column("features")
        _, sizes, plan = _linear_sgd.prepare_sparse_buckets(
            column.indptr, column.indices, column.values, column.dim,
            np.zeros(ROWS, np.float32), None, mesh, 128, seed=0)
        assert bool(plan) == planned
        assert len(sizes) == buckets if buckets else len(sizes) > 1


REACH_FITS = {
    # max_iter, batch -> rows sent of 1003 on one device
    "steps-below-the-rows": (5, 100, 500),
    "steps-equal-the-rows": (10, 100, 1000),
    "into-the-cut-window": (11, 100, 1003),
    "steps-above-the-rows-wrap": (27, 100, 1003),
    "one-step": (1, 32, 32),
    "flink-ml-defaults": (20, 32, 640),
}


@pytest.mark.parametrize("kind", ["dense", "planned"])
@pytest.mark.parametrize("case", REACH_FITS)
def test_rows_no_step_reads_are_not_sent_and_the_fit_is_the_same(
        monkeypatch, case, kind):
    monkeypatch.setattr(mesh_mod, "_STAGE_BYTES", TINY_STAGE)
    max_iter, batch, sent = REACH_FITS[case]
    want, steps, got, counted = _both(
        monkeypatch, _table(kind), 1, max_iter=max_iter, batch=batch)
    assert got.tobytes() == want.tobytes()
    assert steps == counted["trainer.steps"] == max_iter
    assert counted["hostdata.stage.rows"] == ROWS
    assert counted["hostdata.stage.rows_sent"] == sent
    assert counted["trainer.pipelined_steps"] < max_iter


def test_reach_on_four_devices_is_per_shard(monkeypatch):
    monkeypatch.setattr(mesh_mod, "_STAGE_BYTES", TINY_STAGE)
    want, _, got, counted = _both(
        monkeypatch, _table("dense"), 4, max_iter=5, batch=100)
    assert got.tobytes() == want.tobytes()
    # 251 local rows, 25 a step: five windows of 25 on each of four shards
    assert counted["hostdata.stage.rows"] == 4 * 251
    assert counted["hostdata.stage.rows_sent"] == 4 * 125


@pytest.mark.parametrize("kind", ["dense", "planned"])
def test_tol_met_mid_fit_stops_at_the_same_step(monkeypatch, kind):
    """The chunks after the one that met ``tol`` enter with a loss at or
    under it and run no step: the device's own condition, as in the
    one-dispatch fit."""
    monkeypatch.setattr(mesh_mod, "_STAGE_BYTES", TINY_STAGE)
    table = _table(kind)
    fit = dict(max_iter=60, batch=100, tol=0.58 if kind == "dense" else 0.66)
    want, steps, got, counted = _both(monkeypatch, table, 1, **fit)
    assert 1 < steps < 60, steps
    assert got.tobytes() == want.tobytes()
    assert counted["trainer.steps"] == steps
    assert counted["trainer.pipelined_steps"] <= steps


def test_a_pipelined_fit_compiles_the_trainer_once(monkeypatch):
    """The carry goes in on the mesh, as a chunk returns it: the second
    chunk is the first one's program."""
    monkeypatch.setattr(mesh_mod, "_STAGE_BYTES", TINY_STAGE)
    _linear_sgd._dense_trainer.cache_clear()
    mesh = _mesh(4)
    with _Delta() as counted:
        LogisticRegression(mesh=mesh).set_max_iter(20).set_global_batch_size(
            128).set_seed(3).fit(_table("dense"))
    assert counted["trainer.pipelined_steps"] > 0
    trainer = _linear_sgd._dense_trainer(
        mesh.mesh, "logistic", 32, DeviceMesh.DATA_AXIS)
    assert trainer._cache_size() == 1


# -- where the host takes the carry at boundaries -------------------------------

class _Recorder:
    def __init__(self, events):
        self.events = events

    def on_epoch_watermark_incremented(self, epoch, coef):
        self.events.append(("watermark", epoch, np.array(coef)))

    def on_iteration_terminated(self, coef):
        self.events.append(("terminated", None, np.array(coef)))


def _train_dense(mesh, max_iter=12, **kwargs):
    table = _table("dense")
    return _linear_sgd.train_linear_model(
        table.column("features"), table.column("label"), None, "logistic",
        mesh, max_iter, 0.5, 128, 0.0, 0.0, 0.0, 7, dtype=np.float32, **kwargs)


def _rounds_and_dispatches(monkeypatch, events):
    """Record every staging round and every dispatch of the trainer."""
    real_stage = DeviceMesh.stage_rows
    real_trainer = _linear_sgd._dense_trainer

    def stage_rows(self, columns, reach_rows=None):
        for item in real_stage(self, columns, reach_rows):
            events.append(("round", item[1]))
            yield item

    def dense_trainer(*key):
        trainer = real_trainer(*key)

        def run(*args):
            events.append(("dispatch", int(args[-1])))
            return trainer(*args)

        return run

    monkeypatch.setattr(DeviceMesh, "stage_rows", stage_rows)
    monkeypatch.setattr(_linear_sgd, "_dense_trainer", dense_trainer)


@pytest.mark.parametrize("devices", [1, 4])
def test_with_a_checkpoint_manager_the_boundaries_and_snapshots_are_the_callers(
        monkeypatch, tmp_path, devices):
    monkeypatch.setattr(mesh_mod, "_STAGE_BYTES", TINY_STAGE)
    mesh, events, saved = _mesh(devices), [], []

    class Manager(CheckpointManager):
        def save(self, state, epoch, extra=None, **kw):
            saved.append((epoch, np.array(state[0]), float(state[1])))
            return super().save(state, epoch, extra, **kw)

    golden = _train_dense(mesh)
    _rounds_and_dispatches(monkeypatch, events)
    with _Delta() as counted:
        got = _train_dense(mesh, checkpoint_manager=Manager(str(tmp_path)),
                           checkpoint_interval=5)
    assert got.tobytes() == golden.tobytes()
    kinds = [kind for kind, _ in events]
    rounds = kinds.count("round")
    # the placement completes first, then the caller's boundaries
    assert rounds > 2 and kinds == ["round"] * rounds + ["dispatch"] * 3
    assert [end for kind, end in events if kind == "dispatch"] == [5, 10, 12]
    assert [epoch for epoch, _, _ in saved] == [5, 10, 12]
    for epoch, coef, _ in saved:  # each snapshot is the fit of that many steps
        assert coef.tobytes() == _train_dense(mesh, max_iter=epoch).tobytes()
    assert counted["trainer.steps"] == 12
    assert counted["trainer.pipelined_steps"] == 0


def test_with_listeners_the_carry_reaches_the_host_at_the_end_of_the_one_chunk(
        monkeypatch):
    monkeypatch.setattr(mesh_mod, "_STAGE_BYTES", TINY_STAGE)
    mesh, events, heard = _mesh(4), [], []
    golden = _train_dense(mesh)
    _rounds_and_dispatches(monkeypatch, events)
    with _Delta() as counted:
        got = _train_dense(mesh, listeners=(_Recorder(heard),))
    assert got.tobytes() == golden.tobytes()
    assert [e for e in events if e[0] == "dispatch"] == [("dispatch", 12)]
    assert events[-1] == ("dispatch", 12)
    assert [(kind, epoch) for kind, epoch, _ in heard] == [
        ("watermark", 11), ("terminated", None)]
    assert all(coef.tobytes() == golden.tobytes() for _, _, coef in heard)
    assert counted["trainer.pipelined_steps"] == 0


def test_a_resumed_fit_sends_what_its_remaining_steps_read(
        monkeypatch, tmp_path):
    """Resume epoch above 0: the reach is counted from the restored epoch
    (steps 8 and 9 of a ten-window pass read its last two windows, so
    the table goes whole), and the resumed fit is the uninterrupted one."""
    monkeypatch.setattr(mesh_mod, "_STAGE_BYTES", TINY_STAGE)
    mesh = _mesh(1)
    table = _table("dense")

    def train(max_iter, **kwargs):
        return _linear_sgd.train_linear_model(
            table.column("features"), table.column("label"), None, "logistic",
            mesh, max_iter, 0.5, 100, 0.0, 0.0, 0.0, 7, dtype=np.float32,
            **kwargs)

    golden = train(10)
    manager = CheckpointManager(str(tmp_path))
    train(8, checkpoint_manager=manager, checkpoint_interval=4)
    assert manager.latest_epoch() == 8
    with _Delta() as counted:
        got = train(10, checkpoint_manager=manager, checkpoint_interval=4,
                    resume=True)
    assert got.tobytes() == golden.tobytes()
    assert counted["trainer.steps"] == 2
    assert counted["hostdata.stage.rows_sent"] == 1000
    # and from epoch 2 of 5, windows 2 to 4: the reach is their end
    first = CheckpointManager(str(tmp_path / "early"))
    train(2, checkpoint_manager=first, checkpoint_interval=2)
    with _Delta() as counted:
        got = train(5, checkpoint_manager=first, checkpoint_interval=5,
                    resume=True)
    assert got.tobytes() == train(5).tobytes()
    assert counted["trainer.steps"] == 3
    assert counted["hostdata.stage.rows_sent"] == 500


# -- the two metrics ------------------------------------------------------------

METRICS = {
    "hostdata.staged_row_share": ("hostdata.stage.rows_sent", "hostdata.stage.rows",
                                  "Host data", "rows/row", "lower"),
    "trainer.pipelined_step_share": ("trainer.pipelined_steps", "trainer.steps",
                                     "Trainers", "steps/step", "higher"),
}


@pytest.mark.parametrize("name", METRICS)
def test_the_metric_reads_its_two_counters(monkeypatch, name):
    """``benchmark/metrics/<name>.json`` through the benchmark's
    ``counter_ratio`` reader over a fit's counters as ``benchmark/run.py``
    flattens them, and its entry in ``BENCHMARK.json``."""
    from benchmark.readers import counter_ratio

    num, den, layer, unit, better = METRICS[name]
    with open(os.path.join(ROOT, "benchmark", "metrics", f"{name}.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "counter_ratio"
    assert spec["params"] == {"num": num, "den": den}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    # The cold cell is where a fit stages rows and pipelines steps; which
    # other cells list the entry is the benchmark's to decide.
    assert "lr-criteo.fit-cold" in entry.pop("workloads")
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": "program_counter", "layer": layer,
                     "moves": "fit_samples_per_s"}
    monkeypatch.setattr(mesh_mod, "_STAGE_BYTES", TINY_STAGE)
    with _Delta() as counted:
        _fit(_table("dense"), 1, max_iter=5, batch=100)
    obs = {"counters": counted.added, "setup_counters": {}, "units": {"fits": 1}}
    want = {"hostdata.staged_row_share": 500 / 1003,
            "trainer.pipelined_step_share": counted["trainer.pipelined_steps"] / 5}
    assert counter_ratio.read(spec["params"], obs) == want[name]
    assert 0 < want["trainer.pipelined_step_share"] < 1
    # a program without the counts (the parent): no metric, no error
    assert counter_ratio.read(spec["params"], {**obs, "counters": {}}) is None
