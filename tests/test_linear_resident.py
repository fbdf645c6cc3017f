"""The linear fits keep their seeded placement WITH the ``Table`` (PR 37):
a second fit on the same table, seed and mesh permutes, gathers and
uploads nothing (``_linear_sgd._find_or_keep``), and every placement a
table keeps, KMeans' and the FM's among them, is one set that lets go of
its least recently used entries when the device reports no room for a
new one (``table._ResidentSet``)."""

import gc
import weakref

import jax
import numpy as np
import pytest

from flinkml_tpu import table as table_mod
from flinkml_tpu.iteration import CheckpointManager
from flinkml_tpu.models import (
    FMClassifier,
    KMeans,
    LinearRegression,
    LinearSVC,
    LogisticRegression,
    _linear_sgd,
)
from flinkml_tpu.parallel import DeviceMesh, mesh as mesh_mod
from flinkml_tpu.table import CsrColumn, Table
from flinkml_tpu.utils import metrics

ROWS, DIM, BATCH, STEPS, SEED = 1003, 6, 128, 6, 7


def _mesh(devices):
    return DeviceMesh(devices=jax.devices()[:devices])


def _counted():
    """The counters the mechanism moves, flat."""
    out = {}
    for group in ("hostdata", "hostdata.stage", "hostdata.sparse", "span",
                  "kmeans", "fm"):
        for name, value in metrics.group(group).snapshot()["counters"].items():
            out[f"{group}.{name}"] = value
    return out


class _Delta:
    """What a block added to the counters."""

    def __enter__(self):
        self.before = _counted()
        return self

    def __exit__(self, *exc):
        after = _counted()
        self.added = {k: v - self.before.get(k, 0.0) for k, v in after.items()}
        return False

    def __getitem__(self, name):
        return self.added.get(name, 0.0)

    def placed_nothing(self):
        return (self["hostdata.stage.rows_sent"] == 0
                and self["span.hostdata.permute.calls"] == 0
                and self["span.mesh.shard_batch.calls"] == 0)


def _kept_bytes():
    return metrics.group("hostdata").snapshot()["gauges"].get(
        "placement_kept_bytes", 0.0)


# -- tables ---------------------------------------------------------------------

def _dense_columns(classes=2, weights=False, rows=ROWS, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(rows, DIM)).astype(np.float32)
    score = x @ rng.normal(size=DIM)
    y = (np.digitize(score, np.quantile(score, np.arange(1, classes) / classes))
         .astype(np.float64))
    columns = {"features": x, "label": y}
    if weights:
        columns["weight"] = rng.random(rows) + 0.5
    return columns


def _sparse_columns(ragged=False, weights=False, rows=ROWS, seed=0):
    """One cell a field on fields of 50 columns (one width: one bucket
    under a plan), or 0 to 40 cells a row (several buckets)."""
    rng = np.random.default_rng(seed)
    dim = 300
    if ragged:
        nnz = np.clip(rng.geometric(0.2, size=rows) - 1, 0, 40)
        indices = np.concatenate(
            [np.sort(rng.choice(dim, k, replace=False)) for k in nnz])
    else:
        nnz = np.full(rows, 6)
        indices = (rng.integers(0, 50, size=(rows, 6)) + 50 * np.arange(6)).ravel()
    indptr = np.zeros(rows + 1, np.int64)
    np.cumsum(nnz, out=indptr[1:])
    values = rng.normal(size=indices.size).astype(np.float32)
    columns = {
        "features": CsrColumn(indptr, indices.astype(np.int32), values, dim),
        "label": (rng.random(rows) < 0.4).astype(np.float64)}
    if weights:
        columns["weight"] = rng.random(rows) + 0.5
    return columns


def _estimator(cls, mesh, weights, **params):
    est = cls(mesh=mesh, **params.pop("init", {}))
    est.set_global_batch_size(params.pop("batch", BATCH))
    est.set_max_iter(params.pop("max_iter", STEPS)).set_seed(params.pop("seed", SEED))
    if weights:
        est.set_weight_col("weight")
    for name, value in params.items():
        getattr(est, f"set_{name}")(value)
    return est


def _coefficient(model):
    return np.asarray(model.coefficient)


#: kind -> (columns, estimator class, the trainer's cached factory)
KINDS = {
    "dense-binomial": (_dense_columns, LogisticRegression, "_dense_trainer"),
    "softmax": (lambda **kw: _dense_columns(classes=3, **kw), LogisticRegression,
                "_softmax_trainer"),
    "sparse-one-width": (_sparse_columns, LogisticRegression,
                         "_sparse_trainer_bucketed"),
    "sparse-ragged": (lambda **kw: _sparse_columns(ragged=True, **kw),
                      LogisticRegression, "_sparse_trainer_bucketed"),
    "svc-dense": (_dense_columns, LinearSVC, "_dense_trainer"),
    "svc-sparse": (_sparse_columns, LinearSVC, "_sparse_trainer_bucketed"),
    "regression-dense": (_dense_columns, LinearRegression, "_dense_trainer"),
    "regression-sparse": (lambda **kw: _sparse_columns(ragged=True, **kw),
                          LinearRegression, "_sparse_trainer_bucketed"),
}


def _fit(kind, table, mesh, weights=False, **params):
    _, cls, _ = KINDS[kind]
    return _coefficient(_estimator(cls, mesh, weights, **params).fit(table))


# -- find -----------------------------------------------------------------------

@pytest.mark.parametrize("weights", [False, True], ids=["unit", "weighted"])
@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("kind", KINDS)
def test_second_fit_on_one_table_places_nothing(kind, devices, weights):
    columns = KINDS[kind][0](weights=weights)
    table, mesh = Table(columns), _mesh(devices)
    with _Delta() as first:
        a = _fit(kind, table, mesh, weights)
    assert first["hostdata.placement_misses"] == 1
    assert first["hostdata.placement_hits"] == 0
    assert first["hostdata.stage.rows_sent"] > 0
    if kind.endswith("ragged") or kind == "regression-sparse":
        assert first["hostdata.sparse.buckets"] > 1
    with _Delta() as second:
        b = _fit(kind, table, mesh, weights)
    assert second.placed_nothing()
    assert second["hostdata.placement_hits"] == 1
    assert second["hostdata.placement_misses"] == 0
    assert second["span.hostdata.sparse_pack.calls"] == 0
    with _Delta() as fresh:
        c = _fit(kind, Table(columns), mesh, weights)
    assert fresh["hostdata.placement_misses"] == 1
    assert a.tobytes() == b.tobytes() == c.tobytes()
    assert np.isfinite(a).all() and np.any(a != 0)


def _other_seed(kind, table, mesh):
    return dict(table=table, mesh=mesh, seed=SEED + 1)


def _other_mesh(kind, table, mesh):
    return dict(table=table, mesh=_mesh(2))


def _other_label(kind, table, mesh):
    flipped = table.with_column("other", 1.0 - table.column("label"))
    _fit(kind, flipped, mesh)  # its own table: keeps under "label"
    return dict(table=flipped, mesh=mesh, label_col="other")


def _other_weight(kind, table, mesh):
    weighted = table.with_column(
        "weight", np.random.default_rng(3).random(table.num_rows) + 0.5)
    _fit(kind, weighted, mesh)  # keeps under no weight column
    return dict(table=weighted, mesh=mesh, weight_col="weight")


@pytest.mark.parametrize("what", [_other_seed, _other_mesh, _other_label,
                                  _other_weight])
@pytest.mark.parametrize("kind", ["dense-binomial", "sparse-one-width"])
def test_another_key_is_a_miss_and_correct(kind, what):
    table, mesh = Table(KINDS[kind][0]()), _mesh(4)
    _fit(kind, table, mesh)
    case = what(kind, table, mesh)
    table, mesh = case.pop("table"), case.pop("mesh")
    with _Delta() as counted:
        got = _fit(kind, table, mesh, **case)
    assert counted["hostdata.placement_misses"] == 1
    assert counted["hostdata.placement_hits"] == 0
    columns = {n: table.column(n) if table.csr_column(n) is None
               else table.csr_column(n) for n in table.column_names}
    assert got.tobytes() == _fit(kind, Table(columns), mesh, **case).tobytes()
    with _Delta() as again:  # and the table now keeps both
        _fit(kind, table, mesh, **case)
    assert again["hostdata.placement_hits"] == 1 and again.placed_nothing()


def test_another_placed_dtype_is_a_miss_and_correct():
    table, mesh = Table(_dense_columns()), _mesh(4)
    wide = _fit("dense-binomial", table, mesh)  # float64 under the suite's x64
    with jax.enable_x64(False), _Delta() as counted:
        narrow = _fit("dense-binomial", table, mesh)
        fresh = _fit("dense-binomial", Table(_dense_columns()), mesh)
    assert counted["hostdata.placement_misses"] == 2
    assert narrow.tobytes() == fresh.tobytes() and narrow.tobytes() != wide.tobytes()


@pytest.mark.parametrize("operand", [
    dict(learning_rate=0.03), dict(reg=0.01), dict(tol=1e-3),
    dict(max_iter=STEPS - 2)], ids=lambda d: next(iter(d)))
@pytest.mark.parametrize("kind", ["dense-binomial", "softmax", "sparse-one-width",
                                  "sparse-ragged"])
def test_an_operand_of_the_loop_is_a_hit_and_traces_nothing(
        monkeypatch, kind, operand):
    columns = KINDS[kind][0]()
    table, mesh = Table(columns), _mesh(4)
    # The cached factory hands every fit of one key the same jitted
    # trainer: seen here, and asked how many programs it has traced.
    factory, seen = getattr(_linear_sgd, KINDS[kind][2]), []

    def watched(*key, **more):
        seen.append(factory(*key, **more))
        return seen[-1]

    monkeypatch.setattr(_linear_sgd, KINDS[kind][2], watched)
    _fit(kind, table, mesh)
    made, traced = factory.cache_info().misses, seen[0]._cache_size()
    with _Delta() as counted:
        got = _fit(kind, table, mesh, **operand)
    assert counted["hostdata.placement_hits"] == 1 and counted.placed_nothing()
    assert seen[1] is seen[0] and factory.cache_info().misses == made
    assert seen[0]._cache_size() == traced  # the hit entered the miss's program
    assert got.tobytes() == _fit(kind, Table(columns), mesh, **operand).tobytes()


@pytest.mark.parametrize("kind", ["dense-binomial", "sparse-one-width",
                                  "sparse-ragged"])
def test_steps_past_the_kept_reach_place_again_and_replace_the_entry(kind):
    columns = KINDS[kind][0]()
    table, mesh = Table(columns), _mesh(4)
    # Two steps read two windows of eight: the reach stops short.
    _fit(kind, table, mesh, max_iter=2)
    kept = _kept_bytes()
    with _Delta() as longer:
        got = _fit(kind, table, mesh, max_iter=STEPS)
    assert longer["hostdata.placement_misses"] == 1
    assert longer["hostdata.placement_evictions"] == 0
    assert longer["hostdata.stage.rows_sent"] > 0
    assert _kept_bytes() == kept  # replaced, not added
    assert got.tobytes() == _fit(kind, Table(columns), mesh).tobytes()
    with _Delta() as shorter:  # the longer reach covers the shorter fit
        two = _fit(kind, table, mesh, max_iter=2)
    assert shorter["hostdata.placement_hits"] == 1 and shorter.placed_nothing()
    assert two.tobytes() == _fit(kind, Table(columns), mesh, max_iter=2).tobytes()


def test_a_fit_that_raises_mid_placement_keeps_nothing(monkeypatch):
    monkeypatch.setattr(mesh_mod, "_STAGE_BYTES", 2048)
    table, mesh = Table(_dense_columns()), _mesh(4)
    real = DeviceMesh.stage_rows

    def stage_rows(self, columns, reach_rows=None):
        for n, item in enumerate(real(self, columns, reach_rows)):
            if n == 2:
                raise RuntimeError("the host lost its table")
            yield item

    with monkeypatch.context() as patched:
        patched.setattr(DeviceMesh, "stage_rows", stage_rows)
        with pytest.raises(RuntimeError, match="lost its table"):
            _fit("dense-binomial", table, mesh)
    assert not [k for k in table._device_cache if isinstance(k, tuple)]
    with _Delta() as counted:
        got = _fit("dense-binomial", table, mesh)
    assert counted["hostdata.placement_misses"] == 1
    assert got.tobytes() == _fit(
        "dense-binomial", Table(_dense_columns()), mesh).tobytes()


class _Recorder:
    def __init__(self):
        self.heard = []

    def on_epoch_watermark_incremented(self, epoch, state):
        self.heard.append(epoch)

    def on_iteration_terminated(self, state):
        self.heard.append(None)


def test_the_checkpointed_fit_keeps_and_finds(tmp_path):
    columns = _dense_columns()
    table, mesh = Table(columns), _mesh(4)
    golden = _fit("dense-binomial", Table(columns), mesh)

    def checkpointed(where):
        return dict(init=dict(
            checkpoint_manager=CheckpointManager(str(tmp_path / where)),
            checkpoint_interval=4))

    with _Delta() as first:
        a = _fit("dense-binomial", table, mesh, **checkpointed("a"))
    assert first["hostdata.placement_misses"] == 1
    with _Delta() as second:
        b = _fit("dense-binomial", table, mesh, **checkpointed("b"))
    assert second["hostdata.placement_hits"] == 1 and second.placed_nothing()
    with _Delta() as plain:  # and the plain fit finds what that one kept
        c = _fit("dense-binomial", table, mesh)
    assert plain["hostdata.placement_hits"] == 1 and plain.placed_nothing()
    assert a.tobytes() == b.tobytes() == c.tobytes() == golden.tobytes()


def test_a_fit_with_listeners_keeps_and_finds():
    columns = _dense_columns()
    table, mesh, heard = Table(columns), _mesh(4), _Recorder()
    x, y = columns["features"], columns["label"]
    kept = _linear_sgd.table_placements(table, "features", "label", None)

    def train(**kwargs):
        return _linear_sgd.train_linear_model(
            x, y, None, "logistic", mesh, STEPS, 0.1, BATCH, 0.0, 0.0, 0.0,
            SEED, dtype=np.float64, kept=kept, **kwargs)

    with _Delta() as first:
        a = train(listeners=(heard,))
    with _Delta() as second:
        b = train(listeners=(heard,))
    assert first["hostdata.placement_misses"] == 1
    assert second["hostdata.placement_hits"] == 1 and second.placed_nothing()
    assert heard.heard == [STEPS - 1, None] * 2
    assert a.tobytes() == b.tobytes()
    kept = None  # a caller with arrays and no table
    with _Delta() as arrays_only:
        c = train()
    assert arrays_only["hostdata.placement_hits"] == 0
    assert arrays_only["hostdata.placement_misses"] == 0
    assert arrays_only["hostdata.stage.rows_sent"] > 0
    assert c.tobytes() == a.tobytes()


@pytest.mark.parametrize("route", ["sharding_plan", "precision"])
def test_the_plan_and_precision_paths_keep_nothing(route):
    from flinkml_tpu.sharding.plan import REPLICATED

    table, mesh = Table(_dense_columns()), _mesh(4)
    init = (dict(sharding_plan=REPLICATED) if route == "sharding_plan"
            else dict(precision="mixed"))
    kept = _kept_bytes()
    with _Delta() as counted:
        for _ in range(2):
            _fit("dense-binomial", table, mesh, init=init)
    assert counted["hostdata.placement_hits"] == 0
    assert counted["hostdata.placement_misses"] == 0
    assert not [k for k in table._device_cache if isinstance(k, tuple)]
    assert _kept_bytes() == kept


@pytest.mark.parametrize("op", [
    lambda t: t.select("features", "label"),
    lambda t: t.with_column("extra", np.zeros(t.num_rows)),
    lambda t: t.take(np.arange(t.num_rows)),
], ids=["select", "with_column", "take"])
def test_a_relational_op_returns_a_table_without_the_entry(op):
    table, mesh = Table(_dense_columns()), _mesh(4)
    a = _fit("dense-binomial", table, mesh)
    derived = op(table)
    assert not [k for k in derived._device_cache if isinstance(k, tuple)]
    with _Delta() as counted:
        b = _fit("dense-binomial", derived, mesh)
    assert counted["hostdata.placement_misses"] == 1
    assert a.tobytes() == b.tobytes()


@pytest.mark.parametrize("kind", ["dense-binomial", "sparse-one-width"])
def test_dropping_the_table_frees_the_arrays(kind):
    table, mesh = Table(KINDS[kind][0]()), _mesh(4)
    before = _kept_bytes()
    _fit(kind, table, mesh)
    (entry,) = [v for k, v in table._device_cache.items() if isinstance(k, tuple)]
    held = sum(a.nbytes for a in entry.arrays)
    assert _kept_bytes() == before + held
    alive = [weakref.ref(a) for a in entry.arrays]
    del entry, table
    gc.collect()
    assert all(ref() is None for ref in alive)
    assert _kept_bytes() == before


# -- let go ---------------------------------------------------------------------

@pytest.fixture
def device_limit(monkeypatch):
    """A stood-in device: each reports free what ``room`` bytes leave
    beyond the arrays alive on it now (what ``memory_stats`` reports on a
    TPU; the CPU backend reports nothing)."""

    def used(device):
        return sum(a.nbytes // len(a.devices()) for a in jax.live_arrays()
                   if device in a.devices())

    def stand_in(room):
        base = {d: used(d) for d in jax.devices()}
        monkeypatch.setattr(
            table_mod, "_free_bytes", lambda d: base[d] + room - used(d))

    gc.collect()
    return stand_in


def _entry_bytes(table):
    return sum(a.nbytes for k, v in table._device_cache.items()
               if isinstance(k, tuple)
               for a in jax.tree_util.tree_leaves(v)
               if isinstance(a, jax.Array))


def test_the_least_recently_used_entry_goes_first(device_limit):
    mesh = _mesh(1)
    tables = [Table(_dense_columns(seed=s)) for s in range(3)]
    _fit("dense-binomial", tables[0], mesh)
    one = _entry_bytes(tables[0])
    device_limit(int(2.5 * one))  # room for two more beside what is there
    _fit("dense-binomial", tables[1], mesh)
    _fit("dense-binomial", tables[2], mesh)
    late = Table(_dense_columns(seed=9))
    with _Delta() as a:  # tables[0]'s was placed before the limit stood
        _fit("dense-binomial", tables[1], mesh)  # a hit: now the warmest
        _fit("dense-binomial", late, mesh)
    assert a["hostdata.placement_evictions"] == 1
    assert _entry_bytes(tables[0]) == 0
    assert _entry_bytes(tables[1]) == _entry_bytes(tables[2]) == one
    with _Delta() as b:  # the coldest is now tables[2], not tables[1]
        _fit("dense-binomial", Table(_dense_columns(seed=10)), mesh)
    assert b["hostdata.placement_evictions"] == 1
    assert _entry_bytes(tables[2]) == 0 and _entry_bytes(tables[1]) == one


def test_a_released_entrys_next_fit_is_a_miss_and_correct(device_limit):
    mesh, columns = _mesh(4), _dense_columns()
    table = Table(columns)
    a = _fit("dense-binomial", table, mesh)
    device_limit(0)
    with _Delta() as other:
        _fit("dense-binomial", Table(_dense_columns(seed=4)), mesh)
    assert other["hostdata.placement_evictions"] >= 1
    assert _entry_bytes(table) == 0
    with _Delta() as again:
        b = _fit("dense-binomial", table, mesh)
    assert again["hostdata.placement_misses"] == 1
    assert again["hostdata.stage.rows_sent"] > 0
    assert a.tobytes() == b.tobytes()


def test_a_hit_releases_nothing_however_full_the_device(device_limit):
    mesh = _mesh(4)
    table, other = Table(_dense_columns()), Table(_dense_columns(seed=2))
    _fit("dense-binomial", table, mesh)
    _fit("dense-binomial", other, mesh)
    device_limit(0)
    with _Delta() as counted:
        _fit("dense-binomial", table, mesh)
        _fit("dense-binomial", other, mesh)
    assert counted["hostdata.placement_hits"] == 2
    assert counted["hostdata.placement_evictions"] == 0
    assert _entry_bytes(table) > 0 and _entry_bytes(other) > 0


def test_arrays_a_fit_still_holds_outlive_the_release_of_their_entry(device_limit):
    """A release drops the table's reference and deletes nothing: a fit
    that runs on a placement it found holds the arrays it was handed,
    and they stay whole whatever is released under it."""
    mesh = _mesh(4)
    table = Table(_dense_columns())
    _fit("dense-binomial", table, mesh)
    (entry,) = [v for k, v in table._device_cache.items() if isinstance(k, tuple)]
    held = entry.arrays  # as the loop of a running fit holds them
    before = [np.asarray(a).copy() for a in held]
    del entry
    device_limit(0)
    with _Delta() as counted:
        _fit("dense-binomial", Table(_dense_columns(seed=5)), mesh)
    assert counted["hostdata.placement_evictions"] == 1
    assert _entry_bytes(table) == 0
    assert not any(a.is_deleted() for a in held)
    assert all(np.array_equal(np.asarray(a), b) for a, b in zip(held, before))


@pytest.mark.parametrize("kind", ["fm", "dense-binomial", "sparse-one-width"])
def test_three_seeds_on_one_table_stay_under_the_limit(device_limit, kind):
    mesh = _mesh(4)
    columns = _sparse_columns() if kind == "fm" else KINDS[kind][0]()
    table = Table(columns)

    def fit(seed):
        if kind == "fm":
            est = _estimator(FMClassifier, mesh, False, seed=seed, max_iter=3)
            model = est.set_factor_size(4).fit(table)
            return np.asarray(model.get_model_data()[0].column("v"))
        return _fit(kind, table, mesh, seed=seed)

    first = fit(1)
    one = _entry_bytes(table)
    device_limit(int(1.5 * one) // 4)  # one more placement fits, two do not
    with _Delta() as counted:
        fit(2)
        assert _entry_bytes(table) == 2 * one
        fit(3)
    assert _entry_bytes(table) == 2 * one
    assert counted["hostdata.placement_evictions"] == 1
    with _Delta() as again:  # seed 1's went; it is placed again, the same
        assert fit(1).tobytes() == first.tobytes()
    uploads = "fm.table_uploads" if kind == "fm" else "hostdata.placement_misses"
    assert again[uploads] == 1 and again["hostdata.placement_evictions"] == 1


def test_kmeans_entry_is_released_by_a_larger_placement_and_placed_again(
        device_limit):
    mesh = _mesh(4)
    columns = _dense_columns(rows=4 * ROWS)
    small = Table({"features": columns["features"][:ROWS]})

    def cluster():
        est = KMeans(mesh=mesh).set_k(3).set_max_iter(2).set_seed(SEED)
        return np.asarray(est.fit(small).get_model_data()[0].column("centroids"))

    with _Delta() as first:
        a = cluster()
    assert first["kmeans.table_uploads"] == 1 and _entry_bytes(small) > 0
    device_limit(_entry_bytes(small) // 4)  # the larger table's rows do not fit
    big = Table(columns)
    with _Delta() as counted:
        _fit("dense-binomial", big, mesh)
    assert counted["hostdata.placement_evictions"] == 1
    assert _entry_bytes(small) == 0 and _entry_bytes(big) > 0
    with _Delta() as again:
        b = cluster()
    assert again["kmeans.table_uploads"] == 1
    assert a.tobytes() == b.tobytes()


def test_with_no_reported_limit_nothing_is_released():
    assert table_mod._free_bytes(jax.devices()[0]) is None  # the CPU backend
    mesh = _mesh(4)
    tables = [Table(_dense_columns(seed=s)) for s in range(4)]
    with _Delta() as counted:
        for table in tables:
            _fit("dense-binomial", table, mesh)
    assert counted["hostdata.placement_evictions"] == 0
    assert all(_entry_bytes(t) > 0 for t in tables)


# -- the framework's own sweep --------------------------------------------------

def test_a_train_validation_split_places_its_train_rows_once():
    from flinkml_tpu.models import BinaryClassificationEvaluator
    from flinkml_tpu.tuning import ParamGridBuilder, TrainValidationSplit

    mesh = _mesh(4)
    est = _estimator(LogisticRegression, mesh, False)
    grid = (ParamGridBuilder()
            .add_grid(est, LogisticRegression.LEARNING_RATE, [0.01, 0.1, 0.3])
            .build())
    split = TrainValidationSplit(
        estimator=est, estimator_param_maps=grid,
        evaluator=BinaryClassificationEvaluator())
    with _Delta() as counted:
        split.fit(Table(_dense_columns()))
    # three maps on the one train table, then the final fit on the whole
    assert counted["hostdata.placement_misses"] == 2
    assert counted["hostdata.placement_hits"] == 2
