"""``table.CsrColumn``: a sparse features column that *is* CSR, carried by
a ``Table`` and taken by the sparse estimators as it is.

- the column under ``Table``'s operations, against the object column of
  ``SparseVector``s holding the same rows;
- its one validation;
- a fit of a ``CsrColumn`` table equals, bit for bit, the fit of the same
  rows as ``SparseVector``s (the parent's only way in), for the three
  linear estimators, uniform and ragged rows, one and eight devices, with
  a weight column and without;
- a fit against ``tests/reference_sparse_sgd.py`` (NumPy float64) under
  ``COEF_TOL``. **A fit at bfloat16 values, coefficient and sums fails
  that same tolerance** (``test_a_bfloat16_fit_fails_the_tolerance``): the
  comparison can tell the stated precision from the nearest one below it;
- fit and transform build no ``SparseVector``
  (``table.csr_rows_materialized``), and the sparse fit's spans.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flinkml_tpu.linalg import SparseVector
from flinkml_tpu.models import (
    LinearRegression,
    LinearSVC,
    LogisticRegression,
    _linear_sgd,
)
from flinkml_tpu.models._data import labeled_sparse_data, sparse_features
from flinkml_tpu.ops import sparse as sparse_ops
from flinkml_tpu.parallel import DeviceMesh
from flinkml_tpu.table import CsrColumn, Table
from flinkml_tpu.utils import metrics
from tests import reference_sparse_sgd as reference

DIM, NNZ, ROWS = 4096, 39, 3001
#: Widest coefficient gap allowed between a float32 fit of ``ROWS`` rows
#: (40 steps of 512) and the float64 reference: float32 fits read 7e-9 to
#: 3e-8 here (coefficients up to 0.2), a bfloat16 fit 7e-4.
COEF_TOL = 1e-6

ESTIMATORS = {"lr": (LogisticRegression, "logistic"),
              "svc": (LinearSVC, "hinge"),
              "linreg": (LinearRegression, "squared")}


def _rows(uniform, rows=ROWS, seed=5):
    """``rows`` SparseVectors (39 cells each, or 0 to 59), 0/1 labels and
    weights, from the seed."""
    rng = np.random.default_rng(seed)
    vecs = np.empty(rows, dtype=object)
    for r in range(rows):
        k = NNZ if uniform else int(rng.integers(0, 60))
        idx = np.sort(rng.choice(DIM, k, replace=False))
        val = (np.full(k, 1 / np.sqrt(NNZ)) if uniform
               else rng.standard_normal(k)).astype(np.float32)
        vecs[r] = SparseVector(DIM, idx, val)
    y = (rng.random(rows) < 0.3).astype(np.float64)
    return vecs, y, rng.random(rows) + 0.5


def _tables(uniform, rows=ROWS):
    vecs, y, w = _rows(uniform, rows)
    column = CsrColumn.from_vectors(vecs, dtype=np.float32)
    return (Table({"features": column, "label": y, "w": w}),
            Table({"features": vecs, "label": y, "w": w}))


def _same_rows(got, want):
    got, want = got.column("features"), want.column("features")
    assert got.dtype == object and got.shape == want.shape
    assert all(isinstance(g, SparseVector) and g == e for g, e in zip(got, want))


def _materialized():
    return metrics.group("table").snapshot()["counters"].get(
        "csr_rows_materialized", 0.0)


def _span_calls():
    return {k[:-len(".calls")]: v for k, v in
            metrics.group("span").snapshot()["counters"].items()
            if k.endswith(".calls")}


# -- the column under Table's operations ------------------------------------

MASK = np.random.default_rng(1).random(57) < 0.4
ROW_OPS = {
    "take-permuted": lambda t: t.take(np.random.default_rng(0).permutation(57)[:20]),
    "take-repeats-and-negatives": lambda t: t.take(np.array([3, 3, -1, 0, 56, -57])),
    "take-mask": lambda t: t.take(MASK),
    "take-none": lambda t: t.take(np.array([], np.int64)),
    "slice": lambda t: t.slice(5, 31),
    "slice-past-the-end": lambda t: t.slice(40, 400),
    "slice-empty": lambda t: t.slice(9, 9),
    "concat": lambda t: t.concat(t.slice(0, 7)),
    "select-rename": lambda t: t.select("features", "label").rename({"label": "y"}),
    "with-column-drop": lambda t: t.with_column("z", np.arange(57)).drop("w"),
}


@pytest.mark.parametrize("uniform", [True, False], ids=["uniform", "ragged"])
@pytest.mark.parametrize("op", sorted(ROW_OPS))
def test_table_operations_match_the_object_column(op, uniform):
    csr, obj = _tables(uniform, rows=57)
    got, want = ROW_OPS[op](csr), ROW_OPS[op](obj)
    assert got.num_rows == want.num_rows
    assert got.column_names == want.column_names
    column = got.csr_column("features")
    assert isinstance(column, CsrColumn) and len(column) == got.num_rows
    assert column.indices.dtype == np.int32 and column.indptr.dtype == np.int64
    if got.num_rows:
        _same_rows(got, want)
    label = "y" if "y" in got else "label"
    np.testing.assert_array_equal(got.column(label), want.column(label))


def test_relational_operations_carry_the_column_by_reference():
    csr, _ = _tables(True, rows=57)
    column = csr.csr_column("features")
    for t in (csr.select("features"), csr.drop("w"), csr.rename({"w": "v"}),
              csr.with_column("z", np.zeros(57))):
        assert t.csr_column("features") is column


@pytest.mark.parametrize("uniform", [True, False], ids=["uniform", "ragged"])
def test_batches_cover_the_rows_in_order(uniform):
    csr, obj = _tables(uniform, rows=57)
    parts = list(csr.batches(10))
    assert [p.num_rows for p in parts] == [10] * 5 + [7]
    for got, want in zip(parts, obj.batches(10)):
        assert got.csr_column("features") is not None
        _same_rows(got, want)
    assert sum(p.num_rows for p in csr.batches(10, drop_remainder=True)) == 50


def test_column_builds_the_rows_on_demand_and_counts_them():
    csr, obj = _tables(False, rows=57)
    before = _materialized()
    assert csr.csr_column("features") is not None and csr.num_rows == 57
    assert _materialized() == before  # nothing built yet
    _same_rows(csr, obj)
    assert _materialized() == before + 57
    assert csr.column("features") is csr["features"]  # cached per table
    assert _materialized() == before + 57
    assert csr.to_rows()[3]["features"] == obj.to_rows()[3]["features"]
    with pytest.raises(TypeError, match="object"):
        csr.device_column("features")
    assert not csr.is_device_resident("features")


def test_arrays_that_fit_are_kept_by_reference():
    indptr = np.array([0, 2, 2, 5], np.int64)
    indices = np.array([0, 31, 4, 5, 30], np.int32)
    values = np.arange(5, dtype=np.float32)
    column = CsrColumn(indptr, indices, values, 32)
    assert column.indptr is indptr and column.indices is indices
    assert column.values is values and column.dim == 32 and len(column) == 3
    wide = CsrColumn([0, 2, 2, 5], [0, 31, 4, 5, 30], [1, 2, 3, 4, 5], 32)
    assert wide.indices.dtype == np.int32 and wide.values.dtype == np.float64
    assert Table({"f": wide, "y": np.zeros(3)}).num_rows == 3
    with pytest.raises(ValueError, match="rows"):
        Table({"f": wide, "y": np.zeros(4)})


BAD_COLUMNS = {
    "unsorted": (([0, 2, 4], [1, 3, 9, 2], 4), "unsorted index 2 in row 1"),
    "duplicate": (([0, 2, 4], [1, 3, 2, 2], 4), "duplicate index 2 in row 1"),
    "index-too-large": (([0, 2, 4], [1, 3, 2, 32], 4), r"must lie in \[0, 32\)"),
    "index-negative": (([0, 2, 4], [-1, 3, 2, 9], 4), r"must lie in \[0, 32\)"),
    "pointer-not-monotone": (([0, 3, 2, 4], [1, 3, 5, 9], 4), "non-decreasing"),
    "pointer-not-from-zero": (([1, 2, 4], [1, 3, 5, 9], 4), "start at 0"),
    "pointer-past-the-cells": (([0, 2, 5], [1, 3, 5, 9], 4), "holds 4 cells"),
    "values-short": (([0, 2, 4], [1, 3, 5, 9], 3), "values 3"),
}


@pytest.mark.parametrize("case", sorted(BAD_COLUMNS))
def test_validation_refuses(case):
    (indptr, indices, n_values), message = BAD_COLUMNS[case]
    with pytest.raises(ValueError, match=message):
        CsrColumn(indptr, indices, np.ones(n_values, np.float32), 32)


def test_validation_walks_the_cells_in_chunks(monkeypatch):
    """A fault in a later chunk, and at a chunk's edge, is found; a row
    boundary at a chunk's edge is not mistaken for one."""
    from flinkml_tpu import linalg

    monkeypatch.setattr(linalg, "_CSR_CHECK_CELLS", 4)
    indptr = np.arange(0, 25, 3)               # 8 rows of 3 cells
    good = np.tile([7, 8, 9], 8).astype(np.int32)
    CsrColumn(indptr, good, np.ones(24, np.float32), 32)
    for cell in (4, 5, 8, 13, 23):             # second or third cell of a row
        bad = good.copy()
        bad[cell] = 0
        with pytest.raises(ValueError, match=f"in row {cell // 3}"):
            CsrColumn(indptr, bad, np.ones(24, np.float32), 32)


# -- ingest ------------------------------------------------------------------

def test_ingest_takes_the_arrays_as_they_are():
    csr, obj = _tables(True, rows=57)
    column = csr.csr_column("features")
    before = _materialized()
    assert sparse_features(csr, "features") is column
    indptr, indices, values, dim, y, w = labeled_sparse_data(
        csr, "features", "label", None)
    assert indptr is column.indptr and indices is column.indices
    assert values is column.values and dim == DIM
    assert y.dtype == np.float32 and np.array_equal(w, np.ones(57, np.float32))
    got = sparse_ops.csr_from_sparse_vectors(column, dtype=np.float64)
    want = sparse_ops.csr_from_sparse_vectors(obj.column("features"),
                                              dtype=np.float64)
    for g, e in zip(got[:3], want[:3]):
        np.testing.assert_array_equal(g, e)
    assert _materialized() == before
    assert sparse_features(csr.slice(0, 0), "features") is None


def test_uniform_rows_are_one_block_of_two_views():
    csr, _ = _tables(True, rows=57)
    column = csr.csr_column("features")
    assert sparse_ops.uniform_row_width(column.indptr) == NNZ
    (block,), (rows,) = sparse_ops.pack_ell_buckets(
        column.indptr, column.indices, column.values, DIM)
    assert np.shares_memory(block["indices"], column.indices)
    assert np.shares_memory(block["values"], column.values)
    assert rows is None  # the block's rows are the column's: no row ids
    ragged = _tables(False, rows=57)[0].csr_column("features")
    assert sparse_ops.uniform_row_width(ragged.indptr) is None
    assert sparse_ops.uniform_row_width(np.zeros(5, np.int64)) is None


def test_the_libsvm_reader_hands_over_its_csr(tmp_path):
    from flinkml_tpu.io import read_libsvm_table

    path = tmp_path / "d.svm"
    path.write_text("1 3:0.5 1:2\n0\n1 2:1 4:4 9:9\n")
    before = _materialized()
    table = read_libsvm_table(str(path), n_features=10)
    column = table.csr_column("features")
    assert column is not None and _materialized() == before
    np.testing.assert_array_equal(column.indptr, [0, 2, 2, 5])
    np.testing.assert_array_equal(column.indices, [0, 2, 1, 3, 8])  # sorted
    np.testing.assert_array_equal(column.values, [2, 0.5, 1, 4, 9])
    assert table.column("features")[0] == SparseVector(10, [0, 2], [2.0, 0.5])


# -- fit and transform -------------------------------------------------------

def _fit(kind, table, devices, weighted, max_iter=7):
    est = (ESTIMATORS[kind][0](mesh=DeviceMesh(devices=jax.devices()[:devices]))
           .set_max_iter(max_iter).set_global_batch_size(512)
           .set_learning_rate(0.5).set_seed(11))
    if weighted:
        est.set_weight_col("w")
    return est.fit(table)


def _coefficient(model):
    return np.asarray(model.get_model_data()[0].column("coefficient"))


@pytest.mark.parametrize("weighted", [False, True], ids=["unweighted", "weighted"])
@pytest.mark.parametrize("devices", [1, 8])
@pytest.mark.parametrize("uniform", [True, False], ids=["uniform", "ragged"])
@pytest.mark.parametrize("kind", sorted(ESTIMATORS))
def test_fit_equals_the_fit_of_the_same_rows_as_sparse_vectors(
        kind, uniform, devices, weighted):
    csr, obj = _tables(uniform)
    before = _materialized()
    got = _coefficient(_fit(kind, csr, devices, weighted))
    assert _materialized() == before  # the fit built no SparseVector
    want = _coefficient(_fit(kind, obj, devices, weighted))
    assert np.isfinite(got).all() and np.abs(got).max() > 0
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("kind", sorted(ESTIMATORS))
def test_transform_equals_the_transform_of_the_object_column(kind):
    csr, obj = _tables(False, rows=301)
    model = _fit(kind, csr, 1, False)
    before = _materialized()
    (got,), (want,) = model.transform(csr), model.transform(obj)
    assert _materialized() == before  # scoring built no SparseVector either
    assert got.csr_column("features") is csr.csr_column("features")
    for name in want.column_names:
        if name != "features":
            np.testing.assert_array_equal(got.column(name), want.column(name))


def _train(kind, column, y, w, dtype, max_iter=40):
    return np.asarray(_linear_sgd.train_linear_model_sparse_csr(
        column.indptr, column.indices, column.values, DIM, y, w,
        loss=ESTIMATORS[kind][1], mesh=DeviceMesh(devices=jax.devices()[:1]),
        max_iter=max_iter, learning_rate=1.0, global_batch_size=512, reg=0.0,
        elastic_net=0.0, tol=0.0, seed=3, dtype=dtype), np.float64)


def _reference(kind, column, y, w, max_iter=40):
    return reference.sparse_sgd(
        column.indices.reshape(ROWS, NNZ), column.values.reshape(ROWS, NNZ), DIM,
        y, w, ESTIMATORS[kind][1], max_iter, 1.0, 512,
        reference.seeded_order(3, ROWS))


@pytest.mark.parametrize("weighted", [False, True], ids=["unweighted", "weighted"])
@pytest.mark.parametrize("kind", sorted(ESTIMATORS))
def test_fit_matches_the_float64_reference(kind, weighted):
    csr, _ = _tables(True)
    column, y = csr.csr_column("features"), csr.column("label")
    w = csr.column("w") if weighted else np.ones(ROWS)
    want = _reference(kind, column, y, w)
    got = _train(kind, column, y, w, np.float32)
    assert np.abs(want).max() > 0.01
    assert np.abs(got - want).max() < COEF_TOL


def test_a_bfloat16_fit_fails_the_tolerance():
    """The control: the same trainer at bfloat16 values, coefficient and
    sums is NOT within ``COEF_TOL`` of the reference (it reads 7e-4), so
    the passing test above cannot be passed at the next precision down."""
    csr, _ = _tables(True)
    column, y, w = csr.csr_column("features"), csr.column("label"), np.ones(ROWS)
    low = _train("lr", column, y, w, jnp.bfloat16)
    assert np.isfinite(low).all()
    assert np.abs(low - _reference("lr", column, y, w)).max() > 100 * COEF_TOL


@pytest.mark.parametrize("uniform", [True, False], ids=["uniform", "ragged"])
def test_the_sparse_fit_has_its_spans_and_counters(uniform):
    csr, _ = _tables(uniform)
    column = csr.csr_column("features")
    spans, counts = _span_calls(), dict(
        metrics.group("hostdata.sparse").snapshot()["counters"])
    _fit("lr", csr, 1, False)
    calls = {k: v - spans.get(k, 0) for k, v in _span_calls().items()
             if v != spans.get(k, 0)}
    after = metrics.group("hostdata.sparse").snapshot()["counters"]
    added = {k: after[k] - counts.get(k, 0) for k in after}
    buckets = int(added["buckets"])
    assert buckets == 1 if uniform else 1 < buckets <= 4
    assert added["cells"] == column.indices.size
    assert (added["padded_cells"] == added["cells"] if uniform
            else added["padded_cells"] > added["cells"])
    # A bucket: its permutation (``hostdata.permute`` inside a
    # ``hostdata.shuffle``), then ONE staging round (wait, gather, place)
    # of its three arrays in lockstep: indices, values, labels. No weight
    # column: the unit weights are made on the device, under no span.
    assert calls == {"fit": 1, "hostdata.ingest": 1, "hostdata.sparse_pack": 1,
                     "hostdata.shuffle": 2 * buckets,
                     "hostdata.permute": buckets,
                     "hostdata.stage_wait": buckets,
                     "mesh.shard_batch": buckets,
                     "trainer.loop": 1, "trainer.readback": 1}


@pytest.mark.parametrize("kind", ["fm", "gbt"])
def test_the_other_sparse_consumers_take_the_column(kind):
    """GBT's hashed route takes the arrays (``hashed_feature_matrix``);
    FM's margin takes them too since PR 36 (``_fm_sparse.csr_margin``:
    the same sums in another order, so float64 rounding apart from the
    row objects' path, and no row object built)."""
    from flinkml_tpu.models.fm import FMClassifier
    from flinkml_tpu.models.gbt import GBTClassifier

    csr, obj = _tables(False, rows=201)
    est = FMClassifier().set_max_iter(3) if kind == "fm" else GBTClassifier()
    model = est.fit(obj)
    built = metrics.group("table").snapshot()["counters"].get(
        "csr_rows_materialized", 0.0)
    (got,) = model.transform(csr)
    if kind == "fm":
        assert metrics.group("table").snapshot()["counters"].get(
            "csr_rows_materialized", 0.0) == built
    (want,) = model.transform(obj)
    for name in want.column_names:
        if name == "features":
            continue
        if kind == "fm" and name == "rawPrediction":
            np.testing.assert_allclose(got.column(name), want.column(name),
                                       rtol=1e-12, atol=1e-15)
        else:
            np.testing.assert_array_equal(got.column(name), want.column(name))
    if kind == "gbt":  # its fit is deterministic: the column trains the same trees
        (again,) = est.fit(csr).transform(obj)
        np.testing.assert_array_equal(again.column("prediction"),
                                      want.column("prediction"))
