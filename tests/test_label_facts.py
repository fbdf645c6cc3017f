"""A table keeps its label column's facts (PR 46): ``LabelFacts.of`` makes
the one chunked pass at the first fit of a ``Table`` and a label column,
keeps the facts WITH the table (``Table.host_kept``) as the fit's
placement is kept, and every later fit of that table finds them. Every
label check still runs at every fit and answers from the kept facts, so a
table that is refused at its first fit is refused the same way at its
second."""

import gc
import weakref

import numpy as np
import pytest

from flinkml_tpu.models import (
    FMClassifier,
    LinearRegression,
    LinearSVC,
    LogisticRegression,
)
from flinkml_tpu.models._data import LabelFacts
from flinkml_tpu.table import CsrColumn, Table
from flinkml_tpu.utils import metrics

ROWS, DIM, NNZ, WIDTH = 600, 5, 3, 64


def _features(sparse: bool, rows=ROWS, seed=0):
    rng = np.random.default_rng(seed)
    if not sparse:
        return rng.normal(size=(rows, DIM)).astype(np.float32)
    indices = np.sort(rng.permuted(np.tile(np.arange(WIDTH), (rows, 1)),
                                   axis=1)[:, :NNZ], axis=1)
    return CsrColumn(np.arange(rows + 1) * NNZ,
                     indices.reshape(-1).astype(np.int32),
                     rng.normal(size=rows * NNZ).astype(np.float32), WIDTH)


def _binary(rows=ROWS):
    return (np.arange(rows) % 3 == 0).astype(np.float64)


def _table(sparse: bool, labels=None, **more):
    labels = _binary() if labels is None else np.asarray(labels)
    return Table({"features": _features(sparse, len(labels)), "label": labels,
                  **more})


def _estimator(kind: str):
    est = {"lr": LogisticRegression, "svc": LinearSVC,
           "linreg": LinearRegression, "fm": FMClassifier}[kind]()
    if kind == "fm":
        est.set_factor_size(4)
    return est.set_max_iter(3).set_global_batch_size(128).set_seed(1)


def _model_bytes(model):
    (data,) = model.get_model_data()
    return [np.asarray(data.column(n)).tobytes() for n in data.column_names]


class _Counted:
    """What a block added to ``hostdata``'s two counters of the facts."""

    NAMES = ("label_facts_kept", "label_facts_made")

    @staticmethod
    def _read():
        counters = metrics.group("hostdata").snapshot()["counters"]
        return [counters.get(n, 0.0) for n in _Counted.NAMES]

    def __enter__(self):
        self.before = self._read()
        return self

    def __exit__(self, *exc):
        self.kept, self.made = (
            after - before for after, before in zip(self._read(), self.before))
        return False


#: Every estimator that ingests through ``fit_columns`` (dense) or
#: ``sparse_fit_columns`` (a ``CsrColumn``); the FMs fit a ``CsrColumn`` only.
CASES = [(kind, sparse) for kind in ("lr", "svc", "linreg") for sparse in (False, True)
         ] + [("fm", True)]
IDS = [f"{kind}-{'csr' if sparse else 'dense'}" for kind, sparse in CASES]


@pytest.mark.parametrize("kind,sparse", CASES, ids=IDS)
def test_a_second_fit_of_a_table_finds_the_first_fits_facts(kind, sparse):
    table = _table(sparse)
    with _Counted() as first:
        want = _model_bytes(_estimator(kind).fit(table))
    assert (first.kept, first.made) == (0, 1)
    with _Counted() as second:
        got = _model_bytes(_estimator(kind).fit(table))
    assert (second.kept, second.made) == (1, 0)
    assert got == want  # to the bit
    # A new Table over the same arrays holds nothing yet.
    with _Counted() as fresh:
        again = _model_bytes(_estimator(kind).fit(
            table.select(*table.column_names)))
    assert (fresh.kept, fresh.made) == (0, 1)
    assert again == want


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "csr"])
def test_two_label_columns_of_one_table_keep_two_entries(sparse):
    table = _table(sparse, other=1.0 - _binary())
    with _Counted() as both:
        one = _estimator("lr").fit(table)
        two = _estimator("lr").set_label_col("other").fit(table)
    assert (both.kept, both.made) == (0, 2)
    assert _model_bytes(one) != _model_bytes(two)
    with _Counted() as again:
        _estimator("svc").fit(table)
        _estimator("svc").set_label_col("other").fit(table)
    assert (again.kept, again.made) == (2, 0)
    assert LabelFacts.of(table, "label") is not LabelFacts.of(table, "other")
    np.testing.assert_array_equal(
        LabelFacts.of(table, "other").values, table.column("other"))


@pytest.mark.parametrize("kind,sparse", CASES, ids=IDS)
def test_a_weight_column_changes_nothing(kind, sparse):
    """The facts are the label column's: a fit with a weight column finds
    the ones a fit without it made, and trains what a fresh table trains."""
    weight = np.linspace(0.5, 1.5, ROWS)
    table = _table(sparse, weight=weight)
    _estimator(kind).fit(table)
    facts = LabelFacts.of(table, "label")
    with _Counted() as weighted:
        got = _model_bytes(_estimator(kind).set_weight_col("weight").fit(table))
    assert (weighted.kept, weighted.made) == (1, 0)
    assert LabelFacts.of(table, "label") is facts
    want = _model_bytes(_estimator(kind).set_weight_col("weight").fit(
        _table(sparse, weight=weight)))
    assert got == want


def _bad_labels(bad):
    return np.resize(np.asarray(bad, np.float64), ROWS)


#: labels, the estimator that refuses them, the start of its message
REFUSED = {
    "a-2-binomial": ((0, 1, 2), lambda: _estimator("lr").set_multi_class("binomial"),
                     "binomial logistic regression requires labels in {0, 1}, "
                     "got [0. 1. 2.]"),
    "a-2-svc": ((0, 2), lambda: _estimator("svc"),
                "LinearSVC requires labels in {0, 1}, got [0. 2.]"),
    "a-2-fm": ((0, 2), lambda: _estimator("fm"),
               "FMClassifier requires labels in {0, 1}, got [0. 2.]"),
    "nan-auto": ((0, 1, float("nan")), lambda: _estimator("lr"), None),
    "nan-binomial": ((0, 1, float("nan")),
                     lambda: _estimator("lr").set_multi_class("binomial"),
                     "binomial logistic regression requires labels in {0, 1}, "
                     "got [ 0.  1. nan]"),
    "halves-multinomial": ((0.0, 0.5, 1.0, 1.5),
                           lambda: _estimator("lr").set_multi_class("multinomial"),
                           None),
}


@pytest.mark.parametrize("case,sparse", [
    (case, sparse) for case in REFUSED for sparse in (False, True)
    if sparse or not case.endswith("fm")])  # the FMs' table fit takes a CsrColumn
def test_refused_labels_are_refused_the_same_at_the_second_fit(case, sparse):
    bad, estimator, message = REFUSED[case]
    table = _table(sparse, _bad_labels(bad))
    with _Counted() as counted:
        with pytest.raises(ValueError) as first:
            estimator().fit(table)
        with pytest.raises(ValueError) as second:
            estimator().fit(table)
    assert str(second.value) == str(first.value)
    assert (counted.kept, counted.made) == (1, 1)
    if message is not None:
        assert str(first.value) == message
    elif sparse:
        assert "supports dense features only" in str(first.value)
    else:
        assert str(first.value).startswith(
            "multinomial logistic regression requires integer labels "
            "covering 0..k-1 exactly, got ")


def test_a_refused_table_is_refused_by_every_estimator_from_the_same_facts():
    """The checks are the estimators', the facts the table's: one pass and
    one sort serve a binomial refusal, a hinge refusal and a multinomial
    fit of the same table."""
    table = _table(False, np.arange(ROWS) % 3)
    with _Counted() as counted:
        with pytest.raises(ValueError, match="binomial logistic regression requires"):
            _estimator("lr").set_multi_class("binomial").fit(table)
        with pytest.raises(ValueError, match="LinearSVC requires labels"):
            _estimator("svc").fit(table)
        coef = _estimator("lr").fit(table).coefficient
    assert coef.shape == (3, DIM)
    assert (counted.kept, counted.made) == (2, 1)


def test_a_label_column_of_another_length_is_refused_at_every_fit():
    table = _table(False, np.zeros((ROWS, 2)))
    for _ in range(2):
        with pytest.raises(ValueError, match="label column 'label' has 1200 "
                                             "rows, features have 600"):
            _estimator("lr").fit(table)


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "csr"])
def test_distinct_sorts_once_a_table(monkeypatch, sparse):
    calls, real = [], np.unique

    def counting(*args, **kwargs):
        calls.append(args[0].shape)
        return real(*args, **kwargs)

    def fallbacks():
        return metrics.group("hostdata").snapshot()["counters"].get(
            "label_unique_fallbacks", 0.0)

    monkeypatch.setattr(np, "unique", counting)
    table, before = _table(sparse, _bad_labels((0, 2))), fallbacks()
    for _ in range(3):
        with pytest.raises(ValueError, match=r"got \[0\. 2\.\]"):
            _estimator("svc").fit(table)
    assert calls == [(ROWS,)] and fallbacks() - before == 1
    # binary labels never sort at all
    good = _table(sparse)
    _estimator("svc").fit(good)
    _estimator("svc").fit(good)
    assert len(calls) == 1


def test_dropping_the_table_frees_the_facts():
    table = _table(False)
    _estimator("lr").fit(table)
    facts = weakref.ref(LabelFacts.of(table, "label"))
    column = weakref.ref(table.column("label"))
    assert facts() is not None
    del table
    gc.collect()
    assert facts() is None and column() is None


def test_host_kept_makes_once_a_key_and_a_new_table_holds_nothing():
    table = _table(False)
    made = []

    def make():
        made.append(1)
        return object()

    first = table.host_kept(("a", "label"), make)
    assert table.host_kept(("a", "label"), make) is first
    assert table.host_kept(("b", "label"), make) is not first
    assert len(made) == 2
    # Every relational op returns a NEW table, without what this one kept.
    other = table.select("features", "label")
    assert other.host_kept(("a", "label"), make) is not first
    assert len(made) == 3
