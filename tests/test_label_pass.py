"""A fit reads its label column once (``models/_data.LabelFacts``, inside
``hostdata.ingest``): the label checks answer from that pass, and
``np.unique`` sorts the column only where the labels are not all 0 or 1,
for the multinomial check or for the text of an error."""

import numpy as np
import pytest

from flinkml_tpu.models import LinearSVC, LogisticRegression
from flinkml_tpu.models._data import LabelFacts, check_binary_labels
from flinkml_tpu.table import CsrColumn, Table
from flinkml_tpu.utils import metrics

ROWS, DIM, NNZ = 600, 5, 3


def _table(labels, sparse=False, seed=0):
    rng = np.random.default_rng(seed)
    rows = len(labels)
    if sparse:
        indices = np.sort(rng.permuted(np.tile(np.arange(64), (rows, 1)),
                                       axis=1)[:, :NNZ], axis=1)
        features = CsrColumn(np.arange(rows + 1) * NNZ,
                             indices.reshape(-1).astype(np.int32),
                             rng.normal(size=rows * NNZ).astype(np.float32), 64)
    else:
        features = rng.normal(size=(rows, DIM)).astype(np.float32)
    return Table({"features": features, "label": np.asarray(labels)})


def _lr(multi_class="auto"):
    return (LogisticRegression().set_multi_class(multi_class).set_max_iter(3)
            .set_global_batch_size(128).set_seed(1))


@pytest.fixture
def unique_calls(monkeypatch):
    calls, real = [], np.unique

    def counting(*args, **kwargs):
        calls.append(args[0].shape)
        return real(*args, **kwargs)

    monkeypatch.setattr(np, "unique", counting)
    return calls


def _fallbacks():
    return metrics.group("hostdata").snapshot()["counters"].get(
        "label_unique_fallbacks", 0.0)


def _binary(dtype, rows=ROWS):
    return (np.arange(rows) % 3 == 0).astype(dtype)


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "csr"])
@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int64, np.bool_])
def test_binary_labels_are_accepted_without_a_sort(unique_calls, dtype, sparse):
    before = _fallbacks()
    coef = _lr().fit(_table(_binary(dtype), sparse)).coefficient
    assert coef.ndim == 1 and np.isfinite(coef).all()  # binomial
    assert unique_calls == [] and _fallbacks() == before
    # every dtype of the same labels trains the same model
    want = _lr().fit(_table(_binary(np.float64), sparse)).coefficient
    assert np.asarray(coef).tobytes() == np.asarray(want).tobytes()


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "csr"])
@pytest.mark.parametrize("bad", [(0, 2), (-1, 1), (0, 1, float("nan"))],
                         ids=["0-2", "minus1-1", "nan"])
def test_labels_outside_0_1_are_refused_with_the_messages_they_had(bad, sparse):
    labels = np.resize(np.asarray(bad, np.float64), ROWS)
    # What the parent printed: np.unique of the float64 (dense) or float32
    # (sparse) copy of the column.
    found = np.unique(labels.astype(np.float32 if sparse else np.float64))
    auto_is_multinomial = found.size > 2
    if not auto_is_multinomial:
        message = ("binomial logistic regression requires labels in {0, 1}, "
                   f"got {found}")
    elif sparse:
        message = "multinomial logistic regression supports dense features only"
    else:
        message = ("multinomial logistic regression requires integer labels "
                   f"covering 0..k-1 exactly, got {found[:6]}")
    before = _fallbacks()
    with pytest.raises(ValueError) as e:
        _lr().fit(_table(labels, sparse))
    assert str(e.value).startswith(message)
    assert _fallbacks() - before == 1  # one sort a fit, not one a check
    with pytest.raises(ValueError) as e:
        _lr("binomial").fit(_table(labels, sparse))
    assert str(e.value) == ("binomial logistic regression requires labels in "
                            f"{{0, 1}}, got {found}")
    with pytest.raises(ValueError) as e:
        LinearSVC().fit(_table(labels, sparse))
    assert str(e.value) == f"LinearSVC requires labels in {{0, 1}}, got {found}"


@pytest.mark.parametrize("dtype", [np.float32, np.int64])
def test_three_classes_go_multinomial_under_auto(unique_calls, dtype):
    labels = (np.arange(ROWS) % 3).astype(dtype)
    before = _fallbacks()
    coef = _lr().fit(_table(labels)).coefficient
    assert coef.shape == (3, DIM)
    assert len(unique_calls) == 1 and _fallbacks() - before == 1
    with pytest.raises(ValueError, match="covering 0..k-1 exactly"):
        _lr().fit(_table(labels * 2))  # {0, 2, 4}: classes 1 and 3 missing
    with pytest.raises(ValueError, match="covering 0..k-1 exactly"):
        _lr().fit(_table(labels.astype(np.float64) + 0.5))


def test_an_explicit_multinomial_on_binary_labels_trains_two_classes():
    coef = _lr("multinomial").fit(_table(_binary(np.float64))).coefficient
    assert coef.shape == (2, DIM)
    with pytest.raises(ValueError, match="supports dense features only"):
        _lr("multinomial").fit(_table(_binary(np.float64), sparse=True))


def test_an_empty_table_is_still_refused():
    for est in (_lr(), LinearSVC()):
        with pytest.raises(ValueError, match="training table is empty"):
            est.fit(_table(np.zeros(0)))


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "csr"])
def test_a_label_column_of_another_length_is_refused(sparse):
    """A ``Table``'s columns have one length, so only a label column of
    several values a row can disagree with the features."""
    table = _table(np.zeros((ROWS, 2)), sparse)
    with pytest.raises(ValueError, match="label column 'label' has 1200 rows, "
                                         "features have 600"):
        _lr().fit(table)


@pytest.mark.parametrize("labels,binary,lo,hi,integral", [
    ([0, 1, 1, 0], True, 0, 1, True),
    ([True, False], True, 0, 1, True),
    ([0.0, 0.0], True, 0, 0, True),
    ([0, 1, 2], False, 0, 2, True),
    ([-1.0, 1.0], False, -1, 1, True),
    ([0.0, 0.5, 1.0], False, 0, 1, False),
    ([[0.0], [1.0]], True, 0, 1, True),  # an [n, 1] column
])
def test_label_facts_of_one_pass(monkeypatch, labels, binary, lo, hi, integral):
    from flinkml_tpu.models import _data

    monkeypatch.setattr(_data, "_LABEL_SCAN_ROWS", 2)  # several steps
    facts = LabelFacts(np.asarray(labels))
    assert (facts.binary, facts.lo, facts.hi, facts.integral) == (
        binary, lo, hi, integral)
    assert facts.values.shape == (len(labels),)
    np.testing.assert_array_equal(facts.distinct(), np.unique(labels))
    assert facts.distinct().dtype.kind == "f"


@pytest.mark.parametrize("where", [0, 1, 4])
def test_a_nan_label_in_any_step_of_the_pass_stays(monkeypatch, where):
    from flinkml_tpu.models import _data

    monkeypatch.setattr(_data, "_LABEL_SCAN_ROWS", 2)
    labels = np.array([0.0, 1.0, 1.0, 0.0, 1.0])
    labels[where] = np.nan
    facts = LabelFacts(labels)
    assert not facts.binary and not facts.integral
    assert np.isnan(facts.lo) and np.isnan(facts.hi)


def test_check_binary_labels_takes_a_column_or_its_facts(unique_calls):
    y = _binary(np.float64)
    check_binary_labels(y, "M")
    check_binary_labels(LabelFacts(y), "M")
    assert unique_calls == []
    with pytest.raises(ValueError, match=r"M requires labels in \{0, 1\}, "
                                         r"got \[0\. 1\. 3\.\]"):
        check_binary_labels(np.array([0.0, 1.0, 3.0, 1.0]), "M")
    assert len(unique_calls) == 1
