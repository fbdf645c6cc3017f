"""libsvm ingest tests: native parser vs sklearn golden + python fallback."""

import os

import numpy as np
import pytest
import scipy.sparse as sp

from flinkml_tpu.io.libsvm import (
    _load_native,
    read_libsvm,
    read_libsvm_dense,
)


@pytest.fixture
def svm_file(tmp_path, rng):
    mat = sp.random(200, 40, density=0.15, random_state=0, format="csr")
    mat.data = np.round(mat.data, 6)
    y = rng.integers(0, 2, 200).astype(np.float64)
    path = str(tmp_path / "data.svm")
    with open(path, "w") as f:
        for i in range(200):
            toks = [str(y[i])]
            for j in range(mat.indptr[i], mat.indptr[i + 1]):
                toks.append(f"{mat.indices[j] + 1}:{float(mat.data[j])!r}")  # 1-based
            f.write(" ".join(toks) + "\n")
    return path, mat, y


def test_native_parser_compiles():
    assert _load_native() is not None, "g++ compile of native parser failed"


def test_native_artifact_is_named_by_source_hash(monkeypatch, caplog):
    """Only a library built from exactly the committed source (and
    compile command) can load: the artifact name carries their hash, a
    stale ``<name>.so`` in the build dir is never looked at, and a build
    that fails falls back to Python with ONE warning."""
    import logging

    from flinkml_tpu.io import _native

    so = _native.artifact_path("libsvm_parser")
    assert os.path.basename(so).startswith("libsvm_parser-")
    assert os.path.basename(so) != "libsvm_parser.so"
    stale = os.path.join(os.path.dirname(so), "libsvm_parser.so")
    with open(stale, "wb") as fh:
        fh.write(b"not a shared object")
    try:
        monkeypatch.setattr(_native, "_cache", {})
        assert _native.compile_and_load("libsvm_parser", lambda lib: None)
    finally:
        os.remove(stale)
    # another compile command names another artifact ...
    monkeypatch.setattr(_native, "_COMPILE", ("false",))
    other = _native.artifact_path("libsvm_parser")
    assert other != so and not os.path.exists(other)
    # ... and when that build fails the fallback is logged, once.
    monkeypatch.setattr(_native, "_cache", {})
    with caplog.at_level(logging.WARNING, logger="flinkml_tpu.io.native"):
        assert _native.compile_and_load("libsvm_parser", lambda lib: None) is None
        assert _native.compile_and_load("libsvm_parser", lambda lib: None) is None
    warned = [r for r in caplog.records if "pure-Python parser" in r.getMessage()]
    assert len(warned) == 1


@pytest.mark.parametrize("use_native", [True, False])
def test_against_sklearn_golden(svm_file, use_native):
    from sklearn.datasets import load_svmlight_file

    path, mat, y = svm_file
    labels, indptr, indices, values, nf = read_libsvm(path, use_native=use_native)
    gx, gy = load_svmlight_file(path)
    np.testing.assert_array_equal(labels, gy)
    assert nf == gx.shape[1]
    ours = sp.csr_matrix((values.astype(np.float64), indices, indptr), shape=(200, nf))
    diff = abs(ours - gx).max()
    assert diff < 1e-6, diff


def test_native_matches_python_fallback(svm_file):
    path, _, _ = svm_file
    a = read_libsvm(path, use_native=True)
    b = read_libsvm(path, use_native=False)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_dense_reader(svm_file):
    path, mat, y = svm_file
    x, labels = read_libsvm_dense(path)
    np.testing.assert_array_equal(labels, y)
    np.testing.assert_allclose(x, mat.toarray(), atol=1e-6)


def test_zero_based_detection(tmp_path):
    path = str(tmp_path / "zb.svm")
    with open(path, "w") as f:
        f.write("1 0:1.5 3:2.0\n0 1:1.0\n")
    labels, indptr, indices, values, nf = read_libsvm(path)
    # Index 0 present -> detected as 0-based; max index 3 -> 4 features.
    assert nf == 4
    np.testing.assert_array_equal(indices, [0, 3, 1])


def test_comments_and_blank_lines(tmp_path):
    path = str(tmp_path / "c.svm")
    with open(path, "w") as f:
        f.write("# header comment\n\n1 1:2.0\n\n0 2:3.0 # trailing\n")
    labels, indptr, indices, values, nf = read_libsvm(path)
    assert labels.tolist() == [1.0, 0.0]
    np.testing.assert_array_equal(indices, [0, 1])


def test_empty_file_raises(tmp_path):
    path = str(tmp_path / "e.svm")
    open(path, "w").close()
    with pytest.raises(ValueError, match="empty"):
        read_libsvm(path)


def test_n_features_override_and_check(svm_file):
    path, _, _ = svm_file
    *_, nf = read_libsvm(path, n_features=100)
    assert nf == 100
    with pytest.raises(ValueError, match="n_features"):
        read_libsvm(path, n_features=3)


@pytest.mark.parametrize("use_native", [True, False])
def test_malformed_label_raises(tmp_path, use_native):
    path = str(tmp_path / "bad.svm")
    with open(path, "w") as f:
        f.write("x 1:2.0\n1 1:3.0\n")
    with pytest.raises(ValueError, match="label"):
        read_libsvm(path, use_native=use_native)
    # Partially-numeric label is also rejected.
    with open(path, "w") as f:
        f.write("1.5x 1:2.0\n")
    os.remove(path + "x") if os.path.exists(path + "x") else None
    with pytest.raises(ValueError, match="label"):
        read_libsvm(path, use_native=use_native)


@pytest.mark.parametrize(
    "line,expected_nnz",
    [
        ("1 5:\n", 0),        # empty value
        ("1 5: 6:2.0\n", 0),  # whitespace after colon ends the line
        ("1 5:abc\n", 0),     # non-numeric value
        ("1 5:2.0x\n", 0),    # trailing garbage on value
        ("1 5:2.0#c\n", 0),   # comment glued to value
        ("1 garbage 3:4.0\n", 0),  # malformed token ends line
        ("1 2:1.0 5:\n", 1),  # valid pair before malformed one survives
    ],
)
def test_malformed_pairs_native_fallback_agree(tmp_path, line, expected_nnz):
    path = str(tmp_path / "m.svm")
    with open(path, "w") as f:
        f.write(line + "0 1:1.0\n")  # well-formed second line
    a = read_libsvm(path, use_native=True)
    b = read_libsvm(path, use_native=False)
    for x, y in zip(a[:4], b[:4]):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # nnz of first row:
    assert a[1][1] - a[1][0] == expected_nnz


def test_multithreaded_consistency(svm_file):
    path, _, _ = svm_file
    a = read_libsvm(path, n_threads=1)
    b = read_libsvm(path, n_threads=8)
    for x, y in zip(a[:4], b[:4]):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_read_libsvm_table_sparse_pipeline(svm_file):
    """Table reader: SparseVector column matching the file exactly, and
    consumable by the sparse LogisticRegression end to end."""
    from flinkml_tpu.io import read_libsvm_table
    from flinkml_tpu.linalg import SparseVector
    from flinkml_tpu.models import LogisticRegression

    path, mat, y = svm_file
    table = read_libsvm_table(path)
    col = table["features"]
    assert col.dtype == object and isinstance(col[0], SparseVector)
    dense = np.stack([v.to_array() for v in col])
    np.testing.assert_allclose(dense, mat.toarray(), rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(table["label"], y)
    # Rows hold sorted unique indices (the SparseVector invariant).
    for v in col[:20]:
        assert (np.diff(v.indices) > 0).all()

    model = (
        LogisticRegression().set_seed(0).set_max_iter(100)
        .set_global_batch_size(200).set_learning_rate(1.0).fit(table)
    )
    (out,) = model.transform(table)
    assert out["prediction"].shape == y.shape


def test_read_libsvm_table_duplicate_index_raises(tmp_path):
    from flinkml_tpu.io import read_libsvm_table

    path = str(tmp_path / "dup.svm")
    with open(path, "w") as f:
        f.write("1 1:2.0 1:3.0 2:1.0\n")
    with pytest.raises(ValueError, match="duplicate feature index"):
        read_libsvm_table(path)


def test_read_libsvm_table_unsorted_indices(tmp_path):
    from flinkml_tpu.io import read_libsvm_table

    path = str(tmp_path / "unsorted.svm")
    with open(path, "w") as f:
        f.write("1 5:5.0 2:2.0 9:9.0\n0 3:3.0 1:1.0\n")
    t = read_libsvm_table(path, n_features=10)
    v0 = t["features"][0]
    np.testing.assert_array_equal(v0.indices, [1, 4, 8])  # 1-based input
    np.testing.assert_array_equal(v0.values, [2.0, 5.0, 9.0])
