"""Column-extraction helpers shared by algorithms.

The analog of the reference's row→POJO maps (e.g.
``LogisticRegression.java:111-130`` mapping rows to
``LabeledPointWithWeight``): tables are already columnar, so "extraction" is
densifying a features column to ``[n, d]`` and reading label/weight columns.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from flinkml_tpu.linalg import SparseVector, Vector, stack_vectors
from flinkml_tpu.table import Table
from flinkml_tpu.utils.profiling import span


def features_matrix(
    table: Table, features_col: str, dtype=np.float64
) -> np.ndarray:
    """Densify a features column to float [n, d].

    Accepts 2-D numeric columns (native layout) or object columns of
    ``Vector`` / array-likes (row-wise user data).

    ``dtype=None`` preserves a floating input dtype (float32 stays
    float32 — elementwise stages then move half the bytes on the CPU
    fallback path; flagged as FML106 by ``flinkml_tpu.analysis`` when
    promoted silently) and promotes non-float inputs to float64.
    """
    col = table.column(features_col)
    if col.dtype == object:
        return stack_vectors(col)
    if dtype is None:
        dtype = col.dtype if col.dtype.kind == "f" else np.float64
    if col.ndim == 1:
        return col.astype(dtype).reshape(-1, 1)
    return np.ascontiguousarray(col, dtype=dtype)


def labeled_data(
    table: Table,
    features_col: str,
    label_col: str,
    weight_col: Optional[str] = None,
    features_dtype=np.float64,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Extract (X [n,d], y [n], w [n]); weight defaults to 1.0 per row.

    ``features_dtype`` is :func:`features_matrix`'s ``dtype``: None keeps
    a floating column as the table has it (a contiguous one is not
    copied), for a caller that casts on the way to the device
    (``_linear_sgd._place_shuffled``) and computes nothing on the host.
    Labels and weights are float64 either way."""
    with span("hostdata.ingest"):
        x = features_matrix(table, features_col, dtype=features_dtype)
        y = np.asarray(table.column(label_col), dtype=np.float64).reshape(-1)
        if y.shape[0] != x.shape[0]:
            raise ValueError(
                f"label column {label_col!r} has {y.shape[0]} rows, features have {x.shape[0]}"
            )
        if weight_col is not None:
            w = np.asarray(table.column(weight_col), dtype=np.float64).reshape(-1)
        else:
            w = np.ones(x.shape[0], dtype=np.float64)
    return x, y, w


def sparse_features(table: Table, features_col: str):
    """The features column if EVERY row is a SparseVector, else None —
    the dispatch every linear model uses to pick the O(nnz) sparse path
    over densification. A mixed Sparse/Dense vector column returns None
    and takes the densifying path (which handles any Vector).

    A :class:`~flinkml_tpu.table.CsrColumn` answers for itself, with no
    row scanned or built: it is returned as it is, and
    :func:`labeled_sparse_data`, ``ops.sparse.csr_from_sparse_vectors``
    and ``ops.sparse.sparse_margins`` take its arrays."""
    csr = table.csr_column(features_col)
    if csr is not None:
        return csr if len(csr) else None
    col = table.column(features_col)
    if (
        col.dtype == object
        and col.size
        and isinstance(col[0], SparseVector)
        and all(isinstance(v, SparseVector) for v in col)
    ):
        return col
    return None


_HASH_MIX = np.uint64(0x9E3779B97F4A7C15)  # golden-ratio multiplicative mix


def hashed_feature_matrix(
    sparse_col: np.ndarray, num_buckets: int, dtype=np.float32
) -> np.ndarray:
    """Hash-bundle a SparseVector column into a dense ``[n, num_buckets]``
    matrix: bucket ``mix(col_id) % num_buckets`` accumulates the sum of
    that row's values whose column hashes there.

    The tree-model route for high-cardinality sparse inputs (one-hot /
    hashed text): histogram GBT needs a bounded dense feature space, and
    one-hot columns are individually uninformative 0/1s — bundling by a
    mixing hash (LightGBM's EFB instinct, sklearn's hashing-trick
    mechanics) keeps memory at ``n x num_buckets`` regardless of the
    original dimensionality. Collisions merge features; num_buckets
    trades memory for collision rate.
    """
    from flinkml_tpu.ops.sparse import csr_from_sparse_vectors

    indptr, indices, values, _dim = csr_from_sparse_vectors(
        sparse_col, dtype=dtype
    )
    n = indptr.size - 1
    mixed = indices.astype(np.uint64) * _HASH_MIX
    buckets = ((mixed >> np.uint64(32)) % np.uint64(num_buckets)).astype(
        np.int64
    )
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    # bincount over flat (row, bucket) keys: orders of magnitude faster
    # than np.add.at's unbuffered per-element scatter at Criteo-scale nnz.
    flat = np.bincount(
        rows * num_buckets + buckets, weights=values,
        minlength=n * num_buckets,
    )
    return flat.reshape(n, num_buckets).astype(dtype)


def check_binary_labels(y: np.ndarray, model_name: str) -> None:
    """Validate labels ∈ {0, 1} (shared by the binomial classifiers)."""
    labels = np.unique(y)
    if not np.all(np.isin(labels, (0.0, 1.0))):
        raise ValueError(
            f"{model_name} requires labels in {{0, 1}}, got {labels}"
        )


def labeled_sparse_data(
    table: Table,
    features_col: str,
    label_col: str,
    weight_col: Optional[str] = None,
    dtype=np.float32,
):
    """Sparse analog of :func:`labeled_data`: host CSR arrays + labels.

    Returns ``(indptr, indices, values, dim, y, w)``; the whole of it is
    the fit's ``hostdata.ingest`` span, as :func:`labeled_data` is the
    dense fit's.
    """
    from flinkml_tpu.ops.sparse import csr_from_sparse_vectors

    with span("hostdata.ingest"):
        col = table.csr_column(features_col)
        if col is None:
            col = table.column(features_col)
        # A CsrColumn's arrays come back as they are where the dtypes
        # fit: no copy of a Criteo-sized column.
        indptr, indices, values, dim = csr_from_sparse_vectors(col, dtype=dtype)
        y = np.asarray(table.column(label_col), dtype=dtype).reshape(-1)
        if y.shape[0] != indptr.size - 1:
            raise ValueError(
                f"label column {label_col!r} has {y.shape[0]} rows, features "
                f"have {indptr.size - 1}"
            )
        if weight_col is not None:
            w = np.asarray(table.column(weight_col), dtype=dtype).reshape(-1)
        else:
            w = np.ones(y.shape[0], dtype=dtype)
    return indptr, indices, values, dim, y, w
