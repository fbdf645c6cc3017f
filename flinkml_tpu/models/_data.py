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
from flinkml_tpu.utils.metrics import metrics
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
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Extract (X [n,d], y [n], w [n]) as float64 host arrays; weight
    defaults to 1.0 per row.

    For a caller that computes on the host. The trainers that only place
    the table on the mesh (``_linear_sgd._place_shuffled``) take
    :func:`fit_columns` instead: no float64 copy, no vector of ones."""
    with span("hostdata.ingest"):
        x = features_matrix(table, features_col)
        y = np.asarray(table.column(label_col), dtype=np.float64).reshape(-1)
        _check_rows(label_col, y, x.shape[0])
        if weight_col is not None:
            w = np.asarray(table.column(weight_col), dtype=np.float64).reshape(-1)
        else:
            w = np.ones(x.shape[0], dtype=np.float64)
    return x, y, w


def _check_rows(label_col: str, y: np.ndarray, rows: int) -> None:
    if y.shape[0] != rows:
        raise ValueError(
            f"label column {label_col!r} has {y.shape[0]} rows, features have {rows}"
        )


#: Labels one step of :class:`LabelFacts`' pass compares: its temporaries
#: stay in the cache, and a column of millions costs no fresh pages.
_LABEL_SCAN_ROWS = 1 << 18


class LabelFacts:
    """A label column as the table holds it (``values``, 1-D, no copy of
    a numeric column) and what ONE chunked pass over it says: ``binary``
    (every label is 0 or 1); ``lo`` and ``hi``; ``integral``. A NaN label
    makes ``binary`` and ``integral`` false and is ``lo`` and ``hi``.

    The fits' label checks answer from these. The distinct values
    themselves (:meth:`distinct`: the one sort of the column) are asked
    for only where the labels are not all 0 or 1.

    A table-level fit takes its table's (:meth:`of`): made at the first
    fit of a ``Table`` and a label column, kept with the table as its
    placement is, and found by every later fit, whose label checks all
    still run and answer from it. A ``Table`` is immutable by its own
    contract: a caller who writes into a column's array in place after a
    fit already trains on a stale placement, and gets stale facts by the
    same act."""

    __slots__ = ("values", "binary", "lo", "hi", "integral", "_distinct",
                 "__weakref__")

    def __init__(self, column):
        y = np.asarray(column).reshape(-1)
        if y.dtype.kind not in "biuf":
            y = y.astype(np.float64)
        self.values, self._distinct = y, None
        self.binary = self.integral = True
        self.lo, self.hi = float("inf"), float("-inf")
        exact = y.dtype.kind != "f"  # bool and integer columns
        for start in range(0, y.shape[0], _LABEL_SCAN_ROWS):
            part = y[start:start + _LABEL_SCAN_ROWS]
            lo, hi = float(part.min()), float(part.max())
            # np.minimum, not min(): a NaN stays.
            self.lo = float(np.minimum(self.lo, lo))
            self.hi = float(np.maximum(self.hi, hi))
            if lo >= 0 and hi <= 1 and (
                    exact or bool(np.all((part == 0) | (part == 1)))):
                continue
            self.binary = False
            if not exact and self.integral:
                self.integral = bool(np.all(part == np.rint(part)))

    @classmethod
    def of(cls, table: Table, label_col: str) -> "LabelFacts":
        """The facts of ``table``'s label column, kept with the table
        (:meth:`Table.host_kept`). ``metrics.group("hostdata")`` counts
        ``label_facts_kept`` (found) and ``label_facts_made`` (the pass
        was run), beside ``placement_hits`` and ``placement_misses``."""
        kept = True

        def make():
            nonlocal kept
            kept = False
            return cls(table.column(label_col))

        facts = table.host_kept(("label_facts", label_col), make)
        counts = metrics.group("hostdata")
        counts.counter("label_facts_kept", float(kept))
        counts.counter("label_facts_made", float(not kept))
        return facts

    def distinct(self) -> np.ndarray:
        """The sorted distinct labels (``np.unique`` over the column,
        floating as every fit has printed them; a fit that asks counts
        one ``label_unique_fallbacks``)."""
        if self._distinct is None:
            metrics.group("hostdata").counter("label_unique_fallbacks")
            found = np.unique(self.values)
            if found.dtype.kind != "f":
                found = found.astype(np.float64)
            self._distinct = found
        return self._distinct


def _label_and_weight_columns(table: Table, label_col: str,
                              weight_col: Optional[str], rows: int):
    """``(LabelFacts, weights)`` as the table holds them (the facts kept
    with it: :meth:`LabelFacts.of`); ``weights`` is None where there is
    no weight column (the trainer makes unit weights on the device)."""
    labels = LabelFacts.of(table, label_col)
    _check_rows(label_col, labels.values, rows)
    if weight_col is None:
        return labels, None
    return labels, np.asarray(table.column(weight_col)).reshape(-1)


def fit_columns(table: Table, features_col: str, label_col: str,
                weight_col: Optional[str] = None):
    """:func:`labeled_data` for a trainer that places the table on the
    mesh and computes nothing on the host (``_linear_sgd._place_shuffled``
    casts each column chunk by chunk on its way up): ``(x, labels, w)``.

    Every column is taken as the table has it (a contiguous floating
    features column is not copied); ``labels`` is the column's
    :class:`LabelFacts`, read once a table (:meth:`LabelFacts.of`: a
    later fit of the table looks them up); ``w`` is None without a
    weight column. The whole of it is the fit's ``hostdata.ingest``
    span."""
    with span("hostdata.ingest"):
        x = features_matrix(table, features_col, dtype=None)
        labels, w = _label_and_weight_columns(
            table, label_col, weight_col, x.shape[0])
    return x, labels, w


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


def check_binary_labels(labels, model_name: str) -> None:
    """Validate labels ∈ {0, 1} (shared by the binomial classifiers).
    ``labels`` is a label column, or the :class:`LabelFacts` a fit's
    ingest has made of it already (then the column is not read again)."""
    if not isinstance(labels, LabelFacts):
        labels = LabelFacts(labels)
    if not labels.binary:
        raise ValueError(
            f"{model_name} requires labels in {{0, 1}}, got {labels.distinct()}"
        )


def labeled_sparse_data(
    table: Table,
    features_col: str,
    label_col: str,
    weight_col: Optional[str] = None,
    dtype=np.float32,
):
    """Sparse analog of :func:`labeled_data`: host CSR arrays + labels.

    Returns ``(indptr, indices, values, dim, y, w)`` with ``y`` and ``w``
    host arrays of ``dtype`` (the streamed fits cache them); the whole of
    it is the fit's ``hostdata.ingest`` span, as :func:`labeled_data` is
    the dense fit's. The table-level sparse fits take
    :func:`sparse_fit_columns` instead.
    """
    with span("hostdata.ingest"):
        indptr, indices, values, dim = _csr_arrays(table, features_col, dtype)
        y = np.asarray(table.column(label_col), dtype=dtype).reshape(-1)
        _check_rows(label_col, y, indptr.size - 1)
        if weight_col is not None:
            w = np.asarray(table.column(weight_col), dtype=dtype).reshape(-1)
        else:
            w = np.ones(y.shape[0], dtype=dtype)
    return indptr, indices, values, dim, y, w


def _csr_arrays(table: Table, features_col: str, dtype):
    from flinkml_tpu.ops.sparse import csr_from_sparse_vectors

    col = table.csr_column(features_col)
    if col is None:
        col = table.column(features_col)
    # A CsrColumn's arrays come back as they are where the dtypes fit: no
    # copy of a Criteo-sized column.
    return csr_from_sparse_vectors(col, dtype=dtype)


def sparse_fit_columns(
    table: Table,
    features_col: str,
    label_col: str,
    weight_col: Optional[str] = None,
    dtype=np.float32,
):
    """Sparse analog of :func:`fit_columns`, for
    ``_linear_sgd.prepare_sparse_buckets``: ``(indptr, indices, values,
    dim, labels, w)``, labels and weights as the table holds them
    (``labels`` a :class:`LabelFacts`, ``w`` None without a weight
    column). The fit's ``hostdata.ingest`` span."""
    with span("hostdata.ingest"):
        indptr, indices, values, dim = _csr_arrays(table, features_col, dtype)
        labels, w = _label_and_weight_columns(
            table, label_col, weight_col, indptr.size - 1)
    return indptr, indices, values, dim, labels, w
