"""Batched sparse representation for TPU compute.

The reference's sparse story is a per-record ``SparseVector`` fed through
scalar BLAS (``BLAS.java`` dot on indices/values). On TPU, dynamic per-row
nnz breaks XLA's static-shape requirement, so batches use a padded ELL-style
layout: ``indices [n, max_nnz] int32`` and ``values [n, max_nnz]`` with
padding entries carrying index 0 / value 0 (value 0 makes padded lanes
no-ops in every product below — no masking needed).

This is the Criteo-scale path (BASELINE.json config #5): forward = gather +
row-sum; gradient = flat ``segment_sum`` scatter-add into the dense model,
both of which XLA lowers to HBM gathers/scatters that run one element at
a time on a TPU (7 ns a cell). A slot whose columns sit in a block of its
own (:func:`slot_block_plan`) is served by :func:`block_lookup` and
:func:`block_accumulate` instead: two-level one-hot products on the MXU,
exact in float32.

On a TPU the linear trainers' blocked step runs those two products as
:mod:`flinkml_tpu.kernels.sparse_blocks`' two Mosaic kernels (PR 39):
through XLA a slot's product ``[batch, 128]`` goes to HBM and comes back
for one float of each 128 to be kept; the kernels make it, select from
it and drop it in fast memory, a tile of up to 4,096 batch rows at a
time, the blocks resident for the whole call in the form a lookup's
product takes them: a block of up to 4,096 columns as three bfloat16
parts (6 bytes a block column), a longer one as the four int8 digits of
its floats' bits (4 bytes; a lookup selects, so the bits travel as
integers at the MXU's int8 rate, PR 58), the looked-up float the
block's own bit for bit either way. Where they apply is read off the step
(``models._linear_sgd._blocks_in_fast_memory``: a TPU, float32, a
device's batch in whole tiles of 128, at most two million block
columns). :func:`block_lookup` and :func:`block_accumulate` are their
reference (``tests/test_sparse_blocks.py`` holds both to the same
gather and scatter-add) and what every other backend, dtype and batch
runs. Their payload forms (a factorization machine's rows) stand the same
way to :mod:`flinkml_tpu.kernels.payload_blocks` since PR 53: on a TPU the
factorization machines' step looks its blocked slots' rows up and
accumulates their gradient in that module's kernels
(``models._fm_sparse._walk_in_fast_memory`` says where), and
:func:`block_lookup` / :func:`block_accumulate` with a payload axis are
the reference (``tests/test_payload_blocks.py``) and every other path.
"""

from __future__ import annotations

from typing import Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from flinkml_tpu.linalg import SparseVector, next_pow2


def ell_matvec(indices, values, w) -> jax.Array:
    """Row-wise dot of a padded ELL block with a dense vector:
    ``out[r] = sum_s values[r, s] * w[indices[r, s]]``, ``[rows]``. Padded
    cells (index 0, value 0) add exactly 0; a block of zero rows or zero
    width gives zeros. The forward margin of every sparse trainer."""
    return jnp.sum(values * jnp.take(w, indices, axis=0), axis=1)


#: Lanes of a vector register: a block's local index is ``128 * hi + lo``.
LANES = 128


def block_lookup(blocks, local, precision=jax.lax.Precision.HIGHEST) -> jax.Array:
    """``blocks[s, local[s, b]]`` for ``blocks [S, R]`` float32, ``R`` a
    multiple of 128, and ``local [S, B]`` int32: a gather written as a
    two-level one-hot product, ``onehot(hi) [B, R/128] @ block [R/128,
    128]`` on the MXU and a select of lane ``lo``, so it does not run
    one element at a time. ``Precision.HIGHEST`` passes the float32
    operand through the MXU in bfloat16 pieces that sum back to it, and a
    0/1 operand is exact in bfloat16: the result is the gather bit for
    bit (any lower precision rounds it to bfloat16). A block of 128
    columns needs no product. An index outside ``[0, R)`` reads 0 or some
    element of its block, so such a cell must carry the value 0 (the zero
    rows a shard is padded with do).

    ``blocks [S, R, k]`` are blocks of ROWS, ``k`` floats a column (a
    factorization machine's weight and factors), and the result is ``[S,
    B, k]``, the rows ``blocks[s, local[s, b], :]`` bit for bit: the same
    two levels with :func:`lookup_columns` columns a product row, so
    that neither the one-hot nor the product's result is long.
    ``precision`` is for a control alone (one bfloat16 pass rounds every
    looked-up float). Since PR 53 the payload form is the reference of
    ``kernels.payload_blocks.lookup`` and what every backend but a TPU
    (and, there, a control or a step the kernels do not take) runs."""
    if blocks.ndim == 3:
        return _payload_lookup(blocks, local, precision)
    s, r = blocks.shape
    k = r // LANES
    rows_of, lanes = _one_hots(local, r, LANES, blocks.dtype)
    if rows_of is None:
        rows = blocks[:, None, :]
    else:
        rows = jnp.einsum(
            "sbk,skl->sbl", rows_of, blocks.reshape(s, k, LANES),
            precision=precision,
            preferred_element_type=blocks.dtype)
    return jnp.sum(jnp.where(lanes, rows, 0), axis=-1)


def block_accumulate(local, contrib, length: int,
                     precision=jax.lax.Precision.HIGHEST) -> jax.Array:
    """``zeros([S, length]).at[s, local[s, b]].add(contrib[s, b])``, the
    transpose of :func:`block_lookup`: ``onehot(hi)ᵀ [R/128, B] @
    (onehot(lo) · contrib) [B, 128]``, the products exact, accumulated in
    float32, the same bits every time. A cell whose index lies outside
    ``[0, length)`` must contribute 0. ``contrib [S, B, k]`` (a row of
    ``k`` floats a cell) gives ``[S, length, k]``."""
    if contrib.ndim == 3:
        return _payload_accumulate(local, contrib, length, precision)
    s, _ = local.shape
    k = length // LANES
    rows_of, lanes = _one_hots(local, length, LANES, contrib.dtype)
    spread = jnp.where(lanes, contrib[..., None], 0)
    if rows_of is None:
        return jnp.sum(spread, axis=1)
    out = jnp.einsum(
        "sbk,sbl->skl", rows_of, spread,
        precision=precision,
        preferred_element_type=contrib.dtype)
    return out.reshape(s, length)


def lookup_columns(length: int) -> int:
    """The columns ``c`` one product row of a payload LOOKUP holds, for a
    block of ``length`` columns: the one-hot is ``length / c`` long and
    the product's row ``c`` columns of the payload (a power of two up to
    128, so it divides every block length). The MXU's work is ``length x
    payload`` a cell whatever ``c`` is; what ``c`` moves is the one-hot
    the vector unit makes, the row it then picks one column from, and
    the schedule the compiler finds. ``length / 64`` up to 16, and 128
    (rows of 128 lanes, as the one-float lookup) from 8,192 columns up:
    read off whole steps on a v5e at 65,536 rows and a payload of 17
    (PERF.md section 5, PR 36: 26.8 ms a step; 29.1 at 32 for long
    blocks, 33.2 at 64, 36.1 at 8). The reference's and the non-TPU
    path's alone since PR 53: ``kernels.payload_blocks`` walks a block in
    chunks of 16 rows of 128 columns whatever its length."""
    return LANES if length >= 8192 else max(2, min(16, length // 64))


def accumulate_columns(length: int) -> int:
    """:func:`lookup_columns` for a payload ACCUMULATION, whose product
    contracts the batch: ``length / 64`` up to 8, and 128 from 6,144
    columns up. Never 16: the compiler's schedule for a ``[.., 16, 17]``
    operand costs a long block 5 to 7 times what 8 or 32 do (101 ms a
    step; PR 36, as above), and 32 for long blocks costs 51 ms beside a
    lookup at 64 or 128 where it costs 29 beside one at 32. As
    :func:`lookup_columns`: XLA's walk alone reads it since PR 53."""
    return LANES if length >= 6144 else max(2, min(8, length // 64))


def _one_hots(local, length: int, c: int, dtype):
    """``local = c * hi + lo`` as the one-hot of ``hi`` over a block's
    ``length / c`` product rows (an operand of the MXU; None where that
    is one row) and the mask of column ``lo`` of a row (a select on the
    VPU). The one-float products have rows of ``c`` = 128 lanes."""
    cols = jax.nn.one_hot(local % c, c, dtype=jnp.bool_)
    if length == c:
        return None, cols
    return jax.nn.one_hot(local // c, length // c, dtype=dtype), cols


def _payload_lookup(blocks, local, precision):
    s, r, k = blocks.shape
    c = lookup_columns(r)
    rows_of, cols = _one_hots(local, r, c, blocks.dtype)
    if rows_of is None:
        rows = blocks[:, None]
    else:
        rows = jnp.einsum(
            "sba,sack->sbck", rows_of, blocks.reshape(s, r // c, c, k),
            precision=precision, preferred_element_type=blocks.dtype)
    return jnp.sum(jnp.where(cols[..., None], rows, 0), axis=2)


def _payload_accumulate(local, contrib, length, precision):
    s, _, k = contrib.shape
    c = accumulate_columns(length)
    rows_of, cols = _one_hots(local, length, c, contrib.dtype)
    spread = jnp.where(cols[..., None], contrib[:, :, None, :], 0)
    if rows_of is None:
        return jnp.sum(spread, axis=1)
    out = jnp.einsum(
        "sba,sbck->sack", rows_of, spread,
        precision=precision, preferred_element_type=contrib.dtype)
    return out.reshape(s, length, k)


class BatchedCSR:
    """Padded batch of sparse rows with static shapes.

    Attributes:
        indices: int32 [n, max_nnz] column indices (0 where padded).
        values: float [n, max_nnz] entries (0.0 where padded).
        dim: dense width of each row.
    """

    def __init__(self, indices, values, dim: int):
        self.indices = jnp.asarray(indices, dtype=jnp.int32)
        self.values = jnp.asarray(values)
        if self.indices.shape != self.values.shape or self.indices.ndim != 2:
            raise ValueError(
                f"indices {self.indices.shape} and values {self.values.shape} "
                "must be equal 2-D shapes"
            )
        self.dim = int(dim)

    @property
    def num_rows(self) -> int:
        return self.indices.shape[0]

    @property
    def max_nnz(self) -> int:
        return self.indices.shape[1]

    # -- construction ------------------------------------------------------
    @staticmethod
    def pack_sparse_vectors(
        vectors: Iterable[SparseVector], max_nnz: int = None,
        dtype=np.float32, sort: bool = False,
    ):
        """Host-side ELL packing: returns numpy ``(indices, values, dim)``
        WITHOUT device placement — callers that shard (training) use this to
        avoid staging the full dataset in one device's HBM.

        ``sort=True`` additionally returns the pack-time global sort
        tables ``(indices, values, dim, perm, segment_ids)`` (see
        :func:`ell_sort_tables`) — the sorted-layout contract: sortedness
        is bought once at pack time, so every downstream gradient scatter
        runs with ``indices_are_sorted=True`` and no runtime sort."""
        vectors = list(vectors)
        if not vectors:
            raise ValueError("empty batch")
        dim = vectors[0].size()
        nnzs = [v.indices.size for v in vectors]
        width = max_nnz if max_nnz is not None else max(max(nnzs), 1)
        n = len(vectors)
        indices = np.zeros((n, width), dtype=np.int32)
        values = np.zeros((n, width), dtype=dtype)
        for i, v in enumerate(vectors):
            if v.size() != dim:
                raise ValueError(f"row {i} has dim {v.size()}, expected {dim}")
            k = min(v.indices.size, width)
            indices[i, :k] = v.indices[:k]
            values[i, :k] = v.values[:k]
        if sort:
            perm, segment_ids = ell_sort_tables(indices)
            return indices, values, dim, perm, segment_ids
        return indices, values, dim

    @staticmethod
    def from_sparse_vectors(
        vectors: Iterable[SparseVector], max_nnz: int = None, dtype=np.float32
    ) -> "BatchedCSR":
        indices, values, dim = BatchedCSR.pack_sparse_vectors(
            vectors, max_nnz, dtype
        )
        return BatchedCSR(indices, values, dim)

    @staticmethod
    def from_scipy(mat, dtype=np.float32) -> "BatchedCSR":
        """From a scipy.sparse matrix (CSR), padding rows to the max nnz."""
        mat = mat.tocsr()
        n, dim = mat.shape
        nnz_per_row = np.diff(mat.indptr)
        width = max(int(nnz_per_row.max()), 1) if n else 1
        indices = np.zeros((n, width), dtype=np.int32)
        values = np.zeros((n, width), dtype=dtype)
        for i in range(n):
            lo, hi = mat.indptr[i], mat.indptr[i + 1]
            k = hi - lo
            indices[i, :k] = mat.indices[lo:hi]
            values[i, :k] = mat.data[lo:hi]
        return BatchedCSR(indices, values, dim)

    # -- compute -----------------------------------------------------------
    def to_dense(self) -> jax.Array:
        """Densify to [n, dim] (for tests / small batches only)."""
        n = self.num_rows
        out = jnp.zeros((n, self.dim), dtype=self.values.dtype)
        rows = jnp.repeat(jnp.arange(n), self.max_nnz)
        return out.at[rows, self.indices.reshape(-1)].add(self.values.reshape(-1))

    def matvec(self, w) -> jax.Array:
        """Row-wise sparse dot against a dense vector: [n]."""
        return ell_matvec(self.indices, self.values, jnp.asarray(w))

    def rmatvec(self, coeffs) -> jax.Array:
        """Transpose product: X^T @ coeffs -> dense [dim].

        The sparse-gradient scatter-add (SURVEY.md §7 hard part (a)):
        flattens to one ``segment_sum`` so XLA emits a single HBM
        scatter.
        """
        coeffs = jnp.asarray(coeffs)
        contrib = (self.values * coeffs[:, None]).reshape(-1)
        flat_idx = self.indices.reshape(-1)
        return jax.ops.segment_sum(contrib, flat_idx, num_segments=self.dim)

    def slice_rows(self, start: int, stop: int) -> "BatchedCSR":
        return BatchedCSR(
            self.indices[start:stop], self.values[start:stop], self.dim
        )

    def sorted(self, nnz=None, place=None):
        """This batch as a :class:`~flinkml_tpu.table.SortedSparseColumn`
        — the pipeline-guaranteed sorted layout (pack-time global sort
        tables, ``indices_are_sorted`` recorded on the column).

        ``nnz`` optionally gives the true per-row nnz for the CSR
        ``indptr``; without it every cell counts (padding cells are the
        ELL index-0/value-0 no-op convention either way, so compute is
        unaffected — only host reconstruction of explicit zeros
        differs). ``place`` is the device placement (default
        ``jax.device_put``)."""
        from flinkml_tpu.table import SortedSparseColumn

        if place is None:
            place = jax.device_put
        idx = np.asarray(self.indices)
        n, width = idx.shape
        if nnz is None:
            nnz = np.full(n, width, dtype=np.int64)
        indptr = np.zeros(n + 1, dtype=np.int32)
        indptr[1:] = np.cumsum(np.asarray(nnz, dtype=np.int64))
        perm, segment_ids = ell_sort_tables(idx)
        return SortedSparseColumn(
            place(self.values), place(self.indices), place(indptr),
            place(perm), place(segment_ids), self.dim, n,
        )


def ell_sort_tables(indices: np.ndarray):
    """Pack-time global sort tables for a padded-ELL index block:
    ``(perm, segment_ids)``, both flat ``[rows * width] int32``.

    ``perm`` is a STABLE argsort of the flattened index block;
    ``segment_ids = flat[perm]`` is ascending by construction. A
    consumer's gradient scatter becomes
    ``segment_sum(take(contrib, perm), segment_ids,
    indices_are_sorted=True)`` — the sort is paid once here (on the
    prefetch worker thread, overlapped with compute), never at step
    time. Padding cells (index 0 / value 0) sort to the front as
    segment-0 no-op adds, so the tables cover the full padded block and
    are independent of the batch's logical row count."""
    flat = np.asarray(indices, dtype=np.int32).reshape(-1)
    perm = np.argsort(flat, kind="stable").astype(np.int32)
    return perm, flat[perm]


def pack_sorted_sparse_column(vectors: Sequence[SparseVector],
                              bucket: int = None, place=None,
                              dtype=np.float32):
    """Pack SparseVector rows into a
    :class:`~flinkml_tpu.table.SortedSparseColumn` (the prefetcher's
    sparse emission path — see that class for the layout contract).

    Rows are zero-padded to ``bucket`` (default: the fused executor's
    power-of-two row bucket) and the ELL width is quantized to the next
    power of two, so batch-size and nnz jitter inside a bucket reuse
    one compiled program downstream (zero retraces). ``place`` is the
    device placement (default ``jax.device_put``)."""
    from flinkml_tpu.pipeline_fusion import row_bucket
    from flinkml_tpu.table import SortedSparseColumn

    vectors = list(vectors)
    if not vectors:
        raise ValueError("empty batch")
    if place is None:
        place = jax.device_put
    n = len(vectors)
    if bucket is None:
        bucket = row_bucket(n)
    if bucket < n:
        raise ValueError(f"bucket {bucket} < {n} rows")
    dim = vectors[0].size()
    nnzs = np.fromiter((v.indices.size for v in vectors), dtype=np.int64,
                       count=n)
    width = next_pow2(max(int(nnzs.max()), 1))
    indices = np.zeros((bucket, width), dtype=np.int32)
    values = np.zeros((bucket, width), dtype=dtype)
    indptr = np.zeros(bucket + 1, dtype=np.int32)
    for i, v in enumerate(vectors):
        if v.size() != dim:
            raise ValueError(f"row {i} has dim {v.size()}, expected {dim}")
        k = v.indices.size
        indices[i, :k] = v.indices
        values[i, :k] = v.values
    indptr[1:n + 1] = np.cumsum(nnzs)
    indptr[n + 1:] = indptr[n]
    perm, segment_ids = ell_sort_tables(indices)
    host = np.empty(n, dtype=object)
    for i, v in enumerate(vectors):
        host[i] = v
    return SortedSparseColumn(
        place(values), place(indices), place(indptr), place(perm),
        place(segment_ids), dim, n, host_rows=host,
    )


# Elements per scoring dispatch (~64 MB of f32 working set); module-level
# so tests can shrink it to force the multi-chunk path.
_SCORING_CHUNK_ELEMS = 16 << 20


def sparse_margins(vectors: Sequence[SparseVector], coef,
                   max_buckets: int = 4) -> np.ndarray:
    """Row-wise dots ``X @ coef`` for SparseVector rows (or a
    :class:`~flinkml_tpu.table.CsrColumn`, whose arrays are taken as
    they are), skew-proof.

    ``coef`` may be a vector ``[d]`` (returns ``[n]``) or a class matrix
    ``[k, d]`` (returns ``[n, k]`` — multinomial scoring). Inference-side
    counterpart of the bucketed trainer: packs rows into nnz buckets
    (padded cells ≈ total nnz, vs n·max_nnz for a uniform
    :class:`BatchedCSR`), computes each bucket's gather-dot on device,
    and reassembles results in the caller's row order. O(nnz) memory at
    any skew and any dim.
    """
    indptr, indices, values, dim = csr_from_sparse_vectors(
        vectors, dtype=np.float32
    )
    # Same guarantee the dense path gets from `x @ coef` shape checking:
    # a dim mismatch must raise, not silently gather-clamp out-of-range
    # indices onto the last coefficient.
    coef = np.asarray(coef)
    n_coef = coef.shape[-1]
    if dim != n_coef:
        raise ValueError(
            f"features have dim {dim} but the model coefficient has "
            f"dim {n_coef}"
        )
    buckets, row_ids = pack_ell_buckets(
        indptr, indices, values, dim, max_buckets=max_buckets,
        dtype=np.float32,
    )
    n = indptr.size - 1
    multinomial = coef.ndim == 2
    k = coef.shape[0] if multinomial else 1
    coef_dev = jnp.asarray(coef.T if multinomial else coef, jnp.float32)
    out = np.empty((n, k) if multinomial else n, dtype=np.float32)
    for bucket, rows in zip(buckets, row_ids):
        n_bucket, width = bucket["indices"].shape
        # The per-dispatch working set ([chunk, slots] values + indices +
        # the gathered coefficients) is bounded so scoring a million-row
        # batch cannot blow host/HBM memory, on either branch.
        chunk = max(1, _SCORING_CHUNK_ELEMS // max(1, width * k))
        for lo in range(0, n_bucket, chunk):
            sl = slice(lo, lo + chunk)
            vb = jnp.asarray(bucket["values"][sl])       # [c, s]
            ib = jnp.asarray(bucket["indices"][sl])      # [c, s]
            # One width: the bucket's rows are the caller's, in order.
            dest = sl if rows is None else rows[sl]
            if multinomial:
                # Gather [c, s, k], contract the slot axis.
                out[dest] = np.asarray(
                    jnp.einsum("rs,rsk->rk", vb, coef_dev[ib])
                )
            else:
                out[dest] = np.asarray(ell_matvec(ib, vb, coef_dev))
    return out


# ---------------------------------------------------------------------------
# nnz-bucketed ELL packing (skew-proof Criteo-scale layout)
# ---------------------------------------------------------------------------

def csr_from_sparse_vectors(vectors: Sequence[SparseVector],
                            dtype=np.float32):
    """Host CSR arrays ``(indptr, indices, values, dim)`` from SparseVectors.

    ``dtype`` bounds host staging memory — at Criteo scale (~1e9 nnz)
    float32 staging halves the transient footprint vs float64.

    A :class:`~flinkml_tpu.table.CsrColumn` already is those arrays: they
    come back by reference (``values`` cast only if its dtype differs).
    """
    from flinkml_tpu.table import CsrColumn

    if isinstance(vectors, CsrColumn):
        if len(vectors) == 0:
            raise ValueError("empty batch")
        return (vectors.indptr, vectors.indices,
                vectors.values.astype(dtype, copy=False), vectors.dim)
    vectors = list(vectors)
    if not vectors:
        raise ValueError("empty batch")
    dim = vectors[0].size()
    nnzs = np.fromiter((v.indices.size for v in vectors), dtype=np.int64,
                       count=len(vectors))
    indptr = np.zeros(len(vectors) + 1, dtype=np.int64)
    np.cumsum(nnzs, out=indptr[1:])
    indices = np.empty(int(indptr[-1]), dtype=np.int32)
    values = np.empty(int(indptr[-1]), dtype=dtype)
    for i, v in enumerate(vectors):
        if v.size() != dim:
            raise ValueError(f"row {i} has dim {v.size()}, expected {dim}")
        lo, hi = indptr[i], indptr[i + 1]
        indices[lo:hi] = v.indices
        values[lo:hi] = v.values
    return indptr, indices, values, dim


def choose_ell_widths(nnz: np.ndarray, max_buckets: int = 4,
                      max_distinct: int = 256):
    """Optimal bucket widths for nnz-sorted rows (minimum padded cells).

    Uniform ELL pads every row to the dataset max — pathological under a
    skewed nnz distribution (round-1 VERDICT "weak" #3). Splitting the
    nnz-sorted rows into ≤ ``max_buckets`` groups, each padded to its own
    max, is solved exactly by DP over the distinct widths: the cost of a
    bucket covering sorted ranks (i, j] is ``count · width_j``. Distinct
    widths beyond ``max_distinct`` are first quantized up (cost model only
    — packing still pads to the chosen widths, correctness unaffected).

    Returns a sorted list of bucket max-widths (the last equals max(nnz),
    after quantization); every row belongs to the first bucket whose
    width ≥ its nnz.
    """
    nnz = np.asarray(nnz, dtype=np.int64)
    if nnz.size == 0:
        return [1]
    widths, counts = np.unique(np.maximum(nnz, 1), return_counts=True)
    if widths.size > max_distinct:
        step = int(np.ceil(widths.max() / max_distinct))
        q = np.maximum((widths + step - 1) // step * step, 1)
        qw, inv = np.unique(q, return_inverse=True)
        qc = np.zeros(qw.size, dtype=np.int64)
        np.add.at(qc, inv, counts)
        widths, counts = qw, qc
    V = widths.size
    G = min(max_buckets, V)
    prefix = np.zeros(V + 1, dtype=np.int64)
    np.cumsum(counts, out=prefix[1:])
    INF = np.iinfo(np.int64).max
    # dp[g][j]: min cells covering the first j distinct widths with g buckets.
    dp = np.full((G + 1, V + 1), INF, dtype=np.int64)
    choice = np.zeros((G + 1, V + 1), dtype=np.int64)
    dp[0][0] = 0
    for g in range(1, G + 1):
        for j in range(1, V + 1):
            best, arg = INF, 0
            for i in range(j):
                if dp[g - 1][i] == INF:
                    continue
                c = dp[g - 1][i] + (prefix[j] - prefix[i]) * int(widths[j - 1])
                if c < best:
                    best, arg = c, i
            dp[g][j] = best
            choice[g][j] = arg
    # Fewer buckets can never beat more here (splitting is free), so read
    # the G-bucket solution and drop empty splits.
    bounds = []
    j = V
    for g in range(G, 0, -1):
        bounds.append(int(widths[j - 1]))
        j = int(choice[g][j])
        if j == 0:
            break
    return sorted(set(bounds))


#: Rows a chunk of :func:`uniform_row_width` compares: a whole-column
#: ``diff`` of 16.8 M row pointers cost 0.44 s a fit in fresh temporaries.
_WIDTH_CHECK_ROWS = 1 << 20


def uniform_row_width(indptr: np.ndarray):
    """``k`` if every CSR row holds exactly ``k >= 1`` cells (hashed
    categorical data: Criteo's 39), else None. Then ``indices`` and
    ``values`` reshape to ``[rows, k]`` ELL blocks with no padding."""
    n = indptr.shape[0] - 1
    k = int(indptr[1]) if n > 0 else 0
    if k < 1 or int(indptr[-1]) != n * k:
        return None
    for lo in range(0, n, _WIDTH_CHECK_ROWS):
        hi = min(lo + _WIDTH_CHECK_ROWS, n)
        if np.any(indptr[lo + 1:hi + 1] - indptr[lo:hi] != k):
            return None
    return k


def one_width_block(indptr, indices, values, dtype):
    """Rows of one width (:func:`uniform_row_width`) as the one ELL
    block ``{"indices": [rows, width] int32, "values": [rows, width]}``:
    CSR already is that block, so it is two views and no cell is copied.
    None for ragged rows."""
    width = uniform_row_width(indptr)
    if width is None:
        return None
    n = indptr.shape[0] - 1
    return {
        "indices": np.asarray(indices, np.int32).reshape(n, width),
        "values": np.asarray(values).astype(dtype, copy=False)
                    .reshape(n, width),
    }


def fill_ell(bi, bv, row_starts, counts, indices, values) -> None:
    """Vectorized CSR→ELL fill: write each row's ``counts[r]`` cells
    (sourced at ``row_starts[r]``) into the padded blocks ``bi``/``bv``
    in place — the one definition of the scatter-gather shared by
    :func:`pack_ell_buckets` and the streamed uniform pack."""
    counts = np.asarray(counts, dtype=np.int64)
    row_rep = np.repeat(np.arange(counts.size), counts)
    slot = np.arange(int(counts.sum()), dtype=np.int64) - np.repeat(
        np.cumsum(counts) - counts, counts
    )
    src = np.repeat(np.asarray(row_starts, dtype=np.int64), counts) + slot
    bi[row_rep, slot] = indices[src]
    bv[row_rep, slot] = values[src]


def pack_ell_buckets(indptr, indices, values, dim: int,
                     max_buckets: int = 4, dtype=np.float32):
    """Pack CSR rows into nnz-bucketed ELL blocks.

    Returns ``(buckets, row_ids)`` where each bucket is a dict with
    ``indices [n_b, w_b] int32`` / ``values [n_b, w_b] dtype`` (padding
    entries index 0 / value 0, exactly as :class:`BatchedCSR`), and
    ``row_ids`` is a list of int64 arrays mapping bucket rows back to the
    caller's row order (for gathering labels/weights); rows of one width
    are one bucket whose rows are the caller's, and its entry is None
    (no ``arange`` of a Criteo-sized table is made). Total padded cells
    = the DP optimum of :func:`choose_ell_widths` — ≈ total nnz for any
    realistic skew, vs ``n · max_nnz`` for uniform ELL.
    """
    indptr = np.asarray(indptr, dtype=np.int64)
    block = one_width_block(indptr, indices, values, dtype)
    if block is not None:
        return [block], [None]
    nnz = np.diff(indptr)
    bucket_widths = choose_ell_widths(nnz, max_buckets=max_buckets)
    edges = np.asarray(bucket_widths, dtype=np.int64)
    which = np.searchsorted(edges, np.maximum(nnz, 1))
    buckets, row_ids = [], []
    for b, width in enumerate(bucket_widths):
        rows = np.nonzero(which == b)[0]
        if rows.size == 0:
            continue
        w = int(width)
        bi = np.zeros((rows.size, w), dtype=np.int32)
        bv = np.zeros((rows.size, w), dtype=dtype)
        fill_ell(bi, bv, indptr[rows], nnz[rows], indices, values)
        buckets.append({"indices": bi, "values": bv})
        row_ids.append(rows)
    return buckets, row_ids


#: Widest block, in columns, that a slot is looked up in as a block
#: (:func:`block_lookup`); a slot that spans more goes through the
#: gather and the scatter-add. The longest block a whole step on a v5e
#: has run and won with (PERF.md §5, PR 29: 65,536 rows a step, Criteo
#: laid out field by field, 34 fields of at most 59,697 columns and five
#: of 138,801 to 193,949): the five blocked, lengths 139,264 to 194,560,
#: 7.7 ms a step against 8.6 with them gathered and scattered, 36.8
#: with no plan. One slot alone (lookup and accumulate): 0.16 ms up to
#: 13,312 columns, 0.22 at 26,624, 0.50 at 65,536, 0.71 at 131,072,
#: 1.27-1.31 at 262,144, against 1.03 for its gather and scatter-add
#: whatever its columns.
BLOCK_MAX_COLUMNS = 194_560

#: The one-hot of one product may have this many elements (a step's rows
#: x the slots served together x a block's rows of 128; 512 MiB in
#: float32): a longer block stays general, a larger group is split.
_BLOCK_ONE_HOT_ELEMENTS = 1 << 27

#: Rows a task of :func:`slot_block_plan` reads, and the rows it folds
#: into one to give the reduction a long inner loop (39-wide rows reduce
#: three times slower unfolded; smaller tasks fight over the interpreter
#: lock).
_PLAN_TASK_ROWS, _PLAN_FOLD = 1 << 18, 32


def _column_ranges(block: np.ndarray):
    """Lowest and highest entry of each column of ``block [n, width]``."""
    n, width = block.shape
    whole = n // _PLAN_FOLD * _PLAN_FOLD
    lows, highs = [block[whole:]], [block[whole:]]
    if whole:
        folded = block[:whole].reshape(whole // _PLAN_FOLD, _PLAN_FOLD * width)
        lows.append(folded.min(axis=0).reshape(_PLAN_FOLD, width))
        highs.append(folded.max(axis=0).reshape(_PLAN_FOLD, width))
    return (np.concatenate(lows).min(axis=0),
            np.concatenate(highs).max(axis=0))


def _block_length(span: int) -> int:
    """``span`` columns rounded up a short ladder of block lengths (128,
    256, 512, 1024, then multiples of 1024): what two samples of one
    table almost surely agree on, and few lengths a program."""
    k = -(-span // LANES)
    return LANES * (next_pow2(k) if k <= 8 else -(-k // 8) * 8)


def slot_block_plan(indices: np.ndarray, dim: int, step_rows: int, pool):
    """The block each ELL slot's columns sit in, read off the cells:
    ``(plan, starts)`` for ``indices [rows, width]``. ``plan`` is the
    static half, what keys a program: per slot the block's length (a
    multiple of 128 up the ladder of :func:`_block_length`, at most
    :data:`BLOCK_MAX_COLUMNS`) or None for a slot whose columns span
    more, or whose one-hot over a step's ``step_rows`` rows would pass
    :data:`_BLOCK_ONE_HOT_ELEMENTS`. ``starts [width] int32`` is the
    runtime half, an operand of the program, in rows of 128 columns:
    every index of a blocked slot lies in ``[128 * start, 128 * start +
    length)``, the start the row of the slot's lowest index, or the last
    that keeps the block inside ``dim`` rounded up to whole rows; 0 for
    the other slots. So the plan knows only how many rows each slot
    spans: two tables of one schema share a program wherever their
    columns lie, until a span crosses a rung of the ladder. Fields laid
    on ranges of their own (one cell a field) have such blocks; rows
    hashed over all of a large ``dim`` have none, and theirs is the
    empty plan ``((), None)``. One chunked pass over the cells (at least
    one row) on ``pool``'s threads."""
    rows = indices.shape[0]
    parts = list(pool.map(
        lambda lo: _column_ranges(indices[lo:lo + _PLAN_TASK_ROWS]),
        range(0, rows, _PLAN_TASK_ROWS)))
    lows = np.min([p[0] for p in parts], axis=0)
    highs = np.max([p[1] for p in parts], axis=0)
    padded_dim = -(-dim // LANES) * LANES
    longest = min(BLOCK_MAX_COLUMNS, padded_dim,
                  _BLOCK_ONE_HOT_ELEMENTS // step_rows * LANES)
    plan, starts = [], []
    for low, high in zip(lows.tolist(), highs.tolist()):
        first = low // LANES
        length = _block_length(high + 1 - LANES * first)
        blocked = low >= 0 and high < dim and length <= longest
        plan.append(length if blocked else None)
        starts.append(min(first, (padded_dim - length) // LANES)
                      if blocked else 0)
    if not any(plan):
        return (), None
    return tuple(plan), np.asarray(starts, np.int32)


def block_groups(slot_plan: tuple, step_rows: int, payload: int = 0):
    """The blocked slots of a plan, those of one block length together
    (one product serves them) as far as :data:`_BLOCK_ONE_HOT_ELEMENTS`
    allows over ``step_rows`` rows: ``[(length, slots)]``. What is
    bounded is a slot's longest operand a row: the one-hot of the
    one-float products, or, with a ``payload`` of floats a column, the
    longer of a payload product's one-hot and its row of columns, of the
    lookup or of the accumulation."""
    by_length: dict = {}
    for slot, length in enumerate(slot_plan):
        if length is not None:
            by_length.setdefault(length, []).append(slot)
    groups = []
    for length, slots in sorted(by_length.items()):
        longest = length // LANES if not payload else max(
            max(length // c, c * payload)
            for c in (lookup_columns(length), accumulate_columns(length)))
        most = max(1, _BLOCK_ONE_HOT_ELEMENTS // (step_rows * longest))
        groups += [(length, slots[i:i + most])
                   for i in range(0, len(slots), most)]
    return groups


#: A ragged table is laid one cell a slot (:func:`align_ragged_rows`)
#: only where that pads it by at most a cell in this many.
_ALIGN_PAD_SHARE = 8

#: Rows a task of :func:`align_ragged_rows` lays: its temporaries (three
#: ``int64`` a cell) stay small enough to be reused from task to task,
#: where at 262,144 rows each is fresh memory and its page faults are
#: most of the pass (four times slower on eight threads).
_ALIGN_TASK_ROWS = 1 << 15


def align_ragged_rows(indptr, indices, values, dtype, pool):
    """Ragged rows of a table whose cells keep to fields, as the one ELL
    block ``{"indices": [rows, width], "values": [rows, width]}`` with
    field ``f`` in slot ``f`` of every row (and ``"slot_cells"``, the
    cells each slot holds), or None where the table is not of that kind.
    A field-blocked table with cells missing (a one-hot encoder that
    drops a category, a file that leaves zeros out) has rows of several
    widths, and padded at the rows' ends (:func:`pack_ell_buckets`) a
    slot would hold a different field from row to row; aligned,
    :func:`slot_block_plan` finds each slot on its field's block. The fields are read off the widest rows (``width`` cells:
    slot ``f``'s lowest column is where field ``f`` starts), every cell
    goes to the field its column falls in, and a missing cell is the
    field's first column with the value 0, which adds nothing to any
    product. Not of that kind, and None: padding past a cell in
    :data:`_ALIGN_PAD_SHARE`, rows that are not ascending, or any row
    with two cells in one field (rows hashed over ``dim``). One chunked
    pass on ``pool``'s threads; it takes the place of
    :func:`pack_ell_buckets`' fill, not a place beside it."""
    indptr = np.asarray(indptr, np.int64)
    n, cells = indptr.size - 1, int(indptr[-1])
    nnz = np.diff(indptr)
    width = int(nnz.max())
    if n * width > cells + cells // _ALIGN_PAD_SHARE:
        return None
    spans = range(0, n, _ALIGN_TASK_ROWS)
    across = np.arange(width)

    def lows_of(lo):
        widest = lo + np.flatnonzero(nnz[lo:lo + _ALIGN_TASK_ROWS] == width)
        if not widest.size:
            return np.full(width, np.iinfo(np.int64).max)
        return indices[indptr[widest][:, None] + across].min(axis=0)

    starts = np.min(list(pool.map(lows_of, spans)), axis=0)
    if np.any(np.diff(starts) <= 0):
        return None
    out_i = np.empty((n, width), np.int32)
    out_v = np.empty((n, width), dtype)
    refused = []

    def fill(lo):
        if refused:
            return None
        hi = min(lo + _ALIGN_TASK_ROWS, n)
        cols = indices[indptr[lo]:indptr[hi]]
        field = np.searchsorted(starts, cols, side="right") - 1
        at = np.repeat(np.arange(hi - lo) * width, nnz[lo:hi]) + field
        # Ascending rows: ``at`` rises from cell to cell unless a cell
        # shares a field with its neighbour (or lies under the first).
        if at.size and (field.min() < 0 or np.any(at[1:] <= at[:-1])):
            refused.append(lo)
            return None
        out_i[lo:hi] = starts
        out_v[lo:hi] = 0
        out_i[lo:hi].reshape(-1)[at] = cols
        out_v[lo:hi].reshape(-1)[at] = values[indptr[lo]:indptr[hi]]
        return np.bincount(field, minlength=width)

    filled = list(pool.map(fill, spans))
    if refused:
        return None
    return {"indices": out_i, "values": out_v,
            "slot_cells": np.sum(filled, axis=0)}


def planned_block(indptr, indices, values, dim: int, dtype, step_rows: int,
                  pool, plan: bool = True):
    """A table's cells as ONE ELL block with its slot plan, where it has
    one: ``(block, slot_plan, starts, blocked_cells)``. Rows of one width
    are the block as held (:func:`one_width_block`); ragged rows that
    keep to fields are laid one field a slot (:func:`align_ragged_rows`);
    either is planned (:func:`slot_block_plan` over ``step_rows`` rows a
    step). ``block`` is None where the table is neither, or where rows
    had to be aligned and no slot came out blocked: the caller pads
    buckets (:func:`pack_ell_buckets`) under the empty plan. ``plan``
    False (a training dtype the block products do not move unrounded)
    aligns and plans nothing. ``blocked_cells`` counts the cells in
    blocked slots (an aligned table's missing cells left out)."""
    n = np.asarray(indptr).size - 1
    slot_plan, starts, aligned = (), None, None
    block = one_width_block(indptr, indices, values, dtype)
    if block is None and plan:
        block = aligned = align_ragged_rows(indptr, indices, values, dtype, pool)
    if block is not None and plan:
        slot_plan, starts = slot_block_plan(block["indices"], dim, step_rows, pool)
    if aligned is not None and not slot_plan:
        return None, (), None, 0.0
    blocked = [j for j, length in enumerate(slot_plan) if length is not None]
    return block, slot_plan, starts, float(
        len(blocked) * n if aligned is None
        else aligned["slot_cells"][blocked].sum())


# Chunk width of the two-level running sum in chunked_run_totals. Within-
# chunk prefix sums bound the f32 cancellation error of a boundary
# difference by the CHUNK's magnitude (~eps·sqrt(C)·sigma) instead of the
# whole array's (~eps·sqrt(cells)·sigma — a fixed bias on small runs at
# 1e7 cells when the inputs are deterministic across steps).
CUMSUM_CHUNK = 65_536


def chunked_run_totals(contrib, ends):
    """Totals of contiguous runs of ``contrib`` (1-D ``[cells]`` or 2-D
    ``[cells, k]``, reduced over axis 0 per column) ending at inclusive
    indices ``ends`` (ascending; a repeated end differences to exactly
    0) — the sort-free segmented reduction behind the ``cumsum`` layouts
    of the GBT histogram and the ALS reduction.

    A single global running sum would give every boundary difference
    absolute error ~eps·|global prefix|; the two-level decomposition
    bounds it by the chunk scale instead: a run inside one chunk
    differences the LOCAL prefix sum, a run spanning chunks takes
    head/tail locally and the full chunks between from a chunk-prefix
    difference that is exactly 0 unless the run contains >= 1 full chunk
    — in which case its own magnitude is chunk-sized and the global
    error is relatively negligible. Verified against float64 at the
    1e7-cell bench shape (``tests/test_sparse_scale.py``)."""
    flat = contrib.ndim == 1
    if flat:
        contrib = contrib[:, None]
    cells, k = contrib.shape
    acc = contrib.dtype
    # Effective chunk width: inputs smaller than one chunk must not pad up
    # to the full 65536 rows — at the ALS cumsum layout ([chunk, k*k+k+1]
    # payload) a 4k-row chunk at rank ~100 would otherwise materialize a
    # multi-GB transient for a few-MB input. The error-bound rationale for
    # chunking is unaffected: an input smaller than one chunk has a single
    # chunk either way.
    C = min(CUMSUM_CHUNK, next_pow2(cells + 1))
    # Front-pad one zero cell so every boundary index shifts to >= 1 and
    # the "previous end" of the first run is index 0 (a zero); tail-pad
    # to a whole number of chunks.
    n_chunks = -(-(cells + 1) // C)
    pad_tail = n_chunks * C - (cells + 1)
    padded = jnp.concatenate([
        jnp.zeros((1, k), acc), contrib, jnp.zeros((pad_tail, k), acc)
    ])
    lcs = jnp.cumsum(padded.reshape(n_chunks, C, k), axis=1)
    chunk_tot = lcs[:, -1, :]                      # [n_chunks, k]
    chunk_prefix = jnp.cumsum(chunk_tot, axis=0)
    flat_lcs = lcs.reshape(-1, k)

    e1 = ends + 1
    s1 = jnp.concatenate([jnp.zeros((1,), ends.dtype), e1[:-1]])
    ce, cs = e1 // C, s1 // C
    local_e = jnp.take(flat_lcs, e1, axis=0)
    local_s = jnp.take(flat_lcs, s1, axis=0)
    same = (ce == cs)[:, None]
    # Spanning: tail of the start chunk + full chunks between (exactly 0
    # when ce == cs + 1) + head of the end chunk.
    tail = jnp.take(chunk_tot, cs, axis=0) - local_s
    between = jnp.take(chunk_prefix, jnp.maximum(ce - 1, 0), axis=0) - \
        jnp.take(chunk_prefix, cs, axis=0)
    out = jnp.where(same, local_e - local_s, tail + between + local_e)
    return out[:, 0] if flat else out


def run_boundary_tables(sorted_keys: np.ndarray):
    """Run boundaries of each ROW of ``sorted_keys [R, L]`` (each row
    ascending): ``(ends, cols)``, both ``[R, max_runs] int32`` — the
    pack-time companion of :func:`chunked_run_totals`. Padding repeats
    the last real end (whose running-sum difference is exactly 0) and
    the last real key. ``max_runs`` is at least 1 (an empty input yields
    a single zero-length table row)."""
    sorted_keys = np.asarray(sorted_keys)
    R, L = sorted_keys.shape
    per = []
    for row in range(R):
        s = sorted_keys[row]
        is_end = np.empty(L, np.bool_)
        is_end[:-1] = s[:-1] != s[1:]
        if L:
            is_end[-1] = True
        per.append(np.nonzero(is_end)[0].astype(np.int32))
    max_runs = max((e.size for e in per), default=1) or 1
    ends = np.full((R, max_runs), max(L - 1, 0), np.int32)
    cols = np.zeros((R, max_runs), np.int32)
    for row, e in enumerate(per):
        ends[row, : e.size] = e
        cols[row, : e.size] = sorted_keys[row, e]
        if e.size:
            cols[row, e.size:] = sorted_keys[row, e[-1]]
    return ends, cols
