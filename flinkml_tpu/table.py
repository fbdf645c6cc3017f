"""Columnar Table — the data-plane analog of Flink's ``Table``.

The reference moves data as row streams (``Table`` ↔ ``DataStream<Row>``,
e.g. ``LogisticRegression.java:111-130`` maps rows to POJOs one at a time).
On TPU, per-record processing wastes the MXU; the native representation is a
batched columnar store: each column is an array with leading axis = rows
(feature columns are 2-D ``[rows, dim]``). This single type replaces the
reference's Table conversions and record-at-a-time operators.

Columns live in one of two homes:

  - **host**: a numpy array (the ingest format, and the only home for
    object/ragged columns);
  - **device**: a ``jax.Array`` resident in accelerator memory — the output
    format of the fused pipeline executor
    (:mod:`flinkml_tpu.pipeline_fusion`), which keeps intermediate columns
    on device across stage boundaries instead of round-tripping per stage.

The relational ops (``select`` / ``with_column`` / ``drop`` / ``rename``)
are **zero-copy for device-backed columns**: they rebind buffers under new
names without touching the host. ``column(name)`` materializes a
device-backed column to numpy **lazily** (cached after the first fetch);
``device_column(name)`` hands back the device buffer with no host copy
(uploading a host column on first use, also cached). Row-indexed ops
(``take`` / ``slice`` / ``concat`` / ``to_rows``) operate on the host
representation.
"""

from __future__ import annotations

import collections
import functools
import threading
import weakref
from typing import Any, Dict, Iterable, Iterator, List, Mapping, Optional

import numpy as np


def _is_device_array(x: Any) -> bool:
    """True for a jax.Array (without importing jax when it can't be one)."""
    if isinstance(x, np.ndarray) or x is None:
        return False
    mod = type(x).__module__
    if not (mod == "jax" or mod.startswith("jax.") or mod.startswith("jaxlib")):
        return False
    import jax

    return isinstance(x, jax.Array)


class PaddedDeviceColumn:
    """A device-resident column whose backing buffer carries extra padding
    rows beyond the column's logical row count.

    The fused pipeline executor (:mod:`flinkml_tpu.pipeline_fusion`)
    computes on row-bucket-padded buffers; wrapping its outputs instead of
    slicing them keeps result construction free of device work — the
    prefix slice happens lazily at access time (and on the CPU backend a
    host read is a zero-copy view). Rows past ``rows`` are unspecified
    (bucket-padding garbage); every consumer must go through
    :meth:`Table.column` / :meth:`Table.device_column`, which slice.
    """

    __slots__ = ("buf", "rows")

    def __init__(self, buf, rows: int):
        if buf.shape[0] < rows:
            raise ValueError(
                f"padded buffer has {buf.shape[0]} rows < logical {rows}"
            )
        self.buf = buf
        self.rows = int(rows)

    @property
    def shape(self):
        return (self.rows,) + tuple(self.buf.shape[1:])

    @property
    def ndim(self) -> int:
        return self.buf.ndim

    @property
    def dtype(self):
        return self.buf.dtype

    def to_host(self) -> np.ndarray:
        """The logical rows as a host numpy array (one device→host
        transfer; :meth:`Table.column` caches the result per table)."""
        return np.asarray(self.buf)[: self.rows]


class LazyDeviceColumn(PaddedDeviceColumn):
    """A :class:`PaddedDeviceColumn` whose buffer is not computed yet.

    The fused pipeline executor materializes only a run's *terminal*
    columns eagerly; intermediates consumed inside the run are wrapped in
    this class with a thunk that, on first access, executes a
    dead-code-eliminated program computing just that column. Shape and
    dtype are known statically (from an abstract trace), so table
    construction and relational ops never trigger the compute.
    """

    __slots__ = ("_thunk", "_buf", "_padded_shape", "_dtype")

    def __init__(self, thunk, rows: int, padded_shape, dtype):
        if padded_shape[0] < rows:
            raise ValueError(
                f"padded buffer has {padded_shape[0]} rows < logical {rows}"
            )
        self._thunk = thunk
        self._buf = None
        self._padded_shape = tuple(padded_shape)
        self._dtype = dtype
        self.rows = int(rows)

    @property
    def buf(self):
        if self._buf is None:
            self._buf = self._thunk()
            self._thunk = None
        elif getattr(self._buf, "is_deleted", None) is not None \
                and self._buf.is_deleted():
            # A materialized buffer later donated/freed must fail loudly,
            # not hand jax's cryptic deleted-array error (or stale data)
            # to whoever touches the column next.
            raise RuntimeError(
                "lazy device column buffer has been donated or freed "
                "after materialization; re-run the producing transform "
                "to recompute it"
            )
        return self._buf

    @property
    def shape(self):
        return (self.rows,) + self._padded_shape[1:]

    @property
    def ndim(self) -> int:
        return len(self._padded_shape)

    @property
    def dtype(self):
        return self._dtype


class SortedSparseColumn(PaddedDeviceColumn):
    """A device-resident SPARSE column in the pipeline-guaranteed sorted
    layout: CSR-style ``indptr`` over padded-ELL ``indices``/``values``
    blocks (zero-padded to the fused executor's power-of-two row bucket,
    exactly like every dense :class:`PaddedDeviceColumn`), plus the
    pack-time global sort tables that make the gradient scatter's
    ``indices_are_sorted=True`` fast path FREE at step time:

    - ``buf``          — ``[bucket, width]`` float values (the inherited
      padded buffer; ``width`` is quantized to a power of two so batch
      nnz jitter inside a bucket causes zero retraces),
    - ``indices``      — ``[bucket, width]`` int32 column ids, per-row
      ascending (``SparseVector`` construction guarantees it); padding
      cells carry index 0 / value 0 (the ELL no-op convention),
    - ``indptr``       — ``[bucket + 1]`` int32 CSR row pointers over
      the LOGICAL nnz (padding rows contribute 0),
    - ``perm`` / ``segment_ids`` — ``[bucket * width]`` int32: a stable
      argsort of the flat index block, computed ONCE on the prefetch
      worker thread. A consumer's scatter is
      ``segment_sum(take(contrib, perm), segment_ids,
      indices_are_sorted=True)`` with no runtime sort.

    ``indices_are_sorted`` is recorded on the column — downstream
    kernels assert the guarantee from provenance instead of trusting a
    caller flag (the FML404 contract). Who sorts: the packer (pack
    time, worker thread). Who asserts: the consumer, by reading this
    attribute. Padding semantics: padded cells sort to the front as
    segment 0 / value 0 no-op adds, so the tables cover the FULL padded
    block and are batch-size independent.
    """

    __slots__ = ("indices", "indptr", "perm", "segment_ids", "dim",
                 "indices_are_sorted", "_host_rows")

    def __init__(self, values, indices, indptr, perm, segment_ids,
                 dim: int, rows: int, host_rows=None):
        super().__init__(values, rows)
        if tuple(indices.shape) != tuple(values.shape):
            raise ValueError(
                f"indices shape {tuple(indices.shape)} != values shape "
                f"{tuple(values.shape)}"
            )
        bucket, width = values.shape
        if indptr.shape != (bucket + 1,):
            raise ValueError(
                f"indptr shape {tuple(indptr.shape)} != ({bucket + 1},)"
            )
        if perm.shape != (bucket * width,) or \
                segment_ids.shape != (bucket * width,):
            raise ValueError(
                "perm/segment_ids must be flat [bucket * width] tables"
            )
        self.indices = indices
        self.indptr = indptr
        self.perm = perm
        self.segment_ids = segment_ids
        self.dim = int(dim)
        self.indices_are_sorted = True
        self._host_rows = host_rows

    def to_host(self) -> np.ndarray:
        """The logical rows as the object array of ``SparseVector``s the
        column was packed from (kept by the packer; reconstructed from
        the CSR buffers when the column was built device-side)."""
        if self._host_rows is not None:
            return self._host_rows
        from flinkml_tpu.linalg import SparseVector

        vals = np.asarray(self.buf)
        idx = np.asarray(self.indices)
        ptr = np.asarray(self.indptr)
        out = np.empty(self.rows, dtype=object)
        for r in range(self.rows):
            k = int(ptr[r + 1] - ptr[r])
            # Columns built without a true per-row nnz count every ELL
            # cell, so index-0 padding duplicates — fold duplicates by
            # sum (the no-op padding convention makes that exact).
            ui, inv = np.unique(idx[r, :k], return_inverse=True)
            uv = np.zeros(ui.size, dtype=np.float64)
            np.add.at(uv, inv, vals[r, :k].astype(np.float64))
            out[r] = SparseVector._from_sorted(
                self.dim, ui.astype(np.int64), uv
            )
        self._host_rows = out
        return out


def _ragged_take(indptr: np.ndarray, rows):
    """Rows ``rows`` (anything that indexes an array of row numbers) of a
    ragged column laid by ``indptr``, in that order: ``(the result's indptr,
    the position in the old cells of each of its cells)``."""
    rows = np.arange(indptr.shape[0] - 1)[rows].reshape(-1)
    counts = indptr[rows + 1] - indptr[rows]
    out = np.zeros(rows.shape[0] + 1, np.int64)
    np.cumsum(counts, out=out[1:])
    # Cell j of the result is cell (its row's first cell + its slot).
    return out, (np.repeat(indptr[rows] - out[:-1], counts)
                 + np.arange(int(out[-1]), dtype=np.int64))


class CsrColumn:
    """A host SPARSE column that *is* CSR: row ``r`` holds the cells
    ``indices[indptr[r]:indptr[r + 1]]`` / ``values[...]`` of a
    ``dim``-wide vector — what a LIBSVM parser or a hashing stage
    produces in bulk, carried by a :class:`Table` without one
    ``SparseVector`` object a row.

    ``indptr`` is int64, ``indices`` int32, ``values`` any float dtype;
    arrays that already fit are kept by reference, never copied. Every
    row keeps :class:`~flinkml_tpu.linalg.SparseVector`'s invariant
    (indices in ``[0, dim)``, strictly ascending), validated ONCE here,
    vectorised (:func:`~flinkml_tpu.linalg.check_csr_structure`); the
    row operations below build from a validated column and do not look
    again. The sparse estimators (``LogisticRegression``, ``LinearSVC``,
    ``LinearRegression`` fit and transform) take the arrays as they are;
    a row-wise consumer asks :meth:`Table.column`, which builds the
    object array of ``SparseVector``s on demand (:meth:`to_vectors`).
    """

    __slots__ = ("indptr", "indices", "values", "dim")

    def __init__(self, indptr, indices, values, dim: int):
        from flinkml_tpu.linalg import check_csr_structure

        indptr = np.asarray(indptr, dtype=np.int64)
        values = np.asarray(values)
        if indptr.ndim != 1 or np.ndim(indices) != 1 or values.ndim != 1:
            raise ValueError("indptr, indices and values must be 1-D")
        if values.shape[0] != np.shape(indices)[0]:
            raise ValueError(
                f"indices hold {np.shape(indices)[0]} cells, values "
                f"{values.shape[0]}"
            )
        if values.dtype.kind != "f":
            values = values.astype(np.float64)
        # Range first, on the caller's integers: a cast to int32 must
        # not wrap an index that is out of range anyway.
        check_csr_structure(indptr, indices, int(dim), ascending=True)
        if indptr.size and int(indptr[-1]) != values.shape[0]:
            raise ValueError(
                f"indptr ends at {int(indptr[-1])}, the column holds "
                f"{values.shape[0]} cells"
            )
        self._set(indptr, np.asarray(indices, dtype=np.int32), values, dim)
        # The count of rows built for row-wise consumers exists, at 0,
        # from the first column on: "none built" is a reading.
        _materialization_metrics().counter("csr_rows_materialized", 0.0)

    def _set(self, indptr, indices, values, dim) -> "CsrColumn":
        self.indptr, self.indices, self.values = indptr, indices, values
        self.dim = int(dim)
        return self

    @classmethod
    def _trusted(cls, indptr, indices, values, dim) -> "CsrColumn":
        """Rows of a validated column: no second look."""
        return object.__new__(cls)._set(indptr, indices, values, dim)

    @classmethod
    def from_vectors(cls, vectors, dtype=np.float64) -> "CsrColumn":
        """The column holding a sequence of ``SparseVector`` rows."""
        from flinkml_tpu.linalg import SparseVector

        vectors = list(vectors)
        if not vectors or not all(isinstance(v, SparseVector) for v in vectors):
            raise ValueError("from_vectors needs at least one SparseVector")
        dim = vectors[0].size()
        if any(v.size() != dim for v in vectors):
            raise ValueError("rows differ in dim")
        indptr = np.zeros(len(vectors) + 1, np.int64)
        np.cumsum([v.indices.size for v in vectors], out=indptr[1:])
        return cls._trusted(
            indptr,
            np.concatenate([v.indices for v in vectors]).astype(np.int32),
            np.concatenate([v.values for v in vectors]).astype(dtype),
            dim,
        )

    # What a Table asks of any column.
    @property
    def shape(self):
        return (self.indptr.shape[0] - 1,)

    ndim = 1
    dtype = np.dtype(object)

    def __len__(self) -> int:
        return self.indptr.shape[0] - 1

    # -- row operations (on the arrays; no per-row object) ----------------
    def __getitem__(self, rows) -> "CsrColumn":
        """``column[a:b]`` and ``column[row numbers or mask]``, as an array
        column answers them; a single row is :meth:`Table.column`'s to
        build."""
        if isinstance(rows, slice):
            if rows.step not in (None, 1):
                return self.take(rows)
            return self.slice(rows.start, rows.stop)
        if np.ndim(rows) == 0:
            raise TypeError(
                "a CsrColumn is indexed by a slice, row numbers or a mask; "
                "Table.column(name)[i] gives row i as a SparseVector"
            )
        return self.take(rows)

    def slice(self, start, stop) -> "CsrColumn":
        start, stop, _ = slice(start, stop).indices(len(self))
        stop = max(start, stop)
        lo, hi = int(self.indptr[start]), int(self.indptr[stop])
        return CsrColumn._trusted(
            self.indptr[start:stop + 1] - lo, self.indices[lo:hi],
            self.values[lo:hi], self.dim,
        )

    def take(self, rows) -> "CsrColumn":
        """The rows ``rows`` (anything that indexes an array of row
        numbers: integers, negative ones, a boolean mask), in that
        order."""
        indptr, src = _ragged_take(self.indptr, rows)
        return CsrColumn._trusted(
            indptr, self.indices[src], self.values[src], self.dim)

    def concat(self, other: "CsrColumn") -> "CsrColumn":
        if not isinstance(other, CsrColumn) or other.dim != self.dim:
            raise ValueError(
                "a CsrColumn concatenates with a CsrColumn of its own dim")
        return CsrColumn._trusted(
            np.concatenate([self.indptr, other.indptr[1:] + self.indptr[-1]]),
            np.concatenate([self.indices, other.indices]),
            np.concatenate([self.values, other.values]),
            self.dim,
        )

    def to_vectors(self) -> np.ndarray:
        """The object array of ``SparseVector`` rows (int64 indices,
        float64 values, views of two whole-column casts), for row-wise
        consumers; counted in ``table.csr_rows_materialized``."""
        from flinkml_tpu.linalg import SparseVector

        n = len(self)
        idx = self.indices.astype(np.int64)
        val = self.values.astype(np.float64)
        idx.setflags(write=False)
        val.setflags(write=False)
        bounds = self.indptr.tolist()
        out = np.empty(n, dtype=object)
        for r in range(n):
            sl = slice(bounds[r], bounds[r + 1])
            out[r] = SparseVector._from_sorted(self.dim, idx[sl], val[sl])
        _materialization_metrics().counter("csr_rows_materialized", float(n))
        return out

    to_rows = to_vectors     # what Table.column asks of an array column

    def __repr__(self) -> str:  # pragma: no cover
        return (f"CsrColumn({len(self)} rows, dim {self.dim}, "
                f"{self.indices.shape[0]} cells, {self.values.dtype})")

class TokenColumn:
    """A host column of token LISTS that *is* two arrays: row ``r`` holds
    the tokens ``vocabulary[ids[indptr[r]:indptr[r + 1]]]``, in order, a
    token as often as it comes — a tokenised corpus as an encoder writes
    it in bulk, carried by a :class:`Table` without one Python list a row
    (a :class:`CsrColumn` cannot carry a sentence: its indices ascend
    strictly).

    ``indptr`` is int64, ``ids`` int32 in ``[0, len(vocabulary))``,
    ``vocabulary`` a 1-D array of the distinct tokens (strings, or
    anything ``str`` names); arrays that already fit are kept by
    reference. Validated ONCE here, vectorised. ``Word2Vec.fit`` takes
    the arrays as they are; a row-wise consumer asks
    :meth:`Table.column`, which builds the object array of token lists on
    demand (:meth:`to_lists`, counted in ``table.token_rows_materialized``).
    """

    __slots__ = ("indptr", "ids", "vocabulary")

    def __init__(self, indptr, ids, vocabulary):
        indptr = np.asarray(indptr, dtype=np.int64)
        vocabulary = np.asarray(vocabulary)
        if indptr.ndim != 1 or np.ndim(ids) != 1 or vocabulary.ndim != 1:
            raise ValueError("indptr, ids and vocabulary must be 1-D")
        if indptr.size == 0 or indptr[0] != 0 or np.any(np.diff(indptr) < 0):
            raise ValueError("indptr must start at 0 and never fall")
        n = np.shape(ids)[0]
        if int(indptr[-1]) != n:
            raise ValueError(
                f"indptr ends at {int(indptr[-1])}, the column holds {n} tokens")
        # Range on the caller's integers: a cast to int32 must not wrap
        # an id that is out of range anyway.
        if n and (np.min(ids) < 0 or np.max(ids) >= vocabulary.shape[0]):
            raise ValueError(
                f"token ids must lie in [0, {vocabulary.shape[0]}), the "
                "vocabulary's positions")
        self._set(indptr, np.asarray(ids, dtype=np.int32), vocabulary)
        _materialization_metrics().counter("token_rows_materialized", 0.0)

    def _set(self, indptr, ids, vocabulary) -> "TokenColumn":
        self.indptr, self.ids, self.vocabulary = indptr, ids, vocabulary
        return self

    @classmethod
    def _trusted(cls, indptr, ids, vocabulary) -> "TokenColumn":
        """Rows of a validated column: no second look."""
        return object.__new__(cls)._set(indptr, ids, vocabulary)

    @classmethod
    def from_lists(cls, docs) -> "TokenColumn":
        """The column holding a sequence of token lists (or arrays), the
        tokens as ``str`` names them: ONE ``np.unique`` over all of them,
        so the vocabulary is sorted."""
        rows = [np.asarray(d, dtype=str).reshape(-1) for d in docs]
        indptr = np.zeros(len(rows) + 1, np.int64)
        np.cumsum([r.shape[0] for r in rows], out=indptr[1:])
        flat = np.concatenate(rows) if rows else np.empty(0, str)
        vocabulary, ids = np.unique(flat, return_inverse=True)
        return cls._trusted(indptr, ids.astype(np.int32).reshape(-1), vocabulary)

    # What a Table asks of any column.
    @property
    def shape(self):
        return (self.indptr.shape[0] - 1,)

    ndim = 1
    dtype = np.dtype(object)

    def __len__(self) -> int:
        return self.indptr.shape[0] - 1

    def __getitem__(self, rows) -> "TokenColumn":
        """``column[a:b]`` and ``column[row numbers or mask]``, as an array
        column answers them; a single row is :meth:`Table.column`'s to
        build."""
        if isinstance(rows, slice) and rows.step in (None, 1):
            start, stop, _ = rows.indices(len(self))
            stop = max(start, stop)
            lo, hi = int(self.indptr[start]), int(self.indptr[stop])
            return TokenColumn._trusted(
                self.indptr[start:stop + 1] - lo, self.ids[lo:hi],
                self.vocabulary)
        if np.ndim(rows) == 0 and not isinstance(rows, slice):
            raise TypeError(
                "a TokenColumn is indexed by a slice, row numbers or a mask; "
                "Table.column(name)[i] gives row i as a list of tokens")
        indptr, src = _ragged_take(self.indptr, rows)
        return TokenColumn._trusted(indptr, self.ids[src], self.vocabulary)

    def concat(self, other: "TokenColumn") -> "TokenColumn":
        if (not isinstance(other, TokenColumn)
                or not np.array_equal(other.vocabulary, self.vocabulary)):
            raise ValueError(
                "a TokenColumn concatenates with a TokenColumn of its own "
                "vocabulary")
        return TokenColumn._trusted(
            np.concatenate([self.indptr, other.indptr[1:] + self.indptr[-1]]),
            np.concatenate([self.ids, other.ids]), self.vocabulary)

    def to_lists(self) -> np.ndarray:
        """The object array of token lists (``str`` tokens), for row-wise
        consumers; counted in ``table.token_rows_materialized``."""
        n = len(self)
        words = self.vocabulary.astype(str)[self.ids].tolist()
        bounds = self.indptr.tolist()
        out = np.empty(n, dtype=object)
        for r in range(n):
            out[r] = words[bounds[r]:bounds[r + 1]]
        _materialization_metrics().counter("token_rows_materialized", float(n))
        return out

    to_rows = to_lists       # what Table.column asks of an array column

    def __repr__(self) -> str:  # pragma: no cover
        return (f"TokenColumn({len(self)} rows, {self.ids.shape[0]} tokens, "
                f"vocabulary {self.vocabulary.shape[0]})")


#: The columns that are arrays of their own and no ``np.ndarray``: carried
#: by reference, row-indexed on their arrays, their object rows built by
#: :meth:`Table.column` alone.
_ARRAY_COLUMNS = (CsrColumn, TokenColumn)


def _is_device_backed(x: Any) -> bool:
    return _is_device_array(x) or isinstance(x, PaddedDeviceColumn)


def _materialization_metrics():
    """The table metric group (lazy import: metrics pulls in the iteration
    runtime, which must not become a hard dependency of the data plane)."""
    from flinkml_tpu.utils.metrics import metrics

    return metrics.group("table")


def _span(name: str):
    """A program span (lazy import, for the same reason)."""
    from flinkml_tpu.utils.profiling import span

    return span(name)


def _free_bytes(device) -> Optional[int]:
    """What ``device`` reports free (``bytes_limit`` less
    ``bytes_in_use``); None where the backend reports no memory (CPU)."""
    stats = device.memory_stats() or {}
    if "bytes_limit" not in stats:
        return None
    return int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))


class _ResidentSet:
    """Every placement kept with a table (:meth:`Table.device_resident`),
    of every table in the process, as ONE set in least-recently-used
    order: the device's memory is one, whoever's table filled it. An
    entry is the table (weakly: a dropped table's entries go with it),
    its key, the bytes of its device arrays and the devices they lie on;
    the arrays themselves stay with the table alone.

    Nothing bounds the set but the device: before a new placement starts,
    :meth:`make_room` releases entries from the cold end until the new
    one's bytes fit what every device it goes to reports free. Releasing
    drops the table's reference: a fit that still runs on those arrays
    holds its own, and they are freed when it returns.
    ``metrics.group("hostdata")`` has ``placement_evictions`` and the
    gauge ``placement_kept_bytes``."""

    def __init__(self):
        self._lock = threading.RLock()  # a weakref's callback may re-enter
        self._entries: "collections.OrderedDict" = collections.OrderedDict()

    @functools.cached_property
    def _counts(self):
        # Resolved at the first placement, not at import (the data plane
        # does not depend on the metrics' runtime) and not in a dropped
        # table's callback, which may run as the interpreter shuts down.
        from flinkml_tpu.utils.metrics import metrics

        return metrics.group("hostdata")

    def _report(self) -> None:
        self._counts.gauge(
            "placement_kept_bytes",
            float(sum(size for _, size, _ in self._entries.values())))

    def _forget(self, ident) -> None:
        with self._lock:
            if self._entries.pop(ident, None) is not None:
                self._report()

    def touch(self, table: "Table", key) -> None:
        with self._lock:
            if (id(table), key) in self._entries:
                self._entries.move_to_end((id(table), key))

    def add(self, table: "Table", key, value) -> None:
        import jax

        arrays = [a for a in jax.tree_util.tree_leaves(value)
                  if _is_device_array(a)]
        ident = (id(table), key)
        where = frozenset(d for a in arrays for d in a.devices())
        with self._lock:
            self._entries[ident] = (
                weakref.ref(table, lambda _: self._forget(ident)),
                sum(a.nbytes for a in arrays), where)
            self._entries.move_to_end(ident)
            self._report()

    def make_room(self, table: "Table", key, nbytes: int, devices) -> None:
        """Before ``nbytes`` are placed evenly over ``devices`` for
        ``table`` under ``key``: what the table still holds under that
        key is stale (its fit found it short) and goes first; then other
        entries with arrays on those devices, least recently used first,
        until every device has the room. A backend that reports no
        memory releases nothing."""
        devices = list(devices)
        free = {d: _free_bytes(d) for d in devices}
        need = -(-int(nbytes) // max(1, len(devices)))
        counts = self._counts
        counts.counter("placement_evictions", 0.0)
        with self._lock:
            mine = (id(table), key)
            if mine in self._entries:
                self._release(mine, free)
            for ident in list(self._entries):
                if None in free.values() or all(
                        f >= need for f in free.values()):
                    break
                entry = self._entries.get(ident)  # gone, if its table went
                if entry is not None and entry[2] & free.keys():
                    self._release(ident, free)
                    counts.counter("placement_evictions")
            self._report()

    def _release(self, ident, free) -> None:
        """Drop the table's reference to the entry ``ident``, and credit
        its bytes to the devices of ``free`` it lay on."""
        ref, size, where = self._entries.pop(ident)
        holder = ref()
        if holder is not None:
            holder._device_cache.pop(ident[1], None)
        for d in where & free.keys():
            if free[d] is not None:
                free[d] += size // len(where)


_RESIDENT = _ResidentSet()


class ResidentSlot:
    """One table's kept placement under one key: :meth:`find` it (None
    where the table holds none), :meth:`make_room` for it before its
    first array is made, :meth:`keep` it once it is whole."""

    def __init__(self, table: "Table", key: tuple):
        self._table, self._key = table, key

    def find(self):
        value = self._table._device_cache.get(self._key)
        if value is not None:
            _RESIDENT.touch(self._table, self._key)
        return value

    def make_room(self, nbytes: int, devices) -> None:
        _RESIDENT.make_room(self._table, self._key, nbytes, devices)

    def keep(self, value):
        self._table._device_cache[self._key] = value
        _RESIDENT.add(self._table, self._key, value)
        return value


class Table:
    """Immutable named-column container backed by host numpy arrays and/or
    device-resident ``jax.Array`` columns.

    All columns share the same leading dimension (row count). Columns may be:
      - 1-D arrays (scalar columns: labels, weights, categories),
      - N-D arrays (vector/matrix columns: features ``[rows, dim]``),
      - object arrays (ragged data, e.g. sparse vectors before densify),
      - ``jax.Array`` buffers (device-resident columns; see module docstring),
      - a :class:`CsrColumn` (a sparse column as CSR arrays): carried by
        reference, row-indexed on its arrays; :meth:`column` builds its
        ``SparseVector`` rows on demand, :meth:`csr_column` hands it over.
      - a :class:`TokenColumn` (token lists as two arrays and their
        vocabulary): carried the same way; :meth:`column` builds the
        lists on demand, :meth:`token_column` hands it over.
    """

    def __init__(self, columns: Mapping[str, Any]):
        if not columns:
            raise ValueError("Table requires at least one column")
        conv: Dict[str, Any] = {}
        n_rows: Optional[int] = None
        for name, col in columns.items():
            if isinstance(col, (np.ndarray,) + _ARRAY_COLUMNS) or _is_device_backed(col):
                arr = col
            else:
                arr = _to_array(col)
            if arr.ndim == 0:
                # Scalar columns become single-row columns so every column
                # supports row slicing uniformly.
                arr = arr.reshape(1)
            if n_rows is None:
                n_rows = arr.shape[0]
            elif arr.shape[0] != n_rows:
                raise ValueError(
                    f"Column {name!r} has {arr.shape[0]} rows, expected {n_rows}"
                )
            conv[name] = arr
        self._columns = conv
        self._num_rows = int(n_rows or 0)
        # Lazy per-home caches: a device column fetched to host (or a host
        # column uploaded to device) is converted at most once per Table.
        self._host_cache: Dict[str, np.ndarray] = {}
        self._device_cache: Dict[str, Any] = {}
        # What a fit read off a host column (:meth:`host_kept`).
        self._host_kept: Dict[tuple, Any] = {}

    # -- construction ------------------------------------------------------
    @staticmethod
    def from_columns(**columns: Any) -> "Table":
        return Table(columns)

    @staticmethod
    def from_rows(rows: Iterable[Mapping[str, Any]]) -> "Table":
        rows = list(rows)
        if not rows:
            raise ValueError("Table.from_rows requires at least one row")
        names = list(rows[0].keys())
        return Table({n: _to_array([r[n] for r in rows]) for n in names})

    # -- schema ------------------------------------------------------------
    @property
    def column_names(self) -> List[str]:
        return list(self._columns.keys())

    @property
    def num_rows(self) -> int:
        return self._num_rows

    def __len__(self) -> int:
        return self._num_rows

    def __contains__(self, name: str) -> bool:
        return name in self._columns

    def _raw_column(self, name: str) -> Any:
        if name not in self._columns:
            raise KeyError(
                f"Column {name!r} not in table (has {self.column_names})"
            )
        return self._columns[name]

    def is_device_resident(self, name: str) -> bool:
        """True when the column's backing buffer lives in device memory."""
        return _is_device_backed(self._raw_column(name))

    def column(self, name: str) -> np.ndarray:
        """The column as a host numpy array.

        Device-backed columns materialize lazily HERE (one device→host
        transfer, cached); until this call they cost no host bandwidth.
        """
        col = self._raw_column(name)
        if isinstance(col, _ARRAY_COLUMNS):
            if name not in self._host_cache:
                self._host_cache[name] = col.to_rows()
            return self._host_cache[name]
        if not _is_device_backed(col):
            return col
        if name not in self._host_cache:
            with _span("table.to_host"):
                if isinstance(col, PaddedDeviceColumn):
                    host = col.to_host()
                else:
                    host = np.asarray(col)
            group = _materialization_metrics()
            group.counter("device_to_host_materializations")
            group.counter("device_to_host_bytes", float(host.nbytes))
            self._host_cache[name] = host
        return self._host_cache[name]

    __getitem__ = column

    def csr_column(self, name: str) -> Optional[CsrColumn]:
        """The column's :class:`CsrColumn` if it is one, else None; no
        row is built."""
        col = self._raw_column(name)
        return col if isinstance(col, CsrColumn) else None

    def token_column(self, name: str) -> Optional[TokenColumn]:
        """The column's :class:`TokenColumn` if it is one, else None; no
        row is built."""
        col = self._raw_column(name)
        return col if isinstance(col, TokenColumn) else None

    def _host_rows(self, name: str):
        """What the row-indexed ops index: a CsrColumn as it is, any
        other column as :meth:`column` gives it."""
        col = self._raw_column(name)
        return col if isinstance(col, _ARRAY_COLUMNS) else self.column(name)

    def device_column(self, name: str):
        """The column as a device-resident ``jax.Array`` — no host copy for
        device-backed columns; host columns upload on first use (cached).

        Object (ragged) columns have no device representation and raise.
        """
        col = self._raw_column(name)
        if _is_device_array(col):
            return col
        if isinstance(col, PaddedDeviceColumn):
            if name not in self._device_cache:
                self._device_cache[name] = col.buf[: col.rows]
            return self._device_cache[name]
        if col.dtype == object:
            raise TypeError(
                f"Column {name!r} is an object (ragged) column; it has no "
                "device representation"
            )
        if name not in self._device_cache:
            import jax
            import jax.numpy as jnp

            # Uploads preserve the host dtype exactly (a float64 column
            # stays float64 even when the ambient x64 flag is off): the
            # fused executor's bit-parity contract depends on the device
            # copy being the same bits as the host column.
            with _span("table.to_device"), jax.enable_x64(True):
                self._device_cache[name] = jnp.asarray(col)
        return self._device_cache[name]

    def has_device_copy(self, name: str) -> bool:
        """True when :meth:`device_column` would cost no host→device copy
        (the column is device-backed, or its upload is already cached)."""
        return _is_device_backed(self._raw_column(name)) or name in self._device_cache

    def device_column_padded(self, name: str, rows: int):
        """:meth:`device_column` zero-padded on device to ``rows`` rows,
        cached per ``(column, rows)`` — the fused pipeline executor's
        ingest path. Tables are immutable, so repeated ``transform`` calls
        over the same table reuse the padded buffer with zero host work.
        """
        key = (name, int(rows))
        if key not in self._device_cache:
            raw = self._raw_column(name)
            if isinstance(raw, PaddedDeviceColumn) and raw.buf.shape[0] == rows:
                # A fused-executor output re-entering a fused run at the
                # same bucket: hand the padded buffer straight through
                # (rows past the logical count are unspecified either way;
                # kernels see only what the validity mask admits).
                self._device_cache[key] = raw.buf
            elif (isinstance(raw, np.ndarray) and raw.dtype != object
                    and name not in self._device_cache
                    and int(rows) > raw.shape[0]):
                # Host-resident source: pad on HOST (one memcpy) and
                # upload the padded buffer — a pure transfer. The old
                # device-side jnp.concatenate pad compiled one XLA
                # program PER (rows, pad) shape pair; a serving replica
                # flushing partial batches of arbitrary sizes (the
                # underloaded-pool shape) hit a fresh ~50 ms compile on
                # almost every dispatch, collapsing multi-replica
                # throughput. Bit-identical to the device pad: zeros are
                # zeros.
                import jax
                import jax.numpy as jnp

                with _span("table.to_device"):
                    buf = np.zeros((int(rows),) + raw.shape[1:], raw.dtype)
                    buf[:raw.shape[0]] = raw
                    with jax.enable_x64(True):
                        self._device_cache[key] = jnp.asarray(buf)
            else:
                import jax
                import jax.numpy as jnp

                arr = self.device_column(name)
                pad = int(rows) - arr.shape[0]
                if pad > 0:
                    with jax.enable_x64(True):
                        arr = jnp.concatenate(
                            [arr, jnp.zeros((pad,) + arr.shape[1:], arr.dtype)]
                        )
                self._device_cache[key] = arr
        return self._device_cache[key]

    def device_resident(self, key: tuple, place):
        """What ``place(make_room)`` put on a device for this table, kept
        WITH the table under ``key`` (a tuple that names the column, the
        mesh and the dtype, and so cannot meet :meth:`device_column`'s
        keys): an estimator that places a column its own way
        (``KMeans.fit``: the rows over a mesh, their norms and mask
        beside them) finds it again at its next fit on this table and
        uploads nothing. ``place`` calls ``make_room(nbytes, devices)``
        once it knows its size, before it makes its first array
        (:class:`ResidentSlot`). The table holds the only reference:
        tables are immutable, so the copy cannot go stale, and it is
        freed when the table is dropped (every relational op returns a
        NEW table, without it) or when a later placement needs its room
        (:class:`_ResidentSet`)."""
        slot = self.resident(key)
        value = slot.find()
        if value is None:
            value = slot.keep(place(slot.make_room))
        return value

    def resident(self, key: tuple) -> "ResidentSlot":
        """The kept placement of this table under ``key``, to find, make
        room for and keep in steps of the caller's own (a linear fit
        keeps its placement only once its last round has landed)."""
        return ResidentSlot(self, key)

    def host_kept(self, key: tuple, make):
        """What ``make()`` read off this table's host columns, kept WITH
        the table under ``key`` and found again by every later call: the
        host's side of :meth:`device_resident` (a label column's
        ``models._data.LabelFacts``: a view of the column and a few
        scalars). Tables are immutable, so it cannot go stale. It holds
        no device bytes: it is no entry of :class:`_ResidentSet`, is
        never let go for room, and goes when the table does."""
        if key not in self._host_kept:
            self._host_kept[key] = make()
        return self._host_kept[key]

    # -- relational ops ----------------------------------------------------
    # Zero-copy on device-backed columns: buffers are rebound, never fetched.
    def select(self, *names: str) -> "Table":
        return Table({n: self._raw_column(n) for n in names})

    def with_column(self, name: str, values: Any) -> "Table":
        cols = dict(self._columns)
        if isinstance(values, (np.ndarray,) + _ARRAY_COLUMNS) or _is_device_backed(values):
            cols[name] = values
        else:
            cols[name] = _to_array(values)
        return Table(cols)

    def drop(self, *names: str) -> "Table":
        cols = {n: c for n, c in self._columns.items() if n not in names}
        return Table(cols)

    def rename(self, mapping: Mapping[str, str]) -> "Table":
        return Table({mapping.get(n, n): c for n, c in self._columns.items()})

    # Row-indexed ops operate on the host representation.
    def take(self, indices: np.ndarray) -> "Table":
        return Table({n: self._host_rows(n)[indices] for n in self._columns})

    def slice(self, start: int, stop: int) -> "Table":
        return Table({n: self._host_rows(n)[start:stop] for n in self._columns})

    def concat(self, other: "Table") -> "Table":
        if set(self.column_names) != set(other.column_names):
            raise ValueError("concat requires identical column sets")

        def join(n):
            a, b = self._raw_column(n), other._raw_column(n)
            if isinstance(a, _ARRAY_COLUMNS) and type(a) is type(b):
                return a.concat(b)
            return np.concatenate([self.column(n), other.column(n)])

        return Table({n: join(n) for n in self.column_names})

    # -- iteration ---------------------------------------------------------
    def batches(self, batch_size: int, drop_remainder: bool = False) -> Iterator["Table"]:
        """Yield consecutive row slices of at most ``batch_size`` rows."""
        n = self._num_rows
        stop = (n // batch_size) * batch_size if drop_remainder else n
        for start in range(0, stop, batch_size):
            yield self.slice(start, min(start + batch_size, n))

    def to_rows(self) -> List[Dict[str, Any]]:
        return [
            {n: self.column(n)[i] for n in self._columns} for i in range(self._num_rows)
        ]

    def __repr__(self) -> str:  # pragma: no cover
        cols = ", ".join(
            f"{n}:{c.dtype}{list(c.shape[1:])}{'@device' if _is_device_backed(c) else ''}"
            for n, c in self._columns.items()
        )
        return f"Table[{self._num_rows} rows; {cols}]"


def _to_array(values: Any) -> np.ndarray:
    """Convert a python sequence to a numpy column, keeping ragged data as object."""
    try:
        arr = np.asarray(values)
        if arr.dtype == object and arr.ndim == 0:
            arr = np.asarray([values])
    except ValueError:
        arr = np.empty(len(values), dtype=object)
        for i, v in enumerate(values):
            arr[i] = v
        return arr
    if arr.dtype == object:
        # Ragged rows (e.g. variable-length lists / sparse vectors).
        out = np.empty(len(values), dtype=object)
        for i, v in enumerate(values):
            out[i] = v
        return out
    return arr
