"""Async host→device prefetch into the fused executor's row buckets.

The tail of a :class:`~flinkml_tpu.data.Dataset` chain: a worker thread
pulls host Tables, zero-pads every dense column to the fused compile
cache's power-of-two row bucket (:func:`flinkml_tpu.pipeline_fusion
.row_bucket`), uploads the padded buffers (``jax.device_put``, or a
mesh-sharded ``place``), and parks up to ``depth`` device-resident
Tables in a bounded queue. With ``depth >= 2`` the next batch's
PCIe/DMA copy runs under the current step's compute — double buffering,
the whole point of the subsystem.

The emitted Tables carry :class:`~flinkml_tpu.table.PaddedDeviceColumn`
columns whose buffers are EXACTLY bucket-height, so the downstream
fused executor (``Table.device_column_padded``) hands them straight
into its cached programs: varying batch sizes within a bucket cause
zero host work, zero re-pads, and **zero retraces** — the validity
handling is the executor's traced ``n_valid`` row count, which the
padded column's logical ``rows`` supplies. Collectives see only
bucket-shaped arrays, so SPMD steps never diverge on a ragged tail
batch.

The queue/worker/lifecycle machinery — timed put that re-checks the
stop event, parked-exception propagation with the producer's original
traceback, idempotent ``close()``, context-manager semantics, and the
no-back-reference worker + GC finalizer that keeps an ABANDONED
consumer from leaking the thread — is inherited from
:class:`~flinkml_tpu.iteration.datacache.PrefetchingDeviceFeed` (one
definition of those concurrency invariants, not two); this class adds
the bucket padding, the ``data.prefetch`` fault seam, and metrics.

Metrics (``utils.metrics.default_registry()``, group
``data.prefetch``): ``queue_depth`` / ``stall_fraction`` /
``rows_per_sec`` gauges plus batch/row counters. Fault seam
``data.prefetch`` (:mod:`flinkml_tpu.faults`) fires in the worker
before each placement.
"""

from __future__ import annotations

import time
from typing import Any, Iterable, Optional

import numpy as np

from flinkml_tpu.iteration.datacache import PrefetchingDeviceFeed
from flinkml_tpu.table import PaddedDeviceColumn, Table


def pad_place_table(table: Table, place=None) -> Table:
    """Pad ``table``'s dense columns to their power-of-two row bucket
    and upload: each becomes a bucket-height
    :class:`~flinkml_tpu.table.PaddedDeviceColumn` with the logical row
    count intact (dtype preserved exactly — the fused executor's
    bit-parity contract). Object columns whose rows are all
    ``SparseVector`` become bucket-height
    :class:`~flinkml_tpu.table.SortedSparseColumn`\\ s — the padded-ELL
    CSR layout plus pack-time global sort tables, built HERE on the
    worker thread (the sort overlaps compute; downstream scatters run
    ``indices_are_sorted=True`` with no runtime sort). Other object
    (ragged) columns have no device representation and stay
    host-resident."""
    import jax

    from flinkml_tpu.linalg import SparseVector
    from flinkml_tpu.pipeline_fusion import row_bucket

    if place is None:
        place = jax.device_put
    n = table.num_rows
    bucket = row_bucket(n)
    cols = {}
    with jax.enable_x64(True):
        for name in table.column_names:
            arr = table.column(name)
            if arr.dtype == object:
                if n and all(isinstance(v, SparseVector) for v in arr):
                    from flinkml_tpu.ops.sparse import (
                        pack_sorted_sparse_column,
                    )

                    cols[name] = pack_sorted_sparse_column(
                        arr, bucket=bucket, place=place
                    )
                else:
                    cols[name] = arr
                continue
            pad = bucket - n
            if pad:
                arr = np.concatenate(
                    [arr, np.zeros((pad,) + arr.shape[1:], arr.dtype)]
                )
            cols[name] = PaddedDeviceColumn(place(arr), n)
    return Table(cols)


class DevicePrefetcher(PrefetchingDeviceFeed):
    """Double-buffered bounded-queue async host→device feed over a
    batch iterator, bucket-padding Tables for the fused executor (see
    module docstring). Iterate it; ``close()`` (or the ``with`` block,
    or GC of an abandoned handle) stops the worker."""

    def __init__(self, batches: Iterable[Any], depth: int = 2, place=None,
                 metrics_group: str = "data.prefetch"):
        from flinkml_tpu.utils.metrics import default_registry

        group = (
            default_registry().group(metrics_group) if metrics_group else None
        )
        self._group = group
        self._stalled_s = 0.0
        self._consume_t0: Optional[float] = None
        self._rows_out = 0.0
        reads = [0]

        def pad_and_place(batch):
            # Runs on the worker thread (the inherited _feed_worker
            # applies `place` per batch): fault seam, bucket pad +
            # upload, producer-side counters.
            import flinkml_tpu.faults as faults

            reads[0] += 1
            if faults.ACTIVE is not None:  # scripted-failure seam
                faults.fire("data.prefetch", read=reads[0])
            if isinstance(batch, Table):
                placed = pad_place_table(batch, place)
                if group is not None:
                    group.counter("batches_prefetched")
                    group.counter("rows_prefetched", float(batch.num_rows))
                return placed
            import jax

            if group is not None:
                group.counter("batches_prefetched")
            return (place or jax.device_put)(batch)

        super().__init__(batches, place=pad_and_place, depth=depth,
                         thread_name="data-prefetch")

    def __next__(self):
        t0 = time.perf_counter()
        if self._consume_t0 is None:
            self._consume_t0 = t0
        try:
            item = super().__next__()
        finally:
            now = time.perf_counter()
            self._stalled_s += now - t0
            if self._group is not None:
                self._group.gauge("queue_depth", self._q.qsize())
                elapsed = now - self._consume_t0
                if elapsed > 0:
                    self._group.gauge(
                        "stall_fraction", self._stalled_s / elapsed
                    )
        if self._group is not None and isinstance(item, Table):
            self._rows_out += item.num_rows
            elapsed = time.perf_counter() - self._consume_t0
            if elapsed > 0:
                self._group.gauge("rows_per_sec", self._rows_out / elapsed)
        return item

    @property
    def stall_fraction(self) -> float:
        """Fraction of consumer wall-clock spent blocked on the queue —
        the headline 'is the producer keeping up' number."""
        if self._consume_t0 is None:
            return 0.0
        elapsed = time.perf_counter() - self._consume_t0
        return self._stalled_s / elapsed if elapsed > 0 else 0.0
