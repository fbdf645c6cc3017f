"""Device mesh and sharding helpers — the parallelism substrate.

The reference's parallelism is Flink operator parallelism: P subtasks over
partitioned streams, wired by Netty shuffles (SURVEY.md §2.5). Here the
substrate is a named ``jax.sharding.Mesh``: data parallelism is a sharded
leading batch axis, model replication is a replicated sharding, and every
cross-device exchange is an XLA collective over ICI inserted by the compiler
or written explicitly in ``flinkml_tpu.parallel.collectives``.

The default mesh is 1-D over all local devices with axis ``"data"``; multi-
axis meshes (e.g. ``{"data": 4, "model": 2}``) are supported so model/expert
sharding can be layered on without changing this substrate.
"""

from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flinkml_tpu.utils.metrics import metrics
from flinkml_tpu.utils.profiling import named_program, span

#: :meth:`DeviceMesh.stage_rows` stages a table through host buffers of
#: this many bytes a column (all shards' rows of one round together; the
#: widest column's rows set a round's length). Read on
#: a v5e's host (PERF.md §5, PR 25): 64 and 128 MiB place a 4.64 GB
#: table equally fast; at 32 MiB the per-round dispatch shows, from 256
#: MiB the buffers' first-touch page faults do (≈ 0.9 s a GiB, every
#: fit). Every transfer is far below the runtime's ≈ 4 GiB pre-mapped
#: limit, above which it runs ten times slower.
_STAGE_BYTES = 64 << 20
#: Sets of staging buffers in rotation: one gathered into while the other
#: is on its way to the device. Reusing a set waits for the write that
#: read it, which in a pipelined fit queues behind the chunk of steps
#: dispatched before it (≈ 24 ms of steps a round of 156-byte rows). A
#: third set shortened that wait (0.24 → 0.21 s a fit) and bought
#: nothing: the device loop sets the pace of the pipelined phase either
#: way (fits of 1.463 s with two, 1.460 with three; PERF.md §6, PR 33).
_STAGE_BUFFERS = 2
#: Threads one round's gather is split over (``ndarray.take`` releases
#: the interpreter lock). One thread gathers 492-byte rows at 4.5 GB/s
#: and sets the pace; eight reach the transfer's own 8-9 GB/s.
_GATHER_THREADS = 8


def gather_pool() -> ThreadPoolExecutor:
    """A pool of as many threads as a staging round's gather is split
    over, for a fit's other chunked passes over its host columns."""
    return ThreadPoolExecutor(_GATHER_THREADS)


class DeviceMesh:
    """A named device mesh plus sharding conveniences.

    Replaces (SURVEY.md §2.5): Flink operator parallelism (data axis),
    ``.broadcast()`` partitioners + per-TM ``BroadcastContext`` (replicated
    sharding), and co-location constraints (meaningless in SPMD — every
    device runs the same program).
    """

    DATA_AXIS = "data"
    #: Model/optimizer state sharding axis (FSDP/ZeRO-3) and tensor-
    #: parallel axis — the named axes the ``flinkml_tpu.sharding``
    #: plans key their ``PartitionSpec``s to.
    FSDP_AXIS = "fsdp"
    TP_AXIS = "tp"

    def __init__(
        self,
        axis_shapes: Optional[Dict[str, int]] = None,
        devices: Optional[Sequence[jax.Device]] = None,
    ):
        if devices is None:
            devices = jax.devices()
        if axis_shapes is None:
            axis_shapes = {self.DATA_AXIS: len(devices)}
        names = tuple(axis_shapes.keys())
        shape = tuple(axis_shapes.values())
        n = int(np.prod(shape))
        if n > len(devices):
            raise ValueError(
                f"mesh shape {dict(axis_shapes)} needs {n} devices, "
                f"only {len(devices)} available"
            )
        device_array = np.asarray(devices[:n]).reshape(shape)
        self.mesh = Mesh(device_array, names)

    # -- basic properties --------------------------------------------------
    @property
    def axis_names(self) -> Tuple[str, ...]:
        return self.mesh.axis_names

    @property
    def num_devices(self) -> int:
        return self.mesh.devices.size

    def axis_size(self, name: str = DATA_AXIS) -> int:
        return self.mesh.shape[name]

    # -- plan-shaped construction ------------------------------------------
    @classmethod
    def for_plan(cls, plan, devices: Optional[Sequence[jax.Device]] = None,
                 tp_size: Optional[int] = None) -> "DeviceMesh":
        """A mesh shaped for a :class:`~flinkml_tpu.sharding.plan.
        ShardingPlan`'s required axes over the given devices (all local
        devices by default).

        - only ``data`` (or no axes at all): 1-D ``{"data": n}`` — the
          classic substrate, unchanged;
        - ``fsdp`` without ``tp``: ``{"data": 1, "fsdp": n}`` — every
          device serves both batch and state sharding (the plans' batch
          axes are ``("data", "fsdp")``, so batches still split n ways);
        - ``fsdp`` + ``tp``: ``{"data": 1, "fsdp": n // tp, "tp": tp}``
          with ``tp_size`` defaulting to 2 (must divide the device
          count).
        """
        if devices is None:
            devices = jax.devices()
        n = len(devices)
        axes = set(plan.required_axes())
        if cls.TP_AXIS in axes and cls.FSDP_AXIS in axes:
            tp = int(tp_size) if tp_size is not None else min(2, n)
            if n % tp != 0:
                raise ValueError(
                    f"tp_size {tp} does not divide {n} devices"
                )
            return cls({cls.DATA_AXIS: 1, cls.FSDP_AXIS: n // tp,
                        cls.TP_AXIS: tp}, devices=devices)
        if cls.FSDP_AXIS in axes:
            return cls({cls.DATA_AXIS: 1, cls.FSDP_AXIS: n},
                       devices=devices)
        return cls({cls.DATA_AXIS: n}, devices=devices)

    # -- elastic re-shaping ------------------------------------------------
    def shrink(self, new_size: int, axis: str = DATA_AXIS) -> "DeviceMesh":
        """A new mesh over a SUBSET of this mesh's devices: ``axis``
        reduced to ``new_size`` (the leading ``new_size`` slots in mesh
        order — survivors keep their relative order, matching
        :func:`~flinkml_tpu.parallel.distributed.compact_rank`'s dense
        renumbering). The elastic shrink's device-plane half: after the
        survivors re-rendezvous at world M, the training mesh is
        ``old_mesh.shrink(M * local_devices)`` — or simply a fresh
        ``DeviceMesh()`` of the new world's devices."""
        new_size = int(new_size)
        old = self.axis_size(axis)
        if not (1 <= new_size <= old):
            raise ValueError(
                f"cannot shrink axis {axis!r} from {old} to {new_size}"
            )
        shapes = {name: self.mesh.shape[name] for name in self.axis_names}
        shapes[axis] = new_size
        # Move the shrinking axis's index innermost-last so "the leading
        # new_size slots along `axis`" selects device rows in mesh order.
        idx = tuple(
            slice(0, new_size) if name == axis else slice(None)
            for name in self.axis_names
        )
        devices = self.mesh.devices[idx].reshape(-1)
        return DeviceMesh(shapes, devices=list(devices))

    # -- shardings ---------------------------------------------------------
    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    def data_sharding(self) -> NamedSharding:
        """Leading axis split across the data axis; trailing axes replicated."""
        return self.sharding(self.DATA_AXIS)

    def replicated_sharding(self) -> NamedSharding:
        return self.sharding()

    # -- placement ---------------------------------------------------------
    def shard_batch(self, array) -> jax.Array:
        """Place a host batch onto the mesh, split along the leading axis.

        The batch's leading dimension must be divisible by the data-axis size
        (use :func:`pad_to_multiple` first when it is not) — mirroring the
        reference's ``globalBatchSize / parallelism`` contract
        (``LogisticRegression.java:334-342``).
        """
        n = self.axis_size(self.DATA_AXIS)
        if array.shape[0] % n != 0:
            raise ValueError(
                f"batch dimension {array.shape[0]} not divisible by data-axis "
                f"size {n}; pad with pad_to_multiple first"
            )
        with span("mesh.shard_batch") as phase:
            placed = jax.device_put(array, self.data_sharding())
            # The placed array's bytes, not the host array's: device_put
            # narrows float64 to float32 on the host where x64 is off,
            # and returns before the bytes have landed on the device.
            phase.add(bytes=placed.nbytes)
        return placed

    def shard_rows(self, x: np.ndarray, order: np.ndarray, dtype=None) -> jax.Array:
        """``shard_batch(pad_to_multiple(x.astype(dtype)[order], p)[0])``,
        bit for bit, without its three full-size host arrays: shard ``s``
        holds positions ``[s * n_local, (s + 1) * n_local)`` of the
        reordered table, zero rows past its end. ``x`` is a table of rows
        of any shape: a 1-D column (labels, weights) is one of rows of
        width ``()``. ``dtype`` None is ``x``'s own.

        One column with nothing consuming it as it lands (a model's rows,
        a table kept on the chip): :meth:`stage_rows` with every row in
        reach, run to its last round."""
        *_, ((placed,), _) = self.stage_rows([(x, order, dtype)])
        return placed

    def stage_rows(self, columns, reach_rows: Optional[int] = None):
        """Several columns of one table placed in lockstep, round by
        round, for a consumer that reads them as they land. ``columns``
        is a sequence of ``(x, order, dtype)`` as :meth:`shard_rows`
        takes them (orders of one length); a generator of ``(placed,
        complete)``: after each round the columns' device arrays, and how
        many leading local rows of EVERY shard of every column hold their
        final values. The last item's arrays are, column for column, what
        :meth:`shard_rows` documents, on the local rows below
        ``reach_rows`` (None: all of them), and zero above: rows the
        consumer says no step of its can read are not gathered and not
        sent. The arrays keep their full shapes whatever the reach.

        One pass: each round gathers local rows ``[offset, offset +
        chunk)`` of every shard of every column (cast, where a column is
        not a ``dtype`` array already) into reused staging buffers, one
        ``device_put`` sends them on their way while the next round is
        gathered into the other set, and ONE donated in-place write puts
        them at their offset in the device arrays. The arrays handed out
        after a round are donated to the next round's write: a consumer
        dispatches what reads them before it asks for the next item, and
        the runtime orders the write behind that program on the device,
        so the host waits for neither. ``chunk`` is what fits
        ``_STAGE_BYTES`` of the widest column's rows. Multi-process,
        every process holds all of every column and places its
        addressable shards, as :meth:`shard_batch` does.
        """
        p = self.axis_size(self.DATA_AXIS)
        # ndarray.take copies a strided source whole, every call.
        xs = [np.ascontiguousarray(x) for x, _, _ in columns]
        orders = [order for _, order, _ in columns]
        # The width device_put would have narrowed to where x64 is off.
        dts = [np.dtype(jax.dtypes.canonicalize_dtype(
            dtype if dtype is not None else x.dtype))
            for x, (_, _, dtype) in zip(xs, columns)]
        n = orders[0].shape[0]
        n_local = -(-n // p)
        reach = n_local if reach_rows is None else max(0, min(n_local, reach_rows))
        row_bytes = max(max(1, int(np.prod(x.shape[1:])) * dt.itemsize)
                        for x, dt in zip(xs, dts))
        chunk = max(1, min(reach, _STAGE_BYTES // (p * row_bytes)))
        rounds = -(-reach // chunk)
        stages = [[np.empty((p * chunk,) + x.shape[1:], dt)
                   for x, dt in zip(xs, dts)]
                  for _ in range(min(_STAGE_BUFFERS, rounds))]
        scratch = [None if x.dtype == dt
                   else np.empty((p * chunk,) + x.shape[1:], x.dtype)
                   for x, dt in zip(xs, dts)]
        consumed = [None] * len(stages)
        sharding = self.data_sharding()
        zeros = _zero_rows(self.mesh, self.DATA_AXIS)
        placed = tuple(zeros((p * n_local,) + x.shape[1:], dt)
                       for x, dt in zip(xs, dts))
        counts = metrics.group("hostdata.stage")
        counts.counter("rows", float(p * n_local))
        if not rounds:
            yield placed, 0
            return
        write = _row_writer(self.mesh, self.DATA_AXIS)
        with ThreadPoolExecutor(_GATHER_THREADS) as pool:
            for r in range(rounds):
                # The last round steps back to end at the reach, so
                # every round has one shape (one program): it re-sends
                # rows the round before already placed.
                offset = min(r * chunk, reach - chunk)
                slot = r % len(stages)
                with span("hostdata.stage_wait"):
                    # device_put neither snapshots the host buffers nor
                    # (on CPU) need copy them at all: they are free
                    # only once the write that read them has run.
                    if consumed[slot] is not None:
                        consumed[slot].block_until_ready()
                with span("hostdata.shuffle"):
                    for x, order, stage, spare in zip(
                            xs, orders, stages[slot], scratch):
                        # Shard s takes positions [s * n_local + offset,
                        # + chunk) of the order: a slice of it. Positions
                        # rise with the staging row, so a shard cut
                        # short by the table's end is the last with any
                        # row, and the rows past the end are the
                        # buffer's tail.
                        parts = [order[min(lo, n):min(lo + chunk, n)] for lo in
                                 range(offset, offset + p * n_local, n_local)]
                        index = parts[0] if p == 1 else np.concatenate(parts)
                        valid = index.shape[0]
                        _gather_rows(pool, x, index, stage[:valid], spare)
                        stage[valid:] = 0
                with span("mesh.shard_batch") as phase:
                    sent = jax.device_put(stages[slot], sharding)
                    placed, consumed[slot] = write(
                        placed, tuple(sent), np.int32(offset))
                    phase.add(bytes=sum(s.nbytes for s in sent))
                counts.counter("rows_sent", float(p * min(chunk, reach - r * chunk)))
                yield placed, offset + chunk

    def shard_ones(self, n: int, dtype, total: Optional[int] = None) -> jax.Array:
        """``shard_batch(pad_to_multiple(np.ones(n, dtype), p)[0])``, made
        on the device: 1 at the positions below ``n``, 0 at the padding.
        The unit weights of a table with no weight column, which on the
        host were a vector built, permuted into itself and uploaded.
        ``total`` (a multiple of ``p``) is the length where the caller
        pads further than to ``p`` (a tree fit's whole tiles a device).
        Multi-process it is one SPMD program every process calls."""
        p = self.axis_size(self.DATA_AXIS)
        dt = np.dtype(jax.dtypes.canonicalize_dtype(dtype))
        ones = _ones_below(self.mesh, self.DATA_AXIS)
        return ones(np.int32(n), p * -(-n // p) if total is None else total, dt)

    def replicate(self, tree):
        """Replicate a pytree of arrays onto every device (broadcast-model)."""
        sharding = self.replicated_sharding()
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(a, sharding), tree
        )

    def to_host(self, arr) -> np.ndarray:
        """Fetch a device array to host, multi-process-safe.

        Fully-addressable arrays (single-process, or replicated outputs)
        fetch directly. A data-sharded array on a multi-process mesh
        spans non-addressable devices, so it is all-gathered across
        processes first — in that case this is a COLLECTIVE: every
        process must call it, in the same order (the SPMD transform
        convention: all ranks run the same inference over the same
        global table and all receive the full result).
        """
        if getattr(arr, "is_fully_addressable", True):
            return np.asarray(arr)
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(arr, tiled=True))

    def local_rows(self, arr) -> np.ndarray:
        """Fetch THIS PROCESS's contiguous row block of a data-sharded
        output — the inverse of :meth:`global_batch`.

        For per-row state that lives on the rank owning the rows (GBT's
        node assignments), a full :meth:`to_host` gather would move every
        other rank's rows across DCN just to throw them away; the local
        addressable shards ARE this process's block, in row order.
        Single-process (fully addressable): the whole array.
        """
        if getattr(arr, "is_fully_addressable", True):
            return np.asarray(arr)
        shards = sorted(
            arr.addressable_shards, key=lambda s: s.index[0].start or 0
        )
        return np.concatenate([np.asarray(s.data) for s in shards])

    def global_batch(self, local_rows) -> jax.Array:
        """Assemble a globally-sharded batch from THIS PROCESS's rows.

        The multi-host ingest primitive (the reference's per-subtask
        stream partitions): each host passes only its
        :func:`~flinkml_tpu.parallel.process_slice` of the dataset; the
        returned array is the concatenation of every host's rows, sharded
        over the data axis, without any host materializing the whole
        dataset. Single-process this is exactly :meth:`shard_batch`.

        ``local_rows`` must be divisible by the local device count (every
        process contributes equally per device — pad the *global* dataset
        so every host slice divides evenly).
        """
        local_rows = np.asarray(local_rows)
        if jax.process_count() == 1:
            return self.shard_batch(local_rows)
        return jax.make_array_from_process_local_data(
            self.data_sharding(), local_rows
        )


@functools.lru_cache(maxsize=128)
def _row_writer(mesh: Mesh, axis: str):
    """The in-place write of :meth:`DeviceMesh.stage_rows`: every shard
    of each of ``tables`` takes its shard of the same-numbered of
    ``rows`` at local row ``offset``. ``tables`` are donated. The second
    result is ready when the write has run, i.e. when ``rows`` (and the
    host buffers under them) have been read; the tables themselves are
    donated to the next write and cannot be waited on."""

    def write(tables, rows, offset):
        written = tuple(
            jax.lax.dynamic_update_slice_in_dim(table, block, offset, 0)
            for table, block in zip(tables, rows))
        return written, rows[0][:1].reshape(-1)[:1]

    # Stated, not inferred: on a one-device mesh the inferred sharding of
    # a 1-D table among several is P(), another key for the trainer.
    return jax.jit(
        jax.shard_map(named_program("stage_write", write), mesh=mesh,
                      in_specs=(P(axis), P(axis), P()),
                      out_specs=(P(axis), P(axis))),
        donate_argnums=0, out_shardings=NamedSharding(mesh, P(axis)),
    )


@functools.lru_cache(maxsize=128)
def _zero_rows(mesh: Mesh, axis: str):
    """The zero fill of the arrays :meth:`DeviceMesh.stage_rows` writes
    its rounds into, built as :func:`_row_writer` is, so that a profile
    names it (``jnp.zeros`` runs as a ``broadcast_in_dim`` like any
    other)."""

    def zeros(shape, dtype):
        return jnp.zeros(shape, dtype)

    return jax.jit(named_program("stage_zeros", zeros), static_argnums=(0, 1),
                   out_shardings=NamedSharding(mesh, P(axis)))


@functools.lru_cache(maxsize=128)
def _ones_below(mesh: Mesh, axis: str):
    """The program of :meth:`DeviceMesh.shard_ones`, built as
    :func:`_row_writer` is: one jitted function a mesh, so the compile
    cache keeps it and a fit's first call compiles it."""

    def ones(n, rows, dtype):
        return (jnp.arange(rows) < n).astype(dtype)

    return jax.jit(named_program("stage_ones", ones), static_argnums=(1, 2),
                   out_shardings=NamedSharding(mesh, P(axis)))


def _gather_rows(pool, x, index, out, scratch) -> None:
    """``out[:] = x[index]`` cast to ``out``'s dtype, split over the
    pool's threads. ``scratch`` (``x``'s dtype, at least ``out``'s rows)
    takes the rows first where a cast is needed, else is None."""
    bounds = np.linspace(0, index.shape[0], _GATHER_THREADS + 1).astype(np.intp)

    def part(lo, hi):
        if scratch is None:
            # mode="clip": the default buffers `out` whole to be able to
            # raise; `index` is a slice of a permutation.
            x.take(index[lo:hi], axis=0, out=out[lo:hi], mode="clip")
        else:
            x.take(index[lo:hi], axis=0, out=scratch[lo:hi], mode="clip")
            np.copyto(out[lo:hi], scratch[lo:hi], casting="unsafe")

    # list(): an executor keeps a task's exception until its result is read.
    list(pool.map(part, bounds[:-1], bounds[1:]))


def pad_to_multiple(array: np.ndarray, multiple: int, axis: int = 0):
    """Zero-pad ``array`` along ``axis`` to a multiple; returns (padded, n_valid).

    Algorithms carry ``n_valid`` (or a weight column) so padded rows never
    contribute to sums — the TPU version of the reference's exact per-task
    record counts.
    """
    n = array.shape[axis]
    target = ((n + multiple - 1) // multiple) * multiple
    if target == n:
        return array, n
    pad_width = [(0, 0)] * array.ndim
    pad_width[axis] = (0, target - n)
    return np.pad(array, pad_width), n
