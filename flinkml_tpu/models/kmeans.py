"""KMeans — Lloyd's algorithm with random init.

Capability parity with ``flink-ml-lib/.../clustering/kmeans/KMeans.java:79-335``
(+ ``KMeansModel.java``, ``KMeansModelData.java``), rebuilt TPU-first:

  - ``selectRandomCentroids`` (mapPartition + shuffle at parallelism 1,
    ``KMeans.java:314-335``) → seeded host choice of k distinct rows.
  - The per-epoch machinery — broadcast centroids into a 2-input
    ``SelectNearestCentroidOperator`` caching points in ListState
    (``:239-312``), per-round keyed reduce (``CountAppender``/
    ``CentroidAccumulator``/``CentroidAverager`` + ``EndOfStreamWindows``,
    ``:174-235``) — becomes one fused XLA program: pairwise-distance argmin
    on the MXU, per-cluster sums via a one-hot matmul (k is small; a matmul
    beats scatter on TPU), ``psum`` across the data axis, centroid update —
    the whole Lloyd loop in a single ``lax.while_loop`` on device.
  - Termination: ``TerminateOnMaxIter`` (``:150-151``); the reference has no
    tol-based stop for KMeans.
  - Empty clusters keep their previous centroid (the reference's keyed
    reduce simply never emits for an empty cluster, leaving it unchanged).
  - The reference's points stay cached across rounds (ListState); here
    the in-RAM fit's rows stay on the mesh across FITS: the features
    column is placed as the table holds it (a float32 column is not
    widened, padded or copied on the host) through
    :meth:`DeviceMesh.shard_rows`, and kept with the ``Table``
    (:func:`_rows_on_mesh`), so Lloyd restarted from another seed on the
    same table uploads its ``[k, d]`` start and nothing else.
  - Both products of a round (the distances' and the per-cluster sums')
    run at a stated precision, float32 accuracy
    (:data:`PRODUCT_PRECISION`): a TPU's default, one bfloat16 pass,
    assigns thousands of a large table's rows to another centroid than
    the reference's float64 does.
"""

from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from flinkml_tpu.api import Estimator, Model
from flinkml_tpu.models._streaming import StreamingEstimatorMixin
from flinkml_tpu.common_params import (
    HasDistanceMeasure,
    HasFeaturesCol,
    HasK,
    HasMaxIter,
    HasPredictionCol,
    HasSeed,
)
from flinkml_tpu.models._data import features_matrix
from flinkml_tpu.params import IntParam, ParamValidators, StringParam
from flinkml_tpu.ops import blas
from flinkml_tpu.ops.distance import DistanceMeasure
from flinkml_tpu.parallel import DeviceMesh, pad_to_multiple
from flinkml_tpu.table import Table
from flinkml_tpu.utils.metrics import metrics
from flinkml_tpu.utils.profiling import named_program, phase, span

#: The precision of a round's two products, a static argument of both
#: trainers: float32 accuracy, the distance expansion's own
#: (``ops.blas``). A builder's control on the chip passes ``DEFAULT``
#: (one bfloat16 pass) to show that the benchmark's check tells the two
#: apart.
PRODUCT_PRECISION = blas.DISTANCE_PRECISION
#: A round's phases (``profiling.phase``): the distances with their
#: argmin; the per-cluster sums with the update. The ``psum`` is in none.
PHASES = ("kmeans.assign", "kmeans.sums")


class _KMeansParams(
    HasDistanceMeasure, HasFeaturesCol, HasPredictionCol, HasK, HasMaxIter, HasSeed
):
    """Reference: KMeansParams. KMeans redefines ``k`` (clusters, default 2,
    > 1 — ``KMeansModelParams`` declares gt(1)) over HasK's
    nearest-neighbors variant.

    ``initMode`` is an addition over the reference (random init only there,
    ``KMeans.java:314-335``): "k-means++" gives sklearn-quality starts.
    """

    K = IntParam(
        "k", "The number of clusters to create.", 2, ParamValidators.gt(1)
    )

    INIT_MODE = StringParam(
        "initMode", "Centroid initialization: random or k-means++.", "random",
        ParamValidators.in_array(["random", "k-means++"]),
    )


class KMeans(StreamingEstimatorMixin, _KMeansParams, Estimator):
    """``fit`` accepts, besides a single in-RAM :class:`Table`:

      - an **iterable of batch Tables** — the out-of-core path: epoch 0
        caches the stream (spilling to ``cache_dir`` beyond
        ``cache_memory_budget_bytes``) while reservoir-sampling init
        centroids; each Lloyd iteration then replays the cache through a
        prefetching device feed, accumulating per-cluster sums/counts
        batch-by-batch with bounded HBM residency (reference:
        ``ReplayOperator.java:62-250`` + the point-caching
        ``SelectNearestCentroidOperator``, ``KMeans.java:239-312``);
      - a sealed :class:`~flinkml_tpu.iteration.datacache.DataCache`
        whose batches carry this estimator's features column.
    """


    def fit(self, *inputs) -> "KMeansModel":
        (table,) = inputs
        k = self.get(_KMeansParams.K)
        measure = self.get(_KMeansParams.DISTANCE_MEASURE)
        if measure != "euclidean":
            raise ValueError(
                "KMeans currently supports the euclidean distance measure "
                f"(parity with the reference), got {measure!r}"
            )
        if isinstance(table, Table):
            self._reject_in_ram_checkpointing(
                "the in-RAM fit runs as one whole-loop device program"
            )
            with span("fit"):
                centroids = self._fit_table(table, k)
        else:
            centroids = self._fit_stream(table, k)
        model = KMeansModel()
        model.copy_params_from(self)
        model.set_model_data(Table({"centroids": centroids[None, :, :]}))
        return model

    def _fit_table(self, table: Table, k: int) -> np.ndarray:
        """The in-RAM fit: the features column as the table holds it
        (float32 stays float32 and computes in float32 whatever
        ``jax_enable_x64`` says; nothing of the table's size is copied
        on the host), on the mesh once a table (:func:`_rows_on_mesh`),
        the start centroids read from the host column, the whole loop
        one program."""
        features_col = self.get(_KMeansParams.FEATURES_COL)
        x = features_matrix(table, features_col, dtype=None)
        if x.shape[0] < k:
            raise ValueError(f"k={k} exceeds number of points {x.shape[0]}")
        mesh = self.mesh or DeviceMesh()
        placed = _rows_on_mesh(table, features_col, x, mesh)
        with span("kmeans.init"):
            start = _start_centroids(
                x, k, self.get_seed(), self.get(_KMeansParams.INIT_MODE))
        return _lloyd(placed, start, mesh, k,
                      self.get(_KMeansParams.MAX_ITER))

    def _fit_stream(self, source, k: int) -> np.ndarray:
        from flinkml_tpu.iteration.datacache import DataCache

        features_col = self.get(_KMeansParams.FEATURES_COL)
        if isinstance(source, DataCache):
            batches = source
        else:
            def batches_gen():
                for t in source:
                    yield {
                        "x": features_matrix(t, features_col)
                        .astype(np.float32)
                    }
            batches = batches_gen()
        return train_kmeans_stream(
            batches,
            k=k,
            mesh=self.mesh or DeviceMesh(),
            max_iter=self.get(_KMeansParams.MAX_ITER),
            seed=self.get_seed(),
            init_mode=self.get(_KMeansParams.INIT_MODE),
            cache_dir=self.cache_dir,
            memory_budget_bytes=self.cache_memory_budget_bytes,
            column=(
                features_col if isinstance(source, DataCache) else "x"
            ),
            **self._checkpoint_kwargs(),
        )


class KMeansModel(_KMeansParams, Model):
    """Nearest-centroid prediction (broadcast-model pattern,
    ``KMeansModel.java``)."""

    def __init__(self):
        super().__init__()
        self._centroids: Optional[np.ndarray] = None

    def set_model_data(self, *inputs: Table) -> "KMeansModel":
        (table,) = inputs
        c = np.asarray(table.column("centroids"), dtype=np.float64)
        self._centroids = c.reshape(c.shape[-2], c.shape[-1])
        return self

    def get_model_data(self) -> List[Table]:
        self._require_model()
        return [Table({"centroids": self._centroids[None, :, :]})]

    @property
    def centroids(self) -> np.ndarray:
        self._require_model()
        return self._centroids

    def _require_model(self) -> None:
        if self._centroids is None:
            raise ValueError("Model data is not set; call set_model_data or fit first")

    def transform(self, *inputs: Table) -> Tuple[Table, ...]:
        (table,) = inputs
        self._require_model()
        x = features_matrix(table, self.get(_KMeansParams.FEATURES_COL))
        measure = DistanceMeasure.get_instance(
            self.get(_KMeansParams.DISTANCE_MEASURE)
        )
        assign = np.asarray(
            measure.nearest(jnp.asarray(x), jnp.asarray(self._centroids))
        )
        return (
            table.with_column(self.get(_KMeansParams.PREDICTION_COL), assign),
        )

    def transform_kernel(self):
        """Nearest-centroid assignment as a fusable kernel — the same
        ``DistanceMeasure.nearest`` the per-stage path dispatches, with
        the centroids travelling as a traced constant. The per-stage
        path's dtypes follow the ambient x64 flag (``jnp.asarray`` on the
        float64 feature matrix, argmin's canonical index dtype), so the
        kernel captures that flag at build time rather than inheriting
        the fused executor's always-x64 trace context."""
        if self._centroids is None:
            return None
        if self.get(_KMeansParams.DISTANCE_MEASURE) != "euclidean":
            return None
        fcol = self.get(_KMeansParams.FEATURES_COL)
        pcol = self.get(_KMeansParams.PREDICTION_COL)
        import jax

        x64 = bool(jax.config.jax_enable_x64)
        dt = jnp.float64 if x64 else jnp.float32
        idt = jnp.int64 if x64 else jnp.int32

        from flinkml_tpu.api import ColumnKernel

        def fn(cols, consts, valid):
            # Trace-time policy resolution (the fused program cache keys
            # on the active policy). The distance math follows plain
            # dtype propagation from policy.compute — so its reduce
            # accumulates NARROW, and the FML6xx gate refuses this
            # kernel under a policy whose accum is wider than compute
            # (the strict "mixed" preset); "mixed_inference" admits it.
            from flinkml_tpu import pipeline_fusion

            pol = pipeline_fusion.active_policy()
            # Mixed OR quantized policies declare the compute width (the
            # int8 tier's distances run at its f32 compute, not the
            # captured f64).
            kdt = jnp.dtype(pol.compute_dtype) \
                if pol is not None and (pol.mixed or pol.quant) else dt
            x = cols[fcol]
            if x.ndim == 1:
                x = x.reshape(-1, 1)
            x = x.astype(kdt)
            measure = DistanceMeasure.get_instance("euclidean")
            assign = measure.nearest(x, consts["centroids"].astype(kdt))
            return {pcol: assign.astype(idt)}

        return ColumnKernel(
            input_cols=(fcol,), output_cols=(pcol,), fn=fn,
            constants={"centroids": self._centroids},
            fingerprint=("KMeansModel", fcol, pcol, "euclidean", x64),
            # Distance reductions + argmin lower context-sensitively: the
            # input column must be materialized for per-stage bit parity.
            pin_inputs=True,
        )

    def save(self, path: str) -> None:
        self._require_model()
        self._save_with_arrays(path, {"centroids": self._centroids})

    @classmethod
    def load(cls, path: str) -> "KMeansModel":
        model, arrays, _ = cls._load_with_arrays(path)
        model._centroids = arrays["centroids"]
        return model


def _share_sums(xl, sq, wl, centroids, k: int, precision):
    """``(sums [k, d], counts [k])`` of one share of the rows: each row
    (``sq`` its squared norm, ``wl`` 1, or 0 for a padded row, which
    then counts for nothing) goes to its nearest centroid, a tie to the
    lower cluster; both products at ``precision``. One piece of
    mathematics for the whole-loop trainer and the streamed one.

    XLA fuses the argmin into the distances' product and the one-hot
    into the sums': nothing of ``[rows, k]`` reaches HBM, and a round
    reads the rows twice (PERF.md §5, PR 32)."""
    with phase("kmeans.assign"):
        d2 = blas.squared_distances(xl, centroids, precision=precision, xs_sq=sq)
        assign = jnp.argmin(d2, axis=-1)
    with phase("kmeans.sums"):
        # Per-cluster sums via one-hot matmul (k is small; a matmul beats
        # scatter on TPU). The one-hot side is exact in any precision; the
        # rows are not, in one bfloat16 pass.
        onehot = jax.nn.one_hot(assign, k, dtype=xl.dtype) * wl[:, None]
        counts = jnp.sum(onehot, axis=0)
        # The rows are summed as deviations from a pivot near them (the
        # mean of the centroids; XLA fuses the subtraction into the
        # product's operand): a float32 sum of 400,000 pixels loses a part
        # in 5,000 to its own accumulator, and the deviations' partial
        # sums are a few times smaller. Read on a v5e at 2,025,000 x 784:
        # the centroids' gap to float64 Lloyd 1.1e-4 -> 2.1-2.9e-5 for
        # 1.5 % of a round (PERF.md §5, PR 32).
        pivot = jnp.mean(centroids, axis=0)
        sums = jnp.matmul(onehot.T, xl - pivot, precision=precision)
        return sums + counts[:, None] * pivot, counts


def _moved(sums, counts, centroids):
    """A round's centroids: the mean of each cluster's rows; an empty
    cluster keeps its last centroid."""
    safe = jnp.maximum(counts, 1.0)[:, None]
    return jnp.where(counts[:, None] > 0, sums / safe, centroids)


@functools.lru_cache(maxsize=64)
def _kmeans_trainer(mesh, k: int, axis: str, precision=PRODUCT_PRECISION):
    """Whole Lloyd loop as one XLA program, cached per (mesh, k,
    precision): ``(rows, their squared norms, mask, start, max_iter) ->
    centroids``, exactly ``max_iter`` rounds, one ``psum`` of the
    shares' sums and counts a round.

    A hand-fused Pallas Lloyd pass was built, lost to this plain
    lowering and was removed (not re-measured on the current chip), so
    the argmin + one-hot-matmul form of :func:`_share_sums` IS the
    product path."""

    def per_device(xl, sq, wl, init_centroids, max_iter):
        def body(_, centroids):
            sums, counts = _share_sums(xl, sq, wl, centroids, k, precision)
            sums, counts = jax.lax.psum(sums, axis), jax.lax.psum(counts, axis)
            with phase("kmeans.sums"):
                return _moved(sums, counts, centroids)

        return jax.lax.fori_loop(0, max_iter, body, init_centroids)

    return jax.jit(
        jax.shard_map(
            named_program("kmeans_lloyd", per_device, phases=PHASES),
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P(), P()),
            out_specs=P(),
        )
    )


class _Placed(NamedTuple):
    """A table's rows as the mesh holds them, in the table's own order."""

    rows: jax.Array   # [p * n_local, d], zero rows past the table's end
    norms: jax.Array  # [p * n_local] each row's squared norm
    mask: jax.Array   # [p * n_local] 1 for a row of the table, 0 for padding


def _place_rows(x: np.ndarray, mesh: DeviceMesh) -> _Placed:
    """``x``'s rows on the mesh through :meth:`DeviceMesh.shard_rows` in
    identity order (staged rounds far under the runtime's ≈ 4 GiB
    transfer cliff; no padded or widened host copy), the mask made on
    the device, the squared norms computed there once."""
    with span("kmeans.table_to_device") as phase:
        rows = mesh.shard_rows(x, np.arange(x.shape[0]))
        mask = mesh.shard_ones(x.shape[0], rows.dtype)
        norms = blas.squared_norms(rows)
        phase.add(bytes=rows.nbytes)
    group = metrics.group("kmeans")
    group.counter("table_uploads")
    group.counter("table_h2d_bytes", float(rows.nbytes))
    return _Placed(rows, norms, mask)


def _rows_on_mesh(table: Table, features_col: str, x: np.ndarray,
                  mesh: DeviceMesh) -> _Placed:
    """The features column on ``mesh``, placed at the table's first fit
    and kept WITH the table (:meth:`Table.device_resident`, under a key
    of the column, the mesh and the dtype): the table holds the only
    reference to the device copy (6.35 GB for 2,025,000 x 784 float32
    rows, with 16 MB of norms and mask), a later fit on the same table
    uploads nothing, and dropping the table frees it (as does a later
    placement that needs its room: the next fit then places again)."""
    key = ("rows_on_mesh", features_col, mesh.mesh, x.dtype.name)

    def place(make_room):
        # at the width the device holds them (float64 only under x64)
        make_room(x.size * jax.dtypes.canonicalize_dtype(x.dtype).itemsize,
                  mesh.mesh.devices.flat)
        return _place_rows(x, mesh)

    return table.device_resident(key, place)


def _lloyd(placed: _Placed, start: np.ndarray, mesh: DeviceMesh, k: int,
           max_iter: int, precision=PRODUCT_PRECISION) -> np.ndarray:
    """``max_iter`` rounds from ``start`` over the placed rows: the one
    whole-loop program dispatched and waited for, the ``[k, d]``
    centroids read back."""
    trainer = _kmeans_trainer(mesh.mesh, k, DeviceMesh.DATA_AXIS, precision)
    with span("kmeans.loop"):
        with span("kmeans.dispatch"):
            centroids = trainer(
                placed.rows, placed.norms, placed.mask,
                jnp.asarray(start, placed.rows.dtype),
                jnp.asarray(max_iter, jnp.int32))
        # The caller reads the centroids next, so waiting here costs
        # nothing and gives the loop a span its device time lies in.
        centroids.block_until_ready()
    group = metrics.group("kmeans")
    group.counter("fits")
    group.counter("rounds", float(max_iter))
    group.counter("rows", float(placed.rows.shape[0]))
    with span("kmeans.readback"):
        return np.asarray(centroids)


def _kmeans_pp_init(x: np.ndarray, k: int, rng: np.random.Generator) -> np.ndarray:
    """k-means++ seeding: each next centroid sampled ∝ distance² to the
    nearest chosen one."""
    centroids = [x[rng.integers(x.shape[0])]]
    d2 = ((x - centroids[0]) ** 2).sum(-1)
    for _ in range(1, k):
        probs = d2 / d2.sum() if d2.sum() > 0 else np.full(len(x), 1.0 / len(x))
        nxt = x[rng.choice(x.shape[0], p=probs)]
        centroids.append(nxt)
        d2 = np.minimum(d2, ((x - nxt) ** 2).sum(-1))
    return np.stack(centroids)


def _start_centroids(x: np.ndarray, k: int, seed: int, init_mode: str) -> np.ndarray:
    """``[k, d]`` start centroids from the host rows: ``random`` is ``k``
    distinct rows, ``default_rng(seed).choice(n, size=k, replace=False)``
    (the documented rule: ``benchmark/reference/kmeans.py`` states the
    same one); ``k-means++`` the seeding over all rows."""
    rng = np.random.default_rng(seed)
    if init_mode == "k-means++":
        return _kmeans_pp_init(x, k, rng)
    return np.ascontiguousarray(
        x[rng.choice(x.shape[0], size=k, replace=False)])


def train_kmeans(
    x: np.ndarray,
    k: int,
    mesh: DeviceMesh,
    max_iter: int,
    seed: int,
    init_mode: str = "random",
    initial_centroids: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Returns centroids [k, d]; the full loop runs on device. The rows
    are placed for this call alone (``KMeans.fit`` keeps a table's).
    ``initial_centroids`` overrides the seeded init (used by tests and by
    warm restarts)."""
    if initial_centroids is not None:
        start = np.asarray(initial_centroids, x.dtype)
    else:
        start = _start_centroids(x, k, seed, init_mode)
    return _lloyd(_place_rows(x, mesh), start, mesh, k, max_iter)


@functools.lru_cache(maxsize=64)
def _kmeans_partial_fn(mesh, k: int, axis: str):
    """Per-batch Lloyd partials: psum'd per-cluster (sums, counts) for one
    sharded batch against replicated centroids. The streamed trainer
    accumulates these across batches, then updates centroids once per
    epoch: :func:`_share_sums`, the whole-loop trainer's own round, with
    the batch axis split (a batch's norms are computed with it: a batch
    is seen once an epoch)."""

    def per_device(xb, wb, centroids):
        sums, counts = _share_sums(
            xb, jnp.sum(xb * xb, axis=-1), wb, centroids, k, PRODUCT_PRECISION)
        return jax.lax.psum(sums, axis), jax.lax.psum(counts, axis)

    return jax.jit(
        jax.shard_map(
            per_device, mesh=mesh,
            in_specs=(P(axis), P(axis), P()),
            out_specs=(P(), P()),
        )
    )


def train_kmeans_stream(
    batches,
    k: int,
    mesh: DeviceMesh,
    max_iter: int,
    seed: int,
    init_mode: str = "random",
    cache_dir: Optional[str] = None,
    memory_budget_bytes: Optional[int] = None,
    prefetch_depth: int = 2,
    column: str = "x",
    init_sample_size: int = 65_536,
    initial_centroids: Optional[np.ndarray] = None,
    checkpoint_manager=None,
    checkpoint_interval: int = 0,
    resume: bool = False,
    listeners=(),
) -> np.ndarray:
    """Out-of-core Lloyd: train from a one-shot stream of batch dicts (or
    a sealed :class:`DataCache`) with bounded HBM residency.

    Reference parity: ``ReplayOperator.java:62-250`` (epoch-0 cache +
    per-epoch replay) + ``SelectNearestCentroidOperator``'s ListState
    point cache (``KMeans.java:239-312``). Pass 0 caches the stream
    (spilling beyond ``memory_budget_bytes`` to ``cache_dir``) while
    feeding a seeded :class:`RowReservoir` for centroid init —
    ``init_mode='random'`` takes k reservoir rows (uniform over the
    stream, exactly the reference's random init); ``'k-means++'`` runs
    the seeding on a ``init_sample_size`` uniform row sample. Each Lloyd
    iteration replays the cache through a prefetching device feed,
    accumulating per-cluster sums/counts on device; centroids update once
    per epoch (empty clusters keep their previous centroid). Only one
    batch (plus prefetch depth) is device-resident at a time.

    Fault tolerance (``KMeans.java:239-312`` ListState recovery;
    ``Checkpoints.java:43-211``): ``checkpoint_manager`` +
    ``checkpoint_interval`` snapshot ``(centroids, epoch)`` every N Lloyd
    epochs; ``resume=True`` restores the latest snapshot and continues —
    bit-exact with the uninterrupted run, because each epoch is a pure
    function of (centroids, cache). Resume requires the same durable
    cache (or re-fed identical stream) the crashed run trained from.

    ``listeners`` (:class:`~flinkml_tpu.iteration.IterationListener`)
    fire at every Lloyd epoch boundary with the current centroids and at
    termination — the mid-stream model-emission hook
    (``iteration.runtime.notify_epoch_listeners``): a
    :class:`flinkml_tpu.serving.SnapshotPublisher` attached here
    publishes a consistent versioned model snapshot every N epochs into
    a registry *without stopping the stream*, matching the reference's
    unbounded ``Iterations`` per-round model emission.
    """
    from flinkml_tpu.iteration.checkpoint import begin_resume, should_snapshot
    from flinkml_tpu.iteration.datacache import (
        DataCache,
        DataCacheWriter,
        PrefetchingDeviceFeed,
    )
    from flinkml_tpu.utils.sampling import RowReservoir

    # Multi-process: each process feeds its own stream partition; the SPMD
    # schedule (fixed batch height, agreed step count, zero-weight dummy
    # steps) comes from SyncedReplayPlan, init samples are pooled across
    # processes, checkpoints commit rank-0-write + barrier. See
    # iteration/stream_sync.py and _train_linear_stream_multiprocess for
    # the invariants.
    multi = jax.process_count() > 1
    if resume and not isinstance(batches, DataCache):
        raise ValueError(
            "resume=True requires a durable DataCache input: a one-shot "
            "stream cannot be replayed from the start after a failure"
        )

    # Decide the resume target BEFORE pass 0, so a successful restore
    # skips the reservoir pass + seeding whose centroids it would discard
    # (on a spilled cache that pass re-reads the whole dataset).
    resume_epoch = begin_resume(checkpoint_manager, resume, mesh.mesh.size)

    p_size = mesh.axis_size()
    row_tile = p_size * 8
    axis = DeviceMesh.DATA_AXIS
    fn = _kmeans_partial_fn(mesh.mesh, k, axis)
    n_feat = [None]  # first-seen feature dim; every batch must match

    def check_dims(x):
        if x.ndim != 2:
            raise ValueError(f"stream batches must be [n, d], got {x.shape}")
        if x.shape[0] == 0:
            raise ValueError("stream batch has zero rows; drop empty batches")
        if n_feat[0] is None:
            n_feat[0] = x.shape[1]
        elif x.shape[1] != n_feat[0]:
            raise ValueError(
                f"batch feature dim {x.shape[1]} != first batch's {n_feat[0]}"
            )

    def place(batch):
        x = np.asarray(batch[column], dtype=np.float32)
        check_dims(x)
        x_pad, n_valid = pad_to_multiple(x, row_tile)
        w = np.zeros(x_pad.shape[0], np.float32)
        w[:n_valid] = 1.0  # padded rows never influence centroids
        return mesh.shard_batch(x_pad), mesh.shard_batch(w)

    def make_multi_place(height: int, dim: int):
        """Fixed-shape multi-process placement: every step contributes
        exactly ``height`` local rows (zero-weight padding / dummies)."""

        from flinkml_tpu.iteration.stream_sync import pad_rows_to

        def place_multi(batch):
            if "_dummy" in batch:
                x_pad = np.zeros((height, dim), np.float32)
                w = np.zeros(height, np.float32)
            else:
                x = np.asarray(batch[column], dtype=np.float32)
                check_dims(x)
                x_pad = pad_rows_to(x, height)
                w = pad_rows_to(np.ones(x.shape[0], np.float32), height)
            return mesh.global_batch(x_pad), mesh.global_batch(w)

        return place_multi

    # -- pass 0: cache (if needed) + reservoir sample for init -------------
    reservoir_cap = (
        k if init_mode == "random" else max(k, init_sample_size)
    )
    need_init = initial_centroids is None and resume_epoch is None
    reservoir = RowReservoir(reservoir_cap, seed=seed)
    from flinkml_tpu.iteration.stream_sync import DeferredValidation

    dv = DeferredValidation()

    def ingest(b):
        # Extraction is part of the checked step (a missing column or
        # ragged value raises HERE, not in the reservoir add below).
        x = np.asarray(b[column], np.float32)
        check_dims(x)
        return x

    from flinkml_tpu.iteration.stream_sync import checked_ingest

    if isinstance(batches, DataCache):
        cache = batches
        if need_init:
            # Multi-process, iterator and ingest failures are held for
            # the rendezvous below (a rank-local raise would strand the
            # peers in plan.create's collective; adding a ragged batch
            # to the fixed-width reservoir would be such a raise).
            for x in checked_ingest(cache.reader(), dv, ingest, multi):
                reservoir.add(x)
        elif multi:
            # Cached source with initial_centroids/resume: pre-validate
            # every cached batch anyway — without this, a bad cached
            # batch on one rank first raises rank-locally in
            # place_multi's check_dims on the prefetch thread at replay,
            # stranding the peers mid-collective (LDA's cached-source
            # pre-validation, mirrored).
            for _ in checked_ingest(cache.reader(), dv, ingest, multi):
                pass
    else:
        writer = DataCacheWriter(cache_dir, memory_budget_bytes)

        def ingest_append(b):
            # The append is part of the checked step too: a rank-local
            # writer failure (e.g. disk full while spilling a segment)
            # must ride the rendezvous like any ingest failure.
            x = ingest(b)
            writer.append({column: np.array(x)})
            return x

        for x in checked_ingest(batches, dv, ingest_append, multi):
            if need_init:
                reservoir.add(x)
        cache = writer.finish()
    plan = None
    dim = n_feat[0] or 0
    if multi:
        from flinkml_tpu.iteration.stream_sync import (
            SyncedReplayPlan,
            agree_feature_dim,
            gather_vectors,
            pooled_sample,
        )

        # Rendezvous BEFORE planning: a held ingest error must
        # surface as itself, not as plan.create's "stream is empty
        # on every process" (skip-on-failure can leave every local
        # cache empty).
        dv.rendezvous(mesh, "stream ingest validation")
        plan = SyncedReplayPlan.create(cache, mesh, row_tile)
        dim = agree_feature_dim(cache, column, mesh, local_dim=dim)
        # f64 transport: global row counts can exceed int32.
        total_rows = int(
            gather_vectors(np.asarray([cache.num_rows], np.float64), mesh)
            .sum()
        )
        if total_rows < k:  # replicated value: every rank raises together
            raise ValueError(f"k={k} exceeds number of points {total_rows}")
    elif cache.num_rows < k:
        raise ValueError(f"k={k} exceeds number of points {cache.num_rows}")

    rng = np.random.default_rng(seed)
    start_epoch = 0
    if resume_epoch is not None:
        if multi:
            d_feat = dim
        else:
            # Shape discovery without a full pass: one cached batch gives d.
            reader = cache.reader()
            d_feat = np.asarray(next(iter(reader))[column]).shape[1]
            if hasattr(reader, "close"):
                reader.close()
        from flinkml_tpu.iteration.stream_sync import agreed_restore

        centroids, start_epoch = agreed_restore(
            checkpoint_manager, resume_epoch,
            np.zeros((k, d_feat), np.float32), mesh,
        )
    elif initial_centroids is not None:
        centroids = np.asarray(initial_centroids, np.float32)
        if centroids.shape[0] != k:
            raise ValueError(
                f"initial_centroids has {centroids.shape[0]} rows, need {k}"
            )
    else:
        sample = reservoir.sample()
        if multi:
            # Pool the per-process uniform samples into one global sample
            # (identical on every host), then seed from it.
            sample = pooled_sample(
                sample, cache.num_rows, reservoir_cap, seed, mesh
            )
        if init_mode == "k-means++":
            centroids = _kmeans_pp_init(sample, k, rng).astype(np.float32)
        else:
            # The reservoir IS the uniform k-row sample; a fixed order
            # would bias nothing, but shuffle for parity with the
            # reference's shuffled selection (KMeans.java:314-335).
            centroids = sample[rng.permutation(sample.shape[0])[:k]]

    from flinkml_tpu.parallel import dispatch as _dispatch
    from flinkml_tpu.parallel.dispatch import DispatchGuard, local_execution_lock

    guard = DispatchGuard()  # multi-process backpressure (no-op single)
    cent_dev = jnp.asarray(centroids)
    mesh_device_ids = tuple(d.id for d in mesh.mesh.devices.flatten())
    # Serialize vs. concurrent fits from other host threads over this
    # mesh's devices: interleaved multi-device collective dispatch
    # deadlocks (see local_execution_lock; the analyzer's FML302 check
    # verifies this exact program shape via the dispatch trace below).
    # The lock scopes one EPOCH, not the whole loop: every collective
    # dispatch of an epoch (including the guard flush and the
    # checkpoint's multi-process gather) completes under the lock, and
    # the only cross-release in-flight work (the centroid update) is
    # elementwise on replicated arrays — no rendezvous to interleave.
    # Releasing at epoch boundaries keeps listener callbacks (snapshot
    # publication: disk writes, a following engine's warmup compiles)
    # from stalling concurrent fits on overlapping devices.
    epoch_lock = local_execution_lock(mesh)
    for epoch in range(start_epoch, max_iter):
        with epoch_lock:
            if _dispatch.has_dispatch_observers():
                _dispatch.record_collective_dispatch(
                    "kmeans.lloyd_epoch", mesh_device_ids
                )
            sums = None
            counts = None
            if multi:
                src = plan.epoch_batches(
                    cache.reader(), lambda: {"_dummy": True}
                )
                place_fn = make_multi_place(plan.local_height, dim)
            else:
                src = cache.reader()
                place_fn = place
            feed = PrefetchingDeviceFeed(
                src, place=place_fn, depth=prefetch_depth
            )
            try:
                for xb, wb in feed:
                    s, c = fn(xb, wb, cent_dev)
                    sums = s if sums is None else sums + s
                    counts = c if counts is None else counts + c
                    counts = guard.after_dispatch(counts)
            finally:
                feed.close()
            if sums is None:
                raise ValueError("training stream is empty")
            counts = guard.flush(counts)
            cent_dev = _moved(sums, counts, cent_dev)
            if should_snapshot(checkpoint_manager, checkpoint_interval,
                               epoch + 1, max_iter):
                if multi:
                    from flinkml_tpu.iteration.checkpoint import (
                        save_replicated,
                    )

                    save_replicated(
                        checkpoint_manager, np.asarray(cent_dev), epoch + 1,
                        mesh,
                    )
                else:
                    checkpoint_manager.save(np.asarray(cent_dev), epoch + 1)
        if listeners:
            from flinkml_tpu.iteration.runtime import notify_epoch_listeners

            cent_dev = notify_epoch_listeners(listeners, epoch, cent_dev)
    jax.block_until_ready(cent_dev)
    for listener in listeners:
        listener.on_iteration_terminated(cent_dev)
    return np.asarray(cent_dev)
