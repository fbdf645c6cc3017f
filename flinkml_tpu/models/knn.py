"""Knn — brute-force k-nearest-neighbors classifier.

Capability parity with ``flink-ml-lib/.../classification/knn/Knn.java:52-140``
and ``KnnModel.java:51-197``, rebuilt TPU-first:

  - ``fit`` keeps the train set as the model (the reference packs
    per-partition column-major ``DenseMatrix`` blocks + norms,
    ``Knn.java:87-140``); here the model is the [n, d] features column
    as the table holds it (a float32 column is not widened) + labels.
  - The model data goes to the device ONCE, at the first ``transform``
    after ``fit`` / ``set_model_data`` / ``load``: float32 rows through
    :meth:`DeviceMesh.shard_rows` (staged rounds, far under the ≈ 4 GiB
    transfer cliff), each row's squared norm and its class id beside it.
    It stays until the model is dropped or given new model data; a later
    ``transform`` uploads its queries only.
  - Prediction: the reference broadcasts the whole model and, per query
    row, runs gemv-style distances + a top-k priority queue
    (``KnnModel.java:72-197``). Here ONE program a call: blocks of
    queries against blocks of train rows; a block's squared distances
    are one MXU product at float32 accuracy (``Precision.HIGHEST``: the
    default, one bfloat16 pass, does not rank near neighbours) in the
    ‖x‖² - 2xy + ‖y‖² expansion, and its exact ``k`` best join a running
    ``k`` best; then a one-hot vote. On a TPU the product and the
    ranking are one Pallas kernel
    (:mod:`flinkml_tpu.kernels.knn_search`): a block of distances is
    ranked in fast memory against the running ``k``-th best and never
    reaches HBM. Wherever that kernel does not apply (another backend,
    ``k`` over 128, rows that are not float32 or too wide) the tiled XLA
    search below runs, the same answer. The [queries, train rows] matrix
    never exists. The program is traced in 32-bit mode whatever
    ``jax_enable_x64`` says: every operand is float32 or int32.
  - Exact: neighbours are the ``k`` smallest by (distance, train row),
    ties to the LOWER row; the vote's ties go to the smaller class.
  - One chip answers from the rows it holds. A host that holds a train
    set by rows over several chips merges their [queries, k] answers by
    (distance, row); that merge is not here.
"""

from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flinkml_tpu.api import Estimator, Model
from flinkml_tpu.common_params import (
    HasFeaturesCol,
    HasK,
    HasLabelCol,
    HasPredictionCol,
)
from flinkml_tpu.kernels import _mosaic
from flinkml_tpu.kernels import knn_search
from flinkml_tpu.kernels import topk as topk_kernel
from flinkml_tpu.models._data import features_matrix
from flinkml_tpu.ops import blas
from flinkml_tpu.parallel.mesh import DeviceMesh
from flinkml_tpu.table import Table
from flinkml_tpu.utils.metrics import metrics
from flinkml_tpu.utils.profiling import named_program, span

#: Train rows a tile of the tiled XLA search ranks at once: the [chunk,
#: tile] float32 distances are that program's one large temporary. Read on a
#: v5e at 2,025,000 x 784 (PERF.md §5, PR 30): 16,384 -> 1.668 s a call
#: of 10,000 queries, 32,768 -> 1.589, 65,536 -> 1.554 (whose 2 MB blocks
#: leave the top-k kernel little of its fast memory).
TRAIN_TILE = 32_768
#: A tile's width is padded to a multiple of the TPU's lane width.
LANES = 128
#: The product's precision, a static argument of the search: float32
#: accuracy, the distance expansion's own (``ops.blas``). A builder's
#: control on the chip passes ``DEFAULT`` (one bfloat16 pass) to show
#: that the benchmark's check tells the two apart.
PRODUCT_PRECISION = blas.DISTANCE_PRECISION


class _KnnParams(HasFeaturesCol, HasLabelCol, HasPredictionCol, HasK):
    pass


class Knn(_KnnParams, Estimator):
    def __init__(self):
        super().__init__()

    def fit(self, *inputs: Table) -> "KnnModel":
        (table,) = inputs
        # The column as the table holds it: a float32 [n, d] column is
        # the model's features, not a float64 copy of them.
        x = features_matrix(table, self.get(_KnnParams.FEATURES_COL), dtype=None)
        y = table.column(self.get(_KnnParams.LABEL_COL))
        model = KnnModel()
        model.copy_params_from(self)
        model.set_model_data(Table({"features": x, "labels": y}))
        return model


class _Resident(NamedTuple):
    """The model data as the device holds it, and (host) the labels."""

    features: jax.Array   # [n, d] float32 rows
    norms: jax.Array      # [n] their squared norms
    class_ids: jax.Array  # [n] int32, each row's place in ``classes``
    classes: np.ndarray   # the sorted distinct labels, float64


class KnnModel(_KnnParams, Model):
    CHUNK = 4096  # most query rows a chunk of the search holds

    def __init__(self):
        super().__init__()
        self._features: Optional[np.ndarray] = None
        self._labels: Optional[np.ndarray] = None
        self._resident: Optional[_Resident] = None

    def set_model_data(self, *inputs: Table) -> "KnnModel":
        (table,) = inputs
        features = features_matrix(table, "features", dtype=None)
        labels = np.asarray(table.column("labels")).reshape(-1)
        if labels.dtype.kind not in "biuf":
            labels = labels.astype(np.float64)
        self._features, self._labels = features, labels
        self._resident = None
        return self

    def get_model_data(self) -> List[Table]:
        self._require_model()
        return [Table({"features": self._features, "labels": self._labels})]

    def _require_model(self) -> None:
        if self._features is None:
            raise ValueError("Model data is not set; call set_model_data or fit first")

    def _on_device(self) -> _Resident:
        """The resident model data, placed at the first call."""
        if self._resident is None:
            mesh = DeviceMesh(devices=jax.devices()[:1])
            with span("knn.model_to_device") as phase:
                features = mesh.shard_rows(
                    self._features, np.arange(self._features.shape[0]),
                    np.float32)
                # Distinct labels and each row's class id: the one sort
                # of the label column, here and not in every call.
                classes, ids = np.unique(self._labels, return_inverse=True)
                class_ids = jnp.asarray(ids.reshape(-1), dtype=jnp.int32)
                norms = blas.squared_norms(features)
                nbytes = features.nbytes + class_ids.nbytes
                phase.add(bytes=nbytes)
            group = metrics.group("knn")
            group.counter("model_uploads")
            group.counter("model_h2d_bytes", float(nbytes))
            self._resident = _Resident(features, norms, class_ids,
                                       classes.astype(np.float64))
        return self._resident

    @span("transform")
    def transform(self, *inputs: Table) -> Tuple[Table, ...]:
        (table,) = inputs
        self._require_model()
        k = self.get(_KnnParams.K)
        n_train = self._features.shape[0]
        if n_train == 0:
            raise ValueError("Knn model has no training points")
        # Reference parity: KnnModel's top-k priority queue simply holds
        # all n points when k > n — vote among everything, don't raise.
        k = min(k, n_train)
        x = features_matrix(table, self.get(_KnnParams.FEATURES_COL), dtype=None)
        model = self._on_device()
        chunk, tile = _chunk_rows(x.shape[0], self.CHUNK), _tile_rows(n_train, k)
        with span("knn.search"):
            with span("knn.dispatch"):
                queries = jnp.asarray(x, dtype=jnp.float32)
                ids = _knn_vote(
                    queries, model.features,
                    model.norms, model.class_ids, k=k,
                    num_classes=len(model.classes), chunk=chunk, tile=tile,
                    precision=PRODUCT_PRECISION)
            # The caller reads the prediction next, so waiting here costs
            # nothing and gives the search a span its device time lies in.
            ids.block_until_ready()
        group = metrics.group("knn")
        group.counter("query_rows", float(x.shape[0]))
        # Which search the program held: the kernel's, or the tiled one's.
        fused = _ranks_in_the_product(queries, model.features, k)
        group.counter("fused_query_rows", float(x.shape[0]) if fused else 0.0)
        # ... and whether it made the float32 product from its own parts.
        split = fused and knn_search.splits_the_product(PRODUCT_PRECISION)
        group.counter("split_product_query_rows", float(x.shape[0]) if split else 0.0)
        group.counter("train_tiles", 0.0 if fused else float(
            -(-x.shape[0] // chunk) * -(-n_train // tile)))
        with span("knn.readback"):
            pred = model.classes[np.asarray(ids)]
        return (table.with_column(self.get(_KnnParams.PREDICTION_COL), pred),)

    def save(self, path: str) -> None:
        self._require_model()
        self._save_with_arrays(
            path, {"features": self._features, "labels": self._labels}
        )

    @classmethod
    def load(cls, path: str) -> "KnnModel":
        model, arrays, _ = cls._load_with_arrays(path)
        model._features = arrays["features"]
        model._labels = arrays["labels"]
        return model


def _chunk_rows(n_queries: int, most: int) -> int:
    """Query rows a chunk: the call's rows in the fewest equal chunks of
    at most ``most``, up to a multiple of 8 (10,000 rows are three
    chunks of 3,336, not two of 4,096 and one of 1,808 padded to it)."""
    return knn_search.query_block_rows(n_queries, most)


def _tile_rows(n_train: int, k: int) -> int:
    """Train rows a tile: :data:`TRAIN_TILE`, more where ``k`` is (a
    tile must hold ``k`` rows of its own), never more than there are."""
    return min(n_train, max(TRAIN_TILE, -(-k // LANES) * LANES))


def _tile_top_k(d2, k: int):
    """``(values, positions)`` of each row's ``k`` smallest entries of
    ``d2`` ([rows, width]), ties to the lower position: exact.

    On a TPU, ``k`` masked passes over a block of rows held in fast
    memory (:func:`flinkml_tpu.kernels.topk.pallas_top_k`: each pass
    takes the row maximum and its FIRST index; bit-compatible with
    ``lax.top_k``), which on a v5e costs a quarter less than sorting the
    tile's 32,768 entries a row (PERF.md §5, PR 30). Beyond the kernel's
    unrolled-pass ceiling a sort is the right tool, and ``lax.top_k`` is
    it; so it is where Mosaic does not compile the kernel (a CPU or a
    GPU would run it interpreted, a Python loop over blocks of 8 rows)."""
    if k <= topk_kernel.MAX_K and not _mosaic.interpret_mode():
        neg, at = topk_kernel.pallas_top_k(-d2, k)
    else:
        neg, at = jax.lax.top_k(-d2, k)
    return -neg, at


def _ranks_in_the_product(queries, train_x, k: int) -> bool:
    """Whether :func:`nearest` takes the fused kernel for these operands:
    on a TPU (elsewhere it would run interpreted, a Python loop over
    blocks), and where the kernel takes their type, width and ``k``."""
    return (not _mosaic.interpret_mode()
            and knn_search.unsupported_reason(queries, train_x, k) is None)


def nearest(queries, train_x, train_sq, k: int, *, chunk: int, tile: int,
            precision):
    """``(d2, rows)``, both [queries, k]: each query's ``k`` nearest rows
    of ``train_x`` ([n, d], ``train_sq`` its rows' squared norms) by
    (squared distance, row), and those distances.

    Where it applies (:func:`_ranks_in_the_product`), one kernel forms
    the distances block by block and ranks each block where the product
    leaves it (:func:`flinkml_tpu.kernels.knn_search.fused_nearest`,
    which sizes its own blocks: ``chunk`` and ``tile`` size the search
    below). Everywhere else, and as what that kernel is tested against:

    Chunks of ``chunk`` queries (a multiple of 8); for each, tiles of
    ``tile`` train rows in ascending order. The last tile steps back to
    end at the last row (one shape, one program) and the rows an earlier
    tile ranked are masked out of it. A tile's ``k`` best
    (:func:`_tile_top_k`) are appended to the best so far and the ``k``
    smallest kept: the best so far come first and are lower rows, so
    ties stay with the lower row.

    A tile is cut from ``train_x.T``: a v5e holds a float32 [n, 784]
    array with the ROWS along its lanes (no lane is padded that way), so
    the transpose is free and a tile a run of lanes; cut from the rows
    the compiler first relaid the whole train set, 7.7 GB of scratch a
    call beside the 6.4 GB resident (PERF.md §5, PR 30)."""
    if _ranks_in_the_product(queries, train_x, k):
        return knn_search.fused_nearest(queries, train_x, train_sq, k,
                                        precision=precision)
    n_queries, dim = queries.shape
    n_train = train_x.shape[0]
    n_tiles = -(-n_train // tile)
    pad_cols = -tile % LANES
    n_chunks = -(-n_queries // chunk)
    queries = jnp.pad(queries, ((0, n_chunks * chunk - n_queries), (0, 0)))
    train_t = train_x.T

    def one_chunk(q):
        q_sq = jnp.sum(q * q, axis=-1)

        def one_tile(i, best):
            best_d, best_rows = best
            lo = i.astype(jnp.int32) * tile
            start = jnp.minimum(lo, n_train - tile)
            x_t = jax.lax.dynamic_slice_in_dim(train_t, start, tile, 1)
            x_sq = jax.lax.dynamic_slice_in_dim(train_sq, start, tile, 0)
            # ‖q‖² - 2 q·x + ‖x‖²: one [chunk, d] @ [d, tile] product.
            d2 = blas.squared_distances(q, x_t.T, precision=precision,
                                        xs_sq=q_sq, ys_sq=x_sq)
            ranked_before = start + jnp.arange(tile, dtype=jnp.int32) < lo
            d2 = jnp.where(ranked_before[None, :], jnp.inf, d2)
            d2 = jnp.pad(d2, ((0, 0), (0, pad_cols)), constant_values=jnp.inf)
            tile_d, at = _tile_top_k(d2, k)
            both_d = jnp.concatenate([best_d, tile_d], axis=1)
            both_rows = jnp.concatenate([best_rows, start + at], axis=1)
            neg, keep = jax.lax.top_k(-both_d, k)
            return -neg, jnp.take_along_axis(both_rows, keep, axis=1)

        start_with = (jnp.full((chunk, k), jnp.inf, jnp.float32),
                      jnp.zeros((chunk, k), jnp.int32))
        return jax.lax.fori_loop(0, n_tiles, one_tile, start_with)

    d2, rows = jax.lax.map(one_chunk, queries.reshape(n_chunks, chunk, dim))
    return (d2.reshape(-1, k)[:n_queries], rows.reshape(-1, k)[:n_queries])


@functools.partial(
    jax.jit,
    static_argnames=("k", "num_classes", "chunk", "tile", "precision"))
@functools.partial(named_program, "knn_vote")
def _knn_vote(queries, train_x, train_sq, train_class_ids, *, k: int,
              num_classes: int, chunk: int, tile: int, precision):
    """The ``k`` nearest rows (:func:`nearest`), then a majority vote of
    their class ids: one-hot counts, ties to the smaller class id
    (deterministic), matching the reference's priority-queue + map
    iteration determinism in spirit.

    Traced in 32-bit mode: under x64 the loop counters, the gather's
    indices and the one-hot counts would widen to 64 bits, another
    program than the one measured (the vote in emulated float64)."""
    with jax.enable_x64(False):
        _, rows = nearest(queries, train_x, train_sq, k, chunk=chunk, tile=tile,
                          precision=precision)
        votes = train_class_ids[rows]  # [nq, k]
        counts = jnp.sum(jax.nn.one_hot(votes, num_classes), axis=1)
        return jnp.argmax(counts, axis=-1).astype(jnp.int32)
