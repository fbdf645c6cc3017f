"""MLPClassifier — multilayer perceptron (the Spark/Flink
``MultilayerPerceptronClassifier`` family member), TPU-native.

The natural fit for this framework's design stance: the WHOLE training
run is one device program — a ``lax.while_loop`` of Adam steps (with
tol-based early stopping) over a data-sharded mesh, gradients
``psum``-combined per step. (The upstream operator trains with L-BFGS on
the JVM; Adam-on-device is the TPU-idiomatic equivalent and is documented
as such rather than imitated.)

``fit(Table)`` is ``models/_mlp_table.py``: the table's rows placed once
and kept with it, step ``t`` a window of them, every layer a product
forward and two back, under ``precision="mixed"`` each with bfloat16
operands and a float32 sum. What a chip makes of it, read at
784-2500-2000-1500-1000-500-10 and a batch of 16,384 on a v5e (PR 52;
PERF.md section 5, ``mlp-mnist8m.fit``): a ``mixed`` step is 8.18 ms on
the device, 2.71 forward and 5.33 back, Adam's update inside the weight
gradients' product fusions: 69 % of the 5.64 ms its 1.112 TFLOP take at
the bfloat16 peak; with no policy (six bfloat16 passes a float32
product) a step is 38 ms. ``fit`` of an iterable is the streamed fit
below, whose batches are ``_adam``'s draw.

Architecture: ``layers = [d_in, h_1, ..., h_k, n_classes]``, tanh hidden
activations (the upstream convention), softmax output, cross-entropy
loss, He-scaled Gaussian init. Labels are class ids ``0..n_classes-1``.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flinkml_tpu.api import Estimator, Model
from flinkml_tpu.models import _mlp_table
from flinkml_tpu.models._streaming import StreamingEstimatorMixin
from flinkml_tpu.common_params import (
    HasFeaturesCol,
    HasGlobalBatchSize,
    HasLabelCol,
    HasLearningRate,
    HasMaxIter,
    HasPredictionCol,
    HasRawPredictionCol,
    HasSeed,
    HasTol,
)
from flinkml_tpu.models._data import features_matrix, labeled_data
from flinkml_tpu.params import IntArrayParam, ParamValidators
from flinkml_tpu.parallel import DeviceMesh
from flinkml_tpu.table import Table
from flinkml_tpu.utils.profiling import span


class _MLPParams(
    HasFeaturesCol, HasLabelCol, HasPredictionCol,
    HasMaxIter, HasLearningRate, HasGlobalBatchSize, HasTol, HasSeed,
):
    LAYERS = IntArrayParam(
        "layers",
        "Sizes of every layer, input first, output last.",
        None, ParamValidators.non_empty_array(),
    )


class _MLPClassifierParams(_MLPParams, HasRawPredictionCol):
    """Only the classifier emits a rawPrediction column; the regressor
    must not carry the dead param."""


def _forward(params, x):
    """params: flat tuple (w0, b0, w1, b1, ...); returns logits."""
    n_layers = len(params) // 2
    h = x
    for i in range(n_layers - 1):
        h = jnp.tanh(h @ params[2 * i] + params[2 * i + 1])
    return h @ params[-2] + params[-1]


def _mlp_loss_builder():
    def local_loss(params, xb, yb, wb):
        logits = _forward(params, xb)
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, yb[:, None], axis=1)[:, 0]
        return jnp.sum(nll * wb)

    return local_loss


def _mlp_squared_loss_builder():
    def local_loss(params, xb, yb, wb):
        pred = _forward(params, xb)[:, 0]
        err = pred - yb
        return 0.5 * jnp.sum(err * err * wb)

    return local_loss


class _MLPBase(StreamingEstimatorMixin, _MLPParams, Estimator):
    """Shared fit scaffold: the subclasses differ only in label
    preparation/validation and the loss builder (same pairing pattern as
    ``fm._FMBase``).

    ``fit`` also accepts an iterable of batch Tables or a sealed
    :class:`~flinkml_tpu.iteration.datacache.DataCache` — the
    out-of-core path (reference replay parity:
    ``ReplayOperator.java:62-250``): the stream is cached once, then
    each epoch replays the cache chunk-by-chunk, running Adam minibatch
    steps within the resident chunk with the optimizer state carried
    across chunks as one continuous run. ``checkpoint_manager`` +
    ``checkpoint_interval`` snapshot the full Adam state every N epochs;
    ``resume=True`` (durable DataCache input required) continues
    bit-exactly.
    """

    _MODEL_CLS = None
    _LOSS_BUILDER = None
    #: The table fit's loss: cross-entropy over class ids, or the square.
    _CLASSIFY = True
    #: ``precision=`` declares the products of the table fit
    #: (``models/_mlp_table.py``).
    _PRECISION_AWARE = True

    def _prepare_labels(self, y: np.ndarray, layers) -> np.ndarray:
        raise NotImplementedError

    def _check_labels(self, labels, layers) -> None:
        """The table fit's label checks, from the column's kept
        :class:`~flinkml_tpu.models._data.LabelFacts`."""
        raise NotImplementedError

    def _check_layers(self):
        layers = self.get(self.LAYERS)
        if layers is None or len(layers) < 2:
            raise ValueError("layers must list at least [inputDim, outputDim]")
        return layers

    def fit(self, *inputs):
        (table,) = inputs
        if not isinstance(table, Table):
            return self._fit_stream(table)
        self._reject_in_ram_checkpointing()
        with span("fit"):
            params, losses = _mlp_table.fit_table(self, table, self._CLASSIFY)
        model = self._MODEL_CLS()
        model.copy_params_from(self)
        model._weights = list(params)
        model.loss_history = losses
        return model

    def _fit_stream(self, source):
        """Out-of-core Adam via the shared runner
        (:func:`flinkml_tpu.models._adam.run_streamed_adam`): the
        optimizer state rides across the replayed chunks as one
        continuous run, snapshotted at epoch boundaries."""
        from flinkml_tpu.models._adam import run_streamed_adam

        if self.precision is not None:
            raise ValueError(
                "precision declares the products of the table fit; the "
                "streamed fit runs at the backend's default. Drop the policy "
                "or fit a Table."
            )
        layers = self._check_layers()
        features_col = self.get(self.FEATURES_COL)
        label_col = self.get(self.LABEL_COL)
        mesh = self.mesh or DeviceMesh()

        def ingest(t):
            x, y, w = labeled_data(t, features_col, label_col)
            if x.shape[1] != layers[0]:
                raise ValueError(
                    f"layers[0]={layers[0]} != feature dim {x.shape[1]}"
                )
            return {
                "x": x.astype(np.float32),
                "y": self._prepare_labels(y, layers),
                "w": w.astype(np.float32),
            }

        def params0_fn(d):
            if d != layers[0]:
                raise ValueError(
                    f"layers[0]={layers[0]} != feature dim {d}"
                )
            return _mlp_table.init_params(
                layers, jax.random.PRNGKey(self.get_seed()))

        flat = run_streamed_adam(
            source,
            what="MLP streamed fit",
            mesh=mesh,
            cache_dir=self.cache_dir,
            cache_memory_budget_bytes=self.cache_memory_budget_bytes,
            ingest=ingest,
            place_y=lambda y: self._prepare_labels(y, layers),
            loss_builder=type(self)._LOSS_BUILDER,
            n_params=2 * (len(layers) - 1),
            params0_fn=params0_fn,
            lr=self.get(self.LEARNING_RATE),
            global_bs=self.get(self.GLOBAL_BATCH_SIZE),
            max_iter=self.get(self.MAX_ITER),
            tol=self.get(self.TOL),
            seed=self.get_seed(),
            **self._checkpoint_kwargs(),
        )
        model = self._MODEL_CLS()
        model.copy_params_from(self)
        model._weights = [np.asarray(t, np.float64) for t in flat]
        return model


class MLPClassifier(_MLPClassifierParams, _MLPBase):
    def _prepare_labels(self, y: np.ndarray, layers) -> np.ndarray:
        n_classes = layers[-1]
        yi = y.astype(np.int64)
        if not np.all(y == yi) or yi.min() < 0 or yi.max() >= n_classes:
            raise ValueError(
                f"labels must be class ids in [0, {n_classes}), got "
                f"[{y.min()}, {y.max()}]"
            )
        return yi.astype(np.int32)

    def _check_labels(self, labels, layers) -> None:
        n_classes = layers[-1]
        if not labels.integral or labels.lo < 0 or labels.hi >= n_classes:
            raise ValueError(
                f"labels must be class ids in [0, {n_classes}), got "
                f"[{labels.lo}, {labels.hi}]"
            )


class _MLPModelBase(_MLPParams, Model):
    """Weight storage, forward pass, and persistence shared by the
    sibling classifier/regressor models."""

    def __init__(self):
        super().__init__()
        self._weights: Optional[List[np.ndarray]] = None
        #: The loss of every step of the table fit that made the model
        #: (float32 ``[steps]``); None for any other model.
        self.loss_history: Optional[np.ndarray] = None

    def set_model_data(self, *inputs: Table) -> "MLPClassifierModel":
        (table,) = inputs
        n = int(np.asarray(table.column("numArrays"))[0])
        self._weights = [
            np.asarray(table.column(f"arr{i}"), np.float64)[0]
            for i in range(n)
        ]
        return self

    def get_model_data(self) -> List[Table]:
        self._require()
        cols = {"numArrays": np.asarray([len(self._weights)])}
        for i, a in enumerate(self._weights):
            cols[f"arr{i}"] = a[None, ...]
        return [Table(cols)]

    def _require(self) -> None:
        if self._weights is None:
            raise ValueError("Model data is not set; fit or set_model_data first")

    def _logits(self, table: Table) -> np.ndarray:
        # ``x`` is float64 and every product widens to it: the table fit's
        # float32 arrays are multiplied as their float64 values.
        x = features_matrix(table, self.get(self.FEATURES_COL))
        n_layers = len(self._weights) // 2
        h = x
        for i in range(n_layers - 1):
            h = np.tanh(h @ self._weights[2 * i] + self._weights[2 * i + 1])
        return h @ self._weights[-2] + self._weights[-1]

    def save(self, path: str) -> None:
        self._require()
        arrays = {f"arr{i}": a for i, a in enumerate(self._weights)}
        if self.loss_history is not None:
            arrays["lossHistory"] = self.loss_history
        self._save_with_arrays(
            path, arrays, extra={"numArrays": len(self._weights)},
        )

    @classmethod
    def load(cls, path: str):
        model, arrays, meta = cls._load_with_arrays(path)
        n = int(meta["numArrays"])
        model._weights = [arrays[f"arr{i}"] for i in range(n)]
        model.loss_history = arrays.get("lossHistory")
        return model


class MLPClassifierModel(_MLPClassifierParams, _MLPModelBase):
    def transform(self, *inputs: Table) -> Tuple[Table, ...]:
        (table,) = inputs
        self._require()
        logits = self._logits(table)
        shifted = logits - logits.max(axis=1, keepdims=True)
        probs = np.exp(shifted)
        probs /= probs.sum(axis=1, keepdims=True)
        out = table.with_column(
            self.get(self.PREDICTION_COL),
            np.argmax(logits, axis=1).astype(np.float64),
        )
        out = out.with_column(self.get(self.RAW_PREDICTION_COL), probs)
        return (out,)


class MLPRegressor(_MLPBase):
    """Multilayer perceptron regressor: ``layers = [d_in, h..., 1]``,
    tanh hidden activations, linear output, squared loss — the same
    whole-run Adam device trainer as the classifier."""

    _CLASSIFY = False

    def _prepare_labels(self, y: np.ndarray, layers) -> np.ndarray:
        self._check_labels(None, layers)
        return y.astype(np.float32)

    def _check_labels(self, labels, layers) -> None:
        if layers[-1] != 1:
            raise ValueError(
                "layers must be [inputDim, hidden..., 1] for regression"
            )


class MLPRegressorModel(_MLPModelBase):
    """Sibling of the classifier model (not a subclass of it): the
    transform emits the linear output directly."""

    def transform(self, *inputs: Table) -> Tuple[Table, ...]:
        (table,) = inputs
        self._require()
        pred = self._logits(table)[:, 0]
        return (
            table.with_column(self.get(self.PREDICTION_COL), pred),
        )


MLPClassifier._MODEL_CLS = MLPClassifierModel
MLPClassifier._LOSS_BUILDER = staticmethod(_mlp_loss_builder)
MLPRegressor._MODEL_CLS = MLPRegressorModel
MLPRegressor._LOSS_BUILDER = staticmethod(_mlp_squared_loss_builder)
