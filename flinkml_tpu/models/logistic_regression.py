"""LogisticRegression — binomial logistic regression, mini-batch SGD, L2.

Capability parity with
``flink-ml-lib/.../classification/logisticregression/LogisticRegression.java:76-454``
(+ ``LogisticGradient.java:34-97``, ``LogisticRegressionModel.java:100-170``),
rebuilt TPU-first:

  - The reference's per-epoch machinery — ``CacheDataAndDoTrain`` caching
    partitions in ListState, per-task mini-batch sampling, a ``double[dim+2]``
    feedback buffer (gradient ‖ weightSum ‖ lossSum) AllReduce'd via 3-hop
    network shuffles, coefficient update on the next epoch's watermark —
    becomes ONE jitted SPMD step: per-device batch sampling, batched
    gradient on the MXU, ``psum`` over ICI, coefficient update, all fused
    into a single XLA program per epoch.
  - Loss/gradient match ``LogisticGradient.java:50-96``:
    ``loss = Σ wᵢ·log(1+exp(-ŷᵢ·(2yᵢ-1)))``,
    ``grad = Σ wᵢ·(-(2yᵢ-1)·σ(-ŷᵢ·(2yᵢ-1)))·xᵢ``; update
    ``coef -= lr/weightSum · grad`` (``LogisticRegression.java:354-358``).
    Divergence (intentional): the reference adds the L2 term once *per
    task* before its AllReduce, so regularization scales with parallelism;
    here it is applied once, globally (the mathematically standard form).
  - Termination: ``TerminateOnMaxIterOrTol(maxIter, tol)`` on the epoch's
    weighted-mean loss (``LogisticRegression.java:267-275``).
  - Prediction (``LogisticRegressionModel.java:158-170``): label =
    ``dot >= 0``, raw prediction = ``[1-p, p]`` with ``p = σ(dot)``.
"""

from __future__ import annotations


import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from flinkml_tpu.api import Estimator, Model
from flinkml_tpu.models._streaming import StreamingEstimatorMixin
from flinkml_tpu.common_params import (
    HasFeaturesCol,
    HasGlobalBatchSize,
    HasLabelCol,
    HasLearningRate,
    HasMaxIter,
    HasMultiClass,
    HasPredictionCol,
    HasRawPredictionCol,
    HasReg,
    HasSeed,
    HasTol,
    HasWeightCol,
)
from flinkml_tpu.iteration import IterationConfig, TerminateOnMaxIterOrTol, iterate
from flinkml_tpu.models import _linear_sgd
from flinkml_tpu.models._coefficient import CoefficientModelMixin
from flinkml_tpu.models._data import (
    LabelFacts,
    check_binary_labels,
    features_matrix,
    sparse_features,
    sparse_fit_columns,
)
from flinkml_tpu.parallel import DeviceMesh, pad_to_multiple
from flinkml_tpu.table import Table
from flinkml_tpu.utils.profiling import span


class _LogisticRegressionParams(
    HasFeaturesCol,
    HasLabelCol,
    HasWeightCol,
    HasMaxIter,
    HasReg,
    HasLearningRate,
    HasGlobalBatchSize,
    HasTol,
    HasSeed,
    HasMultiClass,
    HasPredictionCol,
    HasRawPredictionCol,
):
    """Params shared by estimator and model (reference:
    LogisticRegressionParams / LogisticRegressionModelParams)."""


class LogisticRegression(StreamingEstimatorMixin, _LogisticRegressionParams, Estimator):
    """Fits binomial LR by epoch-synchronized distributed SGD.

    ``fit`` accepts, besides a single in-RAM :class:`Table`:

      - an **iterable of batch Tables** (one global mini-batch each) — the
        out-of-core path: epoch 0 caches the stream (spilling to
        ``cache_dir`` beyond ``cache_memory_budget_bytes``) while training,
        later epochs replay the cache through a prefetching device feed
        (reference: ``ReplayOperator.java:62-250``);
      - a sealed :class:`~flinkml_tpu.iteration.datacache.DataCache` whose
        batches carry this estimator's features/label(/weight) columns —
        replayed every epoch, no caching pass needed.
    """

    _SHARDING_PLAN_AWARE = True  # dense binomial path threads a plan
    _PRECISION_AWARE = True  # ... and the FML6xx-gated precision policy

    def fit(self, *inputs) -> "LogisticRegressionModel":
        (table,) = inputs
        if not isinstance(table, Table):
            return self._fit_stream(table)
        with span("fit"):
            return self._fit_table(table)

    def _fit_table(self, table: Table) -> "LogisticRegressionModel":
        multi_class = self.get(_LogisticRegressionParams.MULTI_CLASS)
        features_col = self.get(_LogisticRegressionParams.FEATURES_COL)
        hyper = dict(
            mesh=self.mesh or DeviceMesh(),
            max_iter=self.get(_LogisticRegressionParams.MAX_ITER),
            learning_rate=self.get(_LogisticRegressionParams.LEARNING_RATE),
            global_batch_size=self.get(_LogisticRegressionParams.GLOBAL_BATCH_SIZE),
            reg=self.get(_LogisticRegressionParams.REG),
            tol=self.get(_LogisticRegressionParams.TOL),
            seed=self.get_seed(),
            # The fit's placement stays with the table, for its next fit.
            kept=_linear_sgd.table_placements(
                table, features_col,
                self.get(_LogisticRegressionParams.LABEL_COL),
                self.get(_LogisticRegressionParams.WEIGHT_COL)),
            **self._checkpoint_kwargs(),
        )

        if sparse_features(table, features_col) is not None:
            # Criteo-scale path (BASELINE.json config #5): nnz-bucketed ELL
            # blocks (ops.sparse.pack_ell_buckets — padded cells ≈ total
            # nnz even under skew), gather forward + one fused segment-sum
            # gradient scatter; the dense [dim] model stays replicated.
            # Host-side packing: the trainer shards from host, so the full
            # dataset never stages through a single device's HBM.
            if self.sharding_plan is not None:
                raise ValueError(
                    "sharding_plan supports the dense binomial path "
                    "only; the sparse trainer keeps its replicated "
                    "[dim] model (shard it via ROADMAP item 5's "
                    "embedding-table path instead)"
                )
            if self.precision is not None:
                raise ValueError(
                    "precision supports the dense binomial path only; "
                    "the sparse trainer's gather/segment-sum kernels "
                    "are not yet policy-gated"
                )
            indptr, indices, values, dim, labels, w = sparse_fit_columns(
                table, features_col,
                self.get(_LogisticRegressionParams.LABEL_COL),
                self.get(_LogisticRegressionParams.WEIGHT_COL),
            )
            if _resolve_multi_class(multi_class, labels) == "multinomial":
                raise ValueError(
                    "multinomial logistic regression supports dense "
                    "features only; one-hot/sparse inputs train one "
                    "binomial model per concept"
                )
            _check_binomial_labels(labels)
            coef = _linear_sgd.train_linear_model_sparse_csr(
                indptr, indices, values, dim,
                labels.values, w, loss="logistic", elastic_net=0.0, **hyper,
            )
        else:
            x, labels, w, hyper["dtype"] = _linear_sgd.dense_table_data(
                table,
                features_col,
                self.get(_LogisticRegressionParams.LABEL_COL),
                self.get(_LogisticRegressionParams.WEIGHT_COL),
                replicated=(self.sharding_plan is None
                            and self.precision is None),
            )
            if x.shape[0] == 0:
                raise ValueError("training table is empty")
            if _resolve_multi_class(multi_class, labels) == "multinomial":
                # Softmax cross-entropy over integer classes 0..k-1:
                # coefficient is [k, d] (beyond the reference snapshot,
                # which rejects multinomial outright).
                if self.sharding_plan is not None:
                    raise ValueError(
                        "sharding_plan supports the dense binomial "
                        "path only (the softmax trainer is not yet "
                        "plan-aware)"
                    )
                if self.precision is not None:
                    raise ValueError(
                        "precision supports the dense binomial path "
                        "only (the softmax trainer is not yet "
                        "policy-gated)"
                    )
                num_classes = _check_multinomial_labels(labels)
                coef = _linear_sgd.train_softmax_model(
                    x, labels.values, w, num_classes=num_classes,
                    elastic_net=0.0, **hyper,
                )
            else:
                _check_binomial_labels(labels)
                coef = train_logistic_regression(
                    x, labels.values, w, sharding_plan=self.sharding_plan,
                    precision=self.precision, **hyper,
                )

        model = LogisticRegressionModel(mesh=self.mesh)
        model.copy_params_from(self)
        model.set_model_data(Table({"coefficient": coef[None, ...]}))
        return model

    def _fit_stream(self, source) -> "LogisticRegressionModel":
        """Out-of-core fit from an iterable of batch Tables or a DataCache
        (see class docstring; ReplayOperator.java:62-250 parity)."""
        if self.get(_LogisticRegressionParams.MULTI_CLASS) == "multinomial":
            raise ValueError(
                "multinomial logistic regression does not support "
                "streamed fits; materialize the data as a Table"
            )
        if self.sharding_plan is not None:
            raise ValueError(
                "sharding_plan supports in-RAM Table fits only; streamed "
                "fits keep their replicated carry"
            )
        if self.precision is not None:
            raise ValueError(
                "precision supports in-RAM Table fits only; the streamed "
                "trainer is not yet policy-gated"
            )

        features_col = self.get(_LogisticRegressionParams.FEATURES_COL)
        label_col = self.get(_LogisticRegressionParams.LABEL_COL)
        weight_col = self.get(_LogisticRegressionParams.WEIGHT_COL)
        coef = _linear_sgd.streamed_linear_fit(
            source,
            features_col=features_col,
            label_col=label_col,
            weight_col=weight_col,
            label_check=_check_stream_labels,
            loss="logistic",
            mesh=self.mesh or DeviceMesh(),
            max_iter=self.get(_LogisticRegressionParams.MAX_ITER),
            learning_rate=self.get(_LogisticRegressionParams.LEARNING_RATE),
            reg=self.get(_LogisticRegressionParams.REG),
            elastic_net=0.0,
            tol=self.get(_LogisticRegressionParams.TOL),
            cache_dir=self.cache_dir,
            memory_budget_bytes=self.cache_memory_budget_bytes,
            **self._checkpoint_kwargs(),
        )

        model = LogisticRegressionModel(mesh=self.mesh)
        model.copy_params_from(self)
        model.set_model_data(Table({"coefficient": coef[None, :]}))
        return model


class LogisticRegressionModel(CoefficientModelMixin, _LogisticRegressionParams, Model):
    """Broadcast-model batch inference (reference:
    ``LogisticRegressionModel.java:100-170`` — broadcast the coefficient,
    map each row; here: replicate the coefficient, one batched matmul)."""

    def __init__(self, mesh: Optional[DeviceMesh] = None):
        super().__init__()
        self.mesh = mesh
        self._coefficient: Optional[np.ndarray] = None

    def set_model_data(self, *inputs: Table) -> "LogisticRegressionModel":
        (table,) = inputs
        c = np.asarray(table.column("coefficient"), dtype=np.float64)
        # [1, d] (binomial vector) or [1, k, d] (multinomial matrix).
        self._coefficient = c[0] if c.ndim >= 2 else c.reshape(-1)
        return self

    def get_model_data(self) -> List[Table]:
        self._require_model()
        return [Table({"coefficient": self._coefficient[None, ...]})]

    # -- inference ---------------------------------------------------------
    def transform(self, *inputs: Table) -> Tuple[Table, ...]:
        (table,) = inputs
        self._require_model()
        multinomial = self._coefficient.ndim == 2
        features_col = self.get(_LogisticRegressionParams.FEATURES_COL)
        sparse_col = sparse_features(table, features_col)
        if sparse_col is not None:
            # Sparse inference: nnz-bucketed gather dots — O(nnz) memory
            # even under skewed nnz (same layout the trainer uses), never
            # densifying rows.
            from flinkml_tpu.ops.sparse import sparse_margins

            # Margins arrive on host; the elementwise tail stays on host
            # (no device round-trip for a sigmoid/softmax on [n] values).
            dot = sparse_margins(sparse_col, self._coefficient)
            if multinomial:
                pred, raw = _softmax_from_logits(dot.astype(np.float64))
            else:
                p = 1.0 / (1.0 + np.exp(-dot.astype(np.float64)))
                pred = (dot >= 0).astype(dot.dtype)
                raw = np.stack([1.0 - p, p], axis=-1)
            out = table.with_column(
                self.get(_LogisticRegressionParams.PREDICTION_COL), pred
            ).with_column(
                self.get(_LogisticRegressionParams.RAW_PREDICTION_COL), raw
            )
            return (out,)
        x = features_matrix(table, self.get(_LogisticRegressionParams.FEATURES_COL))
        predict = _predict_multinomial if multinomial else _predict
        if self.mesh is not None and self.mesh.num_devices > 1:
            # Sharded batch inference: rows split over the data axis, the
            # coefficient replicated (the broadcast-model pattern).
            x_pad, n_valid = pad_to_multiple(x, self.mesh.axis_size())
            xd = self.mesh.shard_batch(x_pad)
            coef = self.mesh.replicate(jnp.asarray(self._coefficient, xd.dtype))
            pred, raw = predict(xd, coef)
            # to_host: data-sharded outputs span non-addressable devices
            # on a multi-process mesh; every rank gathers the full result.
            pred = self.mesh.to_host(pred)[:n_valid]
            raw = self.mesh.to_host(raw)[:n_valid]
        else:
            pred, raw = predict(jnp.asarray(x), jnp.asarray(self._coefficient))
        out = table.with_column(
            self.get(_LogisticRegressionParams.PREDICTION_COL), np.asarray(pred)
        ).with_column(
            self.get(_LogisticRegressionParams.RAW_PREDICTION_COL), np.asarray(raw)
        )
        return (out,)

    def transform_kernel(self):
        """Dense single-device inference as a fusable kernel (the same
        math as :func:`_predict`/:func:`_predict_multinomial`). The
        per-stage path's compute dtype is whatever ``jnp.asarray`` gives
        the float64 feature matrix — float64 under the ambient x64 flag,
        float32 otherwise — so the kernel captures that flag at build
        time (the fused executor always traces under x64 for the scaler
        kernels' sake, and must not let that leak into this stage's
        dtypes). Sparse feature columns are object columns, which the
        fused executor rejects per-table — those chains fall back to the
        O(nnz) per-stage path. Multi-device meshes keep the sharded
        per-stage path (fusion is single-program, not SPMD, today)."""
        if self._coefficient is None:
            return None
        if self.mesh is not None and self.mesh.num_devices > 1:
            return None
        multinomial = self._coefficient.ndim == 2
        fcol = self.get(_LogisticRegressionParams.FEATURES_COL)
        pcol = self.get(_LogisticRegressionParams.PREDICTION_COL)
        rcol = self.get(_LogisticRegressionParams.RAW_PREDICTION_COL)
        x64 = bool(jax.config.jax_enable_x64)
        dt = jnp.float64 if x64 else jnp.float32

        from flinkml_tpu.api import ColumnKernel

        def fn(cols, consts, valid):
            # Resolved at TRACE time: the fused executor's program cache
            # keys on the active PrecisionPolicy, so a bf16 trace and an
            # f32 trace never share an executable. Under a mixed policy
            # the kernel computes at policy.compute with the matmul
            # accumulating at policy.accum (preferred_element_type)
            # instead of re-widening to the captured per-stage dtype.
            from flinkml_tpu import pipeline_fusion

            pol = pipeline_fusion.active_policy()
            # A mixed OR quantized policy declares the compute width
            # (the int8 tier runs f32 dequant-fused math — re-widening
            # to the captured f64 would silently double its bandwidth).
            declared = pol is not None and (pol.mixed or pol.quant)
            kdt = jnp.dtype(pol.compute_dtype) if declared else dt
            adt = jnp.dtype(pol.accum_dtype) if declared else None
            x = cols[fcol]
            if x.ndim == 1:
                x = x.reshape(-1, 1)
            x = x.astype(kdt)
            coef = consts["coefficient"].astype(kdt)
            if multinomial:
                logits = jnp.matmul(x, coef.T, preferred_element_type=adt)
                raw = jax.nn.softmax(logits, axis=-1)
                pred = jnp.argmax(logits, axis=-1).astype(x.dtype)
            else:
                dot = jnp.matmul(x, coef, preferred_element_type=adt)
                p = jax.nn.sigmoid(dot)
                pred = (dot >= 0).astype(x.dtype)
                raw = jnp.stack([1.0 - p, p], axis=-1)
            return {pcol: pred, rcol: raw}

        return ColumnKernel(
            input_cols=(fcol,), output_cols=(pcol, rcol), fn=fn,
            constants={"coefficient": self._coefficient},
            fingerprint=(
                "LogisticRegressionModel", fcol, pcol, rcol, multinomial,
                x64,
            ),
            # dot + sigmoid/softmax lower context-sensitively: the input
            # column must be materialized for per-stage bit parity.
            pin_inputs=True,
        )



def _check_binomial_labels(labels) -> None:
    """``labels``: a label column or its ``LabelFacts``."""
    check_binary_labels(labels, "binomial logistic regression")


def _check_stream_labels(y: np.ndarray) -> None:
    """Streamed fits are binomial-only; >2-class data gets the actual
    limitation in the message, not a confusing binomial-labels error."""
    try:
        _check_binomial_labels(y)
    except ValueError as e:
        raise ValueError(
            f"{e}; multinomial (>2 classes) is not supported for "
            "streamed fits — materialize the data as a Table"
        ) from None


def _resolve_multi_class(multi_class: str, labels: LabelFacts) -> str:
    """'auto' follows the label cardinality (≤2 → binomial), like the
    wider flink-ml family; explicit settings are honored as-is. Labels
    all 0 or 1 say so without a sort."""
    if multi_class != "auto":
        return multi_class
    if labels.binary:
        return "binomial"
    return "multinomial" if labels.distinct().size > 2 else "binomial"


def _check_multinomial_labels(labels: LabelFacts) -> int:
    """Labels must be exactly the integers 0..k-1 (every class present);
    returns k. Guards against phantom classes and against a single
    outlier label silently allocating a huge [maxLabel+1, d] matrix."""
    uniq = labels.distinct()
    if (
        not labels.integral
        or labels.lo < 0
        or uniq.size != int(labels.hi) + 1
    ):
        raise ValueError(
            "multinomial logistic regression requires integer labels "
            f"covering 0..k-1 exactly, got {uniq[:6]}"
            f"{'...' if uniq.size > 6 else ''}"
        )
    return int(labels.hi) + 1


@jax.jit
def _predict(x, coef):
    """prediction = 1[dot >= 0]; raw = [1-p, p]
    (parity: LogisticRegressionModel.predictRaw, :158-170)."""
    dot = x @ coef
    p = jax.nn.sigmoid(dot)
    pred = (dot >= 0).astype(x.dtype)
    raw = jnp.stack([1.0 - p, p], axis=-1)
    return pred, raw


@jax.jit
def _predict_multinomial(x, coef):
    """prediction = argmax class; raw = softmax probabilities [n, k]."""
    logits = x @ coef.T
    raw = jax.nn.softmax(logits, axis=-1)
    pred = jnp.argmax(logits, axis=-1).astype(x.dtype)
    return pred, raw


def _softmax_from_logits(logits: np.ndarray):
    z = logits - logits.max(axis=-1, keepdims=True)
    e = np.exp(z)
    raw = e / e.sum(axis=-1, keepdims=True)
    pred = np.argmax(logits, axis=-1).astype(np.float64)
    return pred, raw


def _shard_training_data(x, y, w, mesh: DeviceMesh):
    """Pad to the mesh and shard; padded rows carry weight 0 so they never
    contribute to any weighted sum."""
    p_size = mesh.axis_size()
    row_tile = p_size
    x_pad, _ = pad_to_multiple(x, row_tile)
    y_pad, _ = pad_to_multiple(y, row_tile)
    w_pad, _ = pad_to_multiple(w, row_tile)
    return mesh.shard_batch(x_pad), mesh.shard_batch(y_pad), mesh.shard_batch(w_pad)


# The shared linear-SGD kernels live in _linear_sgd. Mini-batch selection
# divergence from the reference (intentional, HBM-friendly): the reference
# samples WITH replacement per task (LogisticRegression.java:345-352 —
# random row gathers); random gathers waste HBM bandwidth on TPU, so each
# epoch takes a contiguous rotating window of the host-shuffled shard —
# shuffled SGD with full-bandwidth streaming reads.
def _device_trainer(mesh, local_bs: int, axis: str):
    """Whole-training-run XLA program for logistic loss (cached)."""
    return _linear_sgd._dense_trainer(mesh, "logistic", local_bs, axis)


def train_logistic_regression(
    x: np.ndarray,
    y: np.ndarray,
    w: np.ndarray,
    mesh: DeviceMesh,
    max_iter: int,
    learning_rate: float,
    global_batch_size: int,
    reg: float,
    tol: float,
    seed: int,
    dtype=None,
    mode: str = "device",
    checkpoint_manager=None,
    checkpoint_interval: int = 0,
    resume: bool = False,
    listeners=(),
    sharding_plan=None,
    precision=None,
    kept=None,
) -> np.ndarray:
    """The distributed SGD loop; returns the fitted coefficient on host.

    Two modes:
      - ``device`` (default): the ENTIRE epoch loop — sampling, gradient,
        psum, update, termination test — compiles into one XLA program
        (``lax.while_loop`` inside ``shard_map``). One dispatch per
        staging round that completed a window (one per fit where the
        table is one round), the carry never leaving the device between
        them; zero host round-trips per epoch. This is the design inversion of the
        reference's per-epoch feedback/alignment machinery (SURVEY.md §3.2):
        where Flink crosses task, network, and RPC boundaries every epoch,
        the TPU loop never leaves the chip. With a ``checkpoint_manager`` +
        ``checkpoint_interval`` K, the loop runs in K-epoch dispatches with
        a carry snapshot between dispatches (``_linear_sgd._run_chunked``)
        — the fast path is fault-tolerant, and resume is bit-exact because
        chunked and unchunked runs share one compiled executable.
        ``listeners`` fire at chunk boundaries.
      - ``host``: one jitted step per epoch driven by
        ``flinkml_tpu.iteration.iterate`` — per-epoch listener callbacks
        and checkpointing at epoch granularity, at the cost of one dispatch
        per epoch. Termination always honors ``max_iter``/``tol``.

    ``kept`` (``_linear_sgd.table_placements``) is the device mode's:
    where the fit keeps its placement with its table.
    """
    if mode not in ("device", "host"):
        raise ValueError(f"mode must be 'device' or 'host', got {mode!r}")
    if sharding_plan is not None and mode == "host":
        raise ValueError(
            "sharding_plan is supported in mode='device' only (the host "
            "iterate loop replicates its carry)"
        )
    if precision is not None and mode == "host":
        raise ValueError(
            "precision is supported in mode='device' only (the "
            "policy-gated step lives on the plan-sharded path)"
        )
    if mode == "host" and checkpoint_manager is not None:
        # The rescale guard must compare against THIS trainer's mesh, not
        # the process-global device count (they differ on subset meshes).
        # Re-pinned on every run so a manager reused across meshes never
        # carries a stale size (CheckpointManager documents this contract).
        # (Device mode pins it inside _run_chunked.)
        checkpoint_manager.world_size = mesh.mesh.size

    if mode == "device":
        return _linear_sgd.train_linear_model(
            x, y, w, loss="logistic", mesh=mesh, max_iter=max_iter,
            learning_rate=learning_rate, global_batch_size=global_batch_size,
            reg=reg, elastic_net=0.0, tol=tol, seed=seed, dtype=dtype,
            checkpoint_manager=checkpoint_manager,
            checkpoint_interval=checkpoint_interval,
            resume=resume, listeners=listeners,
            sharding_plan=sharding_plan, precision=precision, kept=kept,
        )

    # host mode: per-epoch dispatch with listener/checkpoint support.
    n, dim = x.shape
    p_size = mesh.axis_size()
    if dtype is not None:
        x, y, w = x.astype(dtype), y.astype(dtype), w.astype(dtype)
    # Host-side seeded shuffle; epochs then stream contiguous windows.
    perm = np.random.default_rng(seed).permutation(n)
    x, y, w = x[perm], y[perm], w[perm]
    xd, yd, wd = _shard_training_data(x, y, w, mesh)
    n_local = xd.shape[0] // p_size

    # Reference: localBatchSize = globalBatchSize / numTasks (+1 for low
    # task ids on remainder, LogisticRegression.java:336-341). Here every
    # device takes the ceiling, tile-aligned and clamped to its shard.
    local_bs = _linear_sgd.align_local_bs(global_batch_size, p_size, n_local)
    axis = DeviceMesh.DATA_AXIS
    dt = xd.dtype

    local_step = _linear_sgd.make_dense_step("logistic", local_bs, axis)
    sharded_step = jax.shard_map(
        local_step,
        mesh=mesh.mesh,
        in_specs=(P(), P(), P(axis), P(axis), P(axis), P(), P(), P()),
        out_specs=(P(), P()),
    )

    @jax.jit
    def epoch_step(state, epoch):
        coef = state
        new_coef, mean_loss = sharded_step(
            coef, jnp.asarray(epoch, jnp.int32), xd, yd, wd,
            jnp.asarray(learning_rate, dt), jnp.asarray(reg, dt),
            jnp.asarray(0.0, dt),
        )
        return new_coef, mean_loss

    config = IterationConfig(
        TerminateOnMaxIterOrTol(max_iter, tol),
        checkpoint_interval=checkpoint_interval,
        checkpoint_manager=checkpoint_manager,
    )
    init = jnp.zeros(dim, dtype=xd.dtype)
    result = iterate(
        epoch_step, init, config=config, listeners=listeners, resume=resume
    )
    return np.asarray(result.state)
