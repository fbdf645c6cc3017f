"""Factorization machines: FMClassifier (logistic) and FMRegressor
(squared loss).

Second-order FMs (Rendle): ``ŷ(x) = w₀ + w·x + ½ Σ_f [(x·V_f)² −
(x² · V_f²)]`` — the pairwise-interaction term computed with the
O(n·d·k) "sum-of-squares" identity, which on TPU is two batched MXU
matmuls (``x @ V`` and ``x² @ V²``); no explicit feature-pair loop
exists. Training rides the shared whole-run Adam device trainer
(``_adam.make_adam_trainer``): one program, psum'd minibatch steps over
the data-sharded mesh. L2 regularization applies to w and V (not the
intercept), scaled per-minibatch like the loss.

A features column that is a ``table.CsrColumn`` (hashed click logs at
``dim`` 1e6, which no ``[rows, dim]`` matrix can hold) takes the sparse
fit of ``models/_fm_sparse.py`` instead: the same equations over cells,
the same Adam, batches that are windows of a seeded row order.
"""

from __future__ import annotations

import functools
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
    HasPredictionCol,
    HasRawPredictionCol,
    HasReg,
    HasSeed,
    HasTol,
    HasWeightCol,
)
from flinkml_tpu.models._adam import make_adam_trainer
from flinkml_tpu.models._fm_sparse import LOOKUP_PRECISION  # noqa: F401 — the sparse fit's, public here
from flinkml_tpu.models._data import (
    check_binary_labels,
    features_matrix,
    labeled_data,
)
from flinkml_tpu.params import IntParam, ParamValidators
from flinkml_tpu.parallel import DeviceMesh, pad_to_multiple
from flinkml_tpu.table import Table
from flinkml_tpu.utils.metrics import metrics
from flinkml_tpu.utils.profiling import span


class _FMParams(
    HasFeaturesCol, HasLabelCol, HasPredictionCol, HasRawPredictionCol,
    HasWeightCol, HasMaxIter, HasLearningRate, HasGlobalBatchSize, HasReg,
    HasTol, HasSeed,
):
    FACTOR_SIZE = IntParam(
        "factorSize", "Dimensionality of the interaction factors.", 8,
        ParamValidators.gt(0),
    )


def _fm_margin(params, xb):
    """params = (w0 [1], w [d], v [d, k]); returns [n] margins."""
    w0, w, v = params
    linear = xb @ w
    xv = xb @ v                       # [n, k] on the MXU
    x2v2 = (xb * xb) @ (v * v)        # [n, k]
    pair = 0.5 * jnp.sum(xv * xv - x2v2, axis=1)
    return w0[0] + linear + pair


def _fm_logistic_loss_builder():
    def local_loss(params, xb, yb, wb):
        margin = _fm_margin(params[:3], xb)
        # params[3] is a [1] array holding the L2 strength (a constant
        # carried through the tuple so the builder stays argument-free).
        nll = jnp.logaddexp(0.0, margin) - yb * margin
        w0, w, v = params[:3]
        reg = params[3][0] * (jnp.sum(w * w) + jnp.sum(v * v))
        return jnp.sum(nll * wb) + reg * jnp.sum(wb)

    return local_loss


def _fm_squared_loss_builder():
    def local_loss(params, xb, yb, wb):
        err = _fm_margin(params[:3], xb) - yb
        w0, w, v = params[:3]
        reg = params[3][0] * (jnp.sum(w * w) + jnp.sum(v * v))
        return 0.5 * jnp.sum(err * err * wb) + reg * jnp.sum(wb)

    return local_loss


# -- the embedding-sharded factor path ---------------------------------------
#
# FM's factor matrix V [d, k] IS an embedding table over the feature
# space — the first wall recsys-scale FM hits (100M hashed features x
# k factors x 3 Adam-state copies). The sharded fit stores V, w, and
# their Adam m/v slots row-sharded per an EMBEDDING-family ShardingPlan
# (rows whole, dim intact — "optimizer state shards like its table"),
# with the feature COLUMNS of x sharded to match, so both FM matmuls
# (x·V and x²·V²) contract locally and one batch-sized psum of the
# [bs, k] partials completes the margins. The sparse lookup/exchange
# primitive does NOT apply here — FM features are dense vectors, not
# ids — and the fit refuses plans that split factor rows loudly; what
# the subsystem contributes is the layout, validation, and checkpoint
# family.

#: Parameter names of the sharded-FM state — the ``*embedding*``
#: suffixes land V and w (and, via the shared family rule, their Adam
#: slots) in the plan's EMBEDDING family.
_FM_V_PARAM = "fm/v_embedding"
_FM_W_PARAM = "fm/w_embedding"


@functools.lru_cache(maxsize=16)
def _fm_sharded_trainer(mesh, row_entry, n_shards: int, emu_bs: int,
                        logistic: bool):
    """Whole-run Adam trainer with V/w (+ their m/v slots) row-sharded
    over ``row_entry``'s axes and x column-sharded to match.

    Reproduces the dense :func:`~flinkml_tpu.models._adam.
    make_adam_trainer` SAMPLING trajectory for a data world of
    ``n_shards``: the same per-step ``fold_in`` key draws the same
    ``emu_bs`` local row positions, applied to each of the ``n_shards``
    contiguous row blocks (exactly the rows the dense trainer's devices
    would sample from their shards). Per-step margins and gradients
    agree with the dense trainer up to f32 summation order (pinned
    against autodiff in ``tests/test_embeddings.py``); per-COORDINATE
    parameter parity over many steps is deliberately NOT pinned — Adam's
    first-order update is ``±lr·sign(ĝ)``, which amplifies summation-
    order noise on near-zero gradients into full ``lr``-sized jumps, so
    the end-model pin is quality parity (loss/accuracy/prediction
    agreement), the same contract the convergence-parity suite uses.
    Gradients are the closed-form FM gradients (the scaffold's
    no-collectives-inside-grad discipline, by construction)."""
    from flinkml_tpu.sharding.plan import entry_axes

    axes = entry_axes(row_entry)
    axes_arg = axes if len(axes) > 1 else axes[0]

    def local(x, y, wt, w0, w_sh, v_sh, reg, lr, max_iter, tol, key):
        n_rows = x.shape[0]
        n_block = n_rows // n_shards

        def mb_step(params, m, v, step):
            w0_, w_, v_ = params
            k = jax.random.fold_in(key, step)
            idx = jax.random.randint(k, (emu_bs,), 0, n_block)
            gidx = (
                idx[None, :] + (jnp.arange(n_shards) * n_block)[:, None]
            ).reshape(-1)                       # the dense global batch
            xb = x[gidx]                        # [B, cols_local]
            yb, wb = y[gidx], wt[gidx]
            xv = jax.lax.psum(xb @ v_, axes_arg)              # [B, k]
            x2v2 = jax.lax.psum((xb * xb) @ (v_ * v_), axes_arg)
            lin = jax.lax.psum(xb @ w_, axes_arg)             # [B]
            margin = w0_[0] + lin + 0.5 * jnp.sum(xv * xv - x2v2, axis=1)
            if logistic:
                nll = jnp.logaddexp(0.0, margin) - yb * margin
                g = (jax.nn.sigmoid(margin) - yb) * wb
            else:
                err = margin - yb
                nll = 0.5 * err * err
                g = err * wb
            total_w = jnp.maximum(jnp.sum(wb), 1e-12)
            sq = jax.lax.psum(jnp.sum(w_ * w_) + jnp.sum(v_ * v_),
                              axes_arg)
            loss = (jnp.sum(nll * wb)
                    + reg[0] * sq * jnp.sum(wb)) / total_w
            # Closed-form FM gradients (all local once the [B, k]
            # forward partials are psum'd).
            gw0 = jnp.sum(g)[None] / total_w
            gw = (xb.T @ g + 2.0 * reg[0] * w_ * jnp.sum(wb)) / total_w
            gv = (xb.T @ (g[:, None] * xv)
                  - ((xb * xb).T @ g)[:, None] * v_
                  + 2.0 * reg[0] * v_ * jnp.sum(wb)) / total_w
            grads = (gw0, gw, gv)
            t = (step + 1).astype(jnp.float32)
            b1, b2, eps = 0.9, 0.999, 1e-8
            m = jax.tree.map(lambda a, gg: b1 * a + (1 - b1) * gg,
                             m, grads)
            v2 = jax.tree.map(lambda a, gg: b2 * a + (1 - b2) * gg * gg,
                              v, grads)
            params = jax.tree.map(
                lambda pp, mm, vv: pp - lr * (mm / (1 - b1 ** t))
                / (jnp.sqrt(vv / (1 - b2 ** t)) + eps),
                params, m, v2,
            )
            return params, m, v2, loss

        params0 = (w0, w_sh, v_sh)
        m0 = jax.tree.map(jnp.zeros_like, params0)
        v0 = jax.tree.map(jnp.zeros_like, params0)

        def cond(state):
            step, _, _, _, prev, cur = state
            return (step < max_iter) & (jnp.abs(prev - cur) > tol)

        def body(state):
            step, params, m, v, _, last = state
            params, m, v, loss = mb_step(params, m, v, step)
            return step + 1, params, m, v, last, loss

        inf = jnp.asarray(jnp.inf, jnp.float32)
        state = (jnp.asarray(0, jnp.int32), params0, m0, v0, inf, -inf)
        step, params, m, v, _, loss = jax.lax.while_loop(cond, body, state)
        return params, step, loss

    col_sh = P(None, row_entry)
    param_specs = (P(), P(row_entry), P(row_entry, None))
    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(col_sh, P(), P(), P(), P(row_entry), P(row_entry, None),
                  P(), P(), P(), P(), P()),
        out_specs=(param_specs, P(), P()),
    ))


def start_factors(dim: int, factor_size: int, seed: int) -> jax.Array:
    """The factors every fit starts from: ``0.01 * N(0, 1)`` ``[dim,
    factor_size]`` float32 from the seed (``w0`` and ``w`` start at 0)."""
    return jax.random.normal(
        jax.random.PRNGKey(seed), (dim, factor_size), jnp.float32) * 0.01


class _FMBase(StreamingEstimatorMixin, _FMParams, Estimator):
    """``fit`` of a :class:`Table` whose features column is a
    ``table.CsrColumn`` is the sparse fit (``models/_fm_sparse.py``): the
    cells stay on the mesh WITH the table, batches are windows of a
    seeded row order, the whole run is the program ``fm_adam_loop``. A
    dense or object column takes the dense fit below, whose batches are
    ``_adam``'s draws with replacement.

    ``fit`` also accepts an iterable of batch Tables or a sealed
    :class:`~flinkml_tpu.iteration.datacache.DataCache` — the
    out-of-core path (the shared streamed-Adam runner,
    :func:`flinkml_tpu.models._adam.run_streamed_adam`; reference replay
    parity ``ReplayOperator.java:62-250``). ``checkpoint_manager`` +
    ``checkpoint_interval`` snapshot the full Adam state every N epochs;
    ``resume=True`` (durable DataCache input required) continues
    bit-exactly."""

    _LOGISTIC = True

    #: The FM trainers thread an EMBEDDING-family ShardingPlan through
    #: the factor matrix (see the sharded-factor section above).
    _SHARDING_PLAN_AWARE = True

    def _loss_builder(self):
        return (
            _fm_logistic_loss_builder if self._LOGISTIC
            else _fm_squared_loss_builder
        )

    def _params0(self, d: int):
        """Initial flat params tuple (bias, w, V, frozen reg tail) — the
        single source for the in-RAM, streamed and sparse paths."""
        return (
            jnp.zeros(1, jnp.float32),
            jnp.zeros(d, jnp.float32),
            start_factors(d, self.get(self.FACTOR_SIZE), self.get_seed()),
            jnp.asarray([self.get(self.REG)], jnp.float32),
        )

    def _make_model(self, params):
        """The model of a fit's ``(w0 [1], w, V)``: it holds the float32
        arrays the fit read back as they are (no float64 copy: a float32
        widened is the same number, and every margin widens what it
        gathers)."""
        model = (FMClassifierModel if self._LOGISTIC else FMRegressorModel)()
        model.copy_params_from(self)
        model._set(np.asarray(params[0])[0], params[1], params[2])
        return model

    def _fit_stream(self, source):
        """Out-of-core FM via the shared streamed-Adam runner; the reg
        strength rides as the frozen params-tuple tail, exactly as in
        the in-RAM path."""
        from flinkml_tpu.models._adam import run_streamed_adam

        if self.sharding_plan is not None:
            # Loud refusal (the embedding subsystem's contract): the
            # streamed runner replays cache chunks through the shared
            # replicated-params Adam trainer — silently dropping the
            # plan would replicate the factor matrix, exactly the OOM
            # the plan was configured to avoid.
            raise ValueError(
                f"{type(self).__name__} streamed fit does not thread a "
                "sharding_plan yet: the cache-replay trainer keeps "
                "factors replicated. Use the in-RAM fit (which shards "
                "V/w + Adam slots per the plan's embedding family), or "
                "drop the plan."
            )

        features_col = self.get(self.FEATURES_COL)
        label_col = self.get(self.LABEL_COL)
        weight_col = self.get(self.WEIGHT_COL)
        mesh = self.mesh or DeviceMesh()

        def prepare_y(y):
            y = np.asarray(y, np.float32)
            if self._LOGISTIC:
                check_binary_labels(y, type(self).__name__)
            return y

        def ingest(t):
            x, y, w = labeled_data(t, features_col, label_col, weight_col)
            return {
                "x": x.astype(np.float32),
                "y": prepare_y(y),
                "w": w.astype(np.float32),
            }

        params = run_streamed_adam(
            source,
            what="FM streamed fit",
            mesh=mesh,
            cache_dir=self.cache_dir,
            cache_memory_budget_bytes=self.cache_memory_budget_bytes,
            ingest=ingest,
            place_y=prepare_y,
            loss_builder=self._loss_builder(),
            n_params=4,
            params0_fn=self._params0,
            lr=self.get(self.LEARNING_RATE),
            global_bs=self.get(self.GLOBAL_BATCH_SIZE),
            max_iter=self.get(self.MAX_ITER),
            tol=self.get(self.TOL),
            seed=self.get_seed(),
            frozen_tail=1,
            **self._checkpoint_kwargs(),
        )
        return self._make_model(params)

    def _fit_sharded(self, x, y, w):
        """The embedding-sharded factor fit (see the module section):
        V/w + Adam slots row-sharded per ``self.sharding_plan``, x
        column-sharded to match; refuses loudly where the layout cannot
        host the trainer."""
        from flinkml_tpu.parallel import DeviceMesh
        from flinkml_tpu.sharding.apply import validate_plan
        from flinkml_tpu.sharding.plan import entry_axes

        plan = self.sharding_plan
        spec = plan.spec_for(_FM_V_PARAM, ndim=2)
        row_entry = spec[0] if spec else None
        if any(entry_axes(e) for e in spec[1:]):
            raise ValueError(
                f"plan {plan.name!r} shards the FM factor matrix's "
                "factor dim (dim 1): the sharded trainer keeps factor "
                "rows whole (the embedding-family layout). Use the "
                "EMBEDDING or FSDP preset."
            )
        if not entry_axes(row_entry):
            raise ValueError(
                f"plan {plan.name!r} leaves the FM factor family "
                f"({_FM_V_PARAM!r}) replicated — pass a plan whose "
                "embedding family shards rows (EMBEDDING/FSDP), or drop "
                "sharding_plan to train replicated."
            )
        mesh = self.mesh or DeviceMesh.for_plan(plan)
        sizes = dict(mesh.mesh.shape)
        n_shards = 1
        for axis in entry_axes(row_entry):
            n_shards *= int(sizes.get(axis, 1))
        d = x.shape[1]
        k = self.get(self.FACTOR_SIZE)
        d_pad = -(-d // n_shards) * n_shards
        validate_plan(
            plan, mesh,
            param_shapes={_FM_V_PARAM: (d_pad, k), _FM_W_PARAM: (d_pad,)},
            optimizer_slots=2,  # Adam m/v shard like their table
        )
        n_pad = -(-x.shape[0] // n_shards) * n_shards
        xp = np.zeros((n_pad, d_pad), np.float32)
        xp[: x.shape[0], :d] = x
        yp = np.zeros(n_pad, np.float32)
        yp[: x.shape[0]] = y
        wp = np.zeros(n_pad, np.float32)
        wp[: x.shape[0]] = w[: x.shape[0]]
        w0_0, _, v0, reg = self._params0(d)
        v0p = np.zeros((d_pad, k), np.float32)
        v0p[:d] = np.asarray(v0)
        emu_bs = max(1, self.get(self.GLOBAL_BATCH_SIZE) // n_shards)
        trainer = _fm_sharded_trainer(
            mesh.mesh, row_entry, n_shards, emu_bs, self._LOGISTIC
        )
        f32 = lambda val: jnp.asarray(val, jnp.float32)
        (w0, w_sh, v_sh), steps, loss = trainer(
            xp, yp, wp, np.asarray(w0_0), np.zeros(d_pad, np.float32),
            v0p, np.asarray(reg),
            f32(self.get(self.LEARNING_RATE)),
            jnp.asarray(self.get(self.MAX_ITER), jnp.int32),
            f32(self.get(self.TOL)),
            jax.random.fold_in(jax.random.PRNGKey(self.get_seed()), 321),
        )
        return self._make_model((
            np.asarray(w0), np.asarray(w_sh)[:d], np.asarray(v_sh)[:d],
        ))

    def _fit_csr(self, table: Table):
        """A ``CsrColumn`` of features: the sparse fit
        (``models/_fm_sparse.py``), which never builds ``[rows, dim]``."""
        from flinkml_tpu.models import _fm_sparse

        if self.sharding_plan is not None:
            raise ValueError(
                f"{type(self).__name__} fits a CsrColumn with its parameter "
                "table replicated; the sharding_plan fit shards a DENSE "
                "features matrix's columns. Drop the plan."
            )
        with span("fit"):
            params = _fm_sparse.fit_csr(self, table, self._LOGISTIC)
            model = self._make_model(params)
            # Read off the arrays, not stated: 1.0 a fit whose model holds
            # the read-back's own float32 buffers.
            metrics.group("fm").counter("handover_view_fits", float(all(
                held.dtype == np.float32 and held.flags.c_contiguous
                and np.shares_memory(held, read)
                for held, read in ((model._w, params[1]), (model._v, params[2])))))
            return model

    def fit(self, *inputs):
        (table,) = inputs
        if not isinstance(table, Table):
            return self._fit_stream(table)
        self._reject_in_ram_checkpointing()
        if table.csr_column(self.get(self.FEATURES_COL)) is not None:
            return self._fit_csr(table)
        x, y, w = labeled_data(
            table, self.get(self.FEATURES_COL), self.get(self.LABEL_COL),
            self.get(self.WEIGHT_COL),
        )
        if self._LOGISTIC:
            check_binary_labels(y, type(self).__name__)
        if self.sharding_plan is not None:
            return self._fit_sharded(x, y, w)
        d = x.shape[1]
        mesh = self.mesh or DeviceMesh()
        p = mesh.axis_size()
        x_pad, n_valid = pad_to_multiple(x.astype(np.float32), p)
        y_pad, _ = pad_to_multiple(y.astype(np.float32), p)
        w_pad = np.zeros(x_pad.shape[0], np.float32)
        w_pad[:n_valid] = w[:n_valid].astype(np.float32)
        local_bs = max(1, self.get(self.GLOBAL_BATCH_SIZE) // p)
        trainer = make_adam_trainer(
            mesh.mesh, DeviceMesh.DATA_AXIS, local_bs, self._loss_builder(),
            4, frozen_tail=1,
        )
        f32 = lambda val: jnp.asarray(val, jnp.float32)
        params, steps, loss = trainer(
            mesh.shard_batch(x_pad), mesh.shard_batch(y_pad),
            mesh.shard_batch(w_pad), self._params0(d),
            f32(self.get(self.LEARNING_RATE)),
            jnp.asarray(self.get(self.MAX_ITER), jnp.int32),
            f32(self.get(self.TOL)),
            jax.random.fold_in(jax.random.PRNGKey(self.get_seed()), 321),
        )
        return self._make_model(params)


def _floating(a) -> np.ndarray:
    """``a`` as the model holds it: a floating array as it is, anything
    else as float64."""
    a = np.asarray(a)
    return a if a.dtype.kind == "f" else a.astype(np.float64)


class _FMModelBase(_FMParams, Model):
    """``w0``, ``w [dim]`` and ``V [dim, k]`` in whatever floating dtype
    they were handed: a fit's are the float32 arrays the device returned
    (``get_model_data`` hands out views of them), ``set_model_data`` and
    ``load`` keep the table's or the file's (a model saved as float64
    loads as float64). Every margin of ``transform`` is float64
    arithmetic whatever they are, the parameters widened as they are
    gathered: a float32 model scores as its float64 copy does, to the
    bit."""

    def __init__(self):
        super().__init__()
        self._w0: Optional[float] = None
        self._w: Optional[np.ndarray] = None
        self._v: Optional[np.ndarray] = None

    def _set(self, w0, w, v):
        self._w0, self._w, self._v = float(w0), _floating(w), _floating(v)

    def set_model_data(self, *inputs: Table):
        (table,) = inputs
        self._set(
            np.asarray(table.column("w0"))[0],
            np.asarray(table.column("w"))[0],
            np.asarray(table.column("v"))[0],
        )
        return self

    def get_model_data(self) -> List[Table]:
        self._require()
        return [Table({
            "w0": np.asarray([self._w0]),
            "w": self._w[None, :],
            "v": self._v[None, :, :],
        })]

    def _require(self) -> None:
        if self._w is None:
            raise ValueError("Model data is not set; fit or set_model_data first")

    def _margin(self, table: Table) -> np.ndarray:
        from flinkml_tpu.models._data import sparse_features

        csr = table.csr_column(self.get(self.FEATURES_COL))
        if csr is not None and len(csr):
            from flinkml_tpu.models._fm_sparse import csr_margin

            # From the column's arrays: no row object is built.
            return csr_margin(csr, self._w0, self._w, self._v)
        if sparse_features(table, self.get(self.FEATURES_COL)) is not None:
            return self._margin_sparse(table.column(self.get(self.FEATURES_COL)))
        # float64 rows: the products promote the parameters, and the
        # squares are float64's own (a float32 square rounds).
        x = features_matrix(table, self.get(self.FEATURES_COL))
        xv = x @ self._v
        x2v2 = (x * x) @ np.multiply(self._v, self._v, dtype=np.float64)
        return self._w0 + x @ self._w + 0.5 * (xv * xv - x2v2).sum(axis=1)

    def _margin_sparse(self, vecs) -> np.ndarray:
        """O(nnz·k) sparse margin over a padded-ELL block — the FM
        identity only ever touches the nonzero columns, so an all-
        SparseVector column never densifies to ``[n, dim]`` (ruinous at
        hashed-feature dims). Linear term is the plain ELL matvec;
        the pairwise term gathers factor rows (``v[indices]`` is
        O(nnz·k)) and contracts with two einsums. ELL padding (index 0
        / value 0) is exact: value 0 zeroes both the gather product and
        the squared term. Runs under x64 and widens the gathered
        parameters (never the table), so the margin is float64
        arithmetic, matching the dense path."""
        import jax

        from flinkml_tpu.ops.sparse import BatchedCSR, ell_matvec

        ib, vb, d = BatchedCSR.pack_sparse_vectors(vecs, dtype=np.float64)
        if d != self._w.shape[0]:
            raise ValueError(
                f"sparse features have dim {d}, model expects "
                f"{self._w.shape[0]}"
            )
        if vb.shape[1] == 0:  # all-empty rows: margin is the intercept
            return np.full(vb.shape[0], self._w0)
        with jax.enable_x64(True):
            linear = np.asarray(ell_matvec(ib, vb, self._w))
        gathered = self._v[ib].astype(np.float64, copy=False)   # [n, s, k]
        xv = np.einsum("ns,nsk->nk", vb, gathered)
        x2v2 = np.einsum("ns,nsk->nk", vb * vb, gathered * gathered)
        return self._w0 + linear + 0.5 * (xv * xv - x2v2).sum(axis=1)

    def save(self, path: str) -> None:
        self._require()
        self._save_with_arrays(path, {
            "w0": np.asarray(self._w0), "w": self._w, "v": self._v,
        })

    @classmethod
    def load(cls, path: str):
        model, arrays, _ = cls._load_with_arrays(path)
        model._set(float(arrays["w0"]), arrays["w"], arrays["v"])
        return model


class FMClassifier(_FMBase):
    """Binary factorization-machine classifier (logistic loss)."""

    _LOGISTIC = True


class FMClassifierModel(_FMModelBase):
    def transform(self, *inputs: Table) -> Tuple[Table, ...]:
        (table,) = inputs
        self._require()
        margin = self._margin(table)
        prob = 1.0 / (1.0 + np.exp(-margin))
        out = table.with_column(
            self.get(self.PREDICTION_COL), (margin >= 0).astype(np.float64)
        )
        out = out.with_column(
            self.get(self.RAW_PREDICTION_COL),
            np.stack([1.0 - prob, prob], axis=1),
        )
        return (out,)


class FMRegressor(_FMBase):
    """Factorization-machine regressor (squared loss)."""

    _LOGISTIC = False


class FMRegressorModel(_FMModelBase):
    def transform(self, *inputs: Table) -> Tuple[Table, ...]:
        (table,) = inputs
        self._require()
        return (
            table.with_column(
                self.get(self.PREDICTION_COL), self._margin(table)
            ),
        )
