"""Gradient-boosted trees (histogram-based): GBTClassifier, GBTRegressor.

A major model family beyond the reference snapshot, designed TPU-first
rather than translated from CPU tree libraries:

  - **Quantile binning** (host, once a ``Table``): each feature → bin ids
    in ``[0, maxBins)`` via per-feature quantile edges — the LightGBM/
    HistGradientBoostingClassifier layout. On a dense column
    (:mod:`~flinkml_tpu.models._gbt_table`) the edges are taken over a
    seeded sample of rows (:func:`bin_edges`), the bins are one byte a
    cell, features-major, and the binned table stays on the chip with
    its ``Table``. Raw thresholds are recovered from the edges so
    inference needs no binning.
  - **Level-wise growth with static shapes**: every tree is a complete
    binary tree of depth ``maxDepth`` (heap layout). Each level computes
    ALL (node, feature, bin) gradient/hessian histograms at once — on a
    dense column as one-hot products over tiles of rows
    (:mod:`~flinkml_tpu.kernels.gbt_hist` on a TPU); for hashed sparse
    input (hundreds to thousands of bundled features, made on the host a
    fit) as ONE ``segment_sum`` over ``n·d`` keys
    (:func:`_forest_builder`, with the ``FLINKML_TPU_GBT_HISTOGRAM``
    gate) — cumulative-sums over bins, and
    picks every node's best split with one argmax — no per-node
    recursion, no data-dependent shapes, XLA-friendly end to end.
  - **Whole-boosting-run on device**: trees are built inside a single
    ``lax.scan`` (predictions are the carry; per-tree parameters are the
    stacked outputs), sharded over the data axis with ``psum``-combined
    histograms — every device decides identical splits, SPMD-style.
  - Second-order (XGBoost) gains: ``gain = GL²/(HL+λ) + GR²/(HR+λ) −
    G²/(H+λ)``; leaf value ``−G/(H+λ)``; logistic loss for the
    classifier (base score = train log-odds), squared loss for the
    regressor (base = weighted mean). Per-tree row subsampling.
"""

from __future__ import annotations

import functools
import os
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from flinkml_tpu.api import Estimator, Model
from flinkml_tpu.models._streaming import StreamingEstimatorMixin
from flinkml_tpu.common_params import (
    HasFeaturesCol,
    HasLabelCol,
    HasLearningRate,
    HasPredictionCol,
    HasRawPredictionCol,
    HasSeed,
    HasWeightCol,
)
from flinkml_tpu.models._data import (
    check_binary_labels,
    hashed_feature_matrix,
    labeled_data,
    sparse_features,
)
from flinkml_tpu.models._gbt_table import (  # noqa: F401
    best_splits,
    bin_edges,
    bin_features,
    quantile_bin_edges,
)
from flinkml_tpu.params import FloatParam, IntParam, ParamValidators
from flinkml_tpu.parallel import DeviceMesh, pad_to_multiple
from flinkml_tpu.table import Table
from flinkml_tpu.utils.profiling import span


class _GBTParams(
    HasFeaturesCol, HasLabelCol, HasPredictionCol, HasWeightCol,
    HasLearningRate, HasSeed,
):
    NUM_TREES = IntParam(
        "numTrees", "Number of boosting rounds.", 50, ParamValidators.gt(0)
    )
    MAX_DEPTH = IntParam(
        "maxDepth", "Depth of every (complete) tree.", 5,
        ParamValidators.in_range(1, 12),
    )
    MAX_BINS = IntParam(
        "maxBins", "Histogram bins per feature.", 64,
        ParamValidators.in_range(2, 256),
    )
    REG_LAMBDA = FloatParam(
        "regLambda", "L2 regularization on leaf values.", 1.0,
        ParamValidators.gt_eq(0.0),
    )
    SUBSAMPLE = FloatParam(
        "subsample", "Per-tree row sampling fraction.", 1.0,
        ParamValidators.in_range(0.0, 1.0, lower_inclusive=False),
    )
    VALIDATION_FRACTION = FloatParam(
        "validationFraction",
        "Held-out fraction for early stopping: the forest is truncated "
        "to the prefix with the best holdout loss (0 = off; boosted "
        "estimators only).",
        0.0, ParamValidators.in_range(0.0, 0.9),
    )
    NUM_HASH_FEATURES = IntParam(
        "numHashFeatures",
        "Bundle width for SparseVector feature columns: sparse inputs "
        "(one-hot / hashed text) are hash-bundled into this many dense "
        "features before binning, so trees train in O(n x numHashFeatures) "
        "memory regardless of the sparse dimensionality. Dense inputs "
        "ignore it.",
        256, ParamValidators.in_range(2, 1 << 16),
    )


# -- device forest builder (hashed sparse input's) ----------------------------------------------------


def _hist_layout() -> str:
    """Measured-default gate for the per-level histogram reduction.

    ``segment`` (default): one ``segment_sum`` over ``n·d`` cells per
    level — XLA's sort-based lowering re-sorts every cell at every level
    of every tree (bound in BASELINE.md "Roofline"; its share is not
    measured on the chip — the same sort class as sparse LR). ``cumsum``: the
    (feature, bin) half of the key is STATIC per fit, so cells are
    sorted once at pack time (:func:`gbt_hist_tables`) and each level
    reduces ``2^level``-wide one-hot-expanded (grad, hess) columns with
    :func:`~flinkml_tpu.ops.sparse.chunked_run_totals` — streaming
    passes, no per-level sort. ``FLINKML_TPU_GBT_HISTOGRAM`` selects;
    the device A/B (``tools/gbt_hist_probe.py``) decides the default."""
    layout = os.environ.get("FLINKML_TPU_GBT_HISTOGRAM")
    if layout is None:
        # Measured default for this mesh (autotune tuning table), else
        # the historical "segment".
        from flinkml_tpu.autotune import tuned_default

        return tuned_default("gbt_histogram", "segment",
                             allowed=("segment", "cumsum"))
    if layout not in ("segment", "cumsum"):
        raise ValueError(
            f"FLINKML_TPU_GBT_HISTOGRAM={layout!r}: expected "
            "'segment' or 'cumsum'"
        )
    return layout


def gbt_hist_tables(b_pad: np.ndarray, p_size: int, n_bins: int):
    """Pack-time tables for the ``cumsum`` histogram layout.

    Per device shard of the padded binned matrix ``[n, d]``: flatten the
    ``n_local·d`` cells row-major, sort ONCE by the static key
    ``feat·n_bins + bin``, and record

    - ``srow [p·cells] int32`` — row-in-shard of each sorted cell (the
      level body gathers grad/hess/node through it);
    - ``ends [p·max_runs] int32`` — inclusive end of each (feat, bin)
      run, padded by repeating the last end (differences to exactly 0);
    - ``cols [p·max_runs] int32`` — the run's static key, ascending.
    """
    from flinkml_tpu.ops.sparse import run_boundary_tables

    n, d = b_pad.shape
    n_local = n // p_size
    cells = n_local * d
    srow = np.empty((p_size, cells), np.int32)
    skeys = np.empty((p_size, cells), np.int64)
    for dev in range(p_size):
        shard = b_pad[dev * n_local:(dev + 1) * n_local]
        key = (np.arange(d, dtype=np.int64)[None, :] * n_bins
               + shard).reshape(-1)
        order = np.argsort(key, kind="stable")
        srow[dev] = (order // d).astype(np.int32)
        skeys[dev] = key[order]
    ends, cols = run_boundary_tables(skeys)
    return srow.reshape(-1), ends.reshape(-1), cols.reshape(-1)


def sharded_hist_args(b_pad: np.ndarray, mesh, n_bins: int,
                      hist_layout: str) -> tuple:
    """The extra sharded builder args for ``hist_layout`` — ONE
    definition shared by the product fit path and
    ``tools/gbt_hist_probe.py``, so every consumer passes the builder
    the identical table layout. Empty for ``segment``."""
    if hist_layout != "cumsum":
        return ()
    srow, ends, cols = gbt_hist_tables(b_pad, mesh.axis_size(), n_bins)
    return (
        mesh.shard_batch(srow), mesh.shard_batch(ends),
        mesh.shard_batch(cols),
    )


@functools.lru_cache(maxsize=16)
def _forest_builder(mesh, axis: str, n_feat: int, n_bins: int, depth: int,
                    num_trees: int, logistic: bool, boosting: bool = True,
                    feat_subset: int = 0, hist_layout: str = "segment"):
    """One compiled program that builds the whole forest.

    Static config in the cache key; runtime inputs are the sharded
    binned matrix / labels / weights and scalar hyperparams.

    ``boosting=False`` turns the scan into BAGGING (random forest):
    every tree fits the same base-score residual independently (the
    prediction carry is not updated), row weights become Poisson
    bootstrap multiplicities (diversity even at subsample=1.0), and
    ``feat_subset > 0`` draws exactly that many features per tree (a
    permutation prefix — never empty), masking the rest's gains to -inf
    so an excluded feature can never win the argmax even when every
    in-subset gain is negative.
    """
    n_leaves = 1 << depth
    n_inner = n_leaves - 1          # heap: level L starts at 2^L - 1
    seg = n_leaves * n_feat * n_bins  # uniform segment space per level

    def grad_hess(pred, y, w):
        if logistic:
            p = jax.nn.sigmoid(pred)
            return (p - y) * w, jnp.maximum(p * (1 - p), 1e-6) * w
        return (pred - y) * w, w

    def local(binned, y, w, base, lr, lam, subsample, key, *hist_tables):
        n_local = binned.shape[0]
        feat_ids = jnp.arange(n_feat, dtype=jnp.int32)[None, :]
        if hist_layout == "cumsum":
            srow, ends, cols = hist_tables

        def level_hists_cumsum(g, h, node, level):
            """Sort-free per-level histograms: gather by the pack-time
            cell order, expand by a 2^level-wide node one-hot, reduce
            grad and hess columns in ONE fused run-totals pass at the
            static (feat, bin) boundaries."""
            from flinkml_tpu.ops.sparse import chunked_run_totals

            width = 1 << level
            oh = jax.nn.one_hot(node[srow], width, dtype=g.dtype)
            both = jnp.concatenate(
                [g[srow][:, None] * oh, h[srow][:, None] * oh], axis=1
            )
            t2 = chunked_run_totals(both, ends)    # [runs, 2*width]
            out = []
            for t in (t2[:, :width], t2[:, width:]):
                fb = jnp.zeros((n_feat * n_bins, width), g.dtype) \
                    .at[cols].add(t)
                full = jnp.zeros((n_leaves, n_feat, n_bins), g.dtype) \
                    .at[:width].set(
                        jnp.moveaxis(
                            fb.reshape(n_feat, n_bins, width), -1, 0
                        )
                    )
                out.append(full)
            return out[0], out[1]

        def build_tree(g, h, fmask):
            node = jnp.zeros(n_local, jnp.int32)   # index within level
            feat_arr = jnp.zeros(n_inner, jnp.int32)
            bin_arr = jnp.zeros(n_inner, jnp.int32)
            gain_arr = jnp.zeros(n_inner, jnp.float32)
            for level in range(depth):
                if hist_layout == "cumsum":
                    hg, hh = level_hists_cumsum(g, h, node, level)
                    hg = jax.lax.psum(hg, axis)
                    hh = jax.lax.psum(hh, axis)
                else:
                    ids = ((node[:, None] * n_feat + feat_ids) * n_bins
                           + binned).reshape(-1)
                    hg = jax.lax.psum(jax.ops.segment_sum(
                        jnp.repeat(g, n_feat), ids, num_segments=seg), axis)
                    hh = jax.lax.psum(jax.ops.segment_sum(
                        jnp.repeat(h, n_feat), ids, num_segments=seg), axis)
                    hg = hg.reshape(n_leaves, n_feat, n_bins)
                    hh = hh.reshape(n_leaves, n_feat, n_bins)
                # The module's one split finding (cumulative sums, gains,
                # empty sides and the last bin at 0, the subset's -inf).
                bf, bb, best_gain, *_ = best_splits(hg, hh, lam, fmask)
                start = (1 << level) - 1
                idx = start + jnp.arange(1 << level)
                feat_arr = feat_arr.at[idx].set(bf[: 1 << level])
                bin_arr = bin_arr.at[idx].set(bb[: 1 << level])
                gain_arr = gain_arr.at[idx].set(best_gain[: 1 << level])
                sample_bin = jnp.take_along_axis(
                    binned, bf[node][:, None], axis=1
                )[:, 0]
                node = node * 2 + (sample_bin > bb[node])
            lg = jax.lax.psum(jax.ops.segment_sum(
                g, node, num_segments=n_leaves), axis)
            lh = jax.lax.psum(jax.ops.segment_sum(
                h, node, num_segments=n_leaves), axis)
            # Empty leaves have lh == 0; with lam == 0 the division would
            # be 0/0 — floor the denominator so they get value 0.
            leaf = -lg / jnp.maximum(lh + lam, 1e-12)
            return feat_arr, bin_arr, gain_arr, leaf, node

        def tree_step(carry, tree_key):
            pred = carry
            g, h = grad_hess(pred, y, w)
            k_rows, k_feats = jax.random.split(tree_key)
            if boosting:
                mask = (
                    jax.random.uniform(k_rows, (n_local,)) < subsample
                ).astype(g.dtype)
            else:
                # Poisson bootstrap: multiplicity weights give the
                # classic with-replacement resample (diverse trees even
                # at subsample = 1.0, where a Bernoulli mask would make
                # every tree identical).
                mask = jax.random.poisson(
                    k_rows, subsample, (n_local,)
                ).astype(g.dtype)
            if feat_subset:
                perm = jax.random.permutation(k_feats, n_feat)
                fmask = jnp.zeros(n_feat, jnp.float32).at[
                    perm[:feat_subset]
                ].set(1.0)
            else:
                fmask = jnp.ones(n_feat, jnp.float32)
            feat_arr, bin_arr, gain_arr, leaf, node = build_tree(
                g * mask, h * mask, fmask
            )
            if boosting:
                pred = (pred + lr * leaf[node]).astype(jnp.float32)
            return pred, (feat_arr, bin_arr, gain_arr, leaf)

        keys = jax.random.split(key, num_trees)
        # Derive the initial carry from a sharded input so it is marked
        # varying over the mesh axis (a replicated-scalar broadcast is
        # "unvarying" and shard_map rejects the scan carry).
        pred0 = (jnp.zeros_like(y) + base).astype(jnp.float32)
        _, trees = jax.lax.scan(tree_step, pred0, keys)
        return trees

    hist_specs = (P(axis),) * 3 if hist_layout == "cumsum" else ()
    return jax.jit(
        jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P(), P(), P(), P(), P())
            + hist_specs,
            out_specs=(P(), P(), P(), P()),
        )
    )


def _thresholds(edges: np.ndarray, feats: np.ndarray,
                bins: np.ndarray) -> np.ndarray:
    """Raw thresholds: split "bin <= b" ⟺ "x <= edges[f, b]" (the last
    bin has threshold +inf: everything goes left)."""
    edges_inf = np.concatenate(
        [edges, np.full((edges.shape[0], 1), np.inf)], axis=1
    )
    return edges_inf[feats, np.minimum(bins, edges_inf.shape[1] - 1)]


def _walk_forest_per_tree(x: np.ndarray, feats, thrs, leaves,
                          depth: int) -> np.ndarray:
    """[T, n] per-tree leaf values for raw features (host numpy)."""
    n = x.shape[0]
    out = np.empty((feats.shape[0], n))
    for t in range(feats.shape[0]):
        node = np.zeros(n, dtype=np.int64)   # index within level
        for level in range(depth):
            start = (1 << level) - 1
            f = feats[t, start + node]
            thr = thrs[t, start + node]
            node = node * 2 + (x[np.arange(n), f] > thr)
        out[t] = leaves[t, node]
    return out


def _walk_forest(x: np.ndarray, feats, thrs, leaves, depth: int) -> np.ndarray:
    """Sum of leaf values over all trees (host numpy). Streams one tree
    at a time — an O(n) accumulator, NOT the [T, n] matrix the
    early-stopping path materializes (that would be gigabytes for big
    forests scoring big batches)."""
    n = x.shape[0]
    total = np.zeros(n)
    for t in range(feats.shape[0]):
        node = np.zeros(n, dtype=np.int64)
        for level in range(depth):
            start = (1 << level) - 1
            f = feats[t, start + node]
            thr = thrs[t, start + node]
            node = node * 2 + (x[np.arange(n), f] > thr)
        total += leaves[t, node]
    return total


class _GBTBase(StreamingEstimatorMixin, _GBTParams, Estimator):
    """``fit`` accepts, besides a single in-RAM :class:`Table`:

      - an **iterable of batch Tables** — the out-of-core path: the
        stream is cached once (spilling to ``cache_dir`` beyond
        ``cache_memory_budget_bytes``), bin edges come from a seeded
        reservoir row sample, and every tree level accumulates its
        histograms by replaying the binned cache with bounded HBM
        residency (see :mod:`flinkml_tpu.models._gbt_stream`);
      - a sealed :class:`~flinkml_tpu.iteration.datacache.DataCache`
        whose batches carry this estimator's features/label(/weight)
        columns.

    Streamed mode is boosting-only and excludes ``validationFraction``.
    """

    _LOGISTIC = True
    _BOOSTING = True

    def __init__(self, mesh=None, *, stream_reservoir_capacity: int = 65_536,
                 **knobs):
        super().__init__(mesh=mesh, **knobs)
        # Streamed-fit bin-edge sample size (see _gbt_stream: edges come
        # from a seeded uniform row reservoir; capacity >= n gives exact
        # edges, smaller capacities trade accuracy for a bounded sample —
        # envelope quantified in tests/test_gbt_reservoir.py).
        self.stream_reservoir_capacity = stream_reservoir_capacity

    def _feat_fraction(self, d: int) -> float:
        return 1.0

    def _labeled_maybe_hashed(self, table: Table):
        """(x, y, w, hash_features): SparseVector feature columns are
        hash-bundled to ``numHashFeatures`` dense columns (0 = dense
        input) so one-hot/text pipelines feed trees without densifying
        to the full sparse dimensionality."""
        features_col = self.get(self.FEATURES_COL)
        sp = sparse_features(table, features_col)
        if sp is None:
            x, y, w = labeled_data(
                table, features_col, self.get(self.LABEL_COL),
                self.get(self.WEIGHT_COL),
            )
            return x, y, w, 0
        n_hash = self.get(self.NUM_HASH_FEATURES)
        x = hashed_feature_matrix(sp, n_hash).astype(np.float64)
        y = np.asarray(
            table.column(self.get(self.LABEL_COL)), np.float64
        ).reshape(-1)
        if y.shape[0] != x.shape[0]:
            raise ValueError(
                f"label column has {y.shape[0]} rows, features have "
                f"{x.shape[0]}"
            )
        weight_col = self.get(self.WEIGHT_COL)
        w = (
            np.asarray(table.column(weight_col), np.float64).reshape(-1)
            if weight_col is not None
            else np.ones(x.shape[0], np.float64)
        )
        return x, y, w, n_hash

    def _holdout_rows(self, n: int):
        """``(held, train)``: the rows of ``validationFraction``'s holdout
        and the rest, a seeded permutation's two ends; None where there
        is no holdout."""
        vf = self.get(self.VALIDATION_FRACTION)
        if vf <= 0:
            return None
        if not self._BOOSTING:
            raise ValueError(
                "validationFraction applies to boosted estimators only "
                "(bagged forests don't overfit with more trees)"
            )
        perm = np.random.default_rng(self.get_seed()).permutation(n)
        n_hold = max(1, int(round(vf * n)))
        if n_hold >= n:
            raise ValueError("validationFraction leaves no training rows")
        return perm[:n_hold], perm[n_hold:]

    def _fit_forest(self, table: Table):
        """Hashed sparse input's fit: the bundled columns made, binned
        (int32, row-major) and placed a fit, :func:`_forest_builder`'s
        histograms. (A dense column also runs, as the tests' second
        opinion of :meth:`_fit_table`.)"""
        x, y, w, hash_features = self._labeled_maybe_hashed(table)
        if self._LOGISTIC:
            # Validate on the FULL label column, before any holdout split
            # (an invalid label permuted into the holdout would silently
            # corrupt the early-stopping loss instead of raising).
            check_binary_labels(y, type(self).__name__)
        holdout = None
        rows = self._holdout_rows(x.shape[0])
        if rows is not None:
            hold_idx, train_idx = rows
            holdout = (x[hold_idx], y[hold_idx], w[hold_idx])
            x, y, w = x[train_idx], y[train_idx], w[train_idx]
        if self._LOGISTIC:
            pos = float(np.sum(w * y))
            neg = float(np.sum(w * (1 - y)))
            base = float(np.log(max(pos, 1e-12) / max(neg, 1e-12)))
        else:
            base = float(np.sum(w * y) / np.sum(w))
        max_bins = self.get(self.MAX_BINS)
        depth = self.get(self.MAX_DEPTH)
        edges = quantile_bin_edges(x, max_bins)
        binned = bin_features(x, edges)
        mesh = self.mesh or DeviceMesh()
        p = mesh.axis_size()
        b_pad, n_valid = pad_to_multiple(binned, p)
        y_pad, _ = pad_to_multiple(y.astype(np.float32), p)
        w_pad = np.zeros(b_pad.shape[0], np.float32)
        w_pad[:n_valid] = w[:n_valid].astype(np.float32)
        f = self._feat_fraction(x.shape[1])
        feat_subset = (
            0 if f >= 1.0 else max(1, int(round(f * x.shape[1])))
        )
        hist_layout = _hist_layout()
        builder = _forest_builder(
            mesh.mesh, DeviceMesh.DATA_AXIS, x.shape[1], max_bins, depth,
            self.get(self.NUM_TREES), self._LOGISTIC,
            boosting=self._BOOSTING, feat_subset=feat_subset,
            hist_layout=hist_layout,
        )
        hist_args = sharded_hist_args(b_pad, mesh, max_bins, hist_layout)
        f32 = lambda v: jnp.asarray(v, jnp.float32)
        feats, bins, gains, leaves = builder(
            mesh.shard_batch(b_pad), mesh.shard_batch(y_pad),
            mesh.shard_batch(w_pad),
            f32(base), f32(self.get(self.LEARNING_RATE)),
            f32(self.get(self.REG_LAMBDA)), f32(self.get(self.SUBSAMPLE)),
            jax.random.PRNGKey(self.get_seed()), *hist_args,
        )
        feats = np.asarray(feats)
        bins = np.asarray(bins)
        thrs = _thresholds(edges, feats, bins)
        gains = np.asarray(gains)
        leaves = np.asarray(leaves)
        if holdout is not None:
            feats, thrs, gains, leaves = self._truncate_to_best_prefix(
                holdout, feats, thrs, gains, leaves, base, depth,
            )
        return (feats, thrs, gains, leaves, base, depth, x.shape[1],
                hash_features)

    def _fit_table(self, table: Table):
        """The fit on a dense features column (:mod:`~flinkml_tpu.models.
        _gbt_table`): the binned table kept with ``table``, the whole
        forest one program; raw thresholds from the edges. A holdout's
        rows stay in the table at weight 0 (and out of the edges)."""
        from flinkml_tpu.models import _gbt_table

        rows = self._holdout_rows(table.num_rows)
        held = None if rows is None else np.sort(rows[0])
        feats, bins, gains, leaves, base, edges = _gbt_table.fit_table(
            self, table, held=held)
        thrs = _thresholds(edges, feats, bins)
        depth = self.get(self.MAX_DEPTH)
        if held is not None:
            x, y, w = labeled_data(
                table.take(held), self.get(self.FEATURES_COL),
                self.get(self.LABEL_COL), self.get(self.WEIGHT_COL))
            feats, thrs, gains, leaves = self._truncate_to_best_prefix(
                (x, y, w), feats, thrs, gains, leaves, base, depth)
        return (feats, thrs, gains, leaves, base, depth, edges.shape[0], 0)

    def _truncate_to_best_prefix(self, holdout, feats, thrs, gains, leaves,
                                 base, depth):
        """Early stopping: keep the tree prefix with the best holdout
        loss (cumulative per-tree margins on the held-out rows)."""
        hx, hy, hw = holdout
        lr = self.get(self.LEARNING_RATE)
        contribs = _walk_forest_per_tree(hx, feats, thrs, leaves, depth)
        margins = base + lr * np.cumsum(contribs, axis=0)   # [T, n_hold]
        if self._LOGISTIC:
            # NLL = log(1 + e^m) - y*m, computed stably.
            losses = (
                np.logaddexp(0.0, margins) - hy[None, :] * margins
            )
        else:
            losses = 0.5 * (margins - hy[None, :]) ** 2
        per_prefix = (losses * hw[None, :]).sum(axis=1)
        best = int(np.argmin(per_prefix)) + 1
        return feats[:best], thrs[:best], gains[:best], leaves[:best]

    def _fit_stream_forest(self, source):
        """Out-of-core forest build (see class docstring;
        ``ReplayOperator.java:62-250`` parity)."""
        from flinkml_tpu.iteration.datacache import DataCache, cache_stream
        from flinkml_tpu.models._gbt_stream import train_gbt_stream

        if not self._BOOSTING:
            raise ValueError(
                "streamed fits support boosted estimators only; random "
                "forests need the in-RAM path (independent bagged trees)"
            )
        if self.get(self.VALIDATION_FRACTION) > 0:
            raise ValueError(
                "validationFraction is not supported in streamed fits "
                "(a holdout needs a second materialized stream)"
            )
        if self.resume and not isinstance(source, DataCache):
            raise ValueError(
                "resume=True requires a durable DataCache input: a one-shot "
                "stream cannot be replayed from the start after a failure"
            )
        features_col = self.get(self.FEATURES_COL)
        label_col = self.get(self.LABEL_COL)
        weight_col = self.get(self.WEIGHT_COL)
        if isinstance(source, DataCache):
            cache = source
            columns = (features_col, label_col, weight_col)
        else:
            hash_seen = [None]  # None until first batch decides the mode

            def batches():
                for t in source:
                    # The hashing is stateless (pure function of column
                    # id), so per-batch bundling is consistent across the
                    # stream — but the mode must not flip mid-stream.
                    x, y, w, nh = self._labeled_maybe_hashed(t)
                    if hash_seen[0] is None:
                        hash_seen[0] = nh
                    elif hash_seen[0] != nh:
                        raise ValueError(
                            "stream mixes sparse and dense feature "
                            "batches; use one representation throughout"
                        )
                    yield {"x": x.astype(np.float32),
                           "y": y.astype(np.float32),
                           "w": w.astype(np.float32)}

            cache = cache_stream(
                batches(), self.cache_dir, self.cache_memory_budget_bytes
            )
            columns = ("x", "y", "w")
        label_check = (
            (lambda y: check_binary_labels(y, type(self).__name__))
            if self._LOGISTIC else None
        )
        max_bins = self.get(self.MAX_BINS)
        depth = self.get(self.MAX_DEPTH)
        feats, bins, gains, leaves, base, edges = train_gbt_stream(
            cache,
            mesh=self.mesh or DeviceMesh(),
            logistic=self._LOGISTIC,
            num_trees=self.get(self.NUM_TREES),
            depth=depth,
            max_bins=max_bins,
            learning_rate=self.get(self.LEARNING_RATE),
            reg_lambda=self.get(self.REG_LAMBDA),
            subsample=self.get(self.SUBSAMPLE),
            seed=self.get_seed(),
            columns=columns,
            label_check=label_check,
            reservoir_capacity=self.stream_reservoir_capacity,
            **self._checkpoint_kwargs(),
        )
        thrs = _thresholds(edges, feats, bins)
        hash_features = (
            0 if isinstance(source, DataCache) else (hash_seen[0] or 0)
        )
        return (feats, thrs, gains, leaves, base, depth, edges.shape[0],
                hash_features)

    _MODEL_CLS = None   # set per concrete estimator

    def fit(self, *inputs):
        (table,) = inputs
        if isinstance(table, Table):
            self._reject_in_ram_checkpointing(
                "the in-RAM fit builds the whole forest in one device "
                "program"
            )
            with span("fit"):
                if sparse_features(table, self.get(self.FEATURES_COL)) is not None:
                    forest = self._fit_forest(table)
                else:
                    forest = self._fit_table(table)
        else:
            forest = self._fit_stream_forest(table)
        (feats, thrs, gains, leaves, base, depth, n_features,
         hash_features) = forest
        model = self._MODEL_CLS()
        model.copy_params_from(self)
        # Bagged forests predict the MEAN of tree outputs (lr = 1/T);
        # boosted forests scale each tree by the learning rate.
        lr = (
            self.get(self.LEARNING_RATE) if self._BOOSTING
            else 1.0 / feats.shape[0]
        )
        model._set_forest(feats, thrs, leaves, base, depth, lr,
                          gains, n_features, hash_features)
        return model


class _GBTModelBase(_GBTParams, Model):
    _LOGISTIC = True

    def __init__(self):
        super().__init__()
        self._feats: Optional[np.ndarray] = None
        self._thrs: Optional[np.ndarray] = None
        self._leaves: Optional[np.ndarray] = None
        self._base: float = 0.0
        self._depth: int = 0
        self._lr: float = 0.1
        self._gains: Optional[np.ndarray] = None
        self._n_features: int = 0
        self._hash_features: int = 0

    def _set_forest(self, feats, thrs, leaves, base, depth, lr,
                    gains=None, n_features=None, hash_features=0):
        self._feats = np.asarray(feats, np.int64)
        self._thrs = np.asarray(thrs, np.float64)
        self._leaves = np.asarray(leaves, np.float64)
        self._base = float(base)
        self._depth = int(depth)
        self._lr = float(lr)
        self._gains = (
            np.asarray(gains, np.float64) if gains is not None
            else np.ones_like(self._feats, dtype=np.float64)
        )
        self._n_features = (
            int(n_features) if n_features is not None
            else int(self._feats.max()) + 1
        )
        # > 0 when the forest was trained on hash-bundled sparse input:
        # transform must apply the same stateless bundling.
        self._hash_features = int(hash_features)

    def set_model_data(self, *inputs: Table):
        (table,) = inputs
        self._set_forest(
            table.column("feat"), table.column("threshold"),
            table.column("leaf"),
            float(table.column("base")[0]),
            int(table.column("depth")[0]),
            float(table.column("learningRate")[0]),
            gains=table.column("gain") if "gain" in table else None,
            n_features=(
                int(table.column("numFeatures")[0])
                if "numFeatures" in table else None
            ),
            hash_features=(
                int(table.column("hashFeatures")[0])
                if "hashFeatures" in table else 0
            ),
        )
        return self

    def get_model_data(self) -> List[Table]:
        self._require()
        t = self._feats.shape[0]
        return [Table({
            "feat": self._feats, "threshold": self._thrs,
            "gain": self._gains, "leaf": self._leaves,
            "base": np.full(t, self._base),
            "depth": np.full(t, self._depth),
            "learningRate": np.full(t, self._lr),
            "numFeatures": np.full(t, self._n_features),
            "hashFeatures": np.full(t, self._hash_features),
        })]

    def _require(self) -> None:
        if self._feats is None:
            raise ValueError("Model data is not set; fit or set_model_data first")

    def feature_importances(self, num_features: Optional[int] = None) -> np.ndarray:
        """Gain importance (the XGBoost convention): each feature's share
        of the total split gain across the forest, normalized to sum
        to 1. Degenerate nodes (empty/pure — zero gain) contribute
        nothing, so deep complete trees don't inflate feature 0.
        Default length = the training feature count."""
        self._require()
        d = self._n_features if num_features is None else int(num_features)
        max_feat = int(self._feats.max())
        if d <= max_feat:
            raise ValueError(
                f"num_features={d} but the forest splits on feature "
                f"{max_feat}"
            )
        imp = np.bincount(
            self._feats.reshape(-1),
            weights=self._gains.reshape(-1),
            minlength=d,
        )
        total = imp.sum()
        return imp / total if total > 0 else imp

    def _margin(self, table: Table) -> np.ndarray:
        col = table.column(self.get(self.FEATURES_COL))
        if self._hash_features and col.dtype == object:
            # Hash-trained forest scoring sparse input: apply the same
            # stateless bundling the estimator used.
            x = hashed_feature_matrix(
                col, self._hash_features
            ).astype(np.float64)
        else:
            x = np.asarray(col, dtype=np.float64)
        if x.ndim != 2:
            raise ValueError(f"features must be [n, d], got {x.shape}")
        if self._feats.size and self._feats.max() >= x.shape[1]:
            raise ValueError(
                f"model uses feature {self._feats.max()}, features have "
                f"dim {x.shape[1]}"
            )
        return self._base + self._lr * _walk_forest(
            x, self._feats, self._thrs, self._leaves, self._depth
        )

    def save(self, path: str) -> None:
        self._require()
        self._save_with_arrays(path, {
            "feat": self._feats, "threshold": self._thrs,
            "gain": self._gains, "leaf": self._leaves,
            "base": np.asarray(self._base),
            "depth": np.asarray(self._depth),
            "learningRate": np.asarray(self._lr),
            "numFeatures": np.asarray(self._n_features),
            "hashFeatures": np.asarray(self._hash_features),
        })

    @classmethod
    def load(cls, path: str):
        model, arrays, _ = cls._load_with_arrays(path)
        model._set_forest(
            arrays["feat"], arrays["threshold"], arrays["leaf"],
            float(arrays["base"]), int(arrays["depth"]),
            float(arrays["learningRate"]),
            gains=arrays.get("gain"),
            n_features=(
                int(arrays["numFeatures"]) if "numFeatures" in arrays else None
            ),
            hash_features=int(arrays.get("hashFeatures", 0)),
        )
        return model


class GBTClassifier(_GBTBase):
    """Binary gradient-boosted tree classifier (logistic loss)."""

    _LOGISTIC = True


class GBTClassifierModel(_GBTModelBase):
    _LOGISTIC = True

    RAW_PREDICTION_COL = HasRawPredictionCol.RAW_PREDICTION_COL

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


class GBTRegressor(_GBTBase):
    """Gradient-boosted tree regressor (squared loss)."""

    _LOGISTIC = False


class GBTRegressorModel(_GBTModelBase):
    _LOGISTIC = False

    def transform(self, *inputs: Table) -> Tuple[Table, ...]:
        (table,) = inputs
        self._require()
        return (
            table.with_column(self.get(self.PREDICTION_COL), self._margin(table)),
        )


class _RandomForestParams(_GBTParams):
    FEATURE_SUBSET_FRACTION = FloatParam(
        "featureSubsetFraction",
        "Fraction of features drawn per tree (None = sqrt(d)/d for the "
        "classifier, all features for the regressor — the sklearn "
        "conventions).",
        None, lambda v: v is None or 0 < v <= 1,
    )


class _RFBase(_RandomForestParams, _GBTBase):
    """Random forest = the same device forest builder in BAGGING mode:
    every tree fits the base-score residual independently on a row
    subsample and a per-tree feature subset; prediction averages the
    tree outputs (Newton-step leaves at the constant base score)."""

    _BOOSTING = False

    def _feat_fraction(self, d: int) -> float:
        f = self.get(self.FEATURE_SUBSET_FRACTION)
        return float(f) if f is not None else min(1.0, np.sqrt(d) / d)


class RandomForestClassifier(_RFBase):
    """Bagged binary classifier (defaults: subsample 1.0 — set e.g. 0.7
    for extra diversity; feature subset sqrt(d))."""

    _LOGISTIC = True


class RandomForestClassifierModel(_RandomForestParams, GBTClassifierModel):
    pass


class RandomForestRegressor(_RFBase):
    _LOGISTIC = False

    def _feat_fraction(self, d: int) -> float:
        # Regression forests default to ALL features per tree (the
        # sklearn convention; sqrt is the classification default).
        f = self.get(self.FEATURE_SUBSET_FRACTION)
        return float(f) if f is not None else 1.0


class RandomForestRegressorModel(_RandomForestParams, GBTRegressorModel):
    pass


# Estimator -> model wiring (assigned after all classes exist).
GBTClassifier._MODEL_CLS = GBTClassifierModel
GBTRegressor._MODEL_CLS = GBTRegressorModel
RandomForestClassifier._MODEL_CLS = RandomForestClassifierModel
RandomForestRegressor._MODEL_CLS = RandomForestRegressorModel
