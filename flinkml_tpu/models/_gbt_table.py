"""``GBTClassifier.fit(Table)`` and its siblings on a dense features
column: histogram boosting over a binned table that stays on the chip at
one byte a cell, a level's histograms as one-hot products.

- **Ingest**, once a ``Table`` (:func:`place`, span ``gbt.ingest``): the
  column taken as the table holds it (float32 stays float32: no float64
  copy of it); the bin edges by the module's rule
  (:func:`quantile_bin_edges`: interior quantiles, duplicates collapsed,
  padded with +inf) over a seeded uniform sample of rows
  (:func:`bin_edges`: every row where the table has no more than the
  sample); ``bin = #{edges < x}`` exactly (:func:`bin_features`'
  definition) as uint8, features-major with the ROWS along the lanes
  (:func:`bin_rows`: row chunks on threads, no Python loop over rows);
  the label checks from the table's kept facts (``LabelFacts.of``).
- **Kept with the** ``Table`` (:meth:`Table.device_resident`, span
  ``gbt.table_to_device``): the binned table ``[features, rows]``, the
  labels and the weights (made on the device where there is no weight
  column), the rows padded with weight 0 to whole tiles a device. A
  holdout's rows (``validationFraction``) stay in the table at weight 0
  and out of the edges' sample. The key names the columns, the mesh,
  ``maxBins``, the seed of the edges and the holdout; the learning
  rate, ``regLambda``, ``subsample`` and the key of the row sampling are
  operands.
- **One program** ``gbt_forest`` a fit (:func:`_program`): the
  ``lax.scan`` over trees, a Python loop over levels inside. A level's
  histograms are :mod:`flinkml_tpu.kernels.gbt_hist`'s product on a TPU
  (chosen level by level by its ``unsupported_reason``) and
  :func:`xla_level_histograms`, the same product over row chunks,
  everywhere else (and where the kernel's sums would not fit its fast
  memory): float32-accurate (``g`` and ``h`` in three bfloat16
  parts, or a float32 product at ``HIGHEST``), never an array of
  ``rows x features`` entries wider than a byte. The split finding is
  the module's (:func:`best_splits`). A node's rows are re-assigned by
  selects over the level's nodes and the features, not a row-wise
  gather, and the leaves' sums are the last level's histograms' (the
  chosen split's two sides, each its own sum over its bins).
- On ``p`` > 1 devices the rows are sharded, every device builds its
  share's histograms and the ``psum`` joins them: every device decides
  the same splits.

Spans and counters: ``docs/development/observability.md`` (``gbt.*``; the
group ``gbt``).
"""

from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from flinkml_tpu.kernels import gbt_hist
from flinkml_tpu.models._data import (
    LabelFacts,
    _check_rows,
    check_binary_labels,
    features_matrix,
)
from flinkml_tpu.parallel import DeviceMesh
from flinkml_tpu.utils.metrics import metrics
from flinkml_tpu.utils.profiling import named_program, phase, span

#: Rows the bin edges are taken over (a seeded uniform sample of the
#: table's; all of them where it has no more).
BIN_SAMPLE_ROWS = 1 << 20
#: Rows a task of the binning's pool of threads takes, and the threads.
_BIN_CHUNK_ROWS, _BIN_THREADS = 1 << 18, 12
#: The fit's phases (``profiling.phase``): a tree's gradients with the
#: prediction's update, and a level's histograms, split finding and rows'
#: re-assignment. The ``psum`` of a level's histograms is in none.
PHASES = ("gbt.gradients", "gbt.histograms", "gbt.splits", "gbt.route")
#: Nodes of a level up to which a row's split is looked up by selects
#: (a chain of them, fused with what reads it); a deeper level gathers.
_SELECT_NODES = 64


def quantile_bin_edges(x: np.ndarray, max_bins: int) -> np.ndarray:
    """Per-feature interior quantile edges, padded with +inf to a fixed
    ``[d, max_bins - 1]`` (duplicate quantiles collapse, so features with
    few distinct values just use fewer real edges)."""
    n, d = x.shape
    qs = np.linspace(0, 1, max_bins + 1)[1:-1]
    edges = np.full((d, max_bins - 1), np.inf)
    for j in range(d):
        e = np.unique(np.quantile(x[:, j], qs))
        e = e[np.isfinite(e)]
        edges[j, : len(e)] = e
    return edges


def bin_features(x: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """bin = #{edges < x} per feature; ``bin <= b  ⟺  x <= edges[b]``."""
    n, d = x.shape
    out = np.empty((n, d), dtype=np.int32)
    for j in range(d):
        out[:, j] = np.searchsorted(edges[j], x[:, j], side="left")
    return out


def bin_edges(table, features_col: str, max_bins: int, seed: int,
              sample_rows: Optional[int] = None) -> np.ndarray:
    """The ``[features, max_bins - 1]`` float64 bin edges a fit of
    ``table``'s dense ``features_col`` at ``seed`` bins by: the module's
    quantile rule over ``sample_rows`` rows (None:
    :data:`BIN_SAMPLE_ROWS`, a fit's) drawn uniformly without replacement
    by ``default_rng(seed)`` (in the table's order), over every row where
    the table has no more. A function of (table, seed)."""
    return _edges_of(features_matrix(table, features_col, dtype=None),
                     max_bins, seed, sample_rows or BIN_SAMPLE_ROWS)


def _edges_of(x: np.ndarray, max_bins: int, seed: int, sample_rows: int,
              among: Optional[np.ndarray] = None):
    """:func:`bin_edges`' of ``x``; of its rows ``among`` (ascending)
    alone where a fit holds the others out."""
    n = x.shape[0] if among is None else among.shape[0]
    if n > sample_rows:
        rows = np.random.default_rng(seed).choice(n, sample_rows, replace=False)
        rows.sort()
        among = rows if among is None else among[rows]
    if among is not None:
        x = x[among]
    return quantile_bin_edges(np.asarray(x, np.float64), max_bins)


def _cuts(edges: np.ndarray, dtype) -> np.ndarray:
    """``edges`` as values of ``dtype`` that cut its values where the
    float64 edges do: a float32 ``x`` has ``e < x`` exactly where ``e``
    rounded DOWN to float32 is under it (no float32 lies between)."""
    if np.dtype(dtype) != np.float32:
        return edges
    low = edges.astype(np.float32)
    above = low.astype(np.float64) > edges
    return np.where(above, np.nextafter(low, np.float32(-np.inf)), low)


def bin_rows(x: np.ndarray, edges: np.ndarray,
             padded_rows: Optional[int] = None) -> np.ndarray:
    """``[features, padded_rows]`` uint8: ``#{edges[j] < x[i, j]}`` at
    ``[j, i]`` (:func:`bin_features`' int32 bins, transposed, one byte a
    cell), zeros past the table's end. ``x [rows, features]`` as the
    table holds it; row chunks on threads."""
    n, d = x.shape
    if edges.shape[1] > 255:
        raise ValueError(f"{edges.shape[1] + 1} bins do not fit a byte")
    out = np.zeros((d, n if padded_rows is None else padded_rows), np.uint8)
    cuts = _cuts(edges, x.dtype)

    def chunk(lo):
        hi = min(n, lo + _BIN_CHUNK_ROWS)
        for j in range(d):
            out[j, lo:hi] = np.searchsorted(cuts[j], x[lo:hi, j], side="left")

    with ThreadPoolExecutor(_BIN_THREADS) as pool:
        list(pool.map(chunk, range(0, n, _BIN_CHUNK_ROWS)))
    return out


class _Placed(NamedTuple):
    """A table's binned rows, labels and weights as the mesh holds them,
    in the table's own order, and what the host keeps of its ingest."""

    bins: jax.Array     # [features, p * n_local] uint8, 0 past the end
    y: jax.Array        # [p * n_local] float32
    w: jax.Array        # [p * n_local] float32, 0 past the table's end
    edges: np.ndarray   # [features, max_bins - 1] float64, on the host
    rows: int           # the table's
    label_sum: float    # sum of w * y, float64
    weight_sum: float   # sum of w, float64


def padded_rows(rows: int, p: int) -> int:
    """Rows the mesh holds for a table of ``rows``: whole tiles of the
    histogram kernel a device (of 128 where a device's share is under
    one of its largest)."""
    n_local = -(-rows // p)
    quantum = gbt_hist.TILE if n_local >= gbt_hist.TILE else gbt_hist.LANES
    return p * (-(-n_local // quantum) * quantum)


def place(x: np.ndarray, facts: LabelFacts, weights, max_bins: int, seed: int,
          mesh: DeviceMesh, make_room, held: Optional[np.ndarray] = None) -> _Placed:
    """A table's features ``x``, labels (their kept facts) and weights
    (None: ones) binned (``gbt.ingest``) and put on the mesh
    (``gbt.table_to_device``). ``make_room`` is the table's that will keep
    them, told the bytes first. The rows ``held`` (a holdout's) take
    weight 0 and no part in the edges."""
    n, d = x.shape
    p = mesh.axis_size()
    total = padded_rows(n, p)
    with span("gbt.ingest"):
        among = None
        if held is not None:
            weights = (np.ones(n, np.float32) if weights is None
                       else np.array(weights, np.float32))
            weights[held] = 0.0
            among = np.setdiff1d(np.arange(n), held, assume_unique=True)
        edges = _edges_of(x, max_bins, seed, BIN_SAMPLE_ROWS, among)
        bins = bin_rows(x, edges, total)
        y = np.zeros(total, np.float32)
        y[:n] = facts.values
        # The base score's sums in float64 from the labels as the table
        # holds them, whatever the chip's float32 copy rounds.
        if weights is None:
            w, weight_sum = None, float(n)
            label_sum = float(np.sum(facts.values, dtype=np.float64))
        else:
            w = np.zeros(total, np.float32)
            w[:n] = weights
            weight_sum = float(np.sum(weights, dtype=np.float64))
            label_sum = float(np.dot(np.asarray(weights, np.float64), facts.values))
    nbytes = bins.nbytes + 2 * y.nbytes
    make_room(nbytes, mesh.mesh.devices.flat)
    with span("gbt.table_to_device") as phase:
        placed = jax.block_until_ready((
            jax.device_put(bins, NamedSharding(
                mesh.mesh, P(None, DeviceMesh.DATA_AXIS))),
            mesh.shard_batch(y),
            mesh.shard_ones(n, np.float32, total) if w is None
            else mesh.shard_batch(w)))
        phase.add(bytes=nbytes)
    counters = metrics.group("gbt")
    counters.counter("table_uploads")
    counters.counter("table_h2d_bytes", float(nbytes))
    return _Placed(*placed, edges, n, label_sum, weight_sum)


# -- the level's histograms ---------------------------------------------------

def xla_level_histograms(bins, g, h, node, nodes: int):
    """``(hg, hh)``, each ``[nodes, features, 256]`` float32, as
    :func:`flinkml_tpu.kernels.gbt_hist.level_histograms` gives them, by
    XLA: over chunks of rows in turn, a feature's one-hot of the bins
    ``[chunk, 256]`` against ``one_hot(node) (x) (g, h)`` ``[chunk, 2
    nodes]`` at ``HIGHEST`` (a float32 product whatever the backend),
    summed in float32."""
    d, n = bins.shape
    chunk = min(gbt_hist.tile_rows(n) or n, 2048)
    stats = jnp.stack([g, h], axis=1)
    which = jnp.arange(nodes, dtype=node.dtype)
    bin_ids = jnp.arange(gbt_hist.BINS, dtype=jnp.int32)

    def one_chunk(acc, start):
        rows = lambda a, axis: jax.lax.dynamic_slice_in_dim(a, start, chunk, axis)
        a = ((rows(node, 0)[:, None] == which)[:, None, :]
             * rows(stats, 0)[:, :, None]).reshape(chunk, 2 * nodes)
        for f in range(d):
            of_bin = (rows(bins[f], 0).astype(jnp.int32)[:, None]
                      == bin_ids).astype(jnp.float32)
            acc = acc.at[f].add(jnp.dot(
                of_bin.T, a, precision=jax.lax.Precision.HIGHEST))
        return acc, None

    zeros = jnp.zeros((d, gbt_hist.BINS, 2 * nodes), jnp.float32)
    varying = tuple(jax.typeof(g).vma)   # inside shard_map: as the rows do
    if varying:
        zeros = jax.lax.pcast(zeros, varying, to="varying")
    sums, _ = jax.lax.scan(one_chunk, zeros,
                           jnp.arange(0, n, chunk, dtype=jnp.int32))
    both = sums.reshape(d, gbt_hist.BINS, 2, nodes).transpose(2, 3, 0, 1)
    return both[0], both[1]


def best_splits(hg, hh, lam, fmask):
    """The module's split finding on a level's histograms ``[nodes,
    features, bins]``: cumulative sums over the bins, the second-order
    gain of every ``bin <= b`` split, empty sides and the last bin at 0,
    features outside ``fmask`` at -inf, the argmax's tie to the lowest
    (feature, bin). ``(feature, bin, gain, left g, left h, right g, right
    h)``, a node each: the chosen split's two sides, each ITS OWN sum over
    its bins (the right one a sum from the last bin down, not the total
    less the left: a side of few rows, as one value of a rare flag, would
    carry the whole node's rounding)."""
    nodes, n_feat, n_bins = hg.shape
    gl = jnp.cumsum(hg, axis=2)
    hl = jnp.cumsum(hh, axis=2)
    gt = gl[:, :, -1:]
    ht = hl[:, :, -1:]
    gr = gt - gl
    hr = ht - hl
    gain = (gl * gl / (hl + lam) + gr * gr / (hr + lam) - gt * gt / (ht + lam))
    # Splits with an empty side are not real splits — and with lam == 0
    # their 0/0 gain would be NaN, which argmax treats as the maximum.
    gain = jnp.where((hl > 0) & (hr > 0), gain, 0.0)
    # The last bin's "split" sends everything left.
    gain = gain.at[:, :, -1].set(0.0)
    # -inf, NOT a zero multiply: zeroed gains would still beat negative
    # in-subset gains (possible under regLambda).
    gain = jnp.where(fmask[None, :, None] > 0, gain, -jnp.inf)
    flat = gain.reshape(nodes, n_feat * n_bins)
    best = jnp.argmax(flat, axis=1)
    at = best[:, None]
    chosen = lambda c: jnp.take_along_axis(
        c.reshape(nodes, n_feat * n_bins), at, axis=1)[:, 0]

    def above(h):
        # The sum of the bins over b, from the last bin down (0 at the last).
        down = jnp.cumsum(h[:, :, ::-1], axis=2)[:, :, ::-1]
        return jnp.concatenate([down[:, :, 1:], jnp.zeros_like(down[:, :, :1])], axis=2)

    return ((best // n_bins).astype(jnp.int32), (best % n_bins).astype(jnp.int32),
            jnp.maximum(jnp.max(flat, axis=1), 0.0),
            chosen(gl), chosen(hl), chosen(above(hg)), chosen(above(hh)))


def _of_node(table, node, nodes: int):
    """``table[node]`` for ``node`` in ``[0, nodes)``: a chain of selects
    up to :data:`_SELECT_NODES` nodes, a gather beyond."""
    if nodes > _SELECT_NODES:
        return table[node]
    out = jnp.zeros(node.shape, table.dtype) + table[0]
    for w in range(1, nodes):
        out = jnp.where(node == w, table[w], out)
    return out


@functools.lru_cache(maxsize=16)
def _program(mesh, axis: str, n_feat: int, n_bins: int, depth: int,
             num_trees: int, logistic: bool, boosting: bool, feat_subset: int,
             one_part: bool, product_levels: tuple):
    """The fit's one program ``gbt_forest``. Static: the mesh, the
    shapes, the loss, boosting or bagging (``gbt._forest_builder`` has
    what bagging means), which levels take the Mosaic product, and the
    benchmark's control (``one_part``: ``g`` and ``h`` rounded to one
    bfloat16 part before a tree's histograms).
    Operands: the placed table, the base score, the learning rate,
    ``regLambda``, ``subsample`` and the key of the row sampling."""
    n_leaves = 1 << depth

    def grad_hess(pred, y, w):
        if logistic:
            prob = jax.nn.sigmoid(pred)
            return (prob - y) * w, jnp.maximum(prob * (1 - prob), 1e-6) * w
        return (pred - y) * w, w

    def gbt_forest(bins, y, w, base, lr, lam, subsample, key):
        n_local = bins.shape[1]

        def build_tree(g, h, fmask):
            if one_part:
                g, h = (jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
                        for s in (g, h))
            node = jnp.zeros(n_local, jnp.int32)   # index within level
            feats, cuts, gains = [], [], []
            for level in range(depth):
                nodes = 1 << level
                histograms = (gbt_hist.level_histograms if product_levels[level]
                              else xla_level_histograms)
                with phase("gbt.histograms"):
                    hg, hh = histograms(bins, g, h, node, nodes)
                hg = jax.lax.psum(hg, axis)[:, :, :n_bins]
                hh = jax.lax.psum(hh, axis)[:, :, :n_bins]
                with phase("gbt.splits"):
                    bf, bb, gain, lg, lh, rg, rh = best_splits(hg, hh, lam, fmask)
                feats.append(bf)
                cuts.append(bb)
                gains.append(gain)
                if boosting or level + 1 < depth:
                    with phase("gbt.route"):
                        of_row, cut = _of_node(bf, node, nodes), _of_node(bb, node, nodes)
                        mine = jnp.zeros(n_local, jnp.int32)
                        for f in range(n_feat):
                            mine = jnp.where(of_row == f, bins[f].astype(jnp.int32), mine)
                        node = node * 2 + (mine > cut)
            # The leaves' sums are the last level's: the chosen split's
            # two sides.
            leaf_g = jnp.stack([lg, rg], axis=1).reshape(-1)
            leaf_h = jnp.stack([lh, rh], axis=1).reshape(-1)
            # Empty leaves have h == 0; with lam == 0 the division would
            # be 0/0 — floor the denominator so they get value 0.
            leaf = -leaf_g / jnp.maximum(leaf_h + lam, 1e-12)
            return (jnp.concatenate(feats), jnp.concatenate(cuts),
                    jnp.concatenate(gains).astype(jnp.float32),
                    leaf.astype(jnp.float32), node)

        def tree_step(pred, tree_key):
            with phase("gbt.gradients"):
                g, h = grad_hess(pred, y, w)
                k_rows, k_feats = jax.random.split(tree_key)
                if boosting:
                    # Every row where subsample is 1: the draw is under 1.
                    g, h = jax.lax.cond(
                        subsample < 1.0,
                        lambda: tuple(jnp.where(
                            jax.random.uniform(k_rows, (n_local,)) < subsample, s, 0.0)
                            for s in (g, h)),
                        lambda: (g, h))
                else:
                    # Poisson bootstrap: the with-replacement resample.
                    count = jax.random.poisson(
                        k_rows, subsample, (n_local,)).astype(g.dtype)
                    g, h = g * count, h * count
            if feat_subset:
                perm = jax.random.permutation(k_feats, n_feat)
                fmask = jnp.zeros(n_feat, jnp.float32).at[perm[:feat_subset]].set(1.0)
            else:
                fmask = jnp.ones(n_feat, jnp.float32)
            feat_arr, bin_arr, gain_arr, leaf, node = build_tree(g, h, fmask)
            if boosting:
                with phase("gbt.gradients"):
                    pred = (pred + lr * _of_node(leaf, node, n_leaves)
                            ).astype(jnp.float32)
            return pred, (feat_arr, bin_arr, gain_arr, leaf)

        keys = jax.random.split(key, num_trees)
        # From a sharded input, so that the carry varies over the mesh.
        pred0 = (jnp.zeros_like(y) + base).astype(jnp.float32)
        _, trees = jax.lax.scan(tree_step, pred0, keys)
        return trees

    return jax.jit(jax.shard_map(
        named_program("gbt_forest", gbt_forest, phases=PHASES), mesh=mesh,
        in_specs=(P(None, axis), P(axis), P(axis), P(), P(), P(), P(), P()),
        out_specs=(P(), P(), P(), P())))


def fit_table(est, table, *, held: Optional[np.ndarray] = None,
              one_part: bool = False):
    """``fit(Table)`` on a dense features column: ``(feats, bins, gains,
    leaves)`` as the chip returned them (a tree a row: heap-ordered
    splits, their gains, the leaves), the base score and the ``[features,
    maxBins - 1]`` edges the bins were cut by. The caller's span ``fit``
    holds all of it. ``held`` are the rows (ascending) of the estimator's
    holdout, which the fit leaves out; ``one_part`` is the benchmark's
    control's alone."""
    from flinkml_tpu.kernels import _mosaic

    features_col, label_col = est.get(est.FEATURES_COL), est.get(est.LABEL_COL)
    weight_col = est.get(est.WEIGHT_COL)
    mesh = est.mesh or DeviceMesh()
    p = mesh.axis_size()
    _mosaic.import_beside_host_work()
    max_bins, depth, seed = est.get(est.MAX_BINS), est.get(est.MAX_DEPTH), est.get_seed()
    x = features_matrix(table, features_col, dtype=None)
    if x.ndim != 2:
        raise ValueError(f"features must be [n, d], got {x.shape}")
    facts = LabelFacts.of(table, label_col)
    _check_rows(label_col, facts.values, x.shape[0])
    if est._LOGISTIC:
        check_binary_labels(facts, type(est).__name__)
    # The seed of the edges: none where every row is in the sample.
    sampled = x.shape[0] - (0 if held is None else held.shape[0]) > BIN_SAMPLE_ROWS
    placed = table.device_resident(
        ("gbt_bins_on_mesh", features_col, label_col, weight_col, mesh.mesh,
         max_bins, seed if sampled else None,
         None if held is None else (est.get(est.VALIDATION_FRACTION), seed)),
        lambda make_room: place(
            x, facts,
            None if weight_col is None else
            np.asarray(table.column(weight_col)).reshape(-1),
            max_bins, seed, mesh, make_room, held))
    if est._LOGISTIC:
        pos, neg = placed.label_sum, placed.weight_sum - placed.label_sum
        base = float(np.log(max(pos, 1e-12) / max(neg, 1e-12)))
    else:
        base = placed.label_sum / placed.weight_sum
    n_feat, n_local = placed.bins.shape[0], placed.bins.shape[1] // p
    fraction = est._feat_fraction(n_feat)
    feat_subset = 0 if fraction >= 1.0 else max(1, int(round(fraction * n_feat)))
    num_trees = est.get(est.NUM_TREES)
    product_levels = tuple(
        gbt_hist.unsupported_reason(jnp.float32, placed.bins.dtype, n_feat,
                                    n_local, 1 << level, max_bins) is None
        for level in range(depth))
    run = _program(mesh.mesh, DeviceMesh.DATA_AXIS, n_feat, max_bins, depth,
                   num_trees, est._LOGISTIC, est._BOOSTING, feat_subset,
                   one_part, product_levels)
    f32 = lambda v: np.asarray(v, np.float32)
    with span("gbt.loop"):
        with span("gbt.dispatch"):
            out = run(placed.bins, placed.y, placed.w, f32(base),
                      f32(est.get(est.LEARNING_RATE)), f32(est.get(est.REG_LAMBDA)),
                      f32(est.get(est.SUBSAMPLE)), jax.random.PRNGKey(seed))
        # The caller reads the forest next: waiting here costs nothing
        # and gives the loop a span its device time lies in.
        jax.block_until_ready(out)
    with span("gbt.readback"):
        feats, bins, gains, leaves = (np.asarray(a) for a in out)
    counters = metrics.group("gbt")
    levels = float(num_trees * depth)
    counters.counter("fits")
    counters.counter("trees", float(num_trees))
    counters.counter("levels", levels)
    counters.counter("product_levels", float(num_trees * sum(product_levels)))
    counters.counter("folded_levels", float(num_trees * sum(
        taken and gbt_hist.fold(1 << level)
        for level, taken in enumerate(product_levels))))
    counters.counter("rows", float(placed.rows))
    counters.counter("hist_cells", levels * placed.bins.shape[1] * n_feat)
    return feats, bins, gains, leaves, base, placed.edges
