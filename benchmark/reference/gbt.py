"""Histogram gradient boosting, NumPy float64, the benchmark's own copy
(it imports nothing of the program or of ``tests/``): XGBoost's histogram
method (``tree_method=hist``; Chen and Guestrin, KDD 2016) as the
configuration ``gbt-airline`` states it, **following the program's own
trees**.

    bin[i, f]  = #{edges[f] < x[i, f]}                       (0 .. 255)
    a round    : p = sigmoid(pred); g = (p - y) w; h = max(p (1 - p), 1e-6) w
    a node     : G_L(f, b) = sum of g over its rows with bin[., f] <= b, H_L alike
                 gain(f, b) = G_L^2 / (H_L + lambda) + G_R^2 / (H_R + lambda)
                              - G^2 / (H + lambda)
                 0 where a side is empty (H_L or H_R not > 0) and at the last bin
    a leaf     : -G / max(H + lambda, 1e-12)
    after it   : pred += learning_rate * leaf of the row
    edges[f]   = the distinct finite values among the max_bins - 1 interior
                 quantiles (np.quantile's linear rule at linspace(0, 1,
                 max_bins + 1)[1:-1]) of feature f over the sample: the
                 rows default_rng(seed).choice(rows, sample_rows, replace=
                 False) names (every row where the table has no more), in
                 float64, padded with +inf                  (:func:`edges_of`)
    base score = log(sum of w y / sum of w (1 - y))         (:func:`base_of`)

Two splits of a node may lie within a rounding of each other, and a sound
float32 fit may then choose the other one: so nothing here chooses. For
each tree in turn :func:`follow` computes ``g`` and ``h`` in float64 from
its OWN float64 prediction (the base score, then the learning rate times
its own float64 leaf values over the program's partition), walks every
row down the program's splits, and at every inner node sums the float64
histograms of the rows the program sent there. From them: the float64
gain of EVERY (feature, bin) of the node, the float64 gain of the
program's split, and at the last level the float64 leaf values. The
driver compares the program's leaves and gains with these, and how far
under the node's best float64 gain the program's split lies.

**Departures from XGBoost**, each noted: complete trees of ``max_depth``
levels with no ``min_child_weight`` or ``gamma`` pruning (every inner
node splits, at gain 0 where nothing gains); the edges are quantiles of a
seeded row sample where the source sketches all rows; a split is ``bin <=
b`` over at most 256 quantile bins; the hessian's floor of 1e-6.

The edges and the base score are this module's OWN (:func:`edges_of`,
:func:`base_of`, from the table, the labels and the seed alone): the
driver bins and starts by them, and holds the program's to them, so that
a program that sampled other rows, took its quantiles in float32, kept
fewer bins or started from another score is seen. Only the trees are the
program's.

*How it is made to fit a run.* A level is 1.5 G keyed additions a
statistic at the cell's size, and ``np.bincount`` neither releases the
interpreter's lock nor takes less than 3 ns an addition: the rows are cut
in :data:`WORKERS` slices, each held by a child process that imports
NumPy alone (``python reference/gbt.py``; it never sees the chip), reads
its slice of the binned table and of the labels from two files the parent
wrote under ``scratch`` (removed at the end) and talks pickles over its
pipes, and
the parent adds the slices' float64 histograms. ``workers=0`` keeps
everything in the caller's process (the tests' tables).
"""

from __future__ import annotations

import concurrent.futures as cf
import os
import pickle
import shutil
import subprocess
import sys
import tempfile

import numpy as np

BINS = 256
#: Child processes a followed fit is cut over, and the threads of the
#: binning.
WORKERS = max(1, min(12, (os.cpu_count() or 2) - 1))
#: Rows a slice sums at a time (the keys' int64 copy is 8 MB).
_BLOCK_ROWS = 1 << 20
_BIN_CHUNK_ROWS = 1 << 18
#: Entries of a feature's table of sums up to which it is copied (64 KB:
#: a core's first cache).
_SPREAD_ENTRIES = 8192


def edges_of(x: np.ndarray, max_bins: int, seed: int, sample_rows: int) -> np.ndarray:
    """``[features, max_bins - 1]`` float64: the configuration's bin edges
    of the table ``x [rows, features]`` at ``seed``, by the rule of the
    module docstring."""
    n, d = x.shape
    if n > sample_rows:
        x = x[np.sort(np.random.default_rng(seed).choice(n, sample_rows, replace=False))]
    sample = np.asarray(x, np.float64)
    interior = np.linspace(0.0, 1.0, max_bins + 1)[1:-1]
    edges = np.full((d, max_bins - 1), np.inf)
    for f in range(d):
        distinct = np.unique(np.quantile(sample[:, f], interior))
        distinct = distinct[np.isfinite(distinct)]
        edges[f, :distinct.shape[0]] = distinct
    return edges


def base_of(y: np.ndarray, weights=None) -> float:
    """The base score: the training log-odds ``log(sum of w y / sum of w
    (1 - y))``, the sums in float64 (the second as the weights' sum less
    the first; each floored at 1e-12)."""
    if weights is None:
        positive, total = float(np.sum(y, dtype=np.float64)), float(np.shape(y)[0])
    else:
        positive = float(np.dot(np.asarray(weights, np.float64), y))
        total = float(np.sum(weights, dtype=np.float64))
    return float(np.log(max(positive, 1e-12) / max(total - positive, 1e-12)))


def bins_of(x: np.ndarray, edges: np.ndarray, out=None) -> np.ndarray:
    """``[features, rows]`` uint8 (into ``out``): ``#{edges[f] < x[i,
    f]}``, the comparison in float64, chunks of rows on threads."""
    n, d = x.shape
    if out is None:
        out = np.empty((d, n), np.uint8)

    def chunk(lo):
        part = np.asarray(x[lo:lo + _BIN_CHUNK_ROWS], np.float64)
        for f in range(d):
            out[f, lo:lo + part.shape[0]] = np.searchsorted(
                edges[f], part[:, f], side="left")

    with cf.ThreadPoolExecutor(WORKERS) as pool:
        list(pool.map(chunk, range(0, n, _BIN_CHUNK_ROWS)))
    return out


class Rows:
    """A slice of the table's rows and the followed fit's state on them:
    the float64 prediction, and the leaf of the last tree each row is in
    (29 bytes a row: ``g`` and ``h`` are made a block at a time)."""

    def __init__(self, bins: np.ndarray, y: np.ndarray, w):
        self.bins, self.y, self.w = bins, y, w
        self.pred = self.leaf = None

    def start(self, base: float) -> None:
        self.pred = np.full(self.y.shape[0], base, np.float64)

    def _blocks(self):
        return (slice(lo, lo + _BLOCK_ROWS)
                for lo in range(0, self.y.shape[0], _BLOCK_ROWS))

    def loss(self) -> float:
        """The sum of the rows' weighted logistic losses at ``pred``."""
        total = 0.0
        for rows in self._blocks():
            pred = self.pred[rows]
            each = (np.log1p(np.exp(-np.abs(pred))) + np.maximum(pred, 0.0)
                    - self.y[rows] * pred)
            total += float(each.sum() if self.w is None else (each * self.w[rows]).sum())
        return total

    def _stats(self, rows):
        """``g`` and ``h`` of a block of rows, float64."""
        prob = 1.0 / (1.0 + np.exp(-self.pred[rows]))
        g, h = prob - self.y[rows], np.maximum(prob * (1.0 - prob), 1e-6)
        return (g, h) if self.w is None else (g * self.w[rows], h * self.w[rows])

    def tree(self, feats: np.ndarray, cuts: np.ndarray, depth: int):
        """The rows walked down one tree's splits (heap order: level L's
        nodes from ``2^L - 1``): a level's ``[2, nodes, features, 256]``
        float64 sums of g and h, a list over the levels."""
        n, d = self.y.shape[0], self.bins.shape[0]
        node = np.zeros(n, np.int32)
        row = np.arange(min(n, _BLOCK_ROWS))
        levels = []
        for level in range(depth):
            nodes, first = 1 << level, (1 << level) - 1
            # A bin that holds most rows (Diverted's) would make every
            # addition wait for the one before it: the rows take turns
            # over ``copies`` copies of the table, folded at the end.
            size = nodes * BINS
            copies = max(1, _SPREAD_ENTRIES // size)
            turn = (row % copies) * size
            sums = np.zeros((2, d, copies * size))
            for rows in self._blocks():
                g, h = self._stats(rows)
                mine = node[rows].astype(np.int64)
                at = mine * BINS + turn[:mine.shape[0]]
                for f in range(d):
                    key = at + self.bins[f, rows]
                    sums[0, f] += np.bincount(key, g, copies * size)
                    sums[1, f] += np.bincount(key, h, copies * size)
                split = self.bins[:, rows][feats[first + mine], row[:mine.shape[0]]]
                node[rows] = 2 * mine + (split > cuts[first + mine])
            levels.append(sums.reshape(2, d, copies, nodes, BINS).sum(axis=2)
                          .transpose(0, 2, 1, 3))
        self.leaf = node
        return levels

    def advance(self, rate: float, leaves: np.ndarray) -> None:
        self.pred += rate * leaves[self.leaf]


class _Child:
    """A :class:`Rows` in a child process: the same calls, the answer
    fetched by :meth:`result` (so that every child works at once)."""

    def __init__(self, *where):
        self._p = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE)
        self._send("rows", where)

    def _send(self, op, args):
        pickle.dump((op, args), self._p.stdin, protocol=5)
        self._p.stdin.flush()

    def __getattr__(self, op):
        return lambda *args: self._send(op, args)

    def result(self):
        return pickle.load(self._p.stdout)

    def close(self):
        self._p.stdin.close()
        self._p.wait()


class _Here:
    """A :class:`Rows` in this process, called as a :class:`_Child` is."""

    def __init__(self, *args):
        self._rows, self._out = Rows(*args), None

    def __getattr__(self, op):
        def call(*args):
            self._out = getattr(self._rows, op)(*args)
        return call

    def result(self):
        return self._out

    def close(self):
        pass


def _serve() -> None:
    """A child's loop: ``(op, args)`` in, the answer out, until the pipe
    closes."""
    rd, wr = sys.stdin.buffer, sys.stdout.buffer
    rows = None
    while True:
        try:
            op, args = pickle.load(rd)
        except EOFError:
            return
        if op == "rows":
            # Its slice READ from the parent's files (a mapping would
            # count the whole table in every child).
            (bins, y, w), n, d, lo, hi = args

            def part(path, dtype, first):
                return np.fromfile(path, dtype, hi - lo,
                                   offset=(first + lo) * np.dtype(dtype).itemsize)

            rows = Rows(np.stack([part(bins, np.uint8, f * n) for f in range(d)]),
                        part(y, np.float32, 0),
                        None if w is None else part(w, np.float32, 0))
            continue
        pickle.dump(getattr(rows, op)(*args), wr, protocol=5)
        wr.flush()


def gains_of(sums: np.ndarray, lam: float, n_bins: int) -> np.ndarray:
    """``[nodes, features, n_bins]`` float64: the gain of every ``bin <=
    b`` split of a level's nodes from its sums ``[2, nodes, features,
    256]``, under the rules of the module docstring."""
    left = np.cumsum(sums[..., :n_bins], axis=-1)
    total = left[..., -1:]
    right = total - left
    (gl, hl), (gr, hr), (gt, ht) = left, right, total
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = gl * gl / (hl + lam) + gr * gr / (hr + lam) - gt * gt / (ht + lam)
    gain = np.where((hl > 0) & (hr > 0), gain, 0.0)
    gain[..., -1] = 0.0
    return gain


def follow(x: np.ndarray, y: np.ndarray, edges: np.ndarray, feats: np.ndarray,
           cuts: np.ndarray, *, base: float, rate: float, lam: float,
           n_bins: int, weights=None, workers: int = WORKERS,
           scratch=None) -> dict:
    """The float64 fit that follows the program's trees ``feats``,
    ``cuts`` (``[trees, 2^depth - 1]``: a node's split feature and the
    bin ``b`` of its ``bin <= b``) over the table ``x [rows, features]``,
    ``y`` binned by ``edges`` (:func:`edges_of`'s), from the base score
    ``base`` (:func:`base_of`'s) at learning rate ``rate`` and ``lambda``
    ``lam``. A tree a row of:

    - ``split_gain [trees, inner]``: the float64 gain of the program's
      split, as the program reports one (not under 0);
    - ``best_gain [trees, inner]``: the node's best float64 gain over
      every (feature, bin) (not under 0);
    - ``leaves [trees, 2^depth]``: the float64 leaf values over the
      program's partition;
    - ``root_gain [trees]``; ``loss_before`` and ``loss_after``, the mean
      logistic loss before the first tree and after the last.

    ``workers`` child processes hold a slice of the rows each (0: this
    process holds them all); ``scratch`` is where their two files go
    (None: the system's temporary directory)."""
    trees, inner = feats.shape
    depth = (inner + 1).bit_length() - 1
    n, d = x.shape
    cut = np.linspace(0, n, max(1, workers) + 1).astype(np.int64)
    if workers:
        made = tempfile.mkdtemp(prefix="gbt_reference_", dir=scratch)
        paths = [os.path.join(made, name) for name in ("bins", "y", "w")]
        bins_of(x, edges, np.memmap(paths[0], np.uint8, "w+", shape=(d, n))).flush()
        np.asarray(y, np.float32).tofile(paths[1])
        if weights is None:
            paths[2] = None
        else:
            np.asarray(weights, np.float32).tofile(paths[2])
        held = [_Child(paths, n, d, lo, hi) for lo, hi in zip(cut[:-1], cut[1:])]
    else:
        made, bins = None, bins_of(x, edges)
        held = [_Here(bins, y, weights)]

    def every(op, *args):
        for rows in held:
            getattr(rows, op)(*args)
        return [rows.result() for rows in held]

    total_weight = float(n if weights is None else np.sum(weights, dtype=np.float64))
    try:
        every("start", float(base))
        out = {"loss_before": sum(every("loss")) / total_weight,
               "split_gain": np.zeros((trees, inner)), "best_gain": np.zeros((trees, inner)),
               "leaves": np.zeros((trees, 1 << depth))}
        for t in range(trees):
            parts = every("tree", feats[t], cuts[t], depth)
            for level in range(depth):
                sums = np.sum([p[level] for p in parts], axis=0)
                nodes, first = 1 << level, (1 << level) - 1
                gain = gains_of(sums, lam, n_bins)
                at = (np.arange(nodes), feats[t, first:first + nodes],
                      cuts[t, first:first + nodes])
                out["split_gain"][t, first:first + nodes] = np.maximum(gain[at], 0.0)
                out["best_gain"][t, first:first + nodes] = np.maximum(
                    gain.reshape(nodes, -1).max(axis=1), 0.0)
            # The leaves' sums: the last level's, cut at the program's split.
            left = np.cumsum(sums, axis=-1)[(slice(None),) + at]
            both = np.stack([left, sums[:, :, 0, :].sum(axis=-1) - left], axis=2)
            g, h = both.reshape(2, -1)
            out["leaves"][t] = -g / np.maximum(h + lam, 1e-12)
            every("advance", float(rate), out["leaves"][t])
        out["loss_after"] = sum(every("loss")) / total_weight
    finally:
        for rows in held:
            rows.close()
        if made is not None:
            shutil.rmtree(made, ignore_errors=True)
    out["root_gain"] = out["best_gain"][:, 0].copy()
    return out


if __name__ == "__main__":
    _serve()
