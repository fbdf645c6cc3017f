"""Skip-gram with negative sampling (Mikolov et al., NIPS 2013), NumPy
float64, the benchmark's own copy: it imports nothing of the program or
of ``tests/``. It re-derives every step's pairs and negatives from the
bits that ``configs/w2v-1bw.json`` ("draws") documents, and follows the
WHOLE fit from start vectors it is handed.

Departures from ``word2vec.c``, the configuration's ``departures``:

- a synchronous batch where the source is Hogwild: every gradient of a
  step is taken at the step's start, and a step moves the tables by
  ``rate`` times the batch's MEAN pair gradient (``rate / batch`` a pair);
- a constant rate where the source decays it linearly (nil over the
  cell's 0.15 % of an epoch);
- uniform draws where the source walks the corpus: a step's pairs are
  drawn with replacement from the multiset an epoch of the source makes,
  each pair equally likely (a centre position, a side, an ordinal ``j``
  with the weight ``window - j + 1``, which is how often a reach uniform
  in ``1 .. window`` holds the ``j``-th surviving neighbour), subsampling
  drawn afresh a step; a context is looked for within ``8 * window``
  corpus positions; a negative equal to its pair's centre or context is
  kept; the word vector is the CENTRE's and the context's the output
  vector (the source trains the context's word vector against the
  centre's output vector: the same multiset, the roles' names swapped).

Ingest: counts by ``np.bincount``; words rarer than ``min_count`` leave
the corpus (their sentence closes up); a word's rank is its place by
falling count, ties by the column's own order. Subsampling is the
source's 16-bit comparison: with ``f`` a word's share of the kept tokens
and ``ran = sqrt(t / f) + t / f``, an occurrence survives where 16 fresh
bits are ``<= floor(65536 ran)`` (capped at 65535). Negatives come from
the source's table: ``entries`` slots, word ``r`` holding those from
``floor(entries C[r - 1])`` up to ``floor(entries C[r])``, ``C`` the
cumulated share of ``count ** 0.75``.

The sums of a step's gradients by row are sparse products (a row a word
the step touches, a column a pair), not ``np.add.at``; a step is cut over
a few threads (the batch for the multipliers, the rows for their sums).
"""

from __future__ import annotations

import concurrent.futures as cf
import os
from typing import NamedTuple

import numpy as np
import scipy.sparse as sp

POOL_ENTRIES, POOL_ENTRIES_A_WORD = 100_000_000, 128
SPAN_A_REACH = 8
CANDIDATE_MARGIN = (3, 2)
LANES = 128
GOLDEN = 0x9E3779B9
M32 = 0xFFFFFFFF
(S_POSITION_HI, S_POSITION_LO, S_SIDE_ORDINAL, S_KEEP, S_NEGATIVE_HI,
 S_NEGATIVE_LO) = range(6)
_THREADS = max(1, min(12, os.cpu_count() or 1))


def mix_int(x: int) -> int:
    x ^= x >> 16
    x = (x * 0x7FEB352D) & M32
    x ^= x >> 15
    x = (x * 0x846CA68B) & M32
    return x ^ (x >> 16)


def mix(x: np.ndarray) -> np.ndarray:
    """``lowbias32`` on a uint32 array (products wrap)."""
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> np.uint32(15))
    x = x * np.uint32(0x846CA68B)
    return x ^ (x >> np.uint32(16))


def stream_key(seed: int, step: int, stream: int) -> np.uint32:
    key = mix_int(((seed & M32) + GOLDEN) & M32)
    key = mix_int((key + step) & M32)
    return np.uint32(mix_int((key + stream * GOLDEN) & M32))


def bits(key: np.uint32, counter: np.ndarray) -> np.ndarray:
    return mix(key ^ counter.astype(np.uint32))


def index(hi: np.ndarray, lo: np.ndarray, n: int) -> np.ndarray:
    """``floor((hi 2**32 + lo) n / 2**64)``, int64."""
    n = np.uint64(n)
    whole = hi.astype(np.uint64) * n + ((lo.astype(np.uint64) * n) >> np.uint64(32))
    return (whole >> np.uint64(32)).astype(np.int64)


class Corpus(NamedTuple):
    words: np.ndarray      # int32 [tokens]: each kept token's rank
    first: np.ndarray      # bool [tokens]: its sentence's first kept token
    keep: np.ndarray       # uint16 [vocab]: a rank's subsampling threshold
    pool: np.ndarray       # int32 [entries]: the negatives' table
    order: np.ndarray      # the column's vocabulary positions, by rank
    counts: np.ndarray     # int64 [vocab], by rank
    alive: int             # 65536 times the tokens expected to survive


def ingest(ids: np.ndarray, indptr: np.ndarray, vocab_size: int, min_count: int,
           subsample: float) -> Corpus:
    cuts = np.linspace(0, ids.shape[0], 4 * _THREADS + 1).astype(np.int64)
    with cf.ThreadPoolExecutor(_THREADS) as pool:
        counts = sum(pool.map(
            lambda lo, hi: np.bincount(ids[lo:hi], minlength=vocab_size),
            cuts[:-1], cuts[1:]), np.zeros(vocab_size, np.int64))
    order = np.argsort(-counts, kind="stable")
    order = order[counts[order] >= min_count]
    kept = counts[order]
    rank_of = np.full(vocab_size, -1, np.int32)
    rank_of[order] = np.arange(order.size, dtype=np.int32)
    lengths = np.diff(indptr)
    first = np.zeros(ids.shape[0], bool)
    if order.size == vocab_size or not (counts[rank_of < 0] > 0).any():
        words = np.empty(ids.shape[0], np.int32)

        def look_up(lo, hi):
            words[lo:hi] = rank_of[ids[lo:hi]]

        with cf.ThreadPoolExecutor(_THREADS) as pool:
            list(pool.map(look_up, cuts[:-1], cuts[1:]))
        first[indptr[:-1][lengths > 0]] = True
    else:
        ranks = rank_of[ids]
        inside = ranks >= 0
        # A sentence's first KEPT token: the kept token before which the
        # sentence has none.
        before = np.concatenate([[0], np.cumsum(inside)])
        starts = before[indptr]
        words = ranks[inside]
        first = np.zeros(words.shape[0], bool)
        first[starts[:-1][np.diff(starts) > 0]] = True
    n = int(kept.sum())
    if subsample > 0:
        share = kept / n
        ran = np.sqrt(subsample / share) + subsample / share
        keep = np.minimum(np.floor(ran * 65536.0), 65535).astype(np.uint16)
    else:
        keep = np.full(order.size, 65535, np.uint16)
    entries = min(POOL_ENTRIES, POOL_ENTRIES_A_WORD * order.size)
    weight = kept.astype(np.float64) ** 0.75
    bounds = np.floor(np.cumsum(weight) / weight.sum() * entries).astype(np.int64)
    bounds[-1] = entries
    pool = np.repeat(np.arange(order.size, dtype=np.int32),
                     np.diff(np.concatenate([[0], bounds])))
    alive = int((kept * (keep.astype(np.int64) + 1)).sum())
    return Corpus(words, first, keep, pool, order, kept, alive)


def candidates(c: Corpus, batch: int) -> int:
    """Candidates a step offers: ``batch * 3 / 2`` over the share of the
    tokens that survive, up to a whole 128, in integers."""
    up, down = CANDIDATE_MARGIN
    n = int(c.words.shape[0])
    return -(-(up * 65536 * n * batch) // (down * c.alive * LANES)) * LANES


def draw(c: Corpus, seed: int, step: int, batch: int, negatives: int,
         window: int):
    """Step ``step``'s ``(centre [batch], context [batch], negatives
    [batch, negatives])``, ranks; and how many pairs its candidates held."""
    n, span = c.words.shape[0], SPAN_A_REACH * window
    m = candidates(c, batch)
    at = np.arange(m, dtype=np.uint32)
    centre = index(bits(stream_key(seed, step, S_POSITION_HI), at),
                   bits(stream_key(seed, step, S_POSITION_LO), at), n)
    choice = bits(stream_key(seed, step, S_SIDE_ORDINAL), at)
    right = (choice & np.uint32(1)) == 1
    t = (choice.astype(np.uint64) * np.uint64(window * (window + 1) // 2)
         ) >> np.uint64(32)
    # Weights window, window - 1, ..., 1 on the ordinals 1 .. window.
    cumulated = np.cumsum(np.arange(window, 0, -1))
    ordinal = 1 + np.searchsorted(cumulated, t, side="right")
    keep_key = stream_key(seed, step, S_KEEP)

    def alive(position):
        return (bits(keep_key, position) >> np.uint32(16)) <= c.keep[c.words[position]]

    # Walk outwards from every candidate whose centre survives, one
    # position a turn, until its ordinal-th surviving neighbour, its
    # sentence's end or the span's.
    live = np.flatnonzero(alive(centre))
    context = np.full(m, -1, np.int64)
    seen = np.zeros(m, np.int64)
    for away in range(1, span + 1):
        if not live.size:
            break
        to_right = right[live]
        position = centre[live] + np.where(to_right, away, -away)
        inside = (position >= 0) & (position < n)
        live, position, to_right = live[inside], position[inside], to_right[inside]
        # Rightwards a first token is another sentence's; leftwards the
        # token just left behind must not have been its sentence's first.
        same = ~c.first[np.where(to_right, position, position + 1)]
        live, position = live[same], position[same]
        found = alive(position)
        seen[live] += found
        done = found & (seen[live] == ordinal[live])
        context[live[done]] = c.words[position[done]]
        live = live[~done]
    pairs = np.flatnonzero(context >= 0)
    found = pairs.size
    if found == 0:
        return None
    chosen = pairs[:batch]
    chosen = chosen[np.arange(batch) % chosen.size]
    each = np.arange(batch * negatives, dtype=np.uint32)
    entry = index(bits(stream_key(seed, step, S_NEGATIVE_HI), each),
                  bits(stream_key(seed, step, S_NEGATIVE_LO), each), c.pool.shape[0])
    return (c.words[centre[chosen]].astype(np.int64), context[chosen],
            c.pool[entry].reshape(batch, negatives).astype(np.int64), found)


def _pieces(n: int, parts: int):
    cuts = np.linspace(0, n, min(parts, max(n, 1)) + 1).astype(np.int64)
    return [(int(lo), int(hi)) for lo, hi in zip(cuts[:-1], cuts[1:]) if hi > lo]


def step(v: np.ndarray, u: np.ndarray, centre, context, negatives, rate: float,
         pool: cf.ThreadPoolExecutor = None, work: dict = None):
    """One step in place; the batch's mean loss before it. First every
    pair's scores and multipliers and the centres' gradients, from the
    tables as the step found them (the batch cut over the threads). Then a
    row that several pairs name receives the SUM of its gradients, as a
    sparse product: a row a word the step touches, a column a pair, the
    entry the pair's multiplier (entries that meet are summed), times the
    pairs' word vectors (for the output rows) or the centres' gradients
    (for the word vectors); the rows cut over the threads, no two writing
    one. ``work`` keeps the two ``[batch, dim]`` arrays from step to
    step."""
    batch, k = negatives.shape
    dim = v.shape[1]
    run = (lambda f, jobs: list(pool.map(f, jobs))) if pool else (
        lambda f, jobs: [f(j) for j in jobs])
    work = {} if work is None else work
    if "vc" not in work:
        work["vc"], work["grad_v"] = np.empty((batch, dim)), np.empty((batch, dim))
    vc_all, grad_v = work["vc"], work["grad_v"]
    g_pos, g_neg = np.empty(batch), np.empty((batch, k))

    def multipliers(piece):
        lo, hi = piece
        vc, uc = v[centre[lo:hi]], u[context[lo:hi]]
        un = u[negatives[lo:hi].reshape(-1)].reshape(hi - lo, k, dim)
        pos = np.einsum("bd,bd->b", vc, uc)
        neg = np.einsum("bd,bnd->bn", vc, un)
        g_pos[lo:hi] = 1.0 / (1.0 + np.exp(-pos)) - 1.0
        g_neg[lo:hi] = 1.0 / (1.0 + np.exp(-neg))
        vc_all[lo:hi] = vc
        grad_v[lo:hi] = g_pos[lo:hi, None] * uc + np.einsum("bn,bnd->bd", g_neg[lo:hi], un)
        return float(np.sum(np.logaddexp(0.0, -pos))
                     + np.sum(np.logaddexp(0.0, neg)))

    parts = 4 * _THREADS
    loss = sum(run(multipliers, _pieces(batch, parts))) / batch
    scale = rate / batch
    pairs = np.arange(batch)
    for table, ids, weights, of, rows_of in (
            (v, centre, np.ones(batch), pairs, grad_v),
            (u, np.concatenate([context, negatives.reshape(-1)]),
             np.concatenate([g_pos, g_neg.reshape(-1)]),
             np.concatenate([pairs, np.repeat(pairs, k)]), vc_all)):
        rows, inverse = np.unique(ids, return_inverse=True)
        sums = sp.csr_matrix((weights, (inverse, of)), shape=(rows.size, batch))

        def update(piece):
            lo, hi = piece
            table[rows[lo:hi]] -= scale * (sums[lo:hi] @ rows_of)

        run(update, _pieces(rows.size, parts))
    return loss


def fit(c: Corpus, start: np.ndarray, seed: int, rate: float, steps: int,
        batch: int, negatives: int, window: int):
    """``(v [vocab, dim] float64, losses [steps])`` after ``steps`` steps
    from the word vectors ``start`` (the output vectors start at 0). The
    draws of the steps are made ahead on a few threads; the steps follow
    one another."""
    v = np.asarray(start, np.float64).copy()
    u = np.empty_like(v)
    u.fill(0.0)          # touched in order: a step's scattered first writes crawl
    losses, work = np.zeros(steps), {}
    with cf.ThreadPoolExecutor(_THREADS) as drawing, \
            cf.ThreadPoolExecutor(_THREADS) as pool:
        ahead = [drawing.submit(draw, c, seed, t, batch, negatives, window)
                 for t in range(steps)]
        for t, drawn in enumerate(ahead):
            got = drawn.result()
            ahead[t] = None
            if got is not None:
                losses[t] = step(v, u, got[0], got[1], got[2], rate, pool, work)
    return v, losses
