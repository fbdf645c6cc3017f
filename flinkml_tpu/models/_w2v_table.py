"""``Word2Vec.fit(Table)``: skip-gram with negative sampling over a token
column that stays on the chip, the pairs drawn on the device, the update
a few rows of two tables and never a ``[vocab, dim]`` array.

- **Ingest**, once a ``Table`` (:func:`ingest`, NumPy, no loop over
  tokens): counts by ``np.bincount``, ``minCount`` pruning, the
  vocabulary by falling count (ties by the column's own order); the
  corpus re-coded to those ranks as ONE int32 array whose top bit marks a
  sentence's first token; beside it, a token a uint16, the threshold of
  its word's subsampling (``word2vec.c``'s own 16-bit comparison); and
  the negatives' pool, ``word2vec.c``'s table: each word a run of entries
  in proportion to ``count ** 0.75``, over the WHOLE vocabulary. All
  three are uploaded once and kept with the table
  (:meth:`Table.device_resident`).
- **The draw** (:func:`draw`), a function of (corpus, seed, step) made of
  counter-based bits (:func:`stream_key`, :func:`bits`): a step offers
  :func:`candidates_a_step` candidates, each a corpus position (the centre),
  a side and an ordinal ``j`` with the weight ``window - j + 1`` (a reach
  uniform in ``1 .. window`` holds the ``j``-th neighbour that often). A
  candidate is a pair where its centre survives subsampling and its side
  holds a ``j``-th SURVIVING token of the same sentence within ``span``
  positions: so the pairs are ``word2vec.c``'s multiset, each equally
  likely. The batch is the first ``batch`` pairs in candidate order
  (should a step ever find fewer, they are taken again in turn: every
  slot holds a real pair). A pair's negatives are ``numNegatives`` entries
  of the pool.
- **The step**: the batch's ``2 + numNegatives`` rows a pair fetched,
  :func:`word2vec._sgns_pair_grads` on them, the gradients times ``-
  learningRate / batch`` added to their rows in place (rows that collide
  are summed): the module's mean-of-batch step, every gradient taken at
  the step's start, so a step's updates commute. On a TPU they go in
  sorted order (:mod:`flinkml_tpu.kernels.row_update`: a table's (id,
  contribution) entries sorted by id, stably, and every distinct group of
  eight rows read, added to and written ONCE, many groups in flight; the
  output table's contexts and negatives are one list); every other
  backend keeps XLA's three scatter-adds, a read-modify-write a named
  row one after another. The same float32 sums either way, in another
  order.
- **One program**, ``w2v_sgns_loop``: seed, rate and step count are
  operands. The tables are held ``[vocab, dim rounded up to 128]``: as
  ``[vocab, 300]`` a v5e lays the WORDS along the lanes and re-lays both
  tables around every gather.
- On a mesh of ``p > 1`` devices the corpus is replicated, every device
  makes the same draw and takes its share of the batch, and the tables are
  row-sharded under :func:`word2vec._sgns_trainer_sharded` (the exchange).

Spans and counters: ``docs/development/observability.md`` (``w2v.*``; the
group ``w2v``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from flinkml_tpu.ops.sparse import LANES
from flinkml_tpu.parallel import DeviceMesh
from flinkml_tpu.parallel.mesh import gather_pool
from flinkml_tpu.utils.metrics import metrics
from flinkml_tpu.utils.profiling import named_program, phase, span

#: Top bit of a corpus entry: the token is its sentence's first. Padding
#: is word 0 so marked.
START = np.uint32(0x80000000)
PADDING = START.view(np.int32)
#: ``word2vec.c``'s table of negatives has 1e8 entries; a small
#: vocabulary takes at most this many a word.
POOL_ENTRIES, POOL_ENTRIES_A_WORD = 100_000_000, 128
#: A context is looked for within ``SPAN_A_REACH * windowSize`` corpus
#: positions of its centre.
SPAN_A_REACH = 8
#: Candidates a step offers: ``MARGIN[0] / MARGIN[1]`` for a pair it
#: needs, over the corpus's surviving share, up to a whole ``LANES``.
CANDIDATE_MARGIN = (3, 2)
#: Pieces a long host pass is cut in for the fit's pool of threads.
_PASS_CHUNKS = 16
_GOLDEN = 0x9E3779B9
#: The step's phases (``profiling.phase``); the scatter-adds sort nothing.
PHASES = ("w2v.draw", "w2v.fetch", "w2v.grads", "w2v.sort", "w2v.update")
PHASES_UNSORTED = tuple(p for p in PHASES if p != "w2v.sort")
# What each stream of bits draws (the configuration's file lists them).
(S_POSITION_HI, S_POSITION_LO, S_SIDE_ORDINAL, S_KEEP, S_NEGATIVE_HI,
 S_NEGATIVE_LO) = range(6)


def _mix(x):
    """A 32-bit finaliser (``lowbias32``), uint32 in and out."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def stream_key(seed, step, stream: int):
    """The key of one stream of one step: uint32 scalars ``seed`` and
    ``step``, ``stream`` one of the ``S_*``."""
    key = _mix(seed + jnp.uint32(_GOLDEN))
    key = _mix(key + step)
    return _mix(key + jnp.uint32((stream * _GOLDEN) & 0xFFFFFFFF))


def bits(key, counter):
    """32 bits for every ``counter`` (uint32) of the stream ``key``."""
    return _mix(key ^ counter)


def _mulhi(x, n: int):
    """``floor(x * n / 2**32)`` for uint32 ``x`` and ``0 <= n < 2**32``, in
    16-bit limbs: no 64-bit value, whatever ``jax_enable_x64`` says."""
    nh, nl = jnp.uint32(n >> 16), jnp.uint32(n & 0xFFFF)
    xh, xl = x >> 16, x & jnp.uint32(0xFFFF)
    m1 = xh * nl + ((xl * nl) >> 16)
    m2 = xl * nh + (m1 & jnp.uint32(0xFFFF))
    return xh * nh + (m1 >> 16) + (m2 >> 16)


def uniform_index(hi, lo, n: int):
    """``floor((hi * 2**32 + lo) * n / 2**64)``: an index below ``n`` from
    64 bits, int32."""
    low = hi * jnp.uint32(n)                      # wraps: the product's low word
    carry = (low + _mulhi(lo, n)) < low
    return (_mulhi(hi, n) + carry.astype(jnp.uint32)).astype(jnp.int32)


class Draw(NamedTuple):
    """The shapes of a step's draw (static: they key the program)."""
    batch: int
    negatives: int
    window: int
    candidates: int      # offered a step: see candidates_a_step
    tokens: int          # the corpus's tokens, without its padding
    pool_entries: int

    @property
    def span(self) -> int:
        return SPAN_A_REACH * self.window


def draw(d: Draw, tokens, keep, pool, seed, step):
    """Step ``step``'s batch: ``(centre [batch], context [batch], negatives
    [batch, negatives], pairs found)``, word ranks, int32. ``tokens`` and
    ``keep`` are the corpus as :func:`ingest` lays it: rows of
    :data:`LANES`, ``span`` entries of padding before the first token."""
    span_, m = d.span, d.candidates
    key = functools.partial(stream_key, seed, step)
    at = jnp.arange(m, dtype=jnp.uint32)
    centre = uniform_index(bits(key(S_POSITION_HI), at),
                           bits(key(S_POSITION_LO), at), d.tokens)
    choice = bits(key(S_SIDE_ORDINAL), at)
    right = (choice & jnp.uint32(1)) == 1
    # j with the weight window - j + 1: t uniform below window (window + 1)
    # / 2, j the first whose cumulated weight passes it.
    t = _mulhi(choice, d.window * (d.window + 1) // 2)
    ordinal = 1 + sum((t >= jnp.uint32(j * d.window - j * (j - 1) // 2)
                       ).astype(jnp.int32) for j in range(1, d.window))
    # A candidate's frame: the two rows of LANES corpus entries that hold
    # its centre and its side's span (the padded corpus holds position q at
    # q + span; whole rows are what a chip fetches fast). Nothing is
    # shifted: a lane knows how far from the centre it lies.
    first = centre + jnp.where(right, jnp.int32(span_), jnp.int32(0))
    row = first // LANES
    rows = jnp.stack([row, row + 1], axis=1)
    toks = tokens[rows].reshape(m, 2 * LANES)
    thr = keep[rows].reshape(m, 2 * LANES).astype(jnp.uint32)
    lane = jnp.arange(2 * LANES, dtype=jnp.int32)
    away = lane - (centre + span_ - row * LANES)[:, None]
    position = centre[:, None] + away
    alive = (bits(key(S_KEEP), position.astype(jnp.uint32)) >> 16) <= thr
    at_centre = away == 0
    on_side = jnp.where(right[:, None], (away > 0) & (away <= span_),
                        (away < 0) & (away >= -span_))
    starts = (toks < 0).astype(jnp.int32)
    seen = jnp.cumsum(starts, axis=1, dtype=jnp.int32)
    seen_c = jnp.sum(jnp.where(at_centre, seen, 0), axis=1, dtype=jnp.int32)[:, None]
    # To the right a sentence's first token and all beyond it are another
    # sentence's; to the left, all beyond the nearest first token (the
    # centre itself may be it).
    ended = jnp.where(right[:, None], seen - seen_c, seen_c - seen) > 0
    usable = alive & on_side & ~ended
    count = jnp.cumsum(usable.astype(jnp.int32), axis=1, dtype=jnp.int32)
    count_c = jnp.sum(jnp.where(at_centre, count, 0), axis=1, dtype=jnp.int32)[:, None]
    nth = jnp.where(right[:, None], count - count_c,
                    count_c - count + usable.astype(jnp.int32))
    picked = usable & (nth == ordinal[:, None])
    words = toks & jnp.int32(0x7FFFFFFF)
    context = jnp.sum(jnp.where(picked, words, 0), axis=1, dtype=jnp.int32)
    word = jnp.sum(jnp.where(at_centre, words, 0), axis=1, dtype=jnp.int32)
    pair = jnp.any(alive & at_centre, axis=1) & jnp.any(picked, axis=1)
    # The first `batch` pairs, in candidate order.
    rank = jnp.cumsum(pair.astype(jnp.int32), dtype=jnp.int32) - 1
    found = rank[-1] + 1
    slot = jnp.where(pair & (rank < d.batch), rank, d.batch)
    chosen = jnp.zeros(d.batch, jnp.int32).at[slot].set(
        jnp.arange(m, dtype=jnp.int32), mode="drop")
    # Fewer than a batch (never, at CANDIDATE_MARGIN, on a corpus of any
    # size): those found, again in turn.
    turn = jnp.arange(d.batch, dtype=jnp.int32) % jnp.maximum(
        jnp.minimum(found, d.batch), 1)
    chosen = chosen[turn]
    each = jnp.arange(d.batch * d.negatives, dtype=jnp.uint32)
    entry = uniform_index(bits(key(S_NEGATIVE_HI), each),
                          bits(key(S_NEGATIVE_LO), each), d.pool_entries)
    return (word[chosen], context[chosen],
            pool[entry].reshape(d.batch, d.negatives), found)


def padded_dim(dim: int) -> int:
    return -(-dim // LANES) * LANES


@functools.lru_cache(maxsize=16)
def _program(d: Draw, score_dtype=None, sorted_updates: bool = False):
    """The whole fit on one device, ``w2v_sgns_loop``: ``(v, u [rows,
    padded_dim], tokens, keep, pool, seed, rate, steps) -> (v, u)``. ``v``
    and ``u`` are donated: the rows are updated where they lie, by XLA's
    scatter-adds or, ``sorted_updates``, by ``kernels.row_update`` (the
    same sums in the sorted list's order; :func:`fit_table` says where)."""
    from flinkml_tpu.kernels import row_update
    from flinkml_tpu.models.word2vec import _sgns_pair_grads

    def w2v_sgns_loop(v, u, tokens, keep, pool, seed, rate, steps):
        def step(t, tables):
            v, u = tables
            with phase("w2v.draw"):
                c, ctx, neg, found = draw(d, tokens, keep, pool, seed,
                                          t.astype(jnp.uint32))
            ones = jnp.ones(d.batch, v.dtype)
            with phase("w2v.fetch"):
                vc, uc, un = v[c], u[ctx], u[neg]
            with phase("w2v.grads"):
                grad_vc, grad_uc, grad_un = _sgns_pair_grads(
                    vc, uc, un, ones, score_dtype=score_dtype)
                scale = -jnp.where(found > 0, rate, 0.0) / d.batch
            if sorted_updates:
                with phase("w2v.sort"):
                    # The output rows' entries as ONE list, an ordinal a
                    # run of the batch (a negative's ordinal leads: no
                    # [batch, 5, lanes] array padded to eight sublanes and
                    # re-laid).
                    ids = jnp.concatenate([ctx[None], neg.T]).reshape(-1)
                    rows = jnp.concatenate([
                        (scale * grad_uc)[None],
                        jnp.moveaxis(scale * grad_un, 1, 0)])
                    of_v = row_update.sorted_entries(c, scale * grad_vc)
                with phase("w2v.update"):
                    v = row_update.add_rows_sorted(v, *of_v)
                with phase("w2v.sort"):
                    of_u = row_update.sorted_entries(
                        ids, rows.reshape(-1, rows.shape[-1]))
                with phase("w2v.update"):
                    return v, row_update.add_rows_sorted(u, *of_u)
            with phase("w2v.update"):
                v = v.at[c].add(scale * grad_vc)
                u = u.at[ctx].add(scale * grad_uc)
                u = u.at[neg.reshape(-1)].add(
                    (scale * grad_un).reshape(-1, grad_un.shape[-1]))
            return v, u

        return jax.lax.fori_loop(jnp.int32(0), steps, step, (v, u))

    return jax.jit(
        named_program("w2v_sgns_loop", w2v_sgns_loop,
                      phases=PHASES if sorted_updates else PHASES_UNSORTED),
        donate_argnums=(0, 1))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _start_tables(seed, vocab: int, dim: int, rows: int, lanes: int):
    """Both tables as a fit starts them, ``[rows, lanes]``: the word
    vectors :func:`word2vec.start_vectors` in the first ``[vocab, dim]``,
    zeros around them; the output vectors 0."""
    from flinkml_tpu.models.word2vec import start_vectors

    v = jnp.pad(start_vectors(seed, vocab, dim),
                ((0, rows - vocab), (0, lanes - dim)))
    return v, jnp.zeros_like(v)


class _Placed(NamedTuple):
    """A table's corpus on the mesh, and what a fit needs of its ingest."""
    tokens: jax.Array       # int32 [rows, LANES], START on a first token
    keep: jax.Array         # uint16, as large
    pool: jax.Array         # int32 [pool entries]
    vocabulary: np.ndarray  # the kept words by rank, str (host)
    n_tokens: int
    alive: int              # see Ingested
    pairs_an_epoch: int


class Ingested(NamedTuple):
    """:func:`ingest`'s host arrays."""
    tokens: np.ndarray
    keep: np.ndarray
    pool: np.ndarray
    order: np.ndarray       # the column's vocabulary positions, by rank
    counts: np.ndarray      # their counts
    alive: int              # 65536 times the tokens that survive, expected


def keep_thresholds(counts: np.ndarray, subsample: float) -> np.ndarray:
    """A word's threshold by rank, uint16: an occurrence survives where 16
    fresh bits are ``<=`` it. ``word2vec.c``: with ``f`` the word's share
    of the tokens it survives with probability ``sqrt(t / f) + t / f``,
    compared against ``(next_random & 0xFFFF) / 65536``."""
    if subsample <= 0:
        return np.full(counts.shape[0], 0xFFFF, np.uint16)
    share = counts.astype(np.float64) / counts.sum()
    ran = np.sqrt(subsample / share) + subsample / share
    return np.clip(np.floor(ran * 65536.0), 0, 0xFFFF).astype(np.uint16)


def negative_pool(counts: np.ndarray, entries: int) -> np.ndarray:
    """``word2vec.c``'s table: word ``r`` holds the entries from ``floor(
    entries * C[r - 1])`` up to ``floor(entries * C[r])``, ``C`` the
    cumulated share of ``count ** 0.75``: int32 ``[entries]``."""
    weight = counts.astype(np.float64) ** 0.75
    bounds = np.floor(np.cumsum(weight) / weight.sum() * entries).astype(np.int64)
    bounds[-1] = entries
    return np.repeat(np.arange(counts.shape[0], dtype=np.int32),
                     np.diff(bounds, prepend=0))


def pool_entries(vocab: int) -> int:
    return min(POOL_ENTRIES, POOL_ENTRIES_A_WORD * vocab)


def _chunks(n: int, parts: int):
    step = -(-n // max(1, parts))
    return [(lo, min(lo + step, n)) for lo in range(0, n, step)]


def ingest(column, min_count: int, subsample: float, span_: int) -> Ingested:
    """The corpus of a :class:`~flinkml_tpu.table.TokenColumn` as the
    device holds it. Whole-array NumPy, the long passes on a few
    threads."""
    ids, indptr = column.ids, column.indptr
    words = column.vocabulary.shape[0]
    with gather_pool() as pool:
        counts = sum(pool.map(lambda c: np.bincount(ids[c[0]:c[1]], minlength=words),
                              _chunks(ids.shape[0], _PASS_CHUNKS)),
                     np.zeros(words, np.int64))
    order = np.argsort(-counts, kind="stable")
    order = order[counts[order] >= min_count].astype(np.int32)
    if not order.size:
        raise ValueError(
            f"no token reaches minCount={min_count}; vocabulary is empty")
    kept = counts[order]
    rank_of = np.full(counts.shape[0], -1, np.int32)
    rank_of[order] = np.arange(order.size, dtype=np.int32)
    pruned = order.size < counts.shape[0] and bool(
        (counts[rank_of < 0] > 0).any())
    n = int(kept.sum())
    # Rows of LANES entries, a span of padding before the first token and
    # a span and a row after the last: a frame is two whole rows. Padding
    # reads as a sentence's first token: no context lies past it.
    size = (-(-(n + 2 * span_) // LANES) + 1) * LANES
    tokens = np.empty(size, np.int32)
    keep = np.zeros(size, np.uint16)
    tokens[:span_] = tokens[span_ + n:] = PADDING
    body, body_keep = tokens[span_:span_ + n], keep[span_:span_ + n]
    with gather_pool() as pool:
        workers = _PASS_CHUNKS
        def look_up(table, index, out):
            # (np.take(..., out=) checks every index first: twelve times
            # as long)
            def piece(c):
                out[c[0]:c[1]] = table[index[c[0]:c[1]]]
            list(pool.map(piece, _chunks(index.shape[0], workers)))

        if pruned:
            ranks = np.empty(ids.shape[0], np.int32)
            look_up(rank_of, ids, ranks)
            inside = ranks >= 0
            upto = np.zeros(ids.shape[0] + 1, np.int64)
            np.cumsum(inside, out=upto[1:])
            indptr = upto[indptr]
            body[:] = ranks[inside]
        else:
            look_up(rank_of, ids, body)
        thresholds = keep_thresholds(kept, subsample)
        look_up(thresholds, body, body_keep)
    firsts = indptr[:-1][np.diff(indptr) > 0]
    body.view(np.uint32)[firsts] |= START
    alive = int((kept * (thresholds.astype(np.int64) + 1)).sum())
    return Ingested(tokens.reshape(-1, LANES), keep.reshape(-1, LANES),
                    negative_pool(kept, pool_entries(order.size)), order, kept,
                    alive)


def candidates_a_step(batch: int, tokens: int, alive: int) -> int:
    """Candidates a step offers for a batch of ``batch`` pairs over a
    corpus of ``tokens`` tokens of which ``alive / 65536`` survive
    subsampling: ``batch * MARGIN / share``, up to whole ``LANES``, in
    integers (the benchmark's reference has to find the same)."""
    up, down = CANDIDATE_MARGIN
    return -(-(up * 65536 * tokens * batch) // (down * alive * LANES)) * LANES


def place(column, min_count: int, subsample: float, window: int,
          mesh: DeviceMesh, make_room) -> _Placed:
    """A table's corpus ingested (``w2v.ingest``) and put on the mesh,
    replicated (``w2v.table_to_device``). ``make_room`` is the table's
    that will keep it, told the bytes first."""
    with span("w2v.ingest"):
        got = ingest(column, min_count, subsample, SPAN_A_REACH * window)
        vocabulary = np.asarray(column.vocabulary[got.order]).astype(str)
    devices = list(mesh.mesh.devices.flat)
    nbytes = (got.tokens.nbytes + got.keep.nbytes + got.pool.nbytes) * len(devices)
    make_room(nbytes, devices)
    with span("w2v.table_to_device") as phase:
        placed = jax.block_until_ready(
            mesh.replicate((got.tokens, got.keep, got.pool)))
        phase.add(bytes=nbytes)
    counters = metrics.group("w2v")
    counters.counter("table_uploads")
    counters.counter("table_h2d_bytes", float(nbytes))
    n = int(got.counts.sum())
    # A surviving centre holds a reach of (window + 1) / 2 on either side.
    return _Placed(*placed, vocabulary, n, got.alive,
                   max(1, got.alive * (window + 1) // 65536))


def as_token_column(table, name: str):
    """The table's token column as arrays: its own, or an object column
    of token lists encoded once (``np.unique`` over all its tokens)."""
    from flinkml_tpu.models.text import _token_column
    from flinkml_tpu.table import TokenColumn

    return table.token_column(name) or TokenColumn.from_lists(
        _token_column(table, name))


def fit_table(est, table, score_dtype=None):
    """``Word2Vec.fit(Table)``: ``(vocabulary [vocab] str, vectors [vocab,
    dim] float32)`` as the chip returned them. The caller's span ``fit``
    holds all of it. ``score_dtype`` is the benchmark's control's alone."""
    from flinkml_tpu.kernels import _mosaic, row_update
    from flinkml_tpu.models import word2vec

    name = est.get(est.INPUT_COL)
    mesh = est.mesh or DeviceMesh()
    p = mesh.axis_size()
    if p == 1:
        # A TPU's step holds the sorted update's kernel: what tracing it
        # imports loads beside the ingest.
        _mosaic.import_beside_host_work()
    window, min_count = est.get(est.WINDOW_SIZE), est.get(est.MIN_COUNT)
    subsample = float(est.get(est.SUBSAMPLE))
    placed = table.device_resident(
        ("w2v_corpus_on_mesh", name, mesh.mesh, min_count, subsample, window),
        lambda make_room: place(as_token_column(table, name), min_count,
                                subsample, window, mesh, make_room))
    vocab, dim = placed.vocabulary.shape[0], est.get(est.VECTOR_SIZE)
    local_bs = max(1, est.get(est.BATCH_SIZE) // p)
    batch, negatives = local_bs * p, est.get(est.NUM_NEGATIVES)
    d = Draw(batch, negatives, window,
             candidates_a_step(batch, placed.n_tokens, placed.alive),
             placed.n_tokens, int(placed.pool.shape[0]))
    steps = est.get(est.MAX_STEPS) or (
        max(1, placed.pairs_an_epoch // batch) * est.get(est.MAX_ITER))
    seed = np.uint32(est.get_seed() & 0xFFFFFFFF)
    rate = np.float32(est.get(est.LEARNING_RATE))
    # Where the rows are updated in sorted order, a group of eight at a
    # time, the tables end on a whole group.
    whole_groups = -(-vocab // row_update.GROUP) * row_update.GROUP
    sorted_updates = row_update.unsupported_reason(
        jnp.float32, whole_groups, padded_dim(dim), p) is None
    with span("w2v.init"):
        if p == 1:
            rows = whole_groups if sorted_updates else vocab
            with jax.default_device(mesh.mesh.devices.flat[0]):
                v, u = _start_tables(est.get_seed(), vocab, dim, rows,
                                     padded_dim(dim))
            run = _program(d, score_dtype, sorted_updates)
        else:
            shard_rows = -(-vocab // p)
            v, u = map(mesh.shard_batch, _start_tables(
                est.get_seed(), vocab, dim, shard_rows * p, dim))
            run = word2vec._sgns_trainer_sharded(
                mesh.mesh, DeviceMesh.DATA_AXIS, local_bs, negatives, shard_rows,
                word2vec._exchange_strategy(), corpus=d, score_dtype=score_dtype)
    with span("w2v.loop"):
        with span("w2v.dispatch"):
            out = run(v, u, placed.tokens, placed.keep, placed.pool, seed, rate,
                      np.int32(steps))
        # The caller reads the vectors next: waiting here costs nothing
        # and gives the loop a span its device time lies in.
        jax.block_until_ready(out)
    with span("w2v.readback"):
        vectors = np.asarray(out[0][:vocab, :dim])
    counters = metrics.group("w2v")
    counters.counter("fits")
    counters.counter("steps", float(steps))
    counters.counter("sorted_update_steps", float(steps) if sorted_updates else 0.0)
    counters.counter("pairs", float(steps) * batch)
    counters.counter("row_fetches", float(steps) * batch * (2 + negatives))
    counters.counter("row_updates", float(steps) * batch * (2 + negatives))
    counters.counter("tokens", float(placed.n_tokens))
    return placed.vocabulary, vectors
