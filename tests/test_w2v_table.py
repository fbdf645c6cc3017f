"""``Word2Vec.fit(Table)`` over a token column kept on the device
(``models/_w2v_table.py``) against the benchmark's plain float64 skip-gram
(``benchmark/reference/word2vec.py``, which imports nothing of the
program), on seeded corpora at small sizes, dimension 300 kept:

- whole fits at both of the cell's rates within :data:`TOL` of the
  reference's replay, in units of its widest movement; the same fit with
  the products' operands rounded to bfloat16 (what one pass of a TPU's MXU
  makes of them) fails it;
- the ingest and every step's draw equal to the reference's, id for id;
  the drawn pairs inside their sentences, the ordinals weighted as a
  uniform reach holds them, the survivors' share as ``word2vec.c``'s law
  has it, the negatives' frequencies as ``count ** 0.75`` over a
  vocabulary larger than 2**18;
- four and eight devices (``_sgns_trainer_sharded`` over the same draw)
  against the one-device program;
- what is kept with the ``Table``: a second fit uploads nothing, a second
  rate compiles nothing, a fit equals its repeat to the bit;
- an object column of token lists and the integer column of the same text
  give the same model, with ``minCount`` pruning words out of sentences;
- the token column under row-wise consumers.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import word2vec as reference  # noqa: E402
from flinkml_tpu.models import Word2Vec, _w2v_table, word2vec  # noqa: E402
from flinkml_tpu.parallel import DeviceMesh  # noqa: E402
from flinkml_tpu.table import Table, TokenColumn  # noqa: E402
from flinkml_tpu.utils.metrics import metrics  # noqa: E402

#: Float32 rows, scores and row sums against float64, in units of the
#: reference's widest movement: 16 steps read 8e-7 to 3e-6 here.
TOL = 2e-5
#: Operands rounded to bfloat16 read 6e-4 and more.
ROUNDED = 10 * TOL
DIM, WINDOW, NEGATIVES, MIN_COUNT, SUBSAMPLE = 300, 5, 5, 5, 1e-3
SEED = 2147493104 % (1 << 31)


def _mesh(p: int = 1) -> DeviceMesh:
    return DeviceMesh(devices=jax.devices()[:p])


def _corpus(seed=0, vocab=3000, tokens=120_000):
    """A seeded Zipf corpus as ``(indptr, ids)``: sentences of 1 to 39
    tokens (and an empty one), ids that say nothing of their frequency, a
    tail of words under :data:`MIN_COUNT`."""
    rng = np.random.default_rng(seed)
    law = 1.0 / (np.arange(vocab) + 2.0)
    ids = rng.permutation(vocab)[rng.choice(vocab, tokens, p=law / law.sum())]
    ends = np.cumsum(rng.integers(1, 40, tokens // 10))
    indptr = np.concatenate([[0, 0], ends[ends < tokens], [tokens]])
    return indptr.astype(np.int64), ids.astype(np.int32)


def _table(indptr, ids, vocab=3000) -> Table:
    return Table({"tok": TokenColumn(indptr, ids, np.arange(vocab).astype(str))})


def _estimator(p=1, batch=512, steps=16, rate=0.025, **more):
    est = (Word2Vec(mesh=_mesh(p)).set_input_col("tok").set_vector_size(DIM)
           .set_window_size(WINDOW).set_num_negatives(NEGATIVES)
           .set_min_count(MIN_COUNT).set_subsample(SUBSAMPLE).set_batch_size(batch)
           .set_max_steps(steps).set_learning_rate(rate * batch).set_seed(SEED))
    for name, value in more.items():
        getattr(est, f"set_{name}")(value)
    return est


def _replay(indptr, ids, rate, batch=512, steps=16, vocab=3000):
    c = reference.ingest(ids, indptr, vocab, MIN_COUNT, SUBSAMPLE)
    start = np.asarray(word2vec.start_vectors(SEED, c.order.size, DIM))
    want, losses = reference.fit(c, start, SEED, rate * batch, steps, batch,
                                 NEGATIVES, WINDOW)
    return c, start, want, losses


def _gap(got, want, start) -> float:
    return float(np.abs(got - want).max() / np.abs(want - start).max())


@pytest.mark.parametrize("rate", [0.025, 0.0125])
def test_a_fit_follows_the_reference_at_both_rates(rate):
    indptr, ids = _corpus()
    model = _estimator(rate=rate).fit(_table(indptr, ids))
    c, start, want, losses = _replay(indptr, ids, rate)
    got = model.word_vectors()
    assert got.dtype == np.float32 and got.shape == (c.order.size, DIM)
    assert list(model.vocabulary) == [str(w) for w in c.order]
    assert _gap(got, want, start) < TOL
    assert model.vectors.dtype == np.float64            # widened when asked
    assert losses[-1] < losses[0]


def test_scores_in_bfloat16_fail_the_tolerance():
    indptr, ids = _corpus()
    table = _table(indptr, ids)
    _, start, want, _ = _replay(indptr, ids, 0.025)
    _, rounded = _w2v_table.fit_table(_estimator(), table, score_dtype=jnp.bfloat16)
    assert _gap(rounded, want, start) > ROUNDED
    _, sound = _w2v_table.fit_table(_estimator(), table)
    assert _gap(sound, want, start) < TOL


def test_the_ingest_and_every_draw_are_the_references():
    indptr, ids = _corpus(seed=1)
    c = reference.ingest(ids, indptr, 3000, MIN_COUNT, SUBSAMPLE)
    span = _w2v_table.SPAN_A_REACH * WINDOW
    got = _w2v_table.ingest(TokenColumn(indptr, ids, np.arange(3000)), MIN_COUNT,
                            SUBSAMPLE, span)
    assert c.order.size < 3000                          # the tail was pruned
    flat = got.tokens.reshape(-1)
    body = flat[span:span + c.words.size]
    assert np.array_equal(got.order, c.order) and got.alive == c.alive
    assert np.array_equal(body & 0x7FFFFFFF, c.words)
    assert np.array_equal(body < 0, c.first)
    assert (flat[:span] < 0).all() and (flat[span + c.words.size:] < 0).all()
    assert np.array_equal(got.keep.reshape(-1)[span:span + c.words.size],
                          c.keep[c.words])
    assert np.array_equal(got.pool, c.pool)
    batch = 256
    d = _w2v_table.Draw(batch, NEGATIVES, WINDOW,
                        _w2v_table.candidates_a_step(batch, c.words.size, got.alive),
                        c.words.size, got.pool.size)
    assert d.candidates == reference.candidates(c, batch)
    draw = jax.jit(lambda *a: _w2v_table.draw(d, *a))
    for step in (0, 1, 77):
        mine = draw(got.tokens, got.keep, got.pool, np.uint32(SEED), np.uint32(step))
        theirs = reference.draw(c, SEED, step, batch, NEGATIVES, WINDOW)
        for a, b in zip(mine, theirs):
            assert np.array_equal(np.asarray(a), b)


def test_drawn_pairs_obey_sentences_reach_and_the_subsampling_law():
    """Read off the candidates themselves (the reference's walk is the
    draw's twin, test above): a context lies in its centre's sentence, at
    most ``window`` SURVIVING tokens away; ordinal ``j`` comes ``window -
    j + 1`` times in 15; a word's occurrences survive as often as
    ``word2vec.c``'s threshold says."""
    indptr, ids = _corpus(seed=2, vocab=400, tokens=60_000)
    c = reference.ingest(ids, indptr, 400, 1, SUBSAMPLE)
    sentence = np.cumsum(c.first) - 1
    n, key = c.words.size, reference.stream_key(SEED, 5, reference.S_KEEP)
    alive = (reference.bits(key, np.arange(n, dtype=np.uint32)) >> 16) <= c.keep[c.words]
    # the law, word by word: survivors of the head word against its threshold
    for rank in (0, 1, 5):
        at = c.words == rank
        assert alive[at].mean() == pytest.approx((c.keep[rank] + 1) / 65536, abs=0.02)
    assert c.keep[0] < c.keep[5] < 65535 and c.keep[-1] == 65535
    batch = 4096
    m = reference.candidates(c, batch)
    at = np.arange(m, dtype=np.uint32)
    centre = reference.index(
        reference.bits(reference.stream_key(SEED, 5, reference.S_POSITION_HI), at),
        reference.bits(reference.stream_key(SEED, 5, reference.S_POSITION_LO), at), n)
    got = reference.draw(c, SEED, 5, batch, NEGATIVES, WINDOW)
    assert got[3] >= batch
    # Recover each pair's context POSITION: the survivors of the centre's
    # sentence, in order, hold centre and context at most `window` apart.
    survivors = np.flatnonzero(alive)
    place = np.cumsum(alive) - 1                       # a survivor's number
    seen = np.zeros(WINDOW + 1, int)
    pairs = 0
    for i, q in enumerate(centre):
        if pairs == batch:
            break
        if not alive[q]:
            continue
        # the candidate's own (side, ordinal), re-derived
        w = int(reference.bits(reference.stream_key(SEED, 5, reference.S_SIDE_ORDINAL),
                               np.array([i], np.uint32))[0])
        j = 1 + int(np.searchsorted(np.cumsum(np.arange(WINDOW, 0, -1)),
                                    (w * 15) >> 32, side="right"))
        k = place[q] + (j if w & 1 else -j)
        if k < 0 or k >= survivors.size or sentence[survivors[k]] != sentence[q]:
            continue
        if abs(int(survivors[k]) - int(q)) > _w2v_table.SPAN_A_REACH * WINDOW:
            continue
        assert c.words[q] == got[0][pairs] and c.words[survivors[k]] == got[1][pairs]
        seen[j] += 1
        pairs += 1
    assert pairs == batch
    expected = np.arange(WINDOW, 0, -1) / 15.0
    # a far ordinal falls off its sentence's end more often than a near one
    assert np.all(np.abs(seen[1:] / seen[1:].sum() - expected) < 0.04)
    assert seen[1] > seen[2] > seen[3] > seen[4] > seen[5] > 0


def test_negatives_follow_count_to_the_three_quarters_over_a_large_vocabulary():
    """Over MORE words than the old pool had entries (2**18): every word
    holds at least one entry, and the drawn frequencies follow ``count **
    0.75``."""
    vocab = (1 << 18) + 12_345
    counts = (5 + 3e6 / (np.arange(vocab) + 10.0)).astype(np.int64)
    pool = _w2v_table.negative_pool(counts, _w2v_table.pool_entries(vocab))
    assert pool.size == min(100_000_000, 128 * vocab) and pool.dtype == np.int32
    held = np.bincount(pool, minlength=vocab)
    assert held.min() >= 1 and np.all(np.diff(pool) >= 0)
    share = counts ** 0.75 / (counts ** 0.75).sum()
    assert np.abs(held / pool.size - share).max() < 1.0 / pool.size + 1e-12
    d = _w2v_table.Draw(8192, NEGATIVES, WINDOW, 8192, 1000, pool.size)
    each = jnp.arange(d.batch * NEGATIVES, dtype=jnp.uint32)
    key = lambda s: _w2v_table.stream_key(jnp.uint32(SEED), jnp.uint32(0), s)
    entry = _w2v_table.uniform_index(
        _w2v_table.bits(key(_w2v_table.S_NEGATIVE_HI), each),
        _w2v_table.bits(key(_w2v_table.S_NEGATIVE_LO), each), pool.size)
    drawn = pool[np.asarray(entry)]
    # the head decile of the mass against the rest, within sampling error
    head = int(np.searchsorted(np.cumsum(share), 0.1))
    assert (drawn <= head).mean() == pytest.approx(
        share[:head + 1].sum(), abs=4 * np.sqrt(0.1 / drawn.size))
    assert drawn.max() > vocab // 2                     # the tail is reached


@pytest.mark.parametrize("rate", [0.025, 0.0125])
def test_a_fit_through_the_sorted_update_is_the_plain_fit_and_the_references(
        rate, monkeypatch):
    """The step a TPU runs: the rows' entries sorted by id and added a
    group of eight at a time by ``kernels.row_update`` (interpreted here),
    tables padded to whole groups, near the rehearsal's size (3,997 words,
    the cell's batch of 16,384) against the fit as a CPU runs it and
    against the float64 replay; ``w2v.sorted_update_steps`` says which
    ran. The same sums in another order (the plain step adds a pair's
    context, then its negatives a pair a run; the sorted list holds the
    contexts, then the negatives an ordinal a run): float32's rounding
    apart, nothing more."""
    from flinkml_tpu.kernels import row_update

    vocab, batch, steps = 3997, 16_384, 3
    indptr, ids = _corpus(seed=7, vocab=vocab, tokens=400_000)
    count = lambda: metrics.group("w2v").snapshot()["counters"].get(
        "sorted_update_steps", 0.0)
    before = count()
    est = lambda: _estimator(batch=batch, steps=steps, rate=rate)
    plain = est().fit(_table(indptr, ids, vocab)).word_vectors()
    assert count() == before
    monkeypatch.setattr(row_update, "unsupported_reason",
                        lambda dtype, rows, lanes, devices=1:
                        None if devices == 1 else "the exchange's")
    model = est().fit(_table(indptr, ids, vocab))
    assert count() - before == steps
    got = model.word_vectors()
    c, start, want, _ = _replay(indptr, ids, rate, batch=batch, steps=steps,
                                vocab=vocab)
    assert c.order.size % row_update.GROUP          # the tables were padded
    assert got.shape == plain.shape == (c.order.size, DIM)
    assert _gap(got, want, start) < TOL
    assert _gap(got, plain.astype(np.float64), start) < TOL
    assert not np.array_equal(plain, start)


@pytest.mark.parametrize("p", [4, 8])
def test_the_sharded_trainer_ties_to_the_one_device_step(p):
    """Tables row-sharded, the exchange: the same draw, every device its
    share of the batch; float32's order of summation apart."""
    indptr, ids = _corpus(seed=4, vocab=1000, tokens=40_000)
    one = _estimator(1, batch=256, steps=6).fit(_table(indptr, ids, 1000))
    many = _estimator(p, batch=256, steps=6).fit(_table(indptr, ids, 1000))
    assert np.array_equal(one.vocabulary, many.vocabulary)
    start = np.asarray(word2vec.start_vectors(SEED, len(one.vocabulary), DIM))
    assert _gap(many.word_vectors(), one.word_vectors().astype(np.float64),
                start) < TOL


def test_a_second_fit_uploads_nothing_and_a_second_rate_compiles_nothing():
    indptr, ids = _corpus(seed=5, vocab=1000, tokens=40_000)
    table = _table(indptr, ids, 1000)
    counters = lambda: dict(metrics.group("w2v").snapshot()["counters"])
    before = counters()
    first = _estimator(batch=256, steps=4).fit(table).word_vectors()
    after = counters()
    assert after["table_uploads"] - before.get("table_uploads", 0) == 1
    assert after["table_h2d_bytes"] > before.get("table_h2d_bytes", 0)
    lowered = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_, **__: lowered.append(name)
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration" else None)
    again = _estimator(batch=256, steps=4).fit(table).word_vectors()
    slower = _estimator(batch=256, steps=4, rate=0.0125).fit(table).word_vectors()
    fewer = _estimator(batch=256, steps=3).fit(table).word_vectors()
    assert lowered == []                  # rate and steps are operands
    last = counters()
    assert last["table_uploads"] == after["table_uploads"]
    assert last["table_h2d_bytes"] == after["table_h2d_bytes"]
    assert last["fits"] - after["fits"] == 3 and last["steps"] - after["steps"] == 11
    assert last["pairs"] - after["pairs"] == 11 * 256
    assert np.array_equal(first, again)                 # to the bit
    assert not np.array_equal(first, slower) and not np.array_equal(first, fewer)
    # a new Table over the same arrays ingests and uploads again
    _estimator(batch=256, steps=4).fit(table.select("tok"))
    assert counters()["table_uploads"] == last["table_uploads"] + 1


def test_an_object_column_of_string_lists_gives_the_integer_columns_model():
    rng = np.random.default_rng(6)
    words = np.array([f"w{i:03d}" for i in range(200)])
    law = 1.0 / (np.arange(200) + 2.0) ** 1.6
    docs = [list(rng.choice(words, rng.integers(2, 30), p=law / law.sum()))
            for _ in range(1500)]
    lists = np.empty(len(docs), dtype=object)
    for i, d in enumerate(docs):
        lists[i] = d
    column = TokenColumn.from_lists(docs)
    fit = lambda t: _estimator(batch=256, steps=5).fit(t)
    a, b = fit(Table({"tok": lists})), fit(Table({"tok": column}))
    assert np.array_equal(a.vocabulary, b.vocabulary)
    assert len(a.vocabulary) < 200                      # minCount pruned some
    assert np.array_equal(a.word_vectors(), b.word_vectors())
    # ...and a vocabulary in another order, ids to match: the same words'
    # vectors (ties in count fall by the vocabulary's own order, so only
    # words that tie may trade places)
    flip = np.arange(200)[::-1]
    other = TokenColumn(column.indptr, flip[column.ids].astype(np.int32),
                        column.vocabulary[flip])
    c = fit(Table({"tok": other}))
    assert sorted(c.vocabulary) == sorted(a.vocabulary)


def test_row_wise_consumers_take_the_token_column():
    from flinkml_tpu.models import CountVectorizer, HashingTF
    from flinkml_tpu.models.text import _token_column

    docs = [["a", "b", "a"], [], ["c", "a"]] * 30
    lists = np.empty(len(docs), dtype=object)
    for i, d in enumerate(docs):
        lists[i] = d
    tokens = Table({"tok": TokenColumn.from_lists(docs)})
    plain = Table({"tok": lists})
    built = metrics.group("table").snapshot()["counters"]["token_rows_materialized"]
    assert [list(r) for r in tokens.column("tok")] == docs
    assert metrics.group("table").snapshot()["counters"][
        "token_rows_materialized"] == built + len(docs)
    assert [list(r) for r in tokens.slice(1, 4).column("tok")] == docs[1:4]
    for stage in (HashingTF().set_input_col("tok").set_output_col("tf"),):
        (x,), (y,) = stage.transform(tokens), stage.transform(plain)
        assert all(u == v for u, v in zip(x.column("tf"), y.column("tf")))
    cv = CountVectorizer().set_input_col("tok").set_output_col("n")
    (x,), (y,) = cv.fit(tokens).transform(tokens), cv.fit(plain).transform(plain)
    assert all(u == v for u, v in zip(x.column("n"), y.column("n")))
    model = (Word2Vec(mesh=_mesh()).set_input_col("tok").set_output_col("vec")
             .set_vector_size(8).set_min_count(1).set_max_steps(2).set_batch_size(64)
             .fit(tokens))
    (x,), (y,) = model.transform(tokens), model.transform(plain)
    assert np.array_equal(x.column("vec"), y.column("vec"))
    assert not x.column("vec")[1].any()                 # an empty document
    with pytest.raises(ValueError, match="TokenColumn"):
        _token_column(Table({"tok": np.arange(3)}), "tok")
    with pytest.raises(ValueError, match="vocabulary's positions"):
        TokenColumn([0, 2], [0, 7], ["a", "b"])
