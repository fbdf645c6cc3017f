"""``driver: w2v`` — whole ``Word2Vec(vectorSize, windowSize, numNegatives,
minCount, batchSize, maxSteps).fit(Table)`` calls, back to back, on ONE
host ``Table`` whose input column is a ``TokenColumn`` (``datagen_corpus``:
the source's counts, a synthesised text) that set-up's first fit ingested
and placed on the chip: the rate of a team's word or item vectors swept
over the corpus before the full run. The cell's ``sweep`` lists the rates
a PAIR, taken in turn (``learningRate`` is the rate times the batch: the
module steps on the batch's mean); the ``[vocab, dim]`` word vectors are
read back every fit. A closed loop: a new fit starts while the window is
open and the one in flight always finishes.

Set-up makes the table and fits each rate once: the first fit ingests and
places the corpus and warms the one program (rate, seed and step count are
operands of it), and the window may upload nothing of the corpus again.
The configuration's file gives ``vocab``, ``corpus_tokens``,
``vector_size``, ``window``, ``negatives``, ``subsample``, ``min_count``,
``batch_pairs`` and ``max_steps``; the cell's file ``sweep`` and
``limits``.

``correct`` is decided after the window, on what the timed fits themselves
returned. A fit is ``max_steps`` steps, so its whole output can be
followed: ``reference/word2vec.py`` ingests the same column, re-derives
every step's pairs and negatives from the documented bits and runs the
same steps in float64 from the program's own start vectors
(``word2vec.start_vectors``), against the LAST timed fit's vectors. The
number is ``vector_gap``: the widest ``|v - v_ref|`` over ALL rows, in
units of the reference's own widest movement ``max |v_ref - v0|``; its
limit is the fit's rate's (the cell's ``limits_note``). Every
timed fit equal to set-up's fit of its rate to the bit; the corpus's
upload counter unmoved; steps and pairs as counted; shapes, dtype and
finiteness; no token list built. The reference's mean loss over its first
and last 16 steps is printed and decides nothing.

A program without ``table.TokenColumn`` (the parent of PR 44, whose fit
builds every pair in a Python loop over an object column) stops at this
module's import, before any data is made.

``flops_bytes_w2v.step`` is the roofline's count and
``tests/chip_controls_w2v.py`` the one-bfloat16-pass control, for a
builder on the chip. Rehearse the cell on a CPU (4,000 words, 400,000
tokens, 8 steps; dimension 300 and the batch of 16,384 kept; a minute)::

    JAX_PLATFORMS=cpu python benchmark/run.py --workload w2v-1bw.fit \
        --seed 2147493104 --seconds 1 --trace 1 --rehearse
"""

from __future__ import annotations

import json
import time
import types

import numpy as np

from benchmark import datagen_corpus
from benchmark.drivers import program
from benchmark.reference import word2vec as reference
from flinkml_tpu.table import Table, TokenColumn  # see the docstring

COLUMN = "tokens"


def estimator(s, rate: float):
    from flinkml_tpu.models import Word2Vec

    return (Word2Vec().set_input_col(COLUMN).set_vector_size(s.dim)
            .set_window_size(s.window).set_num_negatives(s.negatives)
            .set_min_count(s.min_count).set_subsample(s.subsample)
            .set_batch_size(s.batch).set_max_steps(s.steps)
            .set_learning_rate(float(rate) * s.batch).set_seed(s.seed))


def _fit(s, rate: float, score_dtype=None):
    """One unit: a whole fit on the one table at ``rate`` a pair, the word
    vectors read back: ``(vectors [vocab, dim] float32 as the chip
    returned them, the model's vocabulary size)``. ``score_dtype`` is a
    control's (None: the program's own)."""
    est = estimator(s, rate)
    if score_dtype is not None:
        from flinkml_tpu.models import _w2v_table

        vocabulary, vectors = _w2v_table.fit_table(est, s.table, score_dtype=score_dtype)
        return vectors, len(vocabulary)
    model = est.fit(s.table)
    return model.word_vectors(), len(model.vocabulary)


def setup(ctx):
    s = types.SimpleNamespace()
    s.vocab, s.tokens = int(ctx.size("vocab")), int(ctx.size("corpus_tokens"))
    s.dim, s.window = int(ctx.config["vector_size"]), int(ctx.config["window"])
    s.negatives, s.subsample = int(ctx.config["negatives"]), float(ctx.config["subsample"])
    s.min_count, s.batch = int(ctx.config["min_count"]), int(ctx.config["batch_pairs"])
    s.steps = int(ctx.size("max_steps"))
    s.seed = ctx.seed % (1 << 31)
    s.sweep = [float(v) for v in ctx.cell["sweep"]]
    t0 = time.perf_counter()
    s.indptr, s.ids = datagen_corpus.corpus(ctx.seed, s.vocab, s.tokens)
    s.table = Table({COLUMN: TokenColumn(s.indptr, s.ids,
                                         np.arange(s.vocab).astype(str))})
    print(json.dumps({"phase": "data", "seconds": time.perf_counter() - t0,
                      "sentences": int(s.indptr.shape[0] - 1)}), flush=True)
    # Each rate's fit once: the first ingests and places the corpus and
    # warms the program (the window's zero-compile count checks that it
    # did), and each is what every timed fit of its rate has to equal.
    s.first = []
    for rate in s.sweep:
        t0 = time.perf_counter()
        s.first.append(_fit(s, rate)[0])
        print(json.dumps({"phase": "warm-fit", "rate": rate,
                          "seconds": time.perf_counter() - t0}), flush=True)
    spans = program.counters().get("span", {}).get("counters", {})
    print(json.dumps({"phase": "set-up's spans", **{
        name: spans.get(f"{name}.seconds") for name in (
            "w2v.ingest", "w2v.table_to_device", "w2v.init", "w2v.loop",
            "w2v.readback")}}), flush=True)
    return s


def window(ctx, s):
    walls, s.timed = [], []
    t_open = time.perf_counter()
    while True:
        which = len(walls) % len(s.sweep)
        t0 = time.perf_counter()
        with ctx.unit("fit", fits=1, steps=s.steps, samples=s.steps * s.batch):
            s.timed.append((which, *_fit(s, s.sweep[which])))
        now = time.perf_counter()
        walls.append(now - t0)
        if now - t_open >= ctx.seconds:
            break
    return {"work": len(walls) * s.steps * s.batch, "wall_s": now - t_open,
            "attempted": len(walls), "failed": 0, "unit_walls_s": walls}


def replay(s, rate: float) -> dict:
    """The float64 replay of a whole fit at ``rate`` a pair from the
    program's own start vectors: ``want [words, dim]`` and what is printed
    of it."""
    from flinkml_tpu.models.word2vec import start_vectors

    corpus = reference.ingest(s.ids, s.indptr, s.vocab, s.min_count, s.subsample)
    words = int(corpus.order.shape[0])
    start = np.asarray(start_vectors(s.seed, words, s.dim))
    want, losses = reference.fit(corpus, start, s.seed, rate * s.batch, s.steps,
                                 s.batch, s.negatives, s.window)
    ends = min(16, max(1, s.steps // 2))
    return {"want": want, "rate": rate, "words": words,
            "candidates_a_step": reference.candidates(corpus, s.batch),
            "reference_widest_movement": float(np.abs(want - start).max()),
            "loss_first": float(losses[:ends].mean()),
            "loss_last": float(losses[-ends:].mean())}


def compare(s, replayed: dict, vectors: np.ndarray) -> dict:
    """What a fit returned against its :func:`replay`."""
    out = {k: v for k, v in replayed.items() if k != "want"}
    want = replayed["want"]
    if (vectors.shape != want.shape or vectors.dtype != np.float32
            or not np.isfinite(vectors).all()):
        return {**out, "vector_gap": None}
    return {**out, "vector_gap": float(
        np.abs(vectors - want).max() / out["reference_widest_movement"])}


def check(ctx, s, result, counters):
    t0 = time.perf_counter()
    which, last, _ = s.timed[-1]
    cmp = compare(s, replay(s, s.sweep[which]), last)
    print(json.dumps({"phase": "reference", "seconds": time.perf_counter() - t0,
                      **cmp}), flush=True)
    return verdicts(ctx, s, cmp, counters)


def verdicts(ctx, s, cmp: dict, counters: dict) -> list:
    """The cell's own checks of one fit's :func:`compare` and of the
    window's fits and counters, each a value beside its limit."""
    limits = ctx.size("limits")
    fits, words = len(s.timed), cmp["words"]
    apart = sum(1 for which, v, _ in s.timed
                if v.shape != s.first[which].shape
                or not np.array_equal(v, s.first[which]))
    strange = sum(1 for _, v, n in s.timed
                  if v.shape != (words, s.dim) or v.dtype != np.float32 or n != words
                  or not np.isfinite(v).all())
    steps, counted = counters.get("w2v.steps"), counters.get("w2v.fits", 0)
    pairs = counters.get("w2v.pairs")
    of = (f"last timed fit (rate {cmp['rate']} a pair; {s.tokens} tokens, "
          f"{words} words, dimension {s.dim}, {s.steps} steps of {s.batch} pairs)")
    rows = [
        {"what": f"{of}: vector_gap, the widest |v - v_ref| over ALL rows in units "
                 "of the reference's widest movement max |v_ref - v0| "
                 f"({cmp.get('reference_widest_movement')}); v_ref every step in "
                 "float64 from the program's own start vectors, the pairs and "
                 "negatives re-derived from the documented bits",
         "value": cmp["vector_gap"], "limit": limits["vector_gap"][str(cmp["rate"])]},
        {"what": f"timed fits ({fits}) that differ in any bit from set-up's fit of "
                 "the same rate",
         "value": apart, "limit": 0},
        {"what": "corpus bytes uploaded inside the window (w2v.table_h2d_bytes)",
         "value": counters.get("w2v.table_h2d_bytes"), "limit": 0},
        {"what": f"steps the program counted, off {s.steps} a timed fit "
                 f"(w2v.steps {steps}, w2v.fits {counted})",
         "value": None if steps is None else
         abs(steps - s.steps * counted) + abs(counted - fits),
         "limit": 0},
        {"what": f"pairs the program counted, off {s.batch} a step (w2v.pairs {pairs})",
         "value": None if pairs is None or steps is None else abs(pairs - s.batch * steps),
         "limit": 0},
        {"what": f"timed fits ({fits}) whose vectors are not [{words}, {s.dim}] "
                 "float32, or not finite, or whose vocabulary is not as long",
         "value": strange, "limit": 0},
        {"what": "token lists built for row-wise consumers inside the window "
                 "(table.token_rows_materialized)",
         "value": counters.get("table.token_rows_materialized"), "limit": 0},
    ]
    if "vocab" not in ctx.cell:     # a rehearsal overrides the count
        rows.append({"what": f"words the corpus gives at min_count {s.min_count}, off "
                             f"the configuration's {s.vocab}",
                     "value": abs(words - s.vocab), "limit": 0})
    return rows
