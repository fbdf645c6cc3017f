"""What PR 44 added for ``w2v-1bw.fit``, on the CPU: the float64 reference
against a step worked by hand and against ``np.add.at``, the bits'
arithmetic against Python's integers, the step's count against its own
arithmetic, the generator's corpus, the configuration and the entries'
form, and a rehearsal of the cell, traced and not, and of the builder's
control script (whose control is ``correct`` false here too: the rounding
is written out, so a CPU shows it). The metric sets are held as SUBSETS:
the next metric a cell gains must not break them."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import datagen_corpus, flops_bytes, flops_bytes_w2v
from benchmark.reference import word2vec as reference

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL_NAME = "w2v-1bw.fit"

with open(os.path.join(BENCH, "configs", "w2v-1bw.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(BENCH, "workloads", f"{CELL_NAME}.json")) as f:
    CELL = json.load(f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)

COUNTED = ["compile.cache_misses.setup", "w2v.sorted_update_share"]
TRACED = ["w2v.step_device_ms", "w2v_step_roofline", "device.idle_share.fit",
          "device.idle_outside_spans.fit"]
SPANS = ["w2v.init_s_per_fit", "w2v.dispatch_s_per_fit", "w2v.readback_s_per_fit"]


def test_the_reference_on_a_step_worked_by_hand():
    """One pair (centre 0, context 1), one negative (2), dimension 2, v =
    [(1, 0), ...], u = [.., (0, 1), (1, 0)]: the positive score is 0, the
    negative 1; g_pos = -0.5, g_neg = s(1). v_0 moves by -rate (g_pos u_1
    + g_neg u_2), u_1 by -rate g_pos v_0, u_2 by -rate g_neg v_0 (batch
    1)."""
    v = np.array([[1.0, 0.0], [0.0, 0.0], [0.0, 0.0]])
    u = np.array([[0.0, 0.0], [0.0, 1.0], [1.0, 0.0]])
    loss = reference.step(v, u, np.array([0]), np.array([1]), np.array([[2]]), 0.5)
    s1 = 1.0 / (1.0 + np.exp(-1.0))
    np.testing.assert_allclose(v[0], [1.0 - 0.5 * s1, 0.25], atol=1e-15)
    np.testing.assert_allclose(u[1], [0.25, 1.0], atol=1e-15)
    np.testing.assert_allclose(u[2], [1.0 - 0.5 * s1, 0.0], atol=1e-15)
    assert loss == pytest.approx(np.log(2.0) + np.log1p(np.exp(1.0)))


def test_a_steps_sums_by_row_are_np_add_ats():
    rng = np.random.default_rng(0)
    words, dim, batch, k = 50, 7, 64, 5
    v0, u0 = rng.normal(size=(words, dim)), rng.normal(size=(words, dim))
    c = rng.integers(0, 6, batch)                 # rows that collide
    ctx, neg = rng.integers(0, words, batch), rng.integers(0, 10, (batch, k))
    v, u = v0.copy(), u0.copy()
    reference.step(v, u, c, ctx, neg, 3.0)
    vc, uc, un = v0[c], u0[ctx], u0[neg]
    g_pos = 1 / (1 + np.exp(-np.sum(vc * uc, axis=1))) - 1
    g_neg = 1 / (1 + np.exp(-np.einsum("bd,bnd->bn", vc, un)))
    want_v, want_u = v0.copy(), u0.copy()
    np.add.at(want_v, c, -3.0 / batch * (g_pos[:, None] * uc
                                         + np.einsum("bn,bnd->bd", g_neg, un)))
    np.add.at(want_u, ctx, -3.0 / batch * g_pos[:, None] * vc)
    np.add.at(want_u, neg.reshape(-1),
              (-3.0 / batch * g_neg[..., None] * vc[:, None, :]).reshape(-1, dim))
    np.testing.assert_allclose(v, want_v, atol=1e-13)
    np.testing.assert_allclose(u, want_u, atol=1e-13)


def test_the_bits_are_their_own_arithmetic():
    """``mix`` on arrays against Python's integers; ``index`` against the
    128-bit product; the ordinals' weights."""
    x = np.array([0, 1, 0xFFFFFFFF, 0x9E3779B9, 123456789], np.uint32)
    assert [int(v) for v in reference.mix(x)] == [reference.mix_int(int(v)) for v in x]
    key = reference.stream_key((1 << 31) + 9, 3, reference.S_KEEP)
    assert int(key) == reference.mix_int(
        (reference.mix_int((reference.mix_int(((1 << 31) + 9 + 0x9E3779B9) & 0xFFFFFFFF)
                            + 3) & 0xFFFFFFFF) + 3 * 0x9E3779B9) & 0xFFFFFFFF)
    hi = np.array([0, 0xFFFFFFFF, 0x80000000, 12345], np.uint32)
    lo = np.array([0, 0xFFFFFFFF, 1, 0xFFFFFFF0], np.uint32)
    for n in (1, 805_306_368, 100_000_000, (1 << 31) - 1):
        want = [((int(h) << 32) + int(l_)) * n >> 64 for h, l_ in zip(hi, lo)]
        assert list(reference.index(hi, lo, n)) == want
    counts = np.array([100, 50, 5])
    corpus = reference.Corpus(np.zeros(155, np.int32), np.zeros(155, bool),
                              np.array([65535] * 3, np.uint16), np.zeros(1, np.int32),
                              np.arange(3), counts, 65536 * 155)
    assert reference.candidates(corpus, 1000) == 1536        # 1.5 a pair, to 128s


def test_the_steps_count_is_its_own_arithmetic():
    c = flops_bytes_w2v.step(CONFIG["batch_pairs"], CONFIG["negatives"],
                             CONFIG["vector_size"])
    rows = 16_384 * 7
    assert c["bytes"] == rows * (3 * 300 * 4 + 4) == 413_335_552
    assert c["flops"] == 16_384 * 6 * 300 * 6 + rows * 300 * 2
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = flops_bytes.least_seconds(c, peaks)
    assert bound == "bytes" and 0.0004 < least < 0.0006       # half a millisecond


def test_the_generators_corpus():
    vocab, tokens = 4000, 400_000
    indptr, ids = datagen_corpus.corpus(11, vocab, tokens)
    assert ids.dtype == np.int32 and indptr.dtype == np.int64
    assert ids.size == tokens and indptr[0] == 0 and indptr[-1] == tokens
    lengths = np.diff(indptr)
    assert lengths.min() >= 1 and 24 < lengths.mean() < 29
    counts = np.bincount(ids, minlength=vocab)
    assert counts.min() >= 1 and 0.04 < counts.max() / tokens < 0.12    # a head word
    # the planted topics: a sentence's content words lean to one class
    from benchmark import datagen

    rank_of = np.empty(vocab, np.int64)
    rank_of[datagen.rng(11, datagen_corpus.TAG_IDS).permutation(vocab)] = np.arange(vocab)
    assert rank_of[np.argmax(counts)] == 0
    topic = np.repeat(np.arange(lengths.size), lengths)
    content = rank_of[ids] >= 2 * datagen_corpus.FUNCTION_WORDS
    klass = rank_of[ids] % datagen_corpus.TOPICS
    of = np.zeros((lengths.size, datagen_corpus.TOPICS))
    np.add.at(of, (topic[content], klass[content]), 1)
    long = of.sum(axis=1) >= 8
    assert np.median(of[long].max(axis=1) / of[long].sum(axis=1)) > 0.25   # 1/64 if flat
    again = datagen_corpus.corpus(11, vocab, tokens)
    assert np.array_equal(ids, again[1]) and np.array_equal(indptr, again[0])
    assert not np.array_equal(ids, datagen_corpus.corpus(12, vocab, tokens)[1])
    law = datagen_corpus.unigram(1_115_011)
    assert 0.05 < law[0] < 0.07 and law[-1] * 805_306_368 > 4 * CONFIG["min_count"]


def test_the_configuration_and_the_entries():
    assert CONFIG["architecture"] is None and CONFIG["reduced"] == ["max_steps"]
    assert (CONFIG["vocab"], CONFIG["vector_size"], CONFIG["window"],
            CONFIG["negatives"], CONFIG["subsample"], CONFIG["min_count"]) == (
        1_115_011, 300, 5, 5, 1e-4, 5)
    assert CONFIG["corpus_tokens"] == 768 << 20 and CONFIG["batch_pairs"] == 16_384
    assert CONFIG["max_steps"] == 256 and CONFIG["rate_per_pair"] == 0.025
    assert len(CONFIG["source"]) <= 200 and len(CONFIG["guarantees"]) == 6
    assert len(CONFIG["departures"]) >= 6 and set(CONFIG["draws"]["streams"]) == {
        "0_1", "2", "3", "4_5"}
    (entry,) = [c for c in BENCHMARK["configs"] if c["name"] == "w2v-1bw"]
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == ["max_steps"]
    assert entry["file"] == "benchmark/configs/w2v-1bw.json"
    (cell,) = [w for w in BENCHMARK["workloads"] if w["name"] == CELL_NAME]
    assert cell["chips"] == CELL["chips"] == 1 and cell["why"] == CELL["why"]
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert CELL["sweep"] == [0.025, 0.0125] and CELL["driver"] == "w2v"
    assert set(CELL["limits"]["vector_gap"]) == {str(r) for r in CELL["sweep"]}
    # a rehearsal overrides counts, never a width
    assert set(CELL["rehearse"]) == {"vocab", "corpus_tokens", "max_steps"}
    mine = {m["name"] for m in BENCHMARK["per_layer"] if CELL_NAME in m.get("workloads", [])}
    assert set(COUNTED + TRACED + SPANS) <= mine
    assert all(m["layer"] == "Word2Vec trainer" for m in BENCHMARK["per_layer"]
               if m["name"].startswith("w2v"))
    rate = next(m for m in BENCHMARK["end_to_end"] if m["name"] == "fit_samples_per_s")
    assert CELL_NAME in rate["workloads"]      # not "the last": the next cell's goes after
    for name in mine:
        assert os.path.exists(os.path.join(BENCH, "metrics", f"{name}.json")), name


def _run(*extra):
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL_NAME,
         "--seed", "2147493104", "--seconds", "1", "--rehearse", *extra],
        capture_output=True, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(line) for line in out.stdout.splitlines()
             if line.startswith("{")]
    return lines[-1], lines


@pytest.mark.parametrize("trace", [0, 1])
def test_a_rehearsal_of_the_cell(trace):
    line, lines = _run("--trace", str(trace))
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    checks = [c for c in lines if c.get("phase") == "check"]
    assert len(checks) == 8 and all(c["ok"] for c in checks)   # no vocabulary row
    assert 0 < checks[0]["value"] < min(CELL["limits"]["vector_gap"].values())
    # held by the check, not by a per-layer metric (PR 54): a miss is not correct
    assert [(c["value"], c["limit"]) for c in checks
            if "bytes uploaded inside the window" in c["what"]] == [(0.0, 0)]
    if trace:
        assert set(COUNTED) <= set(line["metrics"])
        assert 0.0 <= line["metrics"]["w2v.sorted_update_share"]["value"] <= 1.0
    else:
        assert set(line["metrics"]) == {"fit_samples_per_s", "setup_s"}


def test_the_builders_control_script_rehearses_and_its_control_is_not_correct():
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "chip_controls_w2v.py"), "--seeds", "1",
         "--rehearse"], capture_output=True, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines() if ln.startswith("{")]
    rates = [ln for ln in lines if "sound_correct" in ln]
    assert [ln["rate"] for ln in rates] == CELL["sweep"]
    for line in rates:
        assert line["sound_correct"] is True and line["sound_failed_checks"] == []
        limit = CELL["limits"]["vector_gap"][str(line["rate"])]
        assert line["sound_vector_gap"] < limit / 10
        # the operands' rounding is written out, so a CPU shows it too
        assert line["control_correct"] is False
        assert len(line["control_failed_checks"]) == 1         # the gap alone
        assert line["control_vector_gap"] > limit
