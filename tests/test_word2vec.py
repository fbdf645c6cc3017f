"""Word2Vec: embedding quality on a synthetic topic corpus, synonyms,
doc vectors, persistence."""

import numpy as np
import pytest

from flinkml_tpu.models import Tokenizer, Word2Vec, Word2VecModel
from flinkml_tpu.table import Table


def _topic_corpus(n_docs=600, seed=0):
    """Two disjoint topics: words inside a topic co-occur, across don't."""
    rng = np.random.default_rng(seed)
    animals = ["cat", "dog", "horse", "mouse", "bird"]
    tools = ["hammer", "wrench", "drill", "saw", "pliers"]
    docs = []
    for _ in range(n_docs):
        pool = animals if rng.uniform() < 0.5 else tools
        docs.append(" ".join(rng.choice(pool, size=8)))
    return docs, animals, tools


def _fit(docs, mesh=None, stream=False, **kw):
    t = Table({"text": np.asarray(docs)})
    (tok,) = Tokenizer().set_input_col("text").set_output_col("tok").transform(t)
    w2v = (
        Word2Vec(mesh=mesh).set_input_col("tok").set_output_col("vec")
        .set_vector_size(16).set_window_size(3).set_min_count(2)
        .set_max_iter(10).set_learning_rate(2.0).set_batch_size(512)
        .set_seed(0)
    )
    for name, v in kw.items():
        getattr(w2v, f"set_{name}")(v)
    return w2v.fit(iter([tok]) if stream else tok), tok


def _cos(a, b):
    return float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-12))


def test_topic_structure_in_embeddings():
    docs, animals, tools = _topic_corpus()
    model, _ = _fit(docs)
    vecs = {w: model.vectors[list(model.vocabulary).index(w)]
            for w in animals + tools}
    within = np.mean([
        _cos(vecs[a], vecs[b]) for a in animals for b in animals if a != b
    ])
    across = np.mean([
        _cos(vecs[a], vecs[t]) for a in animals for t in tools
    ])
    assert within > across + 0.3, (within, across)


def test_find_synonyms_prefers_same_topic():
    docs, animals, tools = _topic_corpus(seed=1)
    model, _ = _fit(docs)
    words, sims = model.find_synonyms("cat", 4)
    assert "cat" not in words
    same_topic = sum(1 for w in words if w in animals)
    assert same_topic >= 3, words
    assert np.all(np.diff(sims) <= 1e-6)


def test_doc_vectors_and_oov():
    docs, animals, tools = _topic_corpus(seed=2)
    model, tok = _fit(docs)
    (out,) = model.transform(tok)
    assert out["vec"].shape == (len(docs), 16)
    # A doc of only OOV tokens maps to the zero vector.
    oov = Table({"text": np.asarray(["zzz qqq"])})
    (otok,) = Tokenizer().set_input_col("text").set_output_col("tok").transform(oov)
    (ovec,) = model.transform(otok)
    np.testing.assert_array_equal(ovec["vec"][0], np.zeros(16))


def test_min_count_prunes_and_validation():
    docs = ["a a a a b", "a a c"]
    t = Table({"text": np.asarray(docs)})
    (tok,) = Tokenizer().set_input_col("text").set_output_col("tok").transform(t)
    model = (
        Word2Vec().set_input_col("tok").set_output_col("v")
        .set_min_count(2).set_vector_size(4).set_max_iter(1)
        .set_seed(0).fit(tok)
    )
    assert list(model.vocabulary) == ["a"]
    with pytest.raises(ValueError, match="minCount"):
        (
            Word2Vec().set_input_col("tok").set_output_col("v")
            .set_min_count(100).fit(tok)
        )


def test_persistence_and_determinism(tmp_path):
    docs, _, _ = _topic_corpus(n_docs=100, seed=3)
    model, tok = _fit(docs, max_iter=2)
    model.save(str(tmp_path / "w2v"))
    loaded = Word2VecModel.load(str(tmp_path / "w2v"))
    np.testing.assert_array_equal(loaded.vocabulary, model.vocabulary)
    (v1,) = model.transform(tok)
    (v2,) = loaded.transform(tok)
    np.testing.assert_allclose(v2["vec"], v1["vec"])
    model2, _ = _fit(docs, max_iter=2)
    np.testing.assert_array_equal(model2.vectors, model.vectors)


def test_sharded_trainer_matches_one_device():
    """On more than one device the table fit runs the vocab-sharded ring
    trainer over the same draw as the one-device program
    (``w2v_sgns_loop``): the same pairs and negatives every step, so the
    same vectors (f32 summation order differs only through the ring's
    masked partial adds)."""
    import jax

    from flinkml_tpu.parallel import DeviceMesh

    docs, animals, tools = _topic_corpus()
    one_model, _ = _fit(docs, mesh=DeviceMesh(devices=jax.devices()[:1]))
    sharded_model, _ = _fit(docs)
    dv = one_model._vectors
    sv = sharded_model._vectors
    np.testing.assert_allclose(sv, dv, rtol=2e-3, atol=2e-4)
    # And the sharded embedding still carries the topic structure.
    vec = {str(t): sv[i] for i, t in enumerate(sharded_model._vocab)}
    same = _cos(vec["cat"], vec["dog"])
    cross = _cos(vec["cat"], vec["hammer"])
    assert same > cross, (same, cross)


def test_onehot_accum_matches_scatter(monkeypatch):
    """FLINKML_TPU_W2V_ACCUM=onehot (the gated scatter-free one-hot
    matmul accumulation — the sort-class candidate mirroring the
    sparse-LR/GBT/ALS cumsum gates) follows the identical sampling
    sequence as the default scatter layout; the vectors agree up to f32
    summation order, and the embedding still carries the topic
    structure. Pinned so a measured device winner can flip the default
    without a numerics question."""
    docs, animals, tools = _topic_corpus(seed=4)
    # The STREAMED fit's dense trainer reads the gate (a table fit updates
    # rows and makes no [vocab, dim] gradient to accumulate).
    scatter_model, _ = _fit(docs, stream=True)
    monkeypatch.setenv("FLINKML_TPU_W2V_ACCUM", "onehot")
    onehot_model, _ = _fit(docs, stream=True)
    np.testing.assert_array_equal(
        onehot_model.vocabulary, scatter_model.vocabulary
    )
    np.testing.assert_allclose(
        onehot_model._vectors, scatter_model._vectors, rtol=2e-3, atol=2e-4
    )
    vec = {str(t): onehot_model._vectors[i]
           for i, t in enumerate(onehot_model._vocab)}
    assert _cos(vec["cat"], vec["dog"]) > _cos(vec["cat"], vec["hammer"])


def test_w2v_accum_gate_rejects_unknown(monkeypatch):
    from flinkml_tpu.models.word2vec import _w2v_accum

    monkeypatch.setenv("FLINKML_TPU_W2V_ACCUM", "bogus")
    with pytest.raises(ValueError, match="FLINKML_TPU_W2V_ACCUM"):
        _w2v_accum()


def test_streamed_fit_shards_above_vocab_threshold(monkeypatch):
    """Above the threshold, the single-process streamed fit switches to
    the vocab-sharded ring trainer (same SGD trajectory as the dense
    streamed trainer up to ring summation order) instead of psumming a
    [vocab, dim] gradient per step."""
    docs, _, _ = _topic_corpus(n_docs=200)
    t = Table({"text": np.asarray(docs)})
    (tok,) = (
        Tokenizer().set_input_col("text").set_output_col("tok").transform(t)
    )

    def fit():
        return (
            Word2Vec().set_input_col("tok").set_output_col("vec")
            .set_vector_size(8).set_min_count(2).set_max_iter(3)
            .set_learning_rate(1.0).set_batch_size(256).set_seed(0)
            .fit(iter([tok]))
        )

    dense_model = fit()
    monkeypatch.setenv("FLINKML_TPU_EMBEDDING_DENSE_VOCAB", "0")
    sharded_model = fit()
    np.testing.assert_allclose(
        sharded_model._vectors, dense_model._vectors, rtol=2e-3, atol=2e-4
    )
