"""flinkml_tpu.features — hash front end + incremental delta publishes.

Pins, by area:

- **hash contract** — murmur3_x86_32 against published reference
  vectors, the committed golden vectors (``tests/golden_hash_vectors.
  json`` — a diff there is a model-breaking change), vectorized ==
  scalar bit parity, and cross-process determinism under different
  ``PYTHONHASHSEED`` values (``tests/_hash_child.py``).
- **FML505** — the buckets-vs-vocab gate, live (``check_hash_vocab`` /
  model construction) and as an analysis fixture pass
  (``bad_hash_fml505_bucket_vocab_mismatch.features.json``).
- **row patch** — ``EmbeddingTable.apply_row_delta`` /
  ``clone_with_row_delta``: sharded == unsharded == fresh placement,
  bitwise.
- **delta chain** — publish/resolve parity with a full snapshot,
  pruned-base and corrupted-mid-chain regressions raising
  :class:`DeltaChainError` naming the broken link, compaction at
  ``max_depth``.
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from flinkml_tpu.data import ArraySource, Dataset
from flinkml_tpu.embeddings.table import EmbeddingTable
from flinkml_tpu.features import (
    CollisionTracker,
    DeltaPublisher,
    HashedFMModel,
    HashedFeature,
    HashVocabMismatchError,
    ModelDelta,
    StreamingHashedFMTrainer,
    check_hash_vocab,
    expected_collision_fraction,
    hash_buckets,
    murmur3_32,
)
from flinkml_tpu.features.hashing import _hash_ints_vectorized, _key_bytes
from flinkml_tpu.io.read_write import content_fingerprint
from flinkml_tpu.parallel import DeviceMesh
from flinkml_tpu.serving.errors import DeltaChainError
from flinkml_tpu.serving.registry import ModelRegistry
from flinkml_tpu.sharding.plan import EMBEDDING
from flinkml_tpu.table import Table
from flinkml_tpu.utils.metrics import metrics

_HERE = os.path.dirname(__file__)
_GOLDEN = os.path.join(_HERE, "golden_hash_vectors.json")


# ---------------------------------------------------------------------------
# Hash contract
# ---------------------------------------------------------------------------

def test_murmur3_published_reference_vectors():
    """The scalar reference implements murmur3_x86_32 exactly — pinned
    against independently published vectors, not our own output."""
    vectors = [
        (b"", 0, 0x00000000),
        (b"", 1, 0x514E28B7),
        (b"", 0xFFFFFFFF, 0x81F16F39),
        (b"hello", 0, 0x248BFA47),
        (b"hello, world", 0, 0x149BBB7F),
        (b"The quick brown fox jumps over the lazy dog",
         0x9747B28C, 0x2FA826CD),
        (b"abc", 0, 0xB3DD93FA),
    ]
    for data, seed, want in vectors:
        assert murmur3_32(data, seed) == want, (data, seed)


def test_golden_vectors_committed():
    """Recompute every committed golden vector: a mismatch means the
    hash changed and every trained row id with it — that must be a loud
    diff, never a silent rehash."""
    with open(_GOLDEN) as f:
        golden = json.load(f)
    for seed_s, entries in golden["hashes"].items():
        for key_repr, want in entries.items():
            key = eval(key_repr)  # noqa: S307 — our own committed reprs
            assert murmur3_32(_key_bytes(key), int(seed_s)) == want, (
                seed_s, key_repr)
    for buckets_s, entries in golden["buckets"].items():
        for key_repr, want in entries.items():
            key = eval(key_repr)  # noqa: S307
            got = int(hash_buckets([key], seed=42,
                                   num_buckets=int(buckets_s))[0])
            assert got == want, (buckets_s, key_repr)


def test_vectorized_int_path_bitwise_matches_scalar():
    keys = np.array([0, 1, -1, 7, 2**31, -(2**31), 123456789,
                     2**63 - 1, -(2**63)], np.int64)
    vec = _hash_ints_vectorized(keys, 42)
    scalar = [murmur3_32(_key_bytes(int(k)), 42) for k in keys]
    assert [int(v) for v in vec] == [int(s) for s in scalar]


def test_hash_buckets_range_padding_and_types():
    ids = hash_buckets(["a", "b", 17, b"raw"], seed=3, num_buckets=100)
    assert ids.dtype == np.int32
    assert ((ids >= 0) & (ids < 100)).all()
    padded = hash_buckets(["a", "", "b"], seed=3, num_buckets=100,
                          pad_key="")
    assert padded[1] == -1 and padded[0] == ids[0]
    # str and the bytes of its utf-8 encoding hash identically (one
    # canonical encoding), while int 7 and str "7" do NOT (different
    # canonical bytes).
    assert int(hash_buckets(["xy"], seed=1, num_buckets=1000)[0]) == int(
        hash_buckets([b"xy"], seed=1, num_buckets=1000)[0])
    assert int(hash_buckets([7], seed=1, num_buckets=10**9)[0]) != int(
        hash_buckets(["7"], seed=1, num_buckets=10**9)[0])


def test_hash_determinism_across_processes_and_hashseed():
    """The hardening pin: two fresh interpreters with DIFFERENT
    ``PYTHONHASHSEED`` values produce bit-identical row ids, both equal
    to the committed golden vectors — proving no ``hash()`` anywhere in
    the path."""
    reports = []
    for seed in ("0", "424242"):
        proc = subprocess.run(
            [sys.executable, os.path.join(_HERE, "_hash_child.py")],
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "PYTHONHASHSEED": seed,
                 "PYTHONPATH": os.pathsep.join(
                     [os.path.dirname(_HERE)]
                     + ([os.environ["PYTHONPATH"]]
                        if os.environ.get("PYTHONPATH") else []))},
        )
        assert proc.returncode == 0, (
            f"hash child (PYTHONHASHSEED={seed}) failed:\n"
            f"{proc.stdout}\n{proc.stderr}"
        )
        reports.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    a, b = reports
    assert a["python_hash_seed"] == "0" and b["python_hash_seed"] == "424242"
    assert a["hashes"] == b["hashes"]
    assert a["buckets"] == b["buckets"]
    assert a["vectorized_matches_scalar"] is True
    with open(_GOLDEN) as f:
        golden = json.load(f)
    assert a["hashes"] == golden["hashes"]
    assert a["buckets"] == golden["buckets"]


def test_collision_tracker_counts_and_birthday_estimate():
    tracker = CollisionTracker("clicks", num_buckets=8, seed=5)
    keys = [f"user:{i}" for i in range(64)]
    tracker.observe(keys, hash_buckets(keys, seed=5, num_buckets=8))
    snap = metrics.group("features.hash",
                         labels={"feature": "clicks"}).snapshot()["gauges"]
    assert snap["keys_seen"] == 64
    assert snap["collisions"] > 0  # 64 distinct keys into 8 buckets
    assert 0.0 < snap["collision_rate"] <= 1.0
    # Birthday bound sanity: tiny load → near 0; heavy load → near 1.
    assert expected_collision_fraction(2, 10**6) < 1e-3
    assert expected_collision_fraction(10**4, 8) > 0.99


def test_hashed_feature_as_map_and_stage_and_dataset_op():
    feature = HashedFeature(9, 128, input_col="keys",
                            output_col="hashed_ids")
    t = Table({"keys": np.array(["a", "b", "c", "a"])})
    out = feature(t)
    ids = np.asarray(out.column("hashed_ids"))
    assert ids.shape == (4,) and ids[0] == ids[3]
    (out2,) = feature.transform(t)
    assert np.array_equal(np.asarray(out2.column("hashed_ids")), ids)
    # Dataset op form: 1:1 (skip-transparent) and identical ids.
    ds = Dataset.from_source(
        ArraySource({"keys": np.array([["a"], ["b"], ["c"], ["a"]])},
                    batch_size=2)
    ).hash_column("keys", seed=9, num_buckets=128)
    assert ds.skip_transparent
    batches = list(ds)
    got = np.concatenate(
        [np.asarray(b.column("hashed_ids")).reshape(-1) for b in batches])
    assert np.array_equal(got, ids)


# ---------------------------------------------------------------------------
# FML505
# ---------------------------------------------------------------------------

def test_fml505_live_gate():
    check_hash_vocab(64, 64)  # matching sizes pass
    with pytest.raises(HashVocabMismatchError, match="FML505"):
        check_hash_vocab(64, 128, where="test")
    with pytest.raises(HashVocabMismatchError, match="FML505"):
        HashedFMModel.from_arrays(
            np.zeros(1), np.zeros((32, 1)), np.zeros((32, 4)),
            num_buckets=64,
        )


def test_fml505_fixture_fails_analysis_gate():
    from flinkml_tpu.analysis.features_check import check_features_file

    fixture = os.path.join(
        _HERE, "analysis_fixtures",
        "bad_hash_fml505_bucket_vocab_mismatch.features.json")
    findings = check_features_file(fixture)
    assert findings and all(f.rule == "FML505" for f in findings)
    assert any("4096" in f.message and "2048" in f.message
               for f in findings)
    # A matching config passes clean.
    good = {"hash": {"seed": 1, "numBuckets": 256},
            "table": {"vocab": 256, "dim": 8}}
    path = os.path.join(_HERE, "analysis_fixtures")
    import tempfile
    with tempfile.NamedTemporaryFile(
            "w", suffix=".features.json", delete=False) as f:
        json.dump(good, f)
    try:
        assert check_features_file(f.name) == []
    finally:
        os.unlink(f.name)
    assert os.path.isdir(path)


# ---------------------------------------------------------------------------
# EmbeddingTable row patch
# ---------------------------------------------------------------------------

def _patch_case():
    rng = np.random.default_rng(0)
    rows = rng.standard_normal((100, 8)).astype(np.float32)
    ids = np.array([0, 5, 13, 57, 99], np.int32)
    vals = rng.standard_normal((5, 8)).astype(np.float32)
    want = rows.copy()
    want[ids] = vals
    return rows, ids, vals, want


def test_apply_row_delta_unsharded():
    rows, ids, vals, want = _patch_case()
    t = EmbeddingTable("p0", 100, 8, rows=rows)
    clone = t.clone_with_row_delta(ids, vals)
    assert np.array_equal(clone.to_host(), want)
    assert np.array_equal(t.to_host(), rows), "clone mutated the original"
    t.apply_row_delta(ids, vals)
    assert np.array_equal(t.to_host(), want)


def test_apply_row_delta_sharded_bitwise_equals_fresh_placement():
    """The acceptance anchor: a sharded in-place patch must be bitwise
    what a full re-placement of the patched snapshot would produce — a
    SET on the owning shard, not an arithmetic trick."""
    rows, ids, vals, want = _patch_case()
    mesh = DeviceMesh.for_plan(EMBEDDING)
    t = EmbeddingTable("p1", 100, 8, mesh=mesh, plan=EMBEDDING, rows=rows)
    assert t.sharded and t.n_shards == 8
    clone = t.clone_with_row_delta(ids, vals)
    assert np.array_equal(clone.to_host(), want)
    assert np.array_equal(t.to_host(), rows)
    fresh = EmbeddingTable("p2", 100, 8, mesh=mesh, plan=EMBEDDING,
                           rows=want)
    assert np.array_equal(np.asarray(clone.rows), np.asarray(fresh.rows))
    assert np.array_equal(np.asarray(clone.lookup(ids)), vals)


def test_apply_row_delta_validation():
    t = EmbeddingTable("p3", 10, 4, rows=np.zeros((10, 4), np.float32))
    with pytest.raises(ValueError, match="duplicate"):
        t.apply_row_delta([1, 1], np.zeros((2, 4), np.float32))
    with pytest.raises(ValueError, match="out of range"):
        t.apply_row_delta([10], np.zeros((1, 4), np.float32))
    with pytest.raises(ValueError, match="shape"):
        t.apply_row_delta([1], np.zeros((1, 3), np.float32))


# ---------------------------------------------------------------------------
# ModelDelta + HashedFMModel
# ---------------------------------------------------------------------------

def test_model_delta_build_roundtrip(tmp_path):
    ids = np.array([2, 7], np.int32)
    vals = np.arange(8, dtype=np.float32).reshape(2, 4)
    delta = ModelDelta.build(
        base_version=3, base_fingerprint="aa", result_fingerprint="bb",
        watermark=17, depth=2,
        row_deltas={"v": (ids, vals)},
        dense_deltas={"w0": np.array([0.5], np.float32)},
    )
    path = str(tmp_path / "delta")
    delta.save(path)
    loaded = ModelDelta.load(path)
    assert loaded.base_version == 3 and loaded.depth == 2
    assert loaded.watermark == 17
    assert loaded.base_fingerprint == "aa"
    assert loaded.result_fingerprint == "bb"
    (got_ids, got_vals) = loaded.row_deltas()["v"]
    assert np.array_equal(got_ids, ids)
    assert np.array_equal(got_vals, vals)
    assert np.array_equal(loaded.dense_deltas()["w0"], [0.5])
    with pytest.raises(TypeError, match="not servable"):
        loaded.transform(Table({"x": np.zeros(1)}))
    with pytest.raises(ValueError, match="unique"):
        ModelDelta.build(
            base_version=1, base_fingerprint="", result_fingerprint="",
            watermark=0, depth=1,
            row_deltas={"v": (np.array([1, 1]), np.zeros((2, 4)))},
        )


def test_hashed_fm_model_save_load_and_margin(tmp_path):
    rng = np.random.default_rng(1)
    w0 = np.array([0.3], np.float32)
    w = rng.standard_normal((32, 1)).astype(np.float32)
    v = rng.standard_normal((32, 4)).astype(np.float32)
    model = HashedFMModel.from_arrays(w0, w, v, num_buckets=32, hash_seed=9)
    ids = np.array([[1, 5, -1], [3, 3, 7]], np.int64)
    (out,) = model.transform(Table({"ids": ids}))
    margin = np.asarray(out.column("rawPrediction"))
    # Hand-computed FM identity for row 0 ({1, 5}; -1 masked):
    sv = v[1] + v[5]
    want0 = (w0[0] + w[1, 0] + w[5, 0]
             + 0.5 * ((sv * sv) - v[1] ** 2 - v[5] ** 2).sum())
    np.testing.assert_allclose(margin[0], want0, rtol=1e-5)
    prob = np.asarray(out.column("prediction"))
    np.testing.assert_allclose(prob, 1.0 / (1.0 + np.exp(-margin)),
                               rtol=1e-6)
    path = str(tmp_path / "m")
    model.save(path)
    loaded = HashedFMModel.load(path)
    (out2,) = loaded.transform(Table({"ids": ids}))
    assert np.array_equal(np.asarray(out2.column("rawPrediction")), margin)


def test_apply_delta_returns_new_model_and_rejects_unknown_leaves():
    model = HashedFMModel.from_arrays(
        np.zeros(1), np.zeros((8, 1), np.float32),
        np.zeros((8, 4), np.float32), num_buckets=8)
    delta = ModelDelta.build(
        base_version=1, base_fingerprint="", result_fingerprint="",
        watermark=1, depth=1,
        row_deltas={"v": (np.array([2]), np.ones((1, 4), np.float32))},
        dense_deltas={"w0": np.array([1.5], np.float32)},
    )
    patched = model.apply_delta(delta)
    assert patched is not model
    assert model.v[2].sum() == 0.0, "apply_delta mutated the base"
    assert np.array_equal(patched.v[2], np.ones(4, np.float32))
    assert patched.w0[0] == 1.5
    bad = ModelDelta.build(
        base_version=1, base_fingerprint="", result_fingerprint="",
        watermark=1, depth=1,
        row_deltas={"nope": (np.array([0]), np.zeros((1, 4)))},
    )
    with pytest.raises(KeyError, match="nope"):
        model.apply_delta(bad)


# ---------------------------------------------------------------------------
# Registry delta chain
# ---------------------------------------------------------------------------

def _trained(n_batches=6, num_buckets=32, key_range=200, **kwargs):
    rng = np.random.default_rng(7)
    tr = StreamingHashedFMTrainer(num_buckets=num_buckets, factor_size=4,
                                  learning_rate=0.1, **kwargs)

    def feed(k):
        for _ in range(k):
            keys = rng.integers(0, key_range, size=(16, 3))
            ids = hash_buckets(keys.reshape(-1), seed=1,
                               num_buckets=num_buckets).reshape(16, 3)
            tr.fit_batch(ids, (keys.sum(axis=1) % 2).astype(np.float32))
    feed(n_batches)
    return tr, feed


def test_delta_publish_resolves_bitwise_to_full_snapshot(tmp_path):
    tr, feed = _trained()
    reg = ModelRegistry(str(tmp_path / "reg"))
    pub = DeltaPublisher(reg, tr, every_n_batches=1, max_depth=10)
    # serving.registry is one process-global metrics group; count from
    # here so the assertions hold in any suite order.
    base = dict(reg._metrics.snapshot()["counters"])
    pub.publish_now()                    # base snapshot
    feed(3)
    pub.publish_now()
    feed(2)
    v = pub.publish_now()
    assert reg.versions() == [1, 2, 3] and v == 3
    assert pub.chain_depth == 2
    got_v, resolved = reg.get()
    assert got_v == 3
    full = tr.make_model()
    for name, arr in full.delta_state().items():
        assert np.array_equal(resolved.delta_state()[name], arr), name
    ids = np.array([[1, 5, 9], [2, 2, -1]], np.int64)
    t = Table({"hashed_ids": ids})
    (a,) = resolved.transform(t)
    (b,) = full.transform(t)
    assert np.array_equal(np.asarray(a.column("prediction")),
                          np.asarray(b.column("prediction")))
    # Watermarks rode each publish atomically.
    assert reg.watermark_of(1) == 6
    assert reg.watermark_of(3) == 11 == reg.latest_watermark()
    # delta_chain finds the suffix (and refuses a non-chain).
    assert len(reg.delta_chain(1, 3)) == 2
    assert len(reg.delta_chain(2, 3)) == 1
    assert reg.delta_chain(3, 3) is None
    assert reg.delta_chain(2, 1) is None
    snap = reg._metrics.snapshot()["counters"]
    assert snap["delta_publishes"] - base.get("delta_publishes", 0) == 2
    assert snap["full_publishes"] - base.get("full_publishes", 0) == 1
    assert snap["delta_loads"] - base.get("delta_loads", 0) >= 1


def test_delta_chain_pruned_base_raises_named_error(tmp_path):
    tr, feed = _trained()
    reg = ModelRegistry(str(tmp_path / "reg"))
    pub = DeltaPublisher(reg, tr, every_n_batches=1, max_depth=10)
    pub.publish_now()
    feed(1)
    pub.publish_now()
    feed(1)
    pub.publish_now()
    shutil.rmtree(reg.path_of(2))        # prune the mid-chain base
    with pytest.raises(DeltaChainError) as exc:
        reg.get(3)
    msg = str(exc.value)
    assert "3" in msg and "2" in msg and "pruned" in msg
    # NOT a silent fresh start: version 1 still resolves fine.
    _, base = reg.get(1)
    assert isinstance(base, HashedFMModel)


def test_delta_chain_corrupted_mid_chain_fingerprint(tmp_path):
    """Regression: a mid-chain delta whose base fingerprint does not
    match the state it claims to patch is refused with the exact broken
    link named — never silently applied onto the wrong base."""
    tr, feed = _trained()
    reg = ModelRegistry(str(tmp_path / "reg"))
    pub = DeltaPublisher(reg, tr, every_n_batches=1, max_depth=10)
    pub.publish_now()                    # v1 base
    feed(1)
    ids = tr.drain_touched()
    corrupted = ModelDelta.build(
        base_version=1,
        base_fingerprint="0" * 64,       # wrong on purpose
        result_fingerprint=tr.state_fingerprint(),
        watermark=tr.watermark, depth=1,
        row_deltas={name: (ids, vals)
                    for name, vals in tr.rows_for(ids).items()},
        dense_deltas={"w0": np.asarray(tr.w0)},
    )
    reg.publish(corrupted, watermark=tr.watermark)   # v2
    with pytest.raises(DeltaChainError) as exc:
        reg.get(2)
    msg = str(exc.value)
    assert "version 2" in msg and "base 1" in msg and "fingerprint" in msg
    # A result-fingerprint lie is caught the same way.
    feed(1)
    ids = tr.drain_touched()
    lying = ModelDelta.build(
        base_version=1,
        base_fingerprint=content_fingerprint(reg.get(1)[1].delta_state()),
        result_fingerprint="f" * 64,     # wrong on purpose
        watermark=tr.watermark, depth=1,
        row_deltas={name: (ids, vals)
                    for name, vals in tr.rows_for(ids).items()},
        dense_deltas={"w0": np.asarray(tr.w0)},
    )
    v = reg.publish(lying, watermark=tr.watermark)
    with pytest.raises(DeltaChainError, match="result fingerprint"):
        reg.get(v)


def test_publisher_compacts_at_max_depth_and_prices_bytes(tmp_path):
    # A sparse-touch regime (few hot keys in a big bucket space): the
    # whole point of a delta is that it ships only the touched rows.
    tr, feed = _trained(num_buckets=1024, key_range=8)
    reg = ModelRegistry(str(tmp_path / "reg"))
    pub = DeltaPublisher(reg, tr, every_n_batches=1, max_depth=2,
                         name="compact")
    pub.publish_now()                    # v1 full (depth 0)
    for _ in range(4):
        feed(1)
        pub.publish_now()                # d1, d2, full (compaction), d1
    assert pub.chain_depth == 1
    snap = metrics.group("features.publisher",
                         labels={"publisher": "compact"}).snapshot()
    assert snap["counters"]["compactions"] == 1
    assert snap["counters"]["full_publishes"] == 2
    assert snap["counters"]["delta_publishes"] == 3
    # Deltas must be (much) smaller than the full state they stand for:
    # eight hot keys of 1,024 buckets, under half by a wide margin.
    assert 0.0 < snap["gauges"]["delta_ratio"] < 0.5
    # The compacted version resolves directly (no chain walk).
    _, model = reg.get(4)
    assert isinstance(model, HashedFMModel)
