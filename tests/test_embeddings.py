"""flinkml_tpu.embeddings — the sharded-embedding-table subsystem.

The acceptance ladder (ISSUE 14), all on the conftest 8-virtual-device
CPU mesh: exchange parity vs dense references, strategy gating, the
over-budget refuse/route contract, world-8 -> world-2 elastic resume,
mixed-precision serving, and the three consumers (W2V re-expressed on
the primitive, FM's sharded factor matrix, ALS's loud refusal +
factor-table export).
"""

import json
import os

import numpy as np
import pytest

import jax

from flinkml_tpu.embeddings import (
    EmbeddingTable,
    dense_vocab_threshold,
    resolve_exchange,
    shard_rows_for,
)
from flinkml_tpu.embeddings import exchange
from flinkml_tpu.iteration import CheckpointManager
from flinkml_tpu.parallel import DeviceMesh
from flinkml_tpu.sharding import (
    EMBEDDING,
    FSDP,
    FSDP_TP,
    REPLICATED,
    NoFeasiblePlanError,
    infer_plan,
    is_embedding_param,
)
from flinkml_tpu.table import Table


def _table(vocab=1000, dim=16, seed=0, **kw):
    rng = np.random.default_rng(seed)
    rows = rng.normal(size=(vocab, dim)).astype(np.float32)
    mesh = kw.pop("mesh", None) or DeviceMesh.for_plan(EMBEDDING)
    return rows, EmbeddingTable("t", vocab, dim, mesh=mesh,
                                plan=kw.pop("plan", EMBEDDING),
                                rows=rows, **kw)


# ---------------------------------------------------------------------------
# exchange primitives
# ---------------------------------------------------------------------------

def test_family_naming_convention():
    assert is_embedding_param("w2v/center_embedding")
    assert is_embedding_param("t/embedding_slot0")
    assert not is_embedding_param("coef")
    assert shard_rows_for(1000, 8) == 125
    assert shard_rows_for(1001, 8) == 126


def test_lookup_bitwise_vs_dense_and_across_strategies():
    """Lookups are exact (one owning shard per id), so they match the
    dense gather BITWISE — the property serving stability rests on."""
    rows, t = _table()
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 1000, 512).astype(np.int32)
    assert t.n_shards == 8 and t.sharded
    got = np.asarray(t.lookup(ids))
    assert got.tobytes() == rows[ids].tobytes()


@pytest.mark.parametrize("strategy", ["ring", "all_to_all"])
def test_scatter_add_matches_dense_reference(strategy):
    """Both exchange strategies reproduce the dense np.add.at scatter
    (duplicate ids included) up to f32 summation order."""
    rows, t = _table()
    rng = np.random.default_rng(2)
    ids = rng.integers(0, 1000, 777).astype(np.int32)  # odd count: pads
    delta = rng.normal(size=(777, 16)).astype(np.float32)
    t.scatter_add(ids, delta, strategy=strategy)
    ref = rows.copy()
    np.add.at(ref, ids, delta)
    np.testing.assert_allclose(t.to_host(), ref, rtol=1e-5, atol=1e-5)


def test_exchange_strategy_resolution():
    """env > autotune > static; dense_psum is the below-threshold
    placement (subsuming W2V's old static threshold) and never the
    sharded algorithm."""
    assert resolve_exchange(10, 1) == "dense_psum"
    assert resolve_exchange(dense_vocab_threshold(), 8) == "dense_psum"
    over = dense_vocab_threshold() + 1
    assert resolve_exchange(over, 8) in ("ring", "all_to_all")
    env = dict(os.environ)
    try:
        os.environ["FLINKML_TPU_EMBEDDING_EXCHANGE"] = "ring"
        assert resolve_exchange(over, 8) == "ring"
        os.environ["FLINKML_TPU_EMBEDDING_EXCHANGE"] = "bogus"
        with pytest.raises(ValueError, match="bogus"):
            resolve_exchange(over, 8)
        # An EXPLICIT dense_psum request on a sharded table is refused
        # loudly (it is a placement, not an exchange) with the
        # threshold-var remedy in the message — never silently
        # rewritten to ring.
        os.environ["FLINKML_TPU_EMBEDDING_EXCHANGE"] = "dense_psum"
        with pytest.raises(ValueError, match="vocab threshold"):
            resolve_exchange(over, 8)
        # a threshold of 0 shards every vocabulary
        os.environ.pop("FLINKML_TPU_EMBEDDING_EXCHANGE")
        os.environ["FLINKML_TPU_EMBEDDING_DENSE_VOCAB"] = "0"
        assert resolve_exchange(10, 8) in ("ring", "all_to_all")
    finally:
        os.environ.clear()
        os.environ.update(env)


def test_scatter_add_validates_strategy_even_unsharded():
    """A typo'd strategy must fail on a small (unsharded) table too —
    not first in production sharded use."""
    t = EmbeddingTable("small", 16, 4, plan=REPLICATED,
                       mesh=DeviceMesh.for_plan(REPLICATED))
    assert not t.sharded
    with pytest.raises(ValueError, match="unknown exchange strategy"):
        t.scatter_add(np.zeros(2, np.int32), np.zeros((2, 4)),
                      strategy="all_to_al")


def test_footprint_model_agrees_with_padded_placement():
    """infer_plan's footprint is the LARGEST slice (per-dim ceil), so a
    plan it accepts can never fail the table's padded FML503 check at
    the same budget — the indivisible-vocab boundary case."""
    from flinkml_tpu.sharding import per_device_state_bytes

    mesh = {"data": 1, "fsdp": 4, "tp": 2}
    vocab, dim = 8193, 64  # 8193 % 8 != 0: padded rows = 1025 per shard
    shapes = {"edge/embedding": (vocab, dim)}
    padded = 1025 * dim * 4 * 2
    assert per_device_state_bytes(EMBEDDING, mesh, shapes,
                                  optimizer_slots=1) == padded
    # Exactly at the padded footprint: infer_plan routes AND the table
    # constructs (its padded validation sees the same number).
    t = EmbeddingTable("edge", vocab, dim,
                       mesh=DeviceMesh.for_plan(EMBEDDING),
                       hbm_budget_bytes=padded, optimizer_slots=1)
    assert t.plan.name == "embedding" and t.shard_rows == 1025
    # One byte under: refused consistently (NoFeasiblePlanError from
    # the route, never a post-route PlanValidationError surprise).
    with pytest.raises(NoFeasiblePlanError):
        EmbeddingTable("edge", vocab, dim,
                       mesh=DeviceMesh.for_plan(EMBEDDING),
                       hbm_budget_bytes=padded - 1, optimizer_slots=1)


@pytest.mark.parametrize("strategy", ["ring", "all_to_all"])
def test_exchange_traffic_follows_the_batch_not_the_vocabulary(strategy):
    """A step's exchange traffic, reckoned from shapes: a row and its id
    for every id of the batch, over every shard, both ways. Twice the
    batch is twice the bytes, ten times the vocabulary the same bytes,
    and the dense placement's psum of the whole table is what it is
    compared with."""
    dim, batch = 16, 256
    _, small = _table(vocab=1_000, dim=dim)
    _, large = _table(vocab=10_000, dim=dim)
    per_step = small.exchange_bytes_per_step(batch, strategy)
    assert per_step == 2 * small.n_shards * batch * (dim * 4 + 4)
    assert large.exchange_bytes_per_step(batch, strategy) == per_step
    assert small.exchange_bytes_per_step(2 * batch, strategy) == 2 * per_step
    dense = large.exchange_bytes_per_step(batch, "dense_psum")
    assert dense == 2 * large.padded_vocab * dim * 4
    assert per_step < dense


def test_unknown_strategy_refused_in_exchange():
    with pytest.raises(ValueError, match="dense_psum is a placement"):
        exchange.gather((), axes="data", n_shards=8, shard_rows=1,
                        strategy="dense_psum")
    with pytest.raises(ValueError, match="dense_psum is a placement"):
        exchange.scatter_add((), (), axes="data", n_shards=8,
                             shard_rows=1, strategy="dense_psum")


# ---------------------------------------------------------------------------
# refuse / route: the over-budget contract
# ---------------------------------------------------------------------------

def test_over_budget_vocab_refused_replicated_and_routed_sharded():
    """THE acceptance gate: a vocab whose table + optimizer state
    provably exceeds the per-device budget is (a) refused replicated by
    FML503 and (b) routed to the embedding plan by infer_plan."""
    from flinkml_tpu.sharding.apply import PlanValidationError

    mesh = DeviceMesh.for_plan(EMBEDDING)
    vocab, dim = 1 << 16, 16
    rep_bytes = vocab * dim * 4 * 2          # table + 1 slot
    budget = rep_bytes // 6                  # /4 over, /8 fits
    with pytest.raises(PlanValidationError, match="FML503"):
        EmbeddingTable("big", vocab, dim, mesh=mesh, plan=REPLICATED,
                       hbm_budget_bytes=budget, optimizer_slots=1)
    t = EmbeddingTable("big/embedding_probe", vocab, dim, mesh=mesh,
                       hbm_budget_bytes=budget, optimizer_slots=1)
    assert t.plan.name == "embedding" and t.n_shards == 8
    assert t.per_device_bytes() <= budget
    with pytest.raises(NoFeasiblePlanError):
        EmbeddingTable("huge/embedding_probe", vocab, dim, mesh=mesh,
                       hbm_budget_bytes=rep_bytes // 32,
                       optimizer_slots=1)


def test_row_splitting_plan_refused():
    """FSDP_TP splits dim 1 of a [vocab, dim] table — the layout the
    exchange primitives cannot host; refused loudly at construction."""
    with pytest.raises(ValueError, match="WHOLE rows"):
        EmbeddingTable("t", 64, 8, mesh=DeviceMesh.for_plan(FSDP_TP),
                       plan=FSDP_TP)


def test_fsdp_plan_is_a_legal_row_layout():
    """FSDP shards rows over fsdp only (dim intact) — a legal embedding
    layout with 4 shards on the 8-device EMBEDDING-shaped mesh."""
    rows, t = _table(plan=FSDP, mesh=DeviceMesh.for_plan(EMBEDDING))
    assert t.n_shards == 4
    ids = np.arange(100, dtype=np.int32)
    assert np.asarray(t.lookup(ids)).tobytes() == rows[:100].tobytes()


# ---------------------------------------------------------------------------
# checkpoint: world-8 -> world-2 elastic resume
# ---------------------------------------------------------------------------

def test_world8_to_world2_resume_bit_equal(tmp_path):
    rows, t = _table(vocab=1001, optimizer_slots=2)  # odd vocab: pads
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 1001, 256).astype(np.int32)
    delta = rng.normal(size=(256, 16)).astype(np.float32)
    t.scatter_add(ids, delta)
    mgr = CheckpointManager(str(tmp_path), rescale="reshard")
    t.save(mgr, 7)
    with open(tmp_path / "ckpt-7" / "meta.json") as fh:
        meta = json.load(fh)
    # plan-derived tags: the table AND both optimizer slots are
    # sharded:0 (slots land in the same *embedding* family).
    assert meta["layouts"] == ["sharded:0"] * 3
    mesh2 = DeviceMesh.for_plan(EMBEDDING, devices=jax.devices()[:2])
    t2, epoch = EmbeddingTable.restore(
        mgr, "t", 1001, 16, mesh=mesh2, plan=EMBEDDING, optimizer_slots=2
    )
    assert epoch == 7 and t2.n_shards == 2
    assert t2.to_host().tobytes() == t.to_host().tobytes()
    # lookups after the reshard serve identical bytes (the serving
    # stability contract across world sizes).
    q = rng.integers(0, 1001, 64).astype(np.int32)
    assert np.asarray(t2.lookup(q)).tobytes() == \
        np.asarray(t.lookup(q)).tobytes()


def test_restore_without_snapshot_raises(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    with pytest.raises(ValueError, match="no checkpoint"):
        EmbeddingTable.restore(mgr, "t", 10, 4)


# ---------------------------------------------------------------------------
# serving: slice-mesh pool, mixed precision
# ---------------------------------------------------------------------------

def test_pool_serving_bitwise_stable_and_bf16_tolerance():
    from flinkml_tpu.embeddings.serving import EmbeddingLookupModel
    from flinkml_tpu.serving.engine import ServingConfig
    from flinkml_tpu.serving.pool import ReplicaPool, slice_meshes

    rng = np.random.default_rng(4)
    vocab, dim = 2048, 16
    rows = rng.normal(size=(vocab, dim)).astype(np.float32)
    ids = rng.integers(0, vocab, size=(48, 5)).astype(np.int32)
    ids[ids % 11 == 0] = -1
    model = EmbeddingLookupModel(rows, plan=EMBEDDING,
                                 precision="mixed_inference")
    (unbound,) = EmbeddingLookupModel(
        rows, precision="mixed_inference").transform(Table({"ids": ids}))
    pool = ReplicaPool(
        model, Table({"ids": ids[:8]}),
        config=ServingConfig(max_batch_rows=64, max_wait_ms=1.0),
        meshes=slice_meshes(2, plan=EMBEDDING), output_cols=("vector",),
        name="emb_test_pool",
    ).start()
    try:
        v1 = pool.predict({"ids": ids}).columns["vector"]
        v2 = pool.predict({"ids": ids}).columns["vector"]
    finally:
        pool.stop()
    # bitwise-stable across requests AND vs the single-device reference.
    assert v1.tobytes() == v2.tobytes()
    assert v1.tobytes() == np.asarray(unbound.column("vector")).tobytes()
    # mixed-precision tolerance pin: bf16 compute within bf16 epsilon
    # of the f32 pooling (values here are O(1)).
    (f32,) = EmbeddingLookupModel(rows, precision=None).transform(
        Table({"ids": ids}))
    diff = np.abs(v1 - np.asarray(f32.column("vector"))).max()
    assert 0 < diff < 0.05, diff  # bf16 really engaged, and bounded


def test_slice_meshes_plan_shaping():
    from flinkml_tpu.serving.pool import slice_meshes

    meshes = slice_meshes(2, devices=jax.devices()[:8], plan=EMBEDDING)
    assert [dict(m.mesh.shape) for m in meshes] == \
        [{"data": 1, "fsdp": 2, "tp": 2}] * 2
    flat = slice_meshes(4, devices=jax.devices()[:8])
    assert [dict(m.mesh.shape) for m in flat] == [{"data": 2}] * 4


# ---------------------------------------------------------------------------
# consumer: Word2Vec re-expressed on the primitive
# ---------------------------------------------------------------------------

def _w2v_corpus(seed=3):
    rng = np.random.default_rng(seed)
    animals = ["cat", "dog", "horse", "cow", "sheep", "goat"]
    tools = ["hammer", "saw", "drill", "wrench", "screw", "nail"]
    docs = []
    for _ in range(120):
        pool = animals if rng.random() < 0.5 else tools
        docs.append(list(rng.choice(pool, size=8)))
    return docs


@pytest.mark.parametrize("strategy", ["ring", "all_to_all"])
def test_w2v_sharded_strategies_match_dense(monkeypatch, strategy):
    """W2V's sharded SGNS trainer, re-expressed on the exchange
    primitives, reproduces the dense trainer's vectors under BOTH
    strategies (identical sampling sequence; f32 order differs only
    through the exchange's partial adds) — the W2V-primitive-vs-ring
    pinned parity."""
    from flinkml_tpu.models.word2vec import Word2Vec

    docs = _w2v_corpus()
    t = Table({"doc": np.asarray(docs, dtype=object)})

    def fit():
        return Word2Vec().set_input_col("doc").set_vector_size(12) \
            .set_max_iter(2).set_min_count(1).set_seed(0).fit(t)

    dense = fit()
    monkeypatch.setenv("FLINKML_TPU_EMBEDDING_DENSE_VOCAB", "0")
    monkeypatch.setenv("FLINKML_TPU_EMBEDDING_EXCHANGE", strategy)
    sharded = fit()
    np.testing.assert_array_equal(sharded.vocabulary, dense.vocabulary)
    np.testing.assert_allclose(sharded.vectors, dense.vectors,
                               rtol=2e-3, atol=2e-4)


def test_w2v_ring_and_a2a_gathers_agree_bitwise(monkeypatch):
    """The two strategies' GATHER halves are exactly equal (one owning
    shard per id); end-to-end the fits differ only by scatter summation
    order — pinned tight."""
    from flinkml_tpu.models.word2vec import Word2Vec

    docs = _w2v_corpus(seed=5)
    t = Table({"doc": np.asarray(docs, dtype=object)})
    monkeypatch.setenv("FLINKML_TPU_EMBEDDING_DENSE_VOCAB", "0")

    out = {}
    for strategy in ("ring", "all_to_all"):
        monkeypatch.setenv("FLINKML_TPU_EMBEDDING_EXCHANGE", strategy)
        out[strategy] = Word2Vec().set_input_col("doc") \
            .set_vector_size(8).set_max_iter(1).set_min_count(1) \
            .set_seed(0).fit(t).vectors
    np.testing.assert_allclose(out["ring"], out["all_to_all"],
                               rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# consumer: FM's sharded factor matrix
# ---------------------------------------------------------------------------

def _fm_data(n=512, d=24, seed=1):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d))
    true = rng.normal(size=d)
    y = (x @ true > 0).astype(np.float64)
    return Table({"features": x, "label": y}), x, y


def test_fm_sharded_factors_quality_parity():
    """FMClassifier under the EMBEDDING plan shards V/w + Adam slots and
    follows the dense trainer's sampling trajectory; the end-model pin
    is quality parity (Adam's sign normalization amplifies f32
    summation-order noise, so per-coordinate parity is not a valid
    contract — see the trainer docstring)."""
    from flinkml_tpu.models.fm import FMClassifier
    from flinkml_tpu.sharding import EMBEDDING

    t, x, y = _fm_data()
    dense = FMClassifier().set_max_iter(40).set_global_batch_size(256)\
        .fit(t)
    shard = FMClassifier(sharding_plan=EMBEDDING).set_max_iter(40)\
        .set_global_batch_size(256).fit(t)
    assert shard._v.shape == dense._v.shape
    (pd,) = dense.transform(t)
    (ps,) = shard.transform(t)
    yd = np.asarray(pd.column("prediction"))
    ys = np.asarray(ps.column("prediction"))
    acc_d = (yd == y).mean()
    acc_s = (ys == y).mean()
    assert acc_s >= acc_d - 0.05, (acc_s, acc_d)
    assert (yd == ys).mean() >= 0.9, (yd != ys).sum()


def test_fm_sharded_first_step_margins_match_dense():
    """One-step pin at the gradient level: the sharded trainer's
    column-psum'd forward margins equal the dense FM margins to f32
    tolerance — the numerics contract underneath the quality pin."""
    from flinkml_tpu.models.fm import FMRegressor
    from flinkml_tpu.sharding import FSDP

    t, x, y = _fm_data(seed=2)
    # tol=inf-ish via 1 step: compare the one-step w0 (a pure function
    # of the first batch's margins) between the layouts. The label is
    # SHIFTED so the mean margin is decisively nonzero: Adam's first
    # step is ±lr·g/(|g|+eps) — with a near-zero g (the unshifted
    # x[:, 0] label for this seed) the w0 SIGN becomes a coin flip on
    # the two layouts' reduction order, a full-suite flake observed
    # once (shard -0.0999992 vs dense +0.0999993); the margins
    # themselves (the contract under test) match either way.
    label = x[:, 0] + 1.0
    dense = FMRegressor().set_max_iter(1).set_global_batch_size(256)\
        .fit(Table({"features": x, "label": label}))
    shard = FMRegressor(sharding_plan=FSDP).set_max_iter(1)\
        .set_global_batch_size(256)\
        .fit(Table({"features": x, "label": label}))
    np.testing.assert_allclose(shard._w0, dense._w0, rtol=1e-4,
                               atol=1e-6)


def test_fm_streamed_fit_refuses_plan():
    from flinkml_tpu.models.fm import FMClassifier
    from flinkml_tpu.sharding import EMBEDDING

    t, _, _ = _fm_data(n=64)
    est = FMClassifier(sharding_plan=EMBEDDING)
    with pytest.raises(ValueError, match="streamed fit does not thread"):
        est.fit([t, t])


def test_fm_replicated_plan_refused():
    from flinkml_tpu.models.fm import FMClassifier
    from flinkml_tpu.sharding import BATCH_PARALLEL

    t, _, _ = _fm_data(n=64)
    with pytest.raises(ValueError, match="leaves the FM factor family"):
        FMClassifier(sharding_plan=BATCH_PARALLEL).fit(t)


def test_fm_row_splitting_plan_refused():
    from flinkml_tpu.models.fm import FMClassifier
    from flinkml_tpu.sharding import FSDP_TP

    t, _, _ = _fm_data(n=64)
    with pytest.raises(ValueError, match="factor rows whole"):
        FMClassifier(sharding_plan=FSDP_TP).fit(t)


# ---------------------------------------------------------------------------
# consumer: ALS — loud refusal + factor-table export
# ---------------------------------------------------------------------------

def _als_model():
    from flinkml_tpu.models.als import ALS

    rng = np.random.default_rng(0)
    n = 400
    t = Table({
        "user": rng.integers(0, 24, n),
        "item": rng.integers(0, 16, n),
        "rating": rng.random(n) * 5,
    })
    return ALS().set_max_iter(2).fit(t)


def test_als_fit_refuses_sharding_plan():
    from flinkml_tpu.models.als import ALS

    with pytest.raises(ValueError, match="normal-equation buffers"):
        ALS(sharding_plan=EMBEDDING).fit(Table({
            "user": np.zeros(4, np.int64),
            "item": np.zeros(4, np.int64),
            "rating": np.ones(4),
        }))


def test_als_factor_tables_export_sharded():
    model = _als_model()
    user_t, item_t = model.factor_tables(plan=EMBEDDING)
    assert user_t.sharded and item_t.sharded
    np.testing.assert_allclose(
        user_t.to_host(), model.user_factors.astype(np.float32),
        rtol=1e-6, atol=1e-7,
    )
    ids = np.arange(len(model.user_factors), dtype=np.int32)
    got = np.asarray(user_t.lookup(ids))
    assert got.tobytes() == user_t.to_host()[ids].tobytes()
