"""Elastic resharded resume (ISSUE 6): a snapshot committed at world N
resumes at world M.

The acceptance contract (ROADMAP item 4): Dataset-fed training killed at
world 4 resumes at world 2 AND world 8 with a bit-identical model where
the math is world-independent (replicated carries + the global-order
ElasticFeed — all three online trainers, shuffle order preserved), a
documented bounded-divergence contract where it is not (world-grouped
updates), and loud typed errors — RescaleError /
CursorShardMismatchError — for genuinely rank-entangled state. The old
same-world resume paths stay bit-exact.
"""

import logging
import os

import numpy as np
import pytest

from flinkml_tpu import faults
from flinkml_tpu.data import (
    Cursor,
    CursorShardMismatchError,
    Dataset,
    ElasticFeed,
)
from flinkml_tpu.iteration import (
    CheckpointManager,
    RescaleError,
    RescalePolicy,
    reshard_rank_state,
)
from flinkml_tpu.models import OnlineKMeans, OnlineLogisticRegression
from flinkml_tpu.models.online_scaler import OnlineStandardScaler
from flinkml_tpu.table import Table
from flinkml_tpu.utils.preemption import PreemptionWatchdog

B = 12          # global batches
KILL_EPOCH = 7  # rank loss fires here
INTERVAL = 3    # checkpoint cadence

DIM = 5
_TRUE = np.arange(1.0, DIM + 1.0)


def lr_batch(i, rng):
    x = rng.normal(size=(48, DIM))
    return Table({"features": x, "label": (x @ _TRUE > 0).astype(np.float64)})


def km_batch(i, rng):
    centers = np.arange(12.0).reshape(3, 4)
    assign = rng.integers(0, 3, size=40)
    return Table({"features": centers[assign]
                  + rng.normal(scale=0.4, size=(40, 4))})


def sc_batch(i, rng):
    return Table({"input": rng.normal(size=(32, 6)) * (1 + i)})


def lr_feed(world, shuffled=False, prefetched=False):
    feed = ElasticFeed(
        lambda shard: Dataset.synthetic(lr_batch, B, seed=7, shard=shard),
        world,
    )
    if shuffled:
        feed = feed.shuffle(4, seed=13)
    if prefetched:
        feed = feed.prefetch(depth=2)
    return feed


def _lr():
    return OnlineLogisticRegression().set_alpha(0.5).set_reg(0.01)


def _km():
    return OnlineKMeans().set_k(3).set_seed(11).set_decay_factor(0.9)


def _sc():
    return OnlineStandardScaler()


TRAINERS = {
    "lr": (
        _lr, lr_batch,
        lambda m: m.coefficient,
    ),
    "kmeans": (
        _km, km_batch,
        lambda m: m.centroids,
    ),
    "scaler": (
        _sc, sc_batch,
        lambda m: np.concatenate([m._mean, m._std]),
    ),
}


def _feed(make_batch, world):
    return ElasticFeed(
        lambda shard: Dataset.synthetic(make_batch, B, seed=7, shard=shard),
        world,
    )


def _kill_at_world(est_factory, feed, mgr, epoch=KILL_EPOCH, rank=2):
    """The failure half of the acceptance scenario: a peer rank dies at
    ``epoch`` (rank.lost seam -> watchdog), the loop stops cleanly at
    the boundary with a terminal snapshot."""
    wd = PreemptionWatchdog(signals=())
    with wd:
        with faults.armed(faults.FaultPlan(faults.RankLost(epoch=epoch,
                                                           rank=rank))):
            partial = est_factory().fit_stream(
                feed, checkpoint_manager=mgr, checkpoint_interval=INTERVAL,
            )
    assert wd.shrink_requested and wd.lost_ranks == [rank]
    assert mgr.latest_epoch() == epoch  # the preemption's final snapshot
    return wd, partial


# ---------------------------------------------------------------------------
# The ElasticFeed invariant: one canonical global order at every world
# ---------------------------------------------------------------------------

def test_elastic_feed_global_order_world_independent():
    def key_seq(world, shuffled=False):
        return [float(np.asarray(b.column("features"))[0, 0])
                for b in lr_feed(world, shuffled=shuffled)]

    plain = key_seq(1)
    assert len(plain) == B
    assert key_seq(4) == plain and key_seq(8) == plain
    shuffled = key_seq(1, shuffled=True)
    assert key_seq(4, shuffled=True) == shuffled
    assert key_seq(8, shuffled=True) == shuffled
    assert sorted(shuffled) == sorted(plain) and shuffled != plain


def test_elastic_feed_cursor_reshards_mid_stream():
    """A cursor cut mid-stream at world 4 resumes the EXACT tail at
    world 2 and world 8 — shuffle order included (the shuffle runs on
    the global sequence, so it is world-independent by construction)."""
    def heads(it, n):
        return [float(np.asarray(next(it).column("features"))[0, 0])
                for _ in range(n)]

    golden = heads(lr_feed(1, shuffled=True).iterate(), B)
    it4 = lr_feed(4, shuffled=True).iterate()
    head = heads(it4, 6)
    cur = it4.cursor()
    it4.close()
    assert cur.emitted == 6 and cur.num_shards == 4
    assert cur.shard_index is None  # global-scope cursor
    for world in (2, 8):
        it = lr_feed(world, shuffled=True).iterate(cur)
        tail = heads(it, B - 6)
        it.close()
        assert head + tail == golden


def test_elastic_feed_validates_shard_factory():
    with pytest.raises(ValueError, match="honor its shard argument"):
        next(iter(ElasticFeed(
            lambda shard: Dataset.synthetic(lr_batch, B, shard=(0, 1)), 4,
        )))


# ---------------------------------------------------------------------------
# THE acceptance criterion: kill at world 4, resume at world 2 AND 8,
# bit-identical — all three online trainers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(TRAINERS))
def test_kill_world4_resume_world2_and_world8_bit_exact(tmp_path, name):
    est_factory, make_batch, extract = TRAINERS[name]
    golden = est_factory().fit_stream(_feed(make_batch, 1))

    mgr = CheckpointManager(str(tmp_path / "ckpt"), max_to_keep=10,
                            rescale="reshard")
    wd, partial = _kill_at_world(est_factory, _feed(make_batch, 4), mgr)
    assert partial.model_version == KILL_EPOCH

    # The survivors' plan: newest commonly-valid snapshot, shrunken world.
    plan = wd.plan_elastic_resume(mgr, world=4)
    assert plan.epoch == KILL_EPOCH and plan.old_world == 4
    assert plan.new_world == 3  # 4 ranks, 1 lost

    for world in (2, 8):
        m = CheckpointManager(str(tmp_path / f"ckpt-w{world}"),
                              max_to_keep=10, rescale="reshard")
        # Each resume starts from its own copy of the kill-time snapshot
        # state (the shared directory would otherwise be rewritten by
        # the first resume's terminal commit at ITS world).
        import shutil

        shutil.rmtree(str(tmp_path / f"ckpt-w{world}"))
        shutil.copytree(str(tmp_path / "ckpt"),
                        str(tmp_path / f"ckpt-w{world}"))
        recovered = est_factory().fit_stream(
            _feed(make_batch, world), checkpoint_manager=m,
            checkpoint_interval=INTERVAL, resume=True,
        )
        np.testing.assert_array_equal(extract(recovered), extract(golden))
        assert recovered.model_version == golden.model_version == B
        # Resumed from the kill's own snapshot: the cursor of world 4.
        cursor = m.last_restored_extra["data_cursor"]
        assert cursor["num_shards"] == 4 and cursor["emitted"] == KILL_EPOCH


def test_kill_world4_resume_world2_shuffled_dataset_fed(tmp_path):
    """The Dataset-fed variant with a SHUFFLED pipeline: shuffle order
    is preserved across the world change (global-order shuffle), so the
    resumed model is still bit-identical."""
    golden = _lr().fit_stream(lr_feed(1, shuffled=True))

    mgr = CheckpointManager(str(tmp_path / "ckpt"), max_to_keep=10,
                            rescale="reshard")
    _kill_at_world(_lr, lr_feed(4, shuffled=True), mgr)
    recovered = _lr().fit_stream(
        lr_feed(2, shuffled=True), checkpoint_manager=mgr,
        checkpoint_interval=INTERVAL, resume=True,
    )
    np.testing.assert_array_equal(recovered.coefficient, golden.coefficient)
    assert recovered.model_version == B
    cursor = mgr.last_restored_extra["data_cursor"]
    assert cursor["num_shards"] == 4 and cursor["shard_index"] is None
    assert cursor["shuffle"] is not None


@pytest.mark.no_retrace
def test_elasticity_smoke_prefetched_zero_retrace(tmp_path):
    """Tier-1 elasticity smoke: the full pipeline (synthetic source ->
    global merge -> bucket-padded device prefetch) killed at world 4 and
    resumed at world 2, bit-identical, with zero retraces (constant
    batch shapes land in one bucket)."""
    golden = _lr().fit_stream(lr_feed(1, prefetched=True))
    mgr = CheckpointManager(str(tmp_path / "ckpt"), max_to_keep=10,
                            rescale="reshard")
    _kill_at_world(_lr, lr_feed(4, prefetched=True), mgr)
    recovered = _lr().fit_stream(
        lr_feed(2, prefetched=True), checkpoint_manager=mgr,
        checkpoint_interval=INTERVAL, resume=True,
    )
    np.testing.assert_array_equal(recovered.coefficient, golden.coefficient)


def test_same_world_resume_paths_stay_bit_exact(tmp_path):
    """The pre-elastic contract is untouched: kill+resume at the SAME
    world is bit-exact, and the cursor now records its shard count."""
    golden = _lr().fit_stream(lr_feed(4))
    mgr = CheckpointManager(str(tmp_path / "ckpt"), max_to_keep=10)
    with faults.armed(faults.FaultPlan(faults.RaiseAtEpoch(KILL_EPOCH))):
        with pytest.raises(faults.FaultInjected):
            _lr().fit_stream(lr_feed(4), checkpoint_manager=mgr,
                             checkpoint_interval=INTERVAL)
    recovered = _lr().fit_stream(lr_feed(4), checkpoint_manager=mgr,
                                 checkpoint_interval=INTERVAL, resume=True)
    np.testing.assert_array_equal(recovered.coefficient, golden.coefficient)
    cursor = mgr.last_restored_extra["data_cursor"]
    assert cursor["num_shards"] == 4


# ---------------------------------------------------------------------------
# The documented bounded-divergence contract: world-GROUPED updates
# ---------------------------------------------------------------------------

def test_world_grouped_updates_bounded_divergence(tmp_path):
    """When the update itself groups one batch per rank (the psum'd
    data-parallel composition), a world change alters the update
    granularity: the resumed model consumes the identical global data
    but is NOT bit-identical. The documented contract
    (docs/development/fault_tolerance.md, 'Elastic resume') is
    convergence-level equivalence; this pins it with an explicit
    tolerance."""
    def grouped(feed_iter, group):
        pending = []
        for batch in feed_iter:
            pending.append(batch)
            if len(pending) == group:
                out = pending[0]
                for t in pending[1:]:
                    out = out.concat(t)
                yield out
                pending = []
        if pending:
            out = pending[0]
            for t in pending[1:]:
                out = out.concat(t)
            yield out

    # Uninterrupted fixed-world-4 run: 12 global batches in groups of 4.
    golden = _lr().fit_stream(grouped(lr_feed(4).iterate(), 4))

    # Elastic run: groups of 4 until the kill after 2 updates (8 global
    # batches consumed), then resume grouped by the SHRUNKEN world 2
    # over the exact remaining global tail.
    mgr = CheckpointManager(str(tmp_path / "ckpt"), max_to_keep=10,
                            rescale="reshard")
    with faults.armed(faults.FaultPlan(faults.RaiseAtEpoch(2))):
        with pytest.raises(faults.FaultInjected):
            _lr().fit_stream(grouped(lr_feed(4).iterate(), 4),
                             checkpoint_manager=mgr, checkpoint_interval=1)
    assert mgr.latest_epoch() == 2  # two grouped updates committed
    tail = lr_feed(2).iterate(Cursor(emitted=8, num_shards=2))
    recovered = _lr().fit_stream(
        grouped(tail, 2), checkpoint_manager=mgr, checkpoint_interval=1,
        resume=True, stream_resume="continue",
    )
    # Same global data, different grouping: equivalent to tolerance,
    # not to the bit.
    assert not np.array_equal(recovered.coefficient, golden.coefficient)
    np.testing.assert_allclose(recovered.coefficient, golden.coefficient,
                               rtol=0.35, atol=0.05)
    cos = np.dot(recovered.coefficient, golden.coefficient) / (
        np.linalg.norm(recovered.coefficient)
        * np.linalg.norm(golden.coefficient)
    )
    assert cos > 0.99


# ---------------------------------------------------------------------------
# Typed refusals: RescaleError (satellite 2) + CursorShardMismatchError
# (satellite 1)
# ---------------------------------------------------------------------------

def test_rescale_reject_error_carries_triage_context(tmp_path, caplog):
    mgr = CheckpointManager(str(tmp_path), world_size=4)
    mgr.save({"w": np.ones(3)}, 5)
    reader = CheckpointManager(str(tmp_path), world_size=2)
    with caplog.at_level(logging.ERROR, logger="flinkml_tpu.checkpoint"):
        with pytest.raises(RescaleError) as exc:
            reader.restore(5, like={"w": 0})
    msg = str(exc.value)
    # Fleet-log triage needs: which snapshot, which epoch, which worlds,
    # what the policy decided.
    assert os.path.join(str(tmp_path), "ckpt-5") in msg
    assert "epoch 5" in msg
    assert "world_size=4" in msg and "world_size=2" in msg
    assert "reject" in msg
    # ... and the same message through the rank-tagged logger.
    assert any("ckpt-5" in rec.message for rec in caplog.records)


def test_rescale_policy_layout_matrix(tmp_path):
    """reshard policy: replicated restores free; sharded revalidates
    divisibility; per_rank refuses; legacy allow skips validation."""
    state = {"coef": np.ones(3), "rows": np.arange(8.0)}
    writer = CheckpointManager(str(tmp_path), world_size=4)
    writer.save(state, 1, layouts={"coef": "replicated", "rows": "sharded:0"})

    ok = CheckpointManager(str(tmp_path), world_size=2, rescale="reshard")
    restored, epoch = ok.restore(1, like={"coef": 0, "rows": 0})
    assert epoch == 1
    np.testing.assert_array_equal(restored["rows"], np.arange(8.0))

    bad = CheckpointManager(str(tmp_path), world_size=3, rescale="reshard")
    with pytest.raises(RescaleError, match="does not divide"):
        bad.restore(1, like={"coef": 0, "rows": 0})

    writer.save({"m": np.arange(4.0)}, 2, layouts="per_rank")
    with pytest.raises(RescaleError, match="per_rank"):
        CheckpointManager(str(tmp_path), world_size=2,
                          rescale="reshard").restore(2, like={"m": 0})
    # The legacy escape hatch stays available (and unvalidated).
    relaxed = CheckpointManager(str(tmp_path), world_size=2, rescale="allow")
    relaxed.restore(2, like={"m": 0})
    assert relaxed.allow_rescale  # legacy property view

    with pytest.raises(ValueError, match="reject"):
        RescalePolicy("explode")
    with pytest.raises(ValueError, match="layout"):
        writer.save({"m": np.arange(4.0)}, 3, layouts="diagonal")


def test_reshard_rank_state_reassembles_and_resplits(tmp_path):
    like = {"w": 0, "rows": 0}
    for r in range(4):
        mgr = CheckpointManager(str(tmp_path / f"rank-{r}"), world_size=4)
        mgr.save({"w": np.full(3, 7.0), "rows": np.arange(4.0) + 10 * r}, 2,
                 layouts={"w": "replicated", "rows": "sharded:0"})
    # 4-way family -> 2 ranks of 8 rows, rank order preserved.
    st = reshard_rank_state(str(tmp_path), 2, like, new_shard=(1, 2))
    np.testing.assert_array_equal(st["w"], np.full(3, 7.0))
    np.testing.assert_array_equal(
        st["rows"], np.concatenate([np.arange(4.0) + 20, np.arange(4.0) + 30])
    )
    # Diverged "replicated" leaves are a broken family, not a restore.
    mgr0 = CheckpointManager(str(tmp_path / "rank-0"), world_size=4)
    mgr0.save({"w": np.full(3, 9.0), "rows": np.arange(4.0)}, 2,
              layouts={"w": "replicated", "rows": "sharded:0"})
    with pytest.raises(RescaleError, match="diverges"):
        reshard_rank_state(str(tmp_path), 2, like, new_shard=(0, 2))
    # A missing rank's shard cannot be reassembled.
    import shutil

    shutil.rmtree(str(tmp_path / "rank-2"))
    with pytest.raises(RescaleError, match="not contiguous"):
        reshard_rank_state(str(tmp_path), 2, like, new_shard=(0, 2))


def test_cursor_shard_mismatch_is_loud(tmp_path):
    """Satellite 1: a cursor from a 4-way feed must never silently
    fast-forward a 2-way feed to the wrong rows."""
    rows = np.arange(80.0).reshape(40, 2)

    def block_ds(shard):
        return Dataset.from_arrays(Table({"x": rows}), 4, shard=shard)

    # Per-shard Dataset, contiguous-block deal: entangled -> loud.
    it = block_ds((0, 4)).iterate()
    next(it)
    cur = it.cursor()
    it.close()
    assert cur.num_shards == 4 and cur.shard_index == 0
    with pytest.raises(CursorShardMismatchError, match="cannot reshard"):
        block_ds((0, 2)).iterate(cur)
    # Same world: fine (the pre-elastic path).
    it2 = block_ds((0, 4)).iterate(cur)
    assert it2.emitted == 1
    it2.close()

    # Round-robin synthetic deal: the reshard is legal and re-derived.
    syn4 = Dataset.synthetic(lr_batch, B, seed=7, shard=(1, 4))
    it = syn4.iterate()
    next(it)
    scur = it.cursor()
    it.close()
    syn2 = Dataset.synthetic(lr_batch, B, seed=7, shard=(1, 2))
    it = syn2.iterate(scur)
    # global watermark 1*4=4 -> shard 1 of 2 owns indices 1,3 -> skip 2
    assert it.emitted == 2
    it.close()

    # ElasticFeed over block shards: same-world resume fine, world
    # change loud.
    efeed4 = ElasticFeed(block_ds, 4)
    it = efeed4.iterate()
    [next(it) for _ in range(5)]
    gcur = it.cursor()
    it.close()
    it = efeed4.iterate(gcur)
    assert it.emitted == 5
    it.close()
    with pytest.raises(CursorShardMismatchError, match="not round-robin"):
        ElasticFeed(block_ds, 2).iterate(gcur)

    # Scope mixups are refused in both directions.
    with pytest.raises(CursorShardMismatchError, match="global-order"):
        block_ds((0, 4)).iterate(gcur)
    with pytest.raises(CursorShardMismatchError, match="per-shard"):
        efeed4.iterate(scur)


def test_cursor_json_roundtrip_carries_shards():
    c = Cursor(emitted=6, num_shards=4, shard_index=None, in_flight=1)
    d = c.to_json_dict()
    back = Cursor.from_json_dict(d)
    assert back == c and back.global_emitted == 6
    per = Cursor(emitted=3, num_shards=4, shard_index=2)
    assert per.global_emitted == 12  # lockstep: per-shard x world
    legacy = Cursor.from_json_dict({"emitted": 5})  # pre-elastic cursors
    assert legacy.num_shards is None and legacy.shard_index is None


# ---------------------------------------------------------------------------
# The survivors' rendezvous
# ---------------------------------------------------------------------------

def test_agree_resume_epoch_picks_newest_commonly_valid(tmp_path):
    from flinkml_tpu.parallel.distributed import agree_resume_epoch

    mgr = CheckpointManager(str(tmp_path), max_to_keep=10)
    for epoch in (2, 4, 6):
        mgr.save({"w": np.full(2, float(epoch))}, epoch)
    assert agree_resume_epoch(mgr) == 6
    faults.corrupt_latest(mgr, target="arrays")
    # The newest snapshot no longer verifies: survivors agree on 4.
    assert agree_resume_epoch(mgr) == 4
    empty = CheckpointManager(str(tmp_path / "none"))
    assert agree_resume_epoch(empty) is None


def test_rescale_rendezvous_seam_scriptable(tmp_path):
    wd = PreemptionWatchdog(signals=())
    wd.notify_rank_lost(3)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save({"w": np.ones(2)}, 1)
    with faults.armed(faults.FaultPlan(faults.FailRendezvous())) as plan:
        with pytest.raises(faults.FaultInjected, match="rendezvous"):
            wd.plan_elastic_resume(mgr, world=4)
    assert plan.log and plan.log[0][0] == "rendezvous.rescale"
    # Undisturbed, the plan carries the agreed epoch + shrunken world.
    plan2 = wd.plan_elastic_resume(mgr, world=4)
    assert (plan2.epoch, plan2.old_world, plan2.new_world) == (1, 4, 3)


def test_rank_lost_without_watchdog_is_a_hard_crash():
    with faults.armed(faults.FaultPlan(faults.RankLost(epoch=1, rank=0))):
        with pytest.raises(faults.FaultInjected, match="rank loss"):
            _lr().fit_stream(lr_feed(2))


def test_compact_rank_and_survivor_world():
    from flinkml_tpu.parallel.distributed import compact_rank

    assert compact_rank(0, [2]) == 0
    assert compact_rank(3, [2]) == 2
    assert compact_rank(2, [2]) is None
    assert compact_rank(5, [0, 3]) == 3
    wd = PreemptionWatchdog(signals=())
    wd.notify_rank_lost(1)
    wd.notify_rank_lost(1)  # idempotent
    assert wd.lost_ranks == [1] and wd.survivor_world(4) == 3
    assert wd.survivor_world(1) == 1  # floored: this host is alive


def test_chained_reshard_watermark_stays_exact():
    """A reshard whose global watermark does not divide the new world
    leaves UNEVEN per-shard skips; the cursor's recorded
    ``global_watermark`` keeps subsequent reshards exact where the
    lockstep product (emitted x num_shards) would overestimate and
    silently skip batches."""
    N = 60

    def ds(shard):
        return Dataset.synthetic(lr_batch, N, seed=7, shard=shard)

    # World 4, 7 lockstep rounds -> 28 global batches consumed.
    its4 = [ds((i, 4)).iterate() for i in range(4)]
    for _ in range(7):
        for it in its4:
            next(it)
    c4 = its4[0].cursor()
    for it in its4:
        it.close()
    assert c4.global_emitted == 28

    # Reshard rank 0 to world 8: skip ceil(28/8)=4, then ONE more
    # lockstep round -> global 36 (the product 5*8=40 would lie).
    it8 = ds((0, 8)).iterate(c4)
    assert it8.emitted == 4
    next(it8)
    c8 = it8.cursor()
    it8.close()
    assert c8.emitted == 5 and c8.global_emitted == 36

    # Second reshard to world 2 lands exactly at global batch 36.
    it2 = ds((0, 2)).iterate(c8)
    assert it2.emitted == 18  # shard 0 of 2 owns even indices < 36
    batch = next(it2)
    it2.close()
    rng = np.random.default_rng([7, 36])  # SyntheticSource's draw key
    expected = lr_batch(36, rng)
    np.testing.assert_array_equal(
        np.asarray(batch.column("features")),
        np.asarray(expected.column("features")),
    )


# ---------------------------------------------------------------------------
# Plan x elastic resume composition (ISSUE 7): FSDP-sharded state,
# plan-derived layout tags, world change through the rank.lost seam
# ---------------------------------------------------------------------------

def test_fsdp_plan_kill_world4_resume_world2_and_world8(tmp_path):
    """An FSDP-sharded SGD trainer (parameters + momentum sharded per
    the plan, snapshots tagged by ``save(plan=...)``) killed at world 4
    through the ``rank.lost`` seam resumes at world 2 AND world 8 — the
    plan-derived ``sharded:0`` tags are what make the cross-world
    re-layout legal, with no hand-written ``layouts=`` anywhere."""
    import json
    import shutil

    import jax

    from flinkml_tpu.parallel import DeviceMesh
    from flinkml_tpu.sharding import FSDP
    from flinkml_tpu.sharding.apply import train_linear_plan

    dim = 64
    rng = np.random.default_rng(3)
    x = rng.normal(size=(96, dim))
    y = (x @ np.arange(1.0, dim + 1.0) > 0).astype(x.dtype)

    def run(world, mgr=None, resume=False):
        mesh = DeviceMesh.for_plan(FSDP, devices=jax.devices()[:world])
        return train_linear_plan(
            x, y, None, FSDP, mesh, max_iter=B, learning_rate=0.5,
            checkpoint_manager=mgr, checkpoint_interval=INTERVAL,
            resume=resume,
        )

    golden = run(1)

    mgr = CheckpointManager(str(tmp_path / "ckpt"), max_to_keep=10,
                            rescale="reshard")
    wd = PreemptionWatchdog(signals=())
    with wd:
        with faults.armed(faults.FaultPlan(
                faults.RankLost(epoch=KILL_EPOCH, rank=2))):
            run(4, mgr)
    assert wd.shrink_requested and wd.lost_ranks == [2]
    assert mgr.latest_epoch() == KILL_EPOCH  # the preemption's snapshot

    # The kill-time snapshot carries PLAN-derived tags at world 4.
    with open(tmp_path / "ckpt" / f"ckpt-{KILL_EPOCH}" / "meta.json") as fh:
        meta = json.load(fh)
    assert meta["layouts"] == ["sharded:0", "sharded:0"]  # coef, momentum
    assert meta["world_size"] == 4

    for world in (2, 8):
        shutil.copytree(str(tmp_path / "ckpt"), str(tmp_path / f"w{world}"))
        m = CheckpointManager(str(tmp_path / f"w{world}"), max_to_keep=10,
                              rescale="reshard")
        recovered = run(world, m, resume=True)
        np.testing.assert_allclose(recovered, golden, rtol=1e-9,
                                   atol=1e-12)
        # The resumed run's own terminal snapshot records ITS world.
        with open(tmp_path / f"w{world}" / f"ckpt-{B}" /
                  "meta.json") as fh:
            assert json.load(fh)["world_size"] == world


def test_verify_keeps_bool_contract_over_failed_async_write(tmp_path):
    """A parked async-write failure (the crash path verify exists for)
    must not leak out of the verification queries: the failure is
    drained+logged and the COMMITTED snapshots are still nominated —
    elastic planning falls back instead of crashing."""
    mgr = CheckpointManager(str(tmp_path), max_to_keep=10, async_write=True)
    mgr.save({"w": np.ones(2)}, 1)
    mgr.wait()
    with faults.armed(faults.FaultPlan(faults.TornWrite(2))):
        mgr.save({"w": np.full(2, 2.0)}, 2)  # background write will tear
        assert mgr.newest_valid_epoch() == 1  # drains quietly, no raise
    assert mgr.verify(1) and not mgr.verify(2)
    from flinkml_tpu.parallel.distributed import agree_resume_epoch

    assert agree_resume_epoch(mgr) == 1
