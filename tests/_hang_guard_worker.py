"""Multi-process hang-guard worker, launched by test_distributed.py.

Regression for the rank-local-failure hang class: a failure that occurs
on ONE rank only (a bad batch, a raising source iterator, a missing or
corrupt checkpoint shard) must abort EVERY rank together through the
agreement layer (``iteration/stream_sync.py``) — the alternative is the
failing rank exiting while its peers block forever in their next
collective (the Gloo backend wedges permanently). Each case constructs
the failure on rank 0 only and asserts BOTH ranks raise; a hang fails
the parent test's timeout instead.

Also covers the straddled-checkpoint resume protocol for rank-scoped GBT
snapshots: ranks whose checkpoint sets differ (a crash between one
rank's save and the agreed commit, plus pruning) must converge on the
newest COMMON tree — or all restart together when the intersection is
empty — and still reproduce the uninterrupted forest exactly.

Usage: python _hang_guard_worker.py <port> <process_id> <num_processes> <workdir>
Prints ``GUARD_OK <pid>`` on success.
"""

import os
import shutil
import sys

port, pid, nproc, workdir = (
    sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4]
)

os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

import numpy as np  # noqa: E402

from flinkml_tpu.iteration.checkpoint import CheckpointManager  # noqa: E402
from flinkml_tpu.iteration.datacache import cache_stream  # noqa: E402
from flinkml_tpu.iteration.stream_sync import synced_stream  # noqa: E402
from flinkml_tpu.models._gbt_stream import train_gbt_stream  # noqa: E402
from flinkml_tpu.models._linear_sgd import (  # noqa: E402
    train_linear_model_stream,
)
from flinkml_tpu.models.kmeans import train_kmeans_stream  # noqa: E402
from flinkml_tpu.parallel import DeviceMesh, init_distributed  # noqa: E402

idx, count = init_distributed(
    coordinator_address=f"127.0.0.1:{port}",
    num_processes=nproc,
    process_id=pid,
)
assert (idx, count) == (pid, nproc), (idx, count)

mesh = DeviceMesh()
rng = np.random.default_rng(100 + pid)


def expect_all_ranks_raise(label, fn):
    """Run a case whose failure lives on rank 0 only; EVERY rank must
    raise (rank 0 the original error, peers the agreement error). A hang
    here trips the parent's subprocess timeout."""
    try:
        fn()
    except Exception as e:  # noqa: BLE001 — the expected agreed abort
        print(f"{label}: rank {pid} raised {type(e).__name__}", flush=True)
        return
    raise SystemExit(f"{label}: rank {pid} did NOT raise")


def good_batch(n=16, d=4):
    x = rng.normal(size=(n, d)).astype(np.float32)
    return {"x": x, "y": (x[:, 0] > 0).astype(np.float32)}


# --- 1. synced_stream: the SOURCE ITERATOR raises on rank 0 mid-stream.
def case_iterator_raise():
    def source():
        yield np.ones((4, 3), np.float32)
        if pid == 0:
            raise IOError("injected shard read failure")
        yield np.ones((4, 3), np.float32)

    for _ in synced_stream(source(), mesh):
        pass


expect_all_ranks_raise("case1-iterator", case_iterator_raise)


# --- 2. GBT streamed pass A: ragged SECOND batch on rank 0 (the ingest
# accumulation — fixed-width reservoir add — must be skipped, not raise).
def case_gbt_ragged():
    batches = [good_batch()]
    bad_d = 6 if pid == 0 else 4
    x = rng.normal(size=(16, bad_d)).astype(np.float32)
    batches.append({"x": x, "y": (x[:, 0] > 0).astype(np.float32)})
    train_gbt_stream(
        cache_stream(iter(batches)), mesh=mesh, logistic=True,
        num_trees=2, depth=2, max_bins=8, learning_rate=0.3,
        reg_lambda=1.0, subsample=1.0, seed=0,
    )


expect_all_ranks_raise("case2-gbt-ragged", case_gbt_ragged)


# --- 3. KMeans streamed pass 0: ragged second batch on rank 0 (iterable
# source; checked extraction must gate the reservoir add + cache append).
def case_kmeans_ragged():
    batches = [good_batch(), good_batch()]
    if pid == 0:
        batches[1] = {"x": rng.normal(size=(16, 6)).astype(np.float32)}
    train_kmeans_stream(
        iter({"x": b["x"]} for b in batches), k=2, mesh=mesh,
        max_iter=2, seed=0,
    )


expect_all_ranks_raise("case3-kmeans-ragged", case_kmeans_ragged)


# --- 3b. KMeans streamed pass 0: the source ITERATOR raises on rank 0
# (guarded_iter must fold it into the rendezvous, not propagate before
# the plan's collectives).
def case_kmeans_iter_raise():
    def source():
        yield {"x": good_batch()["x"]}
        if pid == 0:
            raise IOError("injected stream failure")
        yield {"x": good_batch()["x"]}

    train_kmeans_stream(source(), k=2, mesh=mesh, max_iter=2, seed=0)


expect_all_ranks_raise("case3b-kmeans-iter", case_kmeans_iter_raise)


# --- 3c. Uniformly bad stream (every batch fails validation on EVERY
# rank): skip-on-failure leaves all local caches empty, but the held
# validation error must surface as ITSELF — rendezvous runs before the
# plan, so the user never debugs a phantom "stream is empty" instead.
def case_all_bad_surfaces_real_error():
    bad = {"x": np.ones(8, np.float32)}  # 1-D: fails the [n, d] check
    try:
        train_kmeans_stream(iter([bad]), k=2, mesh=mesh, max_iter=2, seed=0)
    except ValueError as e:
        assert "must be [n, d]" in str(e), e
        print(f"case3c-real-error: rank {pid} got the validation error",
              flush=True)
        return
    raise SystemExit(f"case3c: rank {pid} did NOT raise")


case_all_bad_surfaces_real_error()


# --- 4. Linear streamed ingest: a ragged VALUE (np.array raises) on
# rank 0 — the checked copy holds it; the append must be skipped.
def case_linear_ragged_value():
    batches = [good_batch(), good_batch()]
    if pid == 0:
        bad = dict(batches[1])
        bad["x"] = [[1.0, 2.0], [3.0]]  # ragged: np.array raises
        batches[1] = bad
    train_linear_model_stream(
        iter(batches), mesh=mesh, loss="logistic", max_iter=2,
        learning_rate=0.5, reg=0.0, elastic_net=0.0, tol=0.0,
    )


expect_all_ranks_raise("case4-linear-ragged", case_linear_ragged_value)


# --- 4b. LDA from a sealed DataCache whose SECOND batch is invalid on
# rank 0 only (negative count): the full-cache pre-validation must hold
# it for the rendezvous, not raise rank-locally at replay time.
def case_lda_bad_cached_batch():
    from flinkml_tpu.models.lda import LDA

    good = np.abs(rng.normal(size=(8, 6))).astype(np.float32)
    bad = good.copy()
    if pid == 0:
        bad[0, 0] = -1.0
    cache = cache_stream(iter({"features": b} for b in [good, bad]))
    LDA(mesh=mesh).set_k(2).set_max_iter(2).fit(cache)


expect_all_ranks_raise("case4b-lda-bad-cache", case_lda_bad_cached_batch)


# --- 4c. Online FTRL: the source stream raises on rank 0 mid-lockstep
# (agree_first_item_dim + synced_padded_stream failure paths).
def case_online_ftrl_iter_raise():
    from flinkml_tpu.models.online_logistic_regression import (
        OnlineLogisticRegression,
    )
    from flinkml_tpu.table import Table

    def source():
        b = good_batch()
        yield Table({"features": b["x"], "label": b["y"]})
        if pid == 0:
            raise IOError("injected stream failure")
        b = good_batch()
        yield Table({"features": b["x"], "label": b["y"]})

    OnlineLogisticRegression(mesh=mesh).fit_stream(source())


expect_all_ranks_raise("case4c-ftrl-iter", case_online_ftrl_iter_raise)


# --- 4d. Word2Vec: a bad document batch on rank 0 (missing token
# column) must ride the ingest rendezvous, not raise rank-locally
# before the vocabulary-union collective.
def case_w2v_bad_batch():
    from flinkml_tpu.models.word2vec import Word2Vec
    from flinkml_tpu.table import Table

    docs = np.asarray([["a", "b", "a", "c"]] * 4, dtype=object)
    batches = [Table({"tok": docs})]
    if pid == 0:
        batches.append(Table({"wrong_col": docs}))
    else:
        batches.append(Table({"tok": docs}))
    (
        Word2Vec(mesh=mesh).set_input_col("tok").set_vector_size(4)
        .set_min_count(1).set_max_iter(1).set_seed(0)
        .fit(iter(batches))
    )


expect_all_ranks_raise("case4d-w2v-bad-batch", case_w2v_bad_batch)


# --- 5. GBT straddled-checkpoint resume (rank-scoped snapshots).
gbt_args = dict(
    mesh=mesh, logistic=True, num_trees=3, depth=2, max_bins=8,
    learning_rate=0.3, reg_lambda=1.0, subsample=1.0, seed=0,
)
gbt_cache = cache_stream(iter([good_batch(48), good_batch(48)]))
golden = train_gbt_stream(gbt_cache, **gbt_args)


def checkpointed_fit(tag):
    ckpt = os.path.join(workdir, tag)
    os.makedirs(ckpt, exist_ok=True)
    mgr = CheckpointManager(ckpt, max_to_keep=3)
    out = train_gbt_stream(
        gbt_cache, checkpoint_manager=mgr, checkpoint_interval=1,
        **gbt_args,
    )
    for a, b in zip(golden, out):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    return ckpt


def drop(ckpt, trees):
    for t in trees:
        shutil.rmtree(
            os.path.join(ckpt, f"rank-{pid}", f"ckpt-{t}"),
            ignore_errors=False,
        )


def resume_fit(ckpt):
    mgr = CheckpointManager(ckpt, max_to_keep=3)
    return train_gbt_stream(
        gbt_cache, checkpoint_manager=mgr, checkpoint_interval=1,
        resume=True, **gbt_args,
    )


# 5a. Straddle: rank 0 holds {2,3} (pruned 1), rank 1 holds {1,2}
# (crashed before saving 3) — the newest COMMON tree is 2; the resumed
# run must rebuild tree 3 and match the uninterrupted forest exactly.
ckpt = checkpointed_fit("ckpt_straddle")
drop(ckpt, [1] if pid == 0 else [3])
resumed = resume_fit(ckpt)
for a, b in zip(golden, resumed):
    assert np.array_equal(np.asarray(a), np.asarray(b)), "straddle resume"
print(f"case5a-straddle: rank {pid} resumed from common tree", flush=True)

# 5b. Disjoint: rank 0 holds only {3}, rank 1 only {1} — no common tree;
# every rank must restart from scratch together and still match.
ckpt = checkpointed_fit("ckpt_disjoint")
drop(ckpt, [1, 2] if pid == 0 else [2, 3])
resumed = resume_fit(ckpt)
for a, b in zip(golden, resumed):
    assert np.array_equal(np.asarray(a), np.asarray(b)), "disjoint resume"
print(f"case5b-disjoint: rank {pid} restarted together", flush=True)


# 5c. Corrupt shard: every rank agrees on tree 3, but rank 0's shard of
# it is unreadable — the agreed restore must abort EVERY rank (not
# strand rank 1 in the training collectives).
def case_corrupt_restore():
    ckpt = checkpointed_fit("ckpt_corrupt")
    if pid == 0:
        os.remove(
            os.path.join(ckpt, f"rank-{pid}", "ckpt-3", "arrays.npz")
        )
    resume_fit(ckpt)


expect_all_ranks_raise("case5c-corrupt", case_corrupt_restore)


# 5d. REAL crash-injection resume on the 2-rank mesh: the rank-scoped
# manager raises after durably writing tree 2's snapshot, crashing the
# fit as an AGREED abort (save_agreed holds the save failure and every
# rank raises together); the resumed fit rebuilds tree 3 to reproduce
# the uninterrupted forest exactly. The injection wraps rank_scoped
# because the GBT path constructs its per-rank manager through it.
def case5d_crash_resume():
    import flinkml_tpu.iteration.checkpoint as ckpt_mod

    ckpt = os.path.join(workdir, "ckpt_crashinject")
    os.makedirs(ckpt, exist_ok=True)

    class Crash(CheckpointManager):
        fired = False

        def save(self, state, epoch, extra=None, **kw):
            p = super().save(state, epoch, extra, **kw)
            if not Crash.fired and epoch >= 2:
                Crash.fired = True
                raise RuntimeError("injected crash")
            return p

    orig_rank_scoped = ckpt_mod.rank_scoped

    def crashing_rank_scoped(manager):
        inner = orig_rank_scoped(manager)
        return Crash(
            inner.directory, max_to_keep=inner.max_to_keep,
            allow_rescale=inner.allow_rescale,
            world_size=inner.world_size, async_write=inner.async_write,
        )

    ckpt_mod.rank_scoped = crashing_rank_scoped
    try:
        train_gbt_stream(
            gbt_cache,
            checkpoint_manager=CheckpointManager(ckpt, max_to_keep=3),
            checkpoint_interval=1, **gbt_args,
        )
        raise SystemExit(f"case5d: rank {pid} did NOT crash")
    except RuntimeError as e:
        assert "injected crash" in str(e), e
    finally:
        ckpt_mod.rank_scoped = orig_rank_scoped
    recovered = resume_fit(ckpt)
    for a, b in zip(golden, recovered):
        assert np.array_equal(np.asarray(a), np.asarray(b)), "crash resume"
    print(f"case5d-crash-resume: rank {pid} resumed exactly", flush=True)


case5d_crash_resume()


# --- 6. Agreed restore for REPLICATED snapshots (ALS; LDA/Word2Vec share
# the identical DeferredValidation-wrapped restore). A rank-local restore
# failure (unreadable checkpoint on the shared FS) must abort every rank,
# not strand the peer in the normal-equation collectives.
def case6_als_restore_ioerror():
    from flinkml_tpu.models.als import ALS

    ckpt = os.path.join(workdir, "ckpt_als_restore")
    os.makedirs(ckpt, exist_ok=True)
    r = np.random.default_rng(40 + pid)
    cache = cache_stream(iter([{
        "user": r.integers(0, 8, size=32).astype(np.int32),
        "item": r.integers(0, 8, size=32).astype(np.int32),
        "rating": r.uniform(1, 5, size=32).astype(np.float32),
    }]))
    ALS(
        mesh=mesh, checkpoint_manager=CheckpointManager(ckpt),
        checkpoint_interval=1,
    ).set_rank(2).set_max_iter(2).set_seed(0).fit(cache)

    class BadRestore(CheckpointManager):
        def restore(self, epoch, like):
            raise IOError("injected unreadable checkpoint")

    mgr = (BadRestore if pid == 0 else CheckpointManager)(ckpt)
    ALS(
        mesh=mesh, checkpoint_manager=mgr, checkpoint_interval=1,
        resume=True,
    ).set_rank(2).set_max_iter(3).set_seed(0).fit(cache)


expect_all_ranks_raise("case6-als-restore", case6_als_restore_ioerror)


# --- 7. Cached-source KMeans with need_init=False (initial_centroids):
# pre-validation must still run — a bad cached batch on rank 0 would
# otherwise first raise rank-locally in place_multi's check_dims on the
# prefetch thread at replay, stranding the peer mid-collective.
def case7_kmeans_cached_bad_batch_no_init():
    blobs = [{"x": good_batch(16)["x"]}]
    if pid == 0:
        blobs.append({"x": np.zeros((4, 7), np.float32)})  # ragged dim
    train_kmeans_stream(
        cache_stream(iter(blobs)), k=2, mesh=mesh, max_iter=2, seed=0,
        initial_centroids=np.zeros((2, 4), np.float32),
    )


expect_all_ranks_raise("case7-kmeans-cached", case7_kmeans_cached_bad_batch_no_init)


# --- 8. Sparse-native CSR streaming (round 5): a ragged CSR batch on
# rank 0 (indices/indptr disagree) must abort every rank at the ingest
# rendezvous, not raise rank-locally before the agreed schedule.
def case8_sparse_stream_ragged_csr():
    from flinkml_tpu.models._linear_sgd import train_linear_model_stream

    def csr(n=8, dim=50, nnz=3, broken=False):
        r = np.random.default_rng(60 + pid)
        indptr = np.arange(n + 1, dtype=np.int64) * nnz
        k = n * nnz - (1 if broken else 0)  # broken: indices too short
        return {
            "indptr": indptr[None, :],
            "indices": r.integers(0, dim, k).astype(np.int32)[None, :],
            "values": r.normal(size=k).astype(np.float32)[None, :],
            "y": (r.random(n) > 0.5).astype(np.float32)[None, :],
            "w": np.ones(n, np.float32)[None, :],
            "dim": np.asarray([[dim]], np.int64),
        }

    train_linear_model_stream(
        iter([csr(), csr(broken=(pid == 0))]),
        loss="logistic", mesh=mesh, max_iter=2, learning_rate=0.5,
        reg=0.0, elastic_net=0.0, tol=0.0, sparse_dim=50,
    )


expect_all_ranks_raise("case8-sparse-ragged", case8_sparse_stream_ragged_csr)

print(f"GUARD_OK {pid}", flush=True)
