"""Multi-process streamed-fit worker, launched by test_distributed.py.

Exercises the round-4 multi-process out-of-core path end to end on a real
jax.distributed (Gloo) mesh: per-process stream partitions, the agreed
SPMD replay schedule (fixed height + dummy steps), pooled init sampling,
bounded in-flight dispatch, and rank-0-write + barrier checkpointing —
the reference's partitioned-stream training (`ReplayOperator.java:62-250`
over per-subtask partitions) without a single-controller restriction.

Usage: python _stream_mp_worker.py <port> <process_id> <num_processes> <workdir>
Prints ``STREAM_OK <pid>`` on success. Writes ``result_<pid>.npz`` with
the fitted models for the parent to cross-check.
"""

import os
import sys

port, pid, nproc, workdir = (
    sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4]
)

os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _stream_mp_common as C  # noqa: E402

from flinkml_tpu.iteration.checkpoint import CheckpointManager  # noqa: E402
from flinkml_tpu.iteration.datacache import cache_stream  # noqa: E402
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
batches = C.local_batches(pid, nproc)

# --- 1. linear streamed fit from a durable local cache + checkpointing
# into the SHARED directory (rank 0 writes, everyone barriers).
cache = cache_stream(iter(batches))
ckpt_dir = os.path.join(workdir, "ckpt_linear")
os.makedirs(ckpt_dir, exist_ok=True)
manager = CheckpointManager(ckpt_dir)
coef = train_linear_model_stream(
    cache, mesh=mesh, checkpoint_manager=manager, checkpoint_interval=2,
    **C.LINEAR_HP,
)
manager.close()
assert np.all(np.isfinite(coef)), coef

# --- 2. resume from the shared checkpoint: the run is already terminal,
# so a resumed fit must return the identical coefficient without
# retraining (exact-resume contract on a multi-process mesh).
manager2 = CheckpointManager(ckpt_dir)
coef_resumed = train_linear_model_stream(
    cache, mesh=mesh, checkpoint_manager=manager2, resume=True,
    **C.LINEAR_HP,
)
manager2.close()
assert np.array_equal(coef, coef_resumed), (coef, coef_resumed)

# --- 3. KMeans streamed fit, fixed init (cross-checked vs single-process
# by the parent) and pooled random init (must agree across ranks).
x_batches = [{"x": b["x"]} for b in batches]
cents = train_kmeans_stream(
    iter(x_batches), k=C.K_CLUSTERS, mesh=mesh,
    initial_centroids=C.initial_centroids(), **C.KMEANS_HP,
)
cents_rand = train_kmeans_stream(
    iter(x_batches), k=C.K_CLUSTERS, mesh=mesh, **C.KMEANS_HP,
)
assert np.all(np.isfinite(cents)) and np.all(np.isfinite(cents_rand))

# --- 3b. an EMPTY local partition is legal (that rank feeds only dummy
# steps; pooled init draws entirely from the non-empty ranks).
cents_empty = train_kmeans_stream(
    iter(x_batches if pid == 0 else []),
    k=C.K_CLUSTERS, mesh=mesh, **C.KMEANS_HP,
)
assert np.all(np.isfinite(cents_empty))

# --- 4. GMM streamed fit: pooled moments + pooled init reservoir; must
# agree across ranks and recover the synthetic components (checked by
# the parent).
from flinkml_tpu.models import GaussianMixture  # noqa: E402
from flinkml_tpu.table import Table  # noqa: E402

gm_tables = [Table({"features": b}) for b in C.gmm_local_batches(pid, nproc)]
gm = (
    GaussianMixture(mesh=mesh).set_k(2).set_max_iter(20).set_tol(0.0)
    .set_seed(5).set_covariance_type("diag").fit(iter(gm_tables))
)

# --- 5. streamed-Adam runner (MLP): agreed per-chunk step schedule +
# agreed label dtype; ranks must agree bit-exactly and the model must
# learn the separable target (checked by the parent).
from flinkml_tpu.models.mlp import MLPClassifier  # noqa: E402

x_all, y_all = C.global_data()
sl = C.slice_for(pid, nproc)
bs = C.BATCH_SIZES[pid]
mlp_tables = [
    Table({
        "features": x_all[sl][i : i + bs],
        "label": (x_all[sl][i : i + bs, 0]
                  + x_all[sl][i : i + bs, 1] > 0).astype(np.float64),
    })
    for i in range(0, x_all[sl].shape[0], bs)
]
mlp = (
    MLPClassifier(mesh=mesh)
    .set_layers([C.N_FEATURES, 8, 2]).set_max_iter(8)
    .set_global_batch_size(64).set_learning_rate(0.05)
    .set_tol(0.0).set_seed(0)
    .fit(iter(mlp_tables))
)
(mlp_out,) = mlp.transform(Table({"features": x_all}))
mlp_acc = float(
    (mlp_out.column("prediction") == (x_all[:, 0] + x_all[:, 1] > 0)).mean()
)

# --- 6. GBT streamed fit: pooled bin edges + gathered base score +
# rank-local per-row state + globally psum'd histograms. Ranks must
# agree on the forest structure bit-exactly.
from flinkml_tpu.models import GBTClassifier  # noqa: E402

gbt_tables = [
    Table({
        "features": t.column("features"),
        "label": (np.asarray(t.column("features"))[:, 0]
                  + np.asarray(t.column("features"))[:, 1] > 0)
        .astype(np.float64),
    })
    for t in mlp_tables
]
gbt = (
    GBTClassifier(mesh=mesh).set_num_trees(3).set_max_depth(2)
    .set_max_bins(16).set_learning_rate(0.3).set_seed(0)
    .fit(iter(gbt_tables))
)
(gbt_out,) = gbt.transform(Table({"features": x_all}))
gbt_acc = float(
    (gbt_out.column("prediction") == (x_all[:, 0] + x_all[:, 1] > 0)).mean()
)

# --- 7. PCA streamed fit: cache-less lockstep single pass (agreed shift,
# per-step height agreement, dummy steps on the drained rank).
from flinkml_tpu.models.pca import PCA  # noqa: E402

pca = (
    PCA(mesh=mesh).set_k(3).set_input_col("features")
    .fit(iter(Table({"features": t.column("features")})
              for t in mlp_tables))
)

# --- 8. LDA streamed fit (round-4 multi-process: per-process corpus
# partitions through the agreed replay schedule; topics replicated).
from flinkml_tpu.models.lda import LDA  # noqa: E402

lda = (
    LDA(mesh=mesh).set_k(2).set_max_iter(8).set_seed(3)
    .fit(iter(Table({"features": b})
              for b in C.lda_local_batches(pid, nproc)))
)
lda_topics = lda.topics_matrix

# --- 9. ALS streamed fit (round-4 multi-process: per-process ratings
# partitions; id vocabularies unioned through the device fabric, agreed
# chunk schedule with dummy fills; factors replicated).
from flinkml_tpu.models.als import ALS  # noqa: E402

als = (
    ALS(mesh=mesh).set_rank(C.ALS_RANK).set_max_iter(10)
    .set_reg_param(0.01).set_seed(0)
    .fit(iter(Table(b) for b in C.als_local_batches(pid, nproc)))
)
au, ai, ar = C.als_global_ratings()
pred = np.sum(
    als._user_factors[np.searchsorted(als._user_ids, au)]
    * als._item_factors[np.searchsorted(als._item_ids, ai)],
    axis=1,
)
als_rmse = float(np.sqrt(np.mean((pred - ar) ** 2)))

# --- 9b. an EMPTY ratings partition is legal: the empty rank adopts the
# agreed vocabularies and dispatches only dummy chunks; factors still
# replicate.
als_empty = (
    ALS(mesh=mesh).set_rank(C.ALS_RANK).set_max_iter(2)
    .set_reg_param(0.01).set_seed(0)
    .fit(iter(Table(b) for b in
              (C.als_local_batches(pid, nproc) if pid == 0 else [])))
)
als_empty_uf = als_empty._user_factors
als_empty_if = als_empty._item_factors

# --- 10. Online (unbounded) operators, round-4 multi-process: FTRL and
# decayed KMeans run psum'd lockstep steps per arriving batch (uneven
# per-rank batch counts force the zero-weight dummy tail); the scaler
# merges per-rank moments exactly at stream end.
from flinkml_tpu.models.online_kmeans import OnlineKMeans  # noqa: E402
from flinkml_tpu.models.online_logistic_regression import (  # noqa: E402
    OnlineLogisticRegression,
)
from flinkml_tpu.models.online_scaler import (  # noqa: E402
    OnlineStandardScaler,
)

olr = (
    OnlineLogisticRegression(mesh=mesh).set_alpha(0.5).set_beta(0.1)
    .set_reg(0.001).set_elastic_net(0.5)
    .fit_stream(iter(Table({"features": b["x"], "label": b["y"]})
                     for b in batches))
)
olr_coef = olr._coefficient
olr_version = olr._model_version

okm = (
    OnlineKMeans(mesh=mesh).set_k(C.K_CLUSTERS).set_seed(7)
    .set_decay_factor(0.9)
    .fit_stream(iter(Table({"features": b["x"]}) for b in batches))
)
okm_cents = okm._centroids

osc = OnlineStandardScaler().set_input_col("features").fit_stream(
    iter(Table({"features": b["x"]}) for b in batches)
)
osc_mean = osc._mean
osc_std = osc._std
osc_version = osc.model_version
# Exactness: the merged moments equal the GLOBAL dataset's f64 moments
# (the scaler accumulates in f64; Chan merge is split-invariant to fp
# rounding).
x_g64 = C.global_data()[0].astype(np.float64)
np.testing.assert_allclose(
    osc_mean, x_g64.mean(axis=0), rtol=1e-9, atol=1e-12
)
np.testing.assert_allclose(
    osc_std, x_g64.std(axis=0), rtol=1e-9, atol=1e-12
)

# --- 11. Word2Vec streamed fit (round-4 multi-process: per-process doc
# partitions; STRING vocabulary unioned through the device fabric as
# UTF-8 bytes; agreed-step SGNS dispatches with zero-weight dummies).
from flinkml_tpu.models.word2vec import Word2Vec  # noqa: E402

w2v_doc_batches = C.w2v_local_docs(pid, nproc)
w2v = (
    Word2Vec(mesh=mesh).set_input_col("tok").set_vector_size(8)
    .set_min_count(1).set_max_iter(8).set_learning_rate(2.0)
    .set_batch_size(512).set_seed(0)
    .fit(iter(
        Table({"tok": np.asarray(b, dtype=object)})
        for b in w2v_doc_batches
    ))
)
w2v_vocab = np.asarray(w2v.vocabulary, dtype=str)
w2v_vecs = w2v.vectors

# --- 11b. an EMPTY document partition is legal: the empty rank adopts
# the agreed (unioned) vocabulary and feeds only zero-weight dummy
# chunks; vectors still replicate.
w2v_empty = (
    Word2Vec(mesh=mesh).set_input_col("tok").set_vector_size(8)
    .set_min_count(1).set_max_iter(2).set_seed(0)
    .fit(iter(
        Table({"tok": np.asarray(b, dtype=object)})
        for b in (w2v_doc_batches if pid == 0 else [])
    ))
)
w2v_empty_vecs = w2v_empty.vectors

# --- round 5: sparse-native CSR streaming across ranks — per-process
# SparseVector partitions (uneven sizes, uneven nnz -> agreed global ELL
# width + dummy tail), cross-checked vs single-process by the parent.
from flinkml_tpu.models.logistic_regression import (  # noqa: E402
    LogisticRegression,
)

sp_est = LogisticRegression(mesh=mesh)
for k, v in C.SPARSE_HP.items():
    getattr(sp_est, f"set_{k}")(v)
sp_coef = sp_est.fit(iter(C.sparse_local_tables(pid, nproc)))._coefficient

np.savez(
    os.path.join(workdir, f"result_{pid}.npz"),
    coef=coef, sp_coef=sp_coef, cents=cents, cents_rand=cents_rand,
    cents_empty=cents_empty,
    gmm_means=gm.means, gmm_weights=gm.weights,
    mlp_w0=np.asarray(mlp._weights[0]), mlp_acc=np.float64(mlp_acc),
    gbt_feats=gbt._feats, gbt_leaves=gbt._leaves,
    gbt_acc=np.float64(gbt_acc),
    pca_components=pca.components, pca_variances=pca.explained_variance,
    lda_topics=lda_topics,
    als_user_f=als._user_factors, als_item_f=als._item_factors,
    als_rmse=np.float64(als_rmse),
    olr_coef=olr_coef, olr_version=np.int64(olr_version),
    okm_cents=okm_cents,
    osc_mean=osc_mean, osc_std=osc_std,
    osc_version=np.int64(osc_version),
    w2v_vocab=w2v_vocab, w2v_vecs=w2v_vecs,
    als_empty_uf=als_empty_uf, als_empty_if=als_empty_if,
    w2v_empty_vecs=w2v_empty_vecs,
)
print(f"STREAM_OK {pid}")
