"""Benchmark: LogisticRegression training throughput (samples/sec/chip)
plus epochs-to-converge — both halves of BASELINE.json's metric.

``python bench.py`` measures the TPU or fails: every device stage runs in
its own child process (a chip belongs to one process at a time; the
orchestrating parent never touches JAX), refuses to start unless
``jax.default_backend() == "tpu"``, and a stage that fails makes the run
exit non-zero. The last line on stdout is one JSON object
{"metric", "value", "unit", "vs_baseline", "extras"}. There is no CPU
fallback: a number from a CPU run is never printed under a device
metric's name.

The ``*_cpu`` stages (``_FLINKML_BENCH_INNER=<stage>_cpu python
bench.py``) are pinned to the host CPU backend and are what
``tools/ci.sh`` parses for counts and CPU-mesh behaviour; they say
nothing about the chip.

The north-star metric (BASELINE.json): samples/sec/chip for
LogisticRegression.fit. The reference publishes no numbers (BASELINE.md), so
``vs_baseline`` is measured against a faithful reimplementation of the
reference's execution model run on this host's CPU: record-at-a-time SGD
with per-record BLAS dot/axpy (``LogisticGradient.java:50-96`` iterates
records in a Java loop over netlib BLAS; the numpy equivalent below gives it
the benefit of C-speed vector ops per record). Both sides time the same
work: epochs of global-batch gradient steps at identical batch size/dim.
The roofline analysis that bounds the device number by bytes/step and
flops/step is in BASELINE.md ("Roofline" section).
"""

import json
import math
import os
import subprocess
import sys
import time

import numpy as np

_INNER_ENV = "_FLINKML_BENCH_INNER"


def _force_cpu():
    """Pin this (child) process to the host CPU backend. Must run before
    the first jax import (bench.py imports jax inside stages only)."""
    os.environ["JAX_PLATFORMS"] = "cpu"


def make_data(n, dim, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, dim)).astype(dtype)
    true_coef = rng.normal(size=dim).astype(dtype)
    y = (x @ true_coef > 0).astype(dtype)
    w = np.ones(n, dtype=dtype)
    return x, y, w


def make_criteo_csr(n, dim=1_000_000, nnz=39, seed=0, n_active=256):
    """Synthetic Criteo-profile CSR: ``nnz`` uniform-random columns per
    row over ``dim``, labels planted by a sparse true model with
    ``n_active`` nonzero coefficients. ONE definition shared by the
    sparse throughput stage and the sparse convergence stage so every
    sparse measurement sees the same distribution."""
    rng = np.random.default_rng(seed)
    indptr = np.arange(n + 1, dtype=np.int64) * nnz
    indices = rng.integers(0, dim, size=n * nnz).astype(np.int32)
    values = rng.normal(size=n * nnz).astype(np.float32)
    active = rng.choice(dim, size=n_active, replace=False)
    beta = np.zeros(dim, dtype=np.float32)
    beta[active] = rng.normal(size=n_active)
    margins = (
        values.reshape(n, nnz) * beta[indices.reshape(n, nnz)]
    ).sum(axis=1)
    y = (margins > 0).astype(np.float32)
    w = np.ones(n, dtype=np.float32)
    return indptr, indices, values, y, w


def _log(msg):
    sys.stderr.write(f"[bench] {msg}\n")
    sys.stderr.flush()


def _setup_jax_cache():
    from flinkml_tpu.utils import jax_cache

    jax_cache.enable()


def _dense_trainer_setup(x, y, w, global_batch_size, tol,
                         loss="logistic", reg_l2=0.0, reg_l1=0.0):
    """Shared setup for the dense throughput, convergence, and proximal
    (SVC) measurements: mesh, product-path sharding and batch alignment
    (round-1 finding: a hand-computed local_bs here could disagree with
    the product program), trainer, initial carry, and the hyperparameter
    args. One definition so the measurements can never drift onto
    different programs."""
    import jax.numpy as jnp
    from flinkml_tpu.models import _linear_sgd
    from flinkml_tpu.models.logistic_regression import _shard_training_data
    from flinkml_tpu.parallel import DeviceMesh

    mesh = DeviceMesh()
    p = mesh.axis_size()
    xd, yd, wd = _shard_training_data(x, y, w, mesh)
    local_bs = _linear_sgd.align_local_bs(
        global_batch_size, p, xd.shape[0] // p
    )
    trainer = _linear_sgd._dense_trainer(
        mesh.mesh, loss, local_bs, DeviceMesh.DATA_AXIS
    )
    f32 = lambda v: jnp.asarray(v, xd.dtype)
    carry0 = (
        jnp.zeros(xd.shape[1], xd.dtype),
        jnp.asarray(0, jnp.int32),
        jnp.asarray(jnp.inf, xd.dtype),
    )
    args = (xd, yd, wd, f32(0.1), f32(reg_l2), f32(reg_l1), f32(tol))
    return trainer, carry0, args, local_bs, p


def bench_tpu(x, y, w, global_batch_size, n_steps):
    """Steady-state training throughput with the dataset resident in HBM —
    the analog of the reference's steady state, which trains from data
    cached in ListState (LogisticRegression.java:375-376) after epoch 0.

    Timing: one dispatch of the whole training loop, synchronized by
    materializing the (dim-sized) result on host — ``np.asarray`` waits
    for the device exactly as ``block_until_ready`` does."""
    import jax.numpy as jnp

    trainer, carry0, args, local_bs, p = _dense_trainer_setup(
        x, y, w, global_batch_size, tol=0.0
    )
    _log("compiling + warm-up dispatch ...")
    np.asarray(trainer(*carry0, *args, jnp.asarray(10, jnp.int32))[0])
    _log("measuring ...")
    start = time.perf_counter()
    coef_out, steps_out, _ = trainer(
        *carry0, *args, jnp.asarray(n_steps, jnp.int32)
    )
    np.asarray(coef_out)
    elapsed = time.perf_counter() - start
    # The while_loop can exit early (tol hit, or a NaN loss — NaN > tol is
    # False); throughput must count the steps that actually ran, and a
    # short-circuited run must never masquerade as a fast one.
    steps_ran = int(steps_out)
    if steps_ran != n_steps:
        raise RuntimeError(
            f"trainer stopped after {steps_ran}/{n_steps} steps "
            "(diverged or converged); measurement invalid"
        )
    return local_bs * p * steps_ran / elapsed


def bench_tpu_sparse(indptr, indices, values, dim, y, w,
                     global_batch_size, n_steps):
    """Sparse (Criteo-profile) training throughput: nnz-bucketed ELL
    blocks resident in HBM, whole loop in one dispatch (same timing
    discipline as :func:`bench_tpu`)."""
    import jax.numpy as jnp
    from flinkml_tpu.models import _linear_sgd
    from flinkml_tpu.parallel import DeviceMesh

    mesh = DeviceMesh()
    p = mesh.axis_size()
    # Same pack/pad/shard/batching policy as the product fit path.
    place, local_bss, slot_plan = _linear_sgd.prepare_sparse_buckets(
        indptr, indices, values, dim, y, w, mesh, global_batch_size,
        seed=0,
    )
    data_args = _linear_sgd._placed(place(0, n_steps))
    trainer = _linear_sgd._sparse_trainer_bucketed(
        mesh.mesh, "logistic", local_bss, DeviceMesh.DATA_AXIS, int(dim),
        slot_plan=slot_plan,
    )
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    carry0 = (
        jnp.zeros(dim, jnp.float32),
        jnp.asarray(0, jnp.int32),
        jnp.asarray(jnp.inf, jnp.float32),
    )
    hy = (f32(0.1), f32(0.0), f32(0.0), f32(0.0))
    _log("sparse: compiling + warm-up dispatch ...")
    np.asarray(trainer(*carry0, *data_args, *hy,
                       jnp.asarray(10, jnp.int32))[0])
    _log("sparse: measuring ...")
    start = time.perf_counter()
    coef_out, steps_out, _ = trainer(
        *carry0, *data_args, *hy, jnp.asarray(n_steps, jnp.int32)
    )
    np.asarray(coef_out)
    elapsed = time.perf_counter() - start
    steps_ran = int(steps_out)
    if steps_ran != n_steps:
        raise RuntimeError(
            f"sparse trainer stopped after {steps_ran}/{n_steps} steps; "
            "measurement invalid"
        )
    return sum(local_bss) * p * steps_ran / elapsed


def bench_convergence(x, y, w, global_batch_size, tol, max_steps):
    """Epochs/wall-clock to convergence — the other half of BASELINE.json's
    north-star metric ("samples/sec/chip + epochs-to-converge").

    Runs the SAME whole-loop device program as :func:`bench_tpu` but with a
    positive ``tol``: the on-device while_loop exits as soon as the epoch's
    mean logistic loss reaches ``tol`` (TerminateOnMaxIterOrTol semantics —
    the contract `LogisticRegressionTest.java:60-90` pins at fixture scale).
    Returns ``(steps_ran, elapsed_s)``; the caller converts steps to epochs
    via ``steps * global_batch_size / n``."""
    import jax.numpy as jnp

    trainer, carry0, args, _, _ = _dense_trainer_setup(
        x, y, w, global_batch_size, tol
    )
    _log("converge: compiling + warm-up dispatch ...")
    np.asarray(trainer(*carry0, *args, jnp.asarray(2, jnp.int32))[0])
    _log("converge: measuring steps-to-tol ...")
    start = time.perf_counter()
    coef_out, steps_out, loss_out = trainer(
        *carry0, *args, jnp.asarray(max_steps, jnp.int32)
    )
    np.asarray(coef_out)
    elapsed = time.perf_counter() - start
    steps_ran = int(steps_out)
    final_loss = float(loss_out)
    if steps_ran >= max_steps or not math.isfinite(final_loss):
        raise RuntimeError(
            f"did not converge: steps={steps_ran}/{max_steps} "
            f"loss={final_loss} tol={tol}"
        )
    return steps_ran, elapsed


def bench_reference_style_cpu(x, y, w, global_batch_size, budget_s=10.0):
    """The reference's per-record execution model (LogisticGradient.java:50-96):
    one dot + one axpy per record per epoch, coefficient update per epoch."""
    n, dim = x.shape
    x64, y64, w64 = x.astype(np.float64), y.astype(np.float64), w.astype(np.float64)
    coef = np.zeros(dim)
    rng = np.random.default_rng(0)
    processed = 0
    start = time.perf_counter()
    grad = np.zeros(dim)
    while time.perf_counter() - start < budget_s:
        idx = rng.integers(0, n, size=global_batch_size)
        grad[:] = 0.0
        wsum = 0.0
        for i in idx:  # record-at-a-time, as the reference's Java loop
            xi = x64[i]
            dot = float(xi @ coef)
            ys = 2.0 * y64[i] - 1.0
            mult = w64[i] * (-ys / (math.exp(dot * ys) + 1.0))
            grad += mult * xi  # BLAS.axpy per record
            wsum += w64[i]
        coef -= (0.1 / wsum) * grad
        processed += global_batch_size
    return processed / (time.perf_counter() - start)


# -- inner (child-process) stages -------------------------------------------

def _dense_stage(dtype=None) -> float:
    """The dense measurement — a9a-like width (BASELINE.json config #1),
    dataset resident in HBM, whole loop in one dispatch. One definition
    for every dtype so f32 and bf16 always measure the same workload."""
    _setup_jax_cache()
    n, dim = 1_000_000, 123
    x, y, w = make_data(n, dim)
    if dtype is not None:
        x, y, w = x.astype(dtype), y.astype(dtype), w.astype(dtype)
    return bench_tpu(x, y, w, global_batch_size=262_144, n_steps=400)


def _inner_dense() -> float:
    return _dense_stage()


def _inner_svc() -> float:
    """Stage: LinearSVC proximal SGD (BASELINE.json config #3) — hinge
    loss with an elastic-net proximal step (both L1 and L2 active so the
    soft-threshold path is really measured), same a9a-like workload and
    timing discipline as the dense stage, through the loss-generic
    product trainer (`_linear_sgd._dense_trainer`)."""
    _setup_jax_cache()
    import jax.numpy as jnp

    n, dim, gbs, n_steps = 1_000_000, 123, 262_144, 400
    x, y, w = make_data(n, dim)
    trainer, carry0, args, local_bs, p = _dense_trainer_setup(
        x, y, w, gbs, tol=0.0, loss="hinge", reg_l2=1e-4, reg_l1=1e-4
    )
    _log("svc: compiling + warm-up dispatch ...")
    np.asarray(trainer(*carry0, *args, jnp.asarray(10, jnp.int32))[0])
    _log("svc: measuring ...")
    start = time.perf_counter()
    coef_out, steps_out, _ = trainer(
        *carry0, *args, jnp.asarray(n_steps, jnp.int32)
    )
    np.asarray(coef_out)
    elapsed = time.perf_counter() - start
    if int(steps_out) != n_steps:
        raise RuntimeError(
            f"svc trainer stopped after {int(steps_out)}/{n_steps} steps"
        )
    return local_bs * p * n_steps / elapsed


def _inner_ftrl() -> float:
    """Stage: OnlineLogisticRegression FTRL (BASELINE.json config #4) —
    steady-state per-batch step throughput of the unbounded online path.
    Batches are pre-resident and the (z, n, coef) state chains through
    async dispatches with ONE end-of-run synchronization, so the number
    measures the architecture (per-batch dispatch + FTRL algebra +
    psum), not host round trips — the same discipline as feed_overlap."""
    _setup_jax_cache()
    import jax.numpy as jnp
    from flinkml_tpu.models.online_logistic_regression import (
        _ftrl_sharded_fn,
    )
    from flinkml_tpu.parallel import DeviceMesh

    n_batches, bs, dim, passes = 64, 16_384, 123, 8
    rng = np.random.default_rng(0)
    true_coef = rng.normal(size=dim).astype(np.float32)
    mesh = DeviceMesh()
    step = _ftrl_sharded_fn(mesh.mesh, DeviceMesh.DATA_AXIS)
    batches = []
    for _ in range(n_batches):
        x = rng.normal(size=(bs, dim)).astype(np.float32)
        y = (x @ true_coef > 0).astype(np.float32)
        batches.append((
            mesh.shard_batch(x), mesh.shard_batch(y),
            mesh.shard_batch(np.ones(bs, np.float32)),
        ))
    import jax

    jax.block_until_ready(batches)
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    hy = (f32(0.1), f32(1.0), f32(0.001), f32(0.001))
    zeros = jnp.zeros(dim, jnp.float32)

    def run(n_passes):
        z, nacc, coef = zeros, zeros, zeros
        for _ in range(n_passes):
            for xb, yb, wb in batches:
                z, nacc, coef, _ = step(xb, yb, wb, z, nacc, coef, *hy)
        np.asarray(coef)  # single synchronization
        return coef

    _log("ftrl: compiling + warm-up pass ...")
    run(1)
    _log("ftrl: measuring ...")
    start = time.perf_counter()
    run(passes)
    elapsed = time.perf_counter() - start
    return n_batches * bs * passes / elapsed


def _inner_dense_bf16() -> float:
    """Same workload, bf16-resident: halves the streamed x bytes (bound
    in BASELINE.md "Roofline"); how much of that shows at d=123, where
    per-step fixed costs are a comparable term, is not measured on the
    chip. Reductions still accumulate in f32 (_linear_sgd._acc_dt)."""
    import jax.numpy as jnp

    return _dense_stage(jnp.bfloat16)


def _kmeans_stage(n, dim, k, iters) -> float:
    """Stage: KMeans Lloyd throughput — the whole loop (assignment on
    the MXU + one-hot aggregation + psum + update) in one dispatch.

    Two profiles: d=128/k=64 (the round-2 measured table's shape, kept
    for cross-round continuity) and MNIST-784/k=10 (BASELINE.json
    config #2)."""
    _setup_jax_cache()
    import jax.numpy as jnp
    from flinkml_tpu.models.kmeans import _kmeans_trainer, _place_rows
    from flinkml_tpu.parallel import DeviceMesh
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, dim)).astype(np.float32)
    mesh = DeviceMesh()
    # The product fit path's own placement (rows, norms, mask) and program.
    placed = _place_rows(x, mesh)
    cent0 = jnp.asarray(x[rng.choice(n, size=k, replace=False)])
    trainer = _kmeans_trainer(mesh.mesh, k, DeviceMesh.DATA_AXIS)
    _log("kmeans: compiling + warm-up dispatch ...")
    np.asarray(trainer(*placed, cent0, jnp.asarray(3, jnp.int32)))
    _log("kmeans: measuring ...")
    start = time.perf_counter()
    np.asarray(trainer(*placed, cent0, jnp.asarray(iters, jnp.int32)))
    elapsed = time.perf_counter() - start
    return n * iters / elapsed


def _inner_kmeans() -> float:
    return _kmeans_stage(n=262_144, dim=128, k=64, iters=100)


def _inner_kmeans_mnist() -> float:
    """BASELINE.json config #2: MNIST-784 vectors, k=10 classes."""
    return _kmeans_stage(n=65_536, dim=784, k=10, iters=100)


def _inner_sparse() -> float:
    """Stage 3: Criteo-profile sparse LR (BASELINE.json config #5):
    dim = 1e6, 39 nnz per row, nnz-bucketed ELL resident in HBM."""
    _setup_jax_cache()
    n, dim = 262_144, 1_000_000
    indptr, indices, values, y, w = make_criteo_csr(n, dim)
    return bench_tpu_sparse(
        indptr, indices, values, dim, y, w,
        global_batch_size=262_144, n_steps=200,
    )


def _inner_gbt() -> float:
    """Stage 5: histogram GBT — the whole forest (scan over trees,
    per-level segment-sum histograms) in one device program. Metric:
    row-tree builds per second (n * numTrees / elapsed)."""
    _setup_jax_cache()
    import jax

    from flinkml_tpu.models.gbt import (
        _forest_builder, _hist_layout, bin_features, quantile_bin_edges,
        sharded_hist_args,
    )
    from flinkml_tpu.parallel import DeviceMesh

    # Compile cost scales hard with the
    # unrolled depth and (nodes x features x bins) segment space; this
    # profile keeps the whole-forest program within the stage cap.
    n, d, bins, depth, trees = 262_144, 16, 32, 4, 20
    rng = np.random.default_rng(0)
    x = rng.uniform(-1, 1, size=(n, d)).astype(np.float32)
    y = (x[:, 0] * x[:, 1] > 0).astype(np.float32)
    w = np.ones(n, dtype=np.float32)
    edges = quantile_bin_edges(x, bins)
    binned = bin_features(x, edges)
    mesh = DeviceMesh()
    # Same FLINKML_TPU_GBT_HISTOGRAM gate as the product fit path.
    hist_layout = _hist_layout()
    builder = _forest_builder(
        mesh.mesh, DeviceMesh.DATA_AXIS, d, bins, depth, trees, True,
        hist_layout=hist_layout,
    )
    import jax.numpy as jnp

    f32 = lambda v: jnp.asarray(v, jnp.float32)
    hist_args = sharded_hist_args(binned, mesh, bins, hist_layout)
    args = (
        mesh.shard_batch(binned), mesh.shard_batch(y), mesh.shard_batch(w),
        f32(0.0), f32(0.2), f32(1.0), f32(1.0), jax.random.PRNGKey(0),
    ) + hist_args
    _log("gbt: compiling + warm-up dispatch ...")
    np.asarray(builder(*args)[2])
    _log("gbt: measuring ...")
    start = time.perf_counter()
    np.asarray(builder(*args)[2])
    elapsed = time.perf_counter() - start
    return n * trees / elapsed


def _inner_als() -> float:
    """Stage: ALS-WR normal-equation half-steps through the product path
    (`ALS.fit`: chunked COO -> segment-sum normal equations -> batched
    Cholesky). Metric: rating visits per second (nnz x 2 sides x iters)."""
    _setup_jax_cache()
    from flinkml_tpu.models.als import ALS
    from flinkml_tpu.table import Table

    n_users, n_items, nnz, rank, iters = 16_384, 16_384, 1 << 21, 32, 10
    rng = np.random.default_rng(0)
    users = rng.integers(0, n_users, size=nnz).astype(np.int32)
    items = rng.integers(0, n_items, size=nnz).astype(np.int32)
    ratings = rng.uniform(1, 5, size=nnz).astype(np.float32)
    table = Table({"user": users, "item": items, "rating": ratings})
    _log("als: compiling + warm-up fit ...")
    ALS().set_rank(rank).set_max_iter(1).set_seed(0).fit(table)
    _log("als: measuring ...")
    start = time.perf_counter()
    ALS().set_rank(rank).set_max_iter(iters).set_seed(0).fit(table)
    elapsed = time.perf_counter() - start
    return nnz * 2 * iters / elapsed


def _inner_word2vec() -> float:
    """Stage: skip-gram negative-sampling SGD through the product trainer
    (`word2vec._sgns_trainer`: whole loop in one dispatch, dense psum of
    embedding grads). Metric: (center, context) pairs per second."""
    _setup_jax_cache()
    import jax
    import jax.numpy as jnp
    from flinkml_tpu.models.word2vec import _sgns_trainer, _w2v_accum
    from flinkml_tpu.parallel import DeviceMesh

    vocab, dim, n_pairs, bs, n_neg, steps = 32_768, 128, 1 << 20, 8_192, 5, 200
    rng = np.random.default_rng(0)
    centers = rng.integers(0, vocab, size=n_pairs).astype(np.int32)
    contexts = rng.integers(0, vocab, size=n_pairs).astype(np.int32)
    pool = rng.integers(0, vocab, size=1 << 17).astype(np.int32)
    v0 = (rng.random((vocab, dim)) - 0.5).astype(np.float32) / dim
    u0 = np.zeros((vocab, dim), np.float32)
    mesh = DeviceMesh()
    local_bs = max(1, bs // mesh.axis_size())
    # The gradient-accumulation gate (FLINKML_TPU_W2V_ACCUM) rides into
    # the measurement, so the probe's winner is benchable the same day.
    trainer = _sgns_trainer(mesh.mesh, DeviceMesh.DATA_AXIS, local_bs,
                            n_neg, _w2v_accum())
    args = (
        mesh.shard_batch(centers), mesh.shard_batch(contexts),
        mesh.shard_batch(np.ones(n_pairs, np.float32)),
        jnp.asarray(pool), jnp.asarray(v0), jnp.asarray(u0),
        jnp.asarray(0.025, jnp.float32),
    )
    key = jax.random.PRNGKey(0)
    _log("word2vec: compiling + warm-up dispatch ...")
    np.asarray(trainer(*args, jnp.asarray(5, jnp.int32), key)[0])
    _log("word2vec: measuring ...")
    start = time.perf_counter()
    np.asarray(trainer(*args, jnp.asarray(steps, jnp.int32), key)[0])
    elapsed = time.perf_counter() - start
    return local_bs * mesh.axis_size() * steps / elapsed


def _five_stage_model(n=100_000, d=32, seed=0, dtype=np.float64):
    """The bench's canonical all-kernel chain (StandardScaler →
    MinMaxScaler → MaxAbsScaler → RobustScaler → LogisticRegressionModel),
    fitted on seeded data; shared by the pipeline_fused and serving
    stages (and ``chip_smoke.py``, at float32) so all run the same
    program. Returns ``(pipeline_model, x)``."""
    from flinkml_tpu.models.logistic_regression import LogisticRegression
    from flinkml_tpu.models.scalers import (
        MaxAbsScaler, MinMaxScaler, RobustScaler, StandardScaler,
    )
    from flinkml_tpu.pipeline import PipelineModel
    from flinkml_tpu.table import Table

    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(dtype, copy=False)
    y = (x @ rng.normal(size=d).astype(dtype) > 0).astype(np.float64)
    train = Table({"features": x, "label": y})
    stages, cur, prev = [], train, "features"
    for i, cls in enumerate(
        (StandardScaler, MinMaxScaler, MaxAbsScaler, RobustScaler), start=1
    ):
        m = cls().set(cls.INPUT_COL, prev).set(cls.OUTPUT_COL, f"s{i}")
        m = m.fit(cur)
        (cur,) = m.transform(cur)
        prev = f"s{i}"
        stages.append(m)
    lr = (
        LogisticRegression()
        .set(LogisticRegression.FEATURES_COL, prev)
        .set(LogisticRegression.LABEL_COL, "label")
        .set_max_iter(2)
        .fit(cur)
    )
    stages.append(lr)
    return PipelineModel(stages), x


def _pipeline_fused_stage(n=100_000, d=32, reps=5) -> dict:
    """Stage: fused pipeline inference throughput — a 5-stage all-kernel
    chain (StandardScaler → MinMaxScaler → MaxAbsScaler → RobustScaler →
    LogisticRegressionModel) through ``PipelineModel.transform``, fused
    (one XLA program, device-resident intermediates, shape-bucketed
    compile cache) vs unfused (the per-stage path: N host↔device round
    trips and four host numpy scaler passes). Metric:
    ``pipeline_transform_rows_per_sec`` for both executions, plus the
    speedup — the per-stage-materialization overhead the fused executor
    (flinkml_tpu/pipeline_fusion.py) exists to delete."""
    from flinkml_tpu import pipeline_fusion
    from flinkml_tpu.table import Table

    pipeline_model, x = _five_stage_model(n, d)
    apply_table = Table({"features": x})

    def rows_per_sec():
        # Warm-up covers compiles on both paths; each timed call ends by
        # materializing the prediction column on host (the device→host
        # copy waits for the program, and is part of what transform costs).
        np.asarray(
            pipeline_model.transform(apply_table)[0].column("prediction")
        )
        start = time.perf_counter()
        for _ in range(reps):
            out = pipeline_model.transform(apply_table)[0]
            np.asarray(out.column("prediction"))
        return n * reps / (time.perf_counter() - start)

    pipeline_fusion.set_enabled(False)
    try:
        unfused = rows_per_sec()
    finally:
        pipeline_fusion.set_enabled(True)
    pipeline_fusion.reset_cache()
    fused = rows_per_sec()
    return {
        "pipeline_transform_rows_per_sec": round(fused, 1),
        "pipeline_transform_rows_per_sec_unfused": round(unfused, 1),
        "fused_speedup": round(fused / unfused, 2),
        "rows": n,
        "dim": d,
        "stages": 5,
    }


def _inner_pipeline_fused() -> dict:
    _setup_jax_cache()
    return _pipeline_fused_stage()


def _inner_pipeline_fused_cpu() -> dict:
    """The same fused-vs-unfused comparison pinned to the host CPU
    backend (CI parses ``fused_speedup``); says nothing about the chip."""
    _force_cpu()
    return _pipeline_fused_stage()


def _serving_stage(n_clients=8, duration_s=4.0, max_batch_rows=256,
                   n=50_000, d=32) -> dict:
    """Stage: online serving throughput/latency — synthetic closed-loop
    clients (each thread issues its next request the moment the previous
    response lands) against the 5-stage fused chain behind a
    ``ServingEngine``: adaptive micro-batching into the fused compile
    cache's row buckets, per-bucket warmup, zero steady-state retraces.
    Metrics: ``serving_rows_per_sec`` (aggregate served rows),
    ``serving_p50_ms`` / ``serving_p99_ms`` (per-request latency,
    enqueue→complete), and mean batch occupancy (rows / bucket rows —
    padding waste of the bucketing policy under this load)."""
    import threading

    from flinkml_tpu.serving import ServingConfig, ServingEngine
    from flinkml_tpu.table import Table

    model, x = _five_stage_model(n, d)
    engine = ServingEngine(
        model,
        example=Table({"features": x[:4]}),
        config=ServingConfig(max_batch_rows=max_batch_rows,
                             max_wait_ms=1.0),
        output_cols=("prediction",),
        name="bench",
    ).start()

    stop = threading.Event()
    served_rows = [0] * n_clients
    errors = []

    def client(tid):
        rng = np.random.default_rng(tid)
        try:
            while not stop.is_set():
                rows = int(rng.integers(1, 33))
                lo = int(rng.integers(0, n - rows))
                engine.predict({"features": x[lo:lo + rows]})
                served_rows[tid] += rows
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [
        threading.Thread(target=client, args=(i,)) for i in range(n_clients)
    ]
    _log(f"serving: {n_clients} closed-loop clients for {duration_s}s ...")
    start = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(duration_s)
    stop.set()
    for t in threads:
        t.join(timeout=60)
    elapsed = time.perf_counter() - start
    stats = engine.stats()
    engine.stop()
    if errors:
        raise errors[0]
    counters = stats["counters"]
    occupancy = (
        counters["batch_rows"] / counters["batch_padded_rows"]
        if counters.get("batch_padded_rows") else 0.0
    )
    return {
        "serving_rows_per_sec": round(sum(served_rows) / elapsed, 1),
        "serving_p50_ms": round(stats["gauges"]["p50_ms"], 3),
        "serving_p99_ms": round(stats["gauges"]["p99_ms"], 3),
        "serving_batch_occupancy": round(occupancy, 3),
        "requests": int(counters["requests"]),
        "batches": int(counters["batches"]),
        "clients": n_clients,
        "stages": 5,
    }


def _serving_scaleout_stage(n_replicas=8, n_clients=None, duration_s=3.0,
                            max_batch_rows=128, max_wait_ms=2.0,
                            n=50_000, d=32) -> dict:
    """Stage: serving scale-out — the ROADMAP item 3 / ISSUE 8 number.

    Three measurements against the 5-stage fused chain, same closed-loop
    offered load (``n_clients`` threads, 1-32 rows per request):

      1. ONE ServingEngine (continuous batching) — the PR 3 shape;
      2. an ``n_replicas`` ReplicaPool with FIFO whole-request packing;
      3. the same pool with continuous batching (the product default).

    Emits ``serving_scaleout_rows_per_sec`` plus
    ``serving_rows_per_sec_per_replica``, pool-level p50/p99 (client-side,
    enqueue→complete), the pool-vs-single speedup (acceptance: >= 4x on
    the 8-CPU-device mesh — requires >= 8 host cores backing the 8
    virtual devices; ``host_cpu_count`` is recorded so a 2-core CI box's
    number is never mistaken for the acceptance measurement), and the
    FIFO-vs-continuous p50 delta at the same offered load (acceptance:
    continuous measurably lower)."""
    import threading

    from flinkml_tpu.serving import ReplicaPool, ServingConfig, ServingEngine
    from flinkml_tpu.table import Table

    if n_clients is None:
        n_clients = 2 * n_replicas
    model, x = _five_stage_model(n, d)
    example = Table({"features": x[:4]})

    def cfg(**kw):
        return ServingConfig(max_batch_rows=max_batch_rows,
                             max_wait_ms=max_wait_ms, **kw)

    def run_load(predict, label):
        stop = threading.Event()
        rows_served = [0] * n_clients
        lat_ms = [[] for _ in range(n_clients)]
        errors = []

        def client(tid):
            rng = np.random.default_rng(tid)
            try:
                while not stop.is_set():
                    rows = int(rng.integers(1, 33))
                    lo = int(rng.integers(0, n - rows))
                    t0 = time.perf_counter()
                    predict({"features": x[lo:lo + rows]})
                    lat_ms[tid].append((time.perf_counter() - t0) * 1e3)
                    rows_served[tid] += rows
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        threads = [
            threading.Thread(target=client, args=(i,))
            for i in range(n_clients)
        ]
        _log(f"serving_scaleout[{label}]: {n_clients} closed-loop clients "
             f"for {duration_s}s ...")
        start = time.perf_counter()
        for t in threads:
            t.start()
        time.sleep(duration_s)
        stop.set()
        for t in threads:
            t.join(timeout=60)
        elapsed = time.perf_counter() - start
        if errors:
            raise errors[0]
        lats = np.concatenate([np.asarray(l) for l in lat_ms if l])
        p50, p99 = np.percentile(lats, [50, 99])
        return {
            "rows_per_sec": round(sum(rows_served) / elapsed, 1),
            "p50_ms": round(float(p50), 3),
            "p99_ms": round(float(p99), 3),
            "requests": int(lats.size),
        }, elapsed

    # 1. Single engine (continuous): the PR 3 baseline shape.
    engine = ServingEngine(
        model, example, cfg(), output_cols=("prediction",),
        name="scaleout_single",
    ).start()
    single, _ = run_load(engine.predict, "single")
    engine.stop()

    # 2. Pool, FIFO packing: isolates the continuous-batching delta.
    pool = ReplicaPool(
        model, example, config=cfg(batching="fifo"),
        n_replicas=n_replicas, output_cols=("prediction",),
        name="scaleout_fifo",
    ).start()
    fifo, _ = run_load(pool.predict, "pool_fifo")
    pool.stop()

    # 3. Pool, continuous batching: the product configuration.
    pool = ReplicaPool(
        model, example, config=cfg(),
        n_replicas=n_replicas, output_cols=("prediction",),
        name="scaleout",
    ).start()
    cont, elapsed = run_load(pool.predict, "pool_continuous")
    stats = pool.stats()
    per_replica = {
        rname: round(rec["counters"].get("rows", 0.0) / elapsed, 1)
        for rname, rec in stats["per_replica"].items()
    }
    pool.stop()

    import jax

    return {
        "serving_scaleout_rows_per_sec": cont["rows_per_sec"],
        "serving_rows_per_sec_per_replica": per_replica,
        "pool_p50_ms": cont["p50_ms"],
        "pool_p99_ms": cont["p99_ms"],
        "pool_speedup_vs_single_engine": round(
            cont["rows_per_sec"] / single["rows_per_sec"], 2
        ),
        "single_engine_rows_per_sec": single["rows_per_sec"],
        "fifo_pool_rows_per_sec": fifo["rows_per_sec"],
        "fifo_p50_ms": fifo["p50_ms"],
        "continuous_p50_ms": cont["p50_ms"],
        "continuous_vs_fifo_p50": round(
            cont["p50_ms"] / fifo["p50_ms"], 3
        ) if fifo["p50_ms"] else None,
        "batching_window_ms": max_wait_ms,
        "replicas": n_replicas,
        "clients": n_clients,
        "devices": len(jax.devices()),
        "host_cpu_count": os.cpu_count(),
    }


def _inner_serving_scaleout() -> dict:
    _setup_jax_cache()
    return _serving_scaleout_stage()


def _inner_serving_scaleout_cpu() -> dict:
    """The scale-out measurement pinned to an 8-virtual-device host CPU
    mesh (CI's serving-scaleout stage parses it); the device variant
    runs the same programs on the chip.

    Replica count is capped at the HOST core count: each replica's
    device executor needs a core behind it, and running 8 executors on a
    2-core CI box measures the OS scheduler (observed: ~100 ms CFS
    timeslice stalls inside 2 ms programs), not the pool. On the
    acceptance host (>= 8 cores) this is exactly the 8-replica config."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    _force_cpu()
    _setup_jax_cache()
    return _serving_scaleout_stage(
        n_replicas=max(2, min(8, os.cpu_count() or 2))
    )


def _multiproc_pool_stage(n_workers=2, duration_s=3.0, n=50_000, d=32,
                          max_batch_rows=128, max_wait_ms=2.0) -> dict:
    """Stage: multi-process worker pool vs the SAME-size in-process
    replica pool (ISSUE 20) — what "N replicas" buys when each replica
    is a real process with its own GIL and XLA executor pool instead of
    a thread behind the shared ones.

    Same closed-loop offered load against both shapes; emits total and
    per-worker rows/s, the worker-vs-thread speedup ratio (acceptance:
    >= 1.5x at 2 workers on a >= 8-core host — ``host_cpu_count`` is
    recorded so a starved box's ratio, where transport overhead buys no
    parallelism, is never mistaken for the acceptance measurement), and
    a bitwise parity check across the process boundary."""
    import threading

    from flinkml_tpu.cluster import ClusterPool
    from flinkml_tpu.serving import ReplicaPool, ServingConfig
    from flinkml_tpu.table import Table

    n_clients = 2 * n_workers
    model, x = _five_stage_model(n, d)
    example = Table({"features": x[:4]})
    cfg = ServingConfig(max_batch_rows=max_batch_rows,
                        max_wait_ms=max_wait_ms)

    def run_load(predict, label):
        stop = threading.Event()
        rows_served = [0] * n_clients
        lat_ms = [[] for _ in range(n_clients)]
        errors = []

        def client(tid):
            rng = np.random.default_rng(tid)
            try:
                while not stop.is_set():
                    rows = int(rng.integers(1, 33))
                    lo = int(rng.integers(0, n - rows))
                    t0 = time.perf_counter()
                    predict({"features": x[lo:lo + rows]})
                    lat_ms[tid].append((time.perf_counter() - t0) * 1e3)
                    rows_served[tid] += rows
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_clients)]
        _log(f"multiproc_pool[{label}]: {n_clients} closed-loop clients "
             f"for {duration_s}s ...")
        start = time.perf_counter()
        for t in threads:
            t.start()
        time.sleep(duration_s)
        stop.set()
        for t in threads:
            t.join(timeout=60)
        elapsed = time.perf_counter() - start
        if errors:
            raise errors[0]
        lats = np.concatenate([np.asarray(l) for l in lat_ms if l])
        p50, p99 = np.percentile(lats, [50, 99])
        return {
            "rows_per_sec": round(sum(rows_served) / elapsed, 1),
            "p50_ms": round(float(p50), 3),
            "p99_ms": round(float(p99), 3),
        }

    # 1. In-process replica pool: N engines behind ONE GIL.
    tpool = ReplicaPool(
        model, example, config=cfg, n_replicas=n_workers,
        output_cols=("prediction",), name="mp_threads",
    ).start()
    ref_out = np.asarray(
        tpool.predict({"features": x[:32]}).columns["prediction"]
    )
    threaded = run_load(tpool.predict, "threads")
    tpool.stop()

    # 2. Process pool: the same router over worker processes.
    cpool = ClusterPool(
        model, example, config=cfg, n_workers=n_workers,
        output_cols=("prediction",), name="mp_workers",
    ).start()
    pool_out = np.asarray(
        cpool.predict({"features": x[:32]}).columns["prediction"]
    )
    proc = run_load(cpool.predict, "workers")
    cpool.stop()

    import jax

    return {
        "multiproc_rows_per_sec": proc["rows_per_sec"],
        "multiproc_rows_per_sec_per_worker": round(
            proc["rows_per_sec"] / n_workers, 1
        ),
        "threaded_rows_per_sec": threaded["rows_per_sec"],
        "worker_vs_thread_speedup": round(
            proc["rows_per_sec"] / threaded["rows_per_sec"], 2
        ) if threaded["rows_per_sec"] else None,
        "multiproc_p50_ms": proc["p50_ms"],
        "multiproc_p99_ms": proc["p99_ms"],
        "threaded_p50_ms": threaded["p50_ms"],
        "parity_bitwise": bool(np.array_equal(ref_out, pool_out)),
        "workers": n_workers,
        "clients": n_clients,
        "devices": len(jax.devices()),
        "host_cpu_count": os.cpu_count(),
    }


def _inner_multiproc_pool_cpu() -> dict:
    """The worker-vs-thread measurement on the host CPU backend (CI's
    cluster smoke stage parses it). The speedup
    ratio is only meaningful with >= 8 host cores (2 workers x their
    executor pools + clients); the record carries host_cpu_count so a
    1-core box's ratio is read as the transport-overhead floor it is."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    _force_cpu()
    _setup_jax_cache()
    return _multiproc_pool_stage()


def _serving_autoscale_stage(duration_s=2.0, n=20_000, d=32,
                             max_replicas=None) -> dict:
    """Stage: autoscaling multi-tenant serving — the ROADMAP item 3 /
    ISSUE 15 numbers. Two measurements against the 5-stage fused chain:

      1. **Closed loop**: a 1-replica pool under light load; offered
         load TRIPLES; a PoolAutoscaler (thresholds from the committed
         tuning table) scales the pool with no operator in the loop.
         Emits the pre-scale spike p99, the post-scale recovered p99,
         scale-event counts, and rows/s per replica. On a host-platform
         CPU mesh the virtual devices share one executor pool, so
         recovered-vs-spike is a REGRESSION TRIPWIRE here (the
         unbounded pad-compile bug this PR fixed degraded it >10x); the
         true recovery ratio is the device variant's number (each
         replica owns a chip).
      2. **Precision tiers**: the same chain served single-engine under
         f32, bf16 ``mixed_inference``, and the int8 PTQ tier
         (``d`` >= the committed ``int8_min_const_elems`` threshold, so
         every model constant really quantizes). Emits rows/s per tier,
         ``int8_vs_bf16_rows_per_sec_ratio`` (the acceptance ratio: on
         CPU bf16 is emulated while the int8 tier's dequant-fused
         compute runs native f32 — int8 must WIN), and the
         int8-vs-f32 max |raw deviation| (the quality contract).
    """
    import threading

    from flinkml_tpu.serving import (
        AutoscaleConfig,
        PoolAutoscaler,
        ReplicaPool,
        ServingConfig,
        ServingEngine,
    )
    from flinkml_tpu.table import Table

    model, x = _five_stage_model(n, d)
    example = Table({"features": x[:4]})
    if max_replicas is None:
        max_replicas = max(2, min(4, (os.cpu_count() or 2) // 2))

    # -- 1. the closed loop ------------------------------------------------
    pool = ReplicaPool(
        model, example,
        config=ServingConfig(max_batch_rows=128, max_queue_rows=256,
                             max_wait_ms=1.0),
        n_replicas=1, output_cols=("prediction",), name="autoscale_bench",
    ).start()
    scaler = PoolAutoscaler(pool, AutoscaleConfig(
        min_replicas=1, max_replicas=max_replicas,
        up_consecutive=10, down_consecutive=10_000,
        cooldown_s=0.3, interval_s=0.1,
    )).start()
    lat: list = []
    lat_lock = threading.Lock()
    stop = threading.Event()
    rows_served = [0]

    def client(tid):
        rng = np.random.default_rng(tid)
        while not stop.is_set():
            rows = int(rng.integers(16, 49))
            lo = int(rng.integers(0, n - rows))
            t0 = time.perf_counter()
            try:
                pool.predict({"features": x[lo:lo + rows]})
            except Exception:  # noqa: BLE001 — overload during the spike
                continue
            with lat_lock:
                lat.append((time.perf_counter(),
                            (time.perf_counter() - t0) * 1e3))
                rows_served[0] += rows

    def p99_window(t0, t1=None):
        with lat_lock:
            vals = [ms for (tc, ms) in lat
                    if tc >= t0 and (t1 is None or tc < t1)]
        return round(float(np.percentile(vals, 99)), 3) if vals else None

    light = [threading.Thread(target=client, args=(i,)) for i in range(2)]
    for t in light:
        t.start()
    time.sleep(duration_s / 2)
    spike_t0 = time.perf_counter()
    heavy = [threading.Thread(target=client, args=(10 + i,))
             for i in range(4)]
    for t in heavy:
        t.start()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and len(pool.replicas) < 2:
        time.sleep(0.05)
    first_scale_t = time.perf_counter()
    spike_p99 = p99_window(spike_t0, first_scale_t)
    # Let scaling settle, then measure the recovered steady state.
    stable_since, last_count = time.monotonic(), len(pool.replicas)
    while time.monotonic() < deadline:
        if len(pool.replicas) != last_count:
            last_count = len(pool.replicas)
            stable_since = time.monotonic()
        if time.monotonic() - stable_since >= 1.0:
            break
        time.sleep(0.05)
    settle_t0 = time.perf_counter()
    time.sleep(duration_s)
    recovered_p99 = p99_window(settle_t0)
    measure_end = time.perf_counter()
    stop.set()
    for t in light + heavy:
        t.join(timeout=60)
    st = scaler.stats()
    pool_stats = pool.stats()
    per_replica = {
        rname: round(
            rec["counters"].get("rows", 0.0)
            / (measure_end - spike_t0), 1
        )
        for rname, rec in pool_stats["per_replica"].items()
    }
    scaler.stop()
    pool.stop()

    # -- 2. precision tiers ------------------------------------------------
    # Transform throughput (the PR 10 `precision` stage's measurement
    # shape): device work dominates, so the tier ratios measure the
    # tiers, not per-dispatch overhead. Serving inherits them through
    # ServingConfig.precision — same programs, same cache keys.
    from flinkml_tpu import pipeline_fusion
    from flinkml_tpu.table import Table as _T

    apply_table = _T({"features": x})
    reps = 3

    def tier_rate(policy):
        with pipeline_fusion.precision_scope(policy):
            np.asarray(  # warmup: compile this tier's program
                model.transform(apply_table)[0].column("prediction")
            )
            t0 = time.perf_counter()
            for _ in range(reps):
                out = model.transform(apply_table)[0]
                np.asarray(out.column("prediction"))
            return n * reps / (time.perf_counter() - t0)

    _log("serving_autoscale: precision tier A/B (f32 / bf16 / int8) ...")
    f32_rate = tier_rate(None)
    bf16_rate = tier_rate("mixed_inference")
    # The canonical d=32 chain's constants sit under the committed
    # cpu/cpu/8 int8_min_const_elems threshold (256 — quantizing tiny
    # vectors measured pure overhead on a CPU mesh), so the A/B pins the
    # threshold via the sanctioned env gate: this measurement IS the
    # quantizing path, or the ratio would be f32-vs-bf16 in disguise.
    _prev_thr = os.environ.get("FLINKML_TPU_INT8_MIN_CONST")
    os.environ["FLINKML_TPU_INT8_MIN_CONST"] = "16"
    try:
        int8_rate = tier_rate("int8_inference")

        # Quality: int8 vs f32 deviation, probed on the 4th scaler
        # output (the LR sigmoid saturates, so rawPrediction would
        # understate the tier's true error).
        probe = _T({"features": x[:512]})
        (o32,) = model.transform(probe)
        r32 = np.asarray(o32.column("s4")).astype(np.float64)
        with pipeline_fusion.precision_scope("int8_inference"):
            (oq,) = model.transform(probe)
            rq = np.asarray(oq.column("s4")).astype(np.float64)
        int8_dev = float(np.max(np.abs(rq - r32)))
    finally:
        if _prev_thr is None:
            os.environ.pop("FLINKML_TPU_INT8_MIN_CONST", None)
        else:
            os.environ["FLINKML_TPU_INT8_MIN_CONST"] = _prev_thr

    import jax

    return {
        "serving_autoscale_rows_per_sec": round(
            sum(per_replica.values()), 1
        ),
        "serving_rows_per_sec_per_replica": per_replica,
        "autoscale_spike_p99_ms": spike_p99,
        "autoscale_recovered_p99_ms": recovered_p99,
        "autoscale_recovery_ratio": (
            round(recovered_p99 / spike_p99, 3)
            if spike_p99 and recovered_p99 else None
        ),
        "scale_events_total": int(
            st["counters"].get("scale_events_total", 0)
        ),
        "replicas_final": len(pool_stats["per_replica"]),
        "backlog_ewma_final": round(st["backlog_ewma"] or 0.0, 4),
        "f32_rows_per_sec": round(f32_rate, 1),
        "bf16_rows_per_sec": round(bf16_rate, 1),
        "int8_rows_per_sec": round(int8_rate, 1),
        "int8_vs_bf16_rows_per_sec_ratio": round(
            int8_rate / bf16_rate, 3
        ) if bf16_rate else None,
        "int8_vs_f32_rows_per_sec_ratio": round(
            int8_rate / f32_rate, 3
        ) if f32_rate else None,
        "int8_vs_f32_max_raw_dev": int8_dev,
        "dim": d,
        "devices": len(jax.devices()),
        "host_cpu_count": os.cpu_count(),
    }


def _serving_grayfail_stage(duration_s=1.5, n=20_000, d=32) -> dict:
    """Stage: gray-failure defense — the ISSUE 19 numbers. A 4-replica
    pool serves the 5-stage fused chain under closed-loop load with the
    GrayFailGuard running; one replica is stalled ~100x (a 0.2 s
    ``StallDispatch`` on every batch — alive, passing dispatches,
    dragging tail latency). Measures the defense end to end:

    - ``p99_during_stall_ms`` — client-observed p99 from the moment the
      stall arms until it clears. Abandonment + hedging bound this to
      roughly the attempt deadline, NOT the 200 ms stall.
    - ``time_to_quarantine_s`` — stall armed -> the guard's MAD outlier
      test trips and the replica goes SLOW (out of routing, not killed).
    - ``hedge_win_fraction`` — hedges_won / hedges_dispatched: how often
      the second dispatch beat a straggling first attempt.
    - ``recovered_p99_ms`` — p99 after the stall clears and the replica
      rejoins via canary probes; the acceptance tripwire is
      recovered <= max(2x baseline, baseline + 50 ms).
    """
    import threading

    from flinkml_tpu import faults
    from flinkml_tpu.recovery.fuzz import serving_grayfail_policy
    from flinkml_tpu.serving import ReplicaPool, ServingConfig
    from flinkml_tpu.table import Table

    model, x = _five_stage_model(n, d)
    example = Table({"features": x[:4]})
    pool = ReplicaPool(
        model, example,
        config=ServingConfig(max_batch_rows=128, max_queue_rows=512,
                             max_wait_ms=1.0, default_timeout_ms=15_000.0),
        n_replicas=4, output_cols=("prediction",), name="grayfail_bench",
        grayfail=serving_grayfail_policy(),
    ).start()
    guard = pool.grayfail_guard(interval_s=0.05).start()
    lat: list = []
    lat_lock = threading.Lock()
    stop = threading.Event()
    rows_served = [0]

    def client(tid):
        rng = np.random.default_rng(tid)
        while not stop.is_set():
            rows = int(rng.integers(16, 49))
            lo = int(rng.integers(0, n - rows))
            t0 = time.perf_counter()
            try:
                pool.predict({"features": x[lo:lo + rows]})
            except Exception:  # noqa: BLE001 — shed/timeout under stall
                continue
            with lat_lock:
                lat.append((time.perf_counter(),
                            (time.perf_counter() - t0) * 1e3))
                rows_served[0] += rows

    def p99_window(t0, t1=None):
        with lat_lock:
            vals = [ms for (tc, ms) in lat
                    if tc >= t0 and (t1 is None or tc < t1)]
        return round(float(np.percentile(vals, 99)), 3) if vals else None

    from flinkml_tpu.serving.health import ReplicaState

    clients = [threading.Thread(target=client, args=(i,)) for i in range(4)]
    for t in clients:
        t.start()
    base_t0 = time.perf_counter()
    time.sleep(duration_s)  # healthy baseline (also seeds attempt rings)
    baseline_p99 = p99_window(base_t0)

    _log("serving_grayfail: stalling r1 (0.2 s per batch) ...")
    stall_t0 = time.perf_counter()
    quarantine_t = None
    with faults.armed(faults.FaultPlan(
        faults.StallDispatch("r1", delay_s=0.2)
    )):
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if pool.replicas[1].health.state is ReplicaState.SLOW:
                quarantine_t = time.perf_counter()
                break
            time.sleep(0.02)
        # Keep the stall up briefly post-quarantine so the stall window
        # has post-detection traffic too (the steady state the defense
        # actually buys), then clear it.
        time.sleep(duration_s / 2)
    stall_t1 = time.perf_counter()
    stall_p99 = p99_window(stall_t0, stall_t1)

    rejoin_t = None
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        if pool.replicas[1].health.state is ReplicaState.HEALTHY:
            rejoin_t = time.perf_counter()
            break
        time.sleep(0.02)
    time.sleep(duration_s / 2)
    recovered_p99 = p99_window(rejoin_t if rejoin_t else stall_t1)
    measure_end = time.perf_counter()
    stop.set()
    for t in clients:
        t.join(timeout=60)
    router = pool.stats()["router"]
    gcount = guard._metrics.snapshot()["counters"]
    guard.stop()
    pool.stop(drain=False, timeout=30.0)

    hedged = router.get("hedges_dispatched", 0.0)
    import jax

    return {
        "serving_grayfail_rows_per_sec": round(
            rows_served[0] / (measure_end - base_t0), 1
        ),
        "baseline_p99_ms": baseline_p99,
        "p99_during_stall_ms": stall_p99,
        "recovered_p99_ms": recovered_p99,
        "time_to_quarantine_s": (
            round(quarantine_t - stall_t0, 3) if quarantine_t else None
        ),
        "time_to_rejoin_s": (
            round(rejoin_t - stall_t1, 3) if rejoin_t else None
        ),
        "hedge_win_fraction": (
            round(router.get("hedges_won", 0.0) / hedged, 3)
            if hedged else 0.0
        ),
        "hedges_dispatched": int(hedged),
        "abandoned_attempts": int(router.get("abandoned_attempts", 0.0)),
        "quarantines_total": int(gcount.get("quarantines_total", 0)),
        "rejoins_total": int(gcount.get("rejoins_total", 0)),
        "dim": d,
        "devices": len(jax.devices()),
        "host_cpu_count": os.cpu_count(),
    }


def _inner_serving_grayfail() -> dict:
    _setup_jax_cache()
    return _serving_grayfail_stage()


def _inner_serving_grayfail_cpu() -> dict:
    """CPU-mesh variant (CI's ``gray-failure smoke`` stage
    parses it): quarantine timing, hedge accounting, and the
    recovered-vs-baseline p99 tripwire are all observable without the
    device — the 0.2 s stall dwarfs any CPU-mesh noise."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    _force_cpu()
    return _serving_grayfail_stage()


def _inner_serving_autoscale() -> dict:
    _setup_jax_cache()
    return _serving_autoscale_stage()


def _inner_serving_autoscale_cpu() -> dict:
    """CPU-mesh variant (CI's ``autoscale smoke`` stage
    parses it): the control loop, the scale-event counts, and the
    int8-vs-bf16 ratio are all observable without the device; the
    recovery RATIO is a tripwire here (shared-executor CPU mesh — see
    the stage docstring) and a real recovery number on the device."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    _force_cpu()
    return _serving_autoscale_stage()


def _inner_serving() -> dict:
    _setup_jax_cache()
    return _serving_stage()


def _inner_serving_cpu() -> dict:
    """The serving measurement pinned to the host CPU backend (what CI
    parses); says nothing about the chip."""
    _force_cpu()
    return _serving_stage()


def _inner_feed_overlap(n_batches=32, bs=8_192, dim=128, k=512,
                        inner_iters=256) -> dict:
    """Stage: feed-overlap efficiency — the architecture-meaningful
    replacement for the retired ``kmeans_stream`` device stage (which
    timed 160 synchronous per-batch host round trips, not the
    framework).

    Measures ``fed_s / resident_s``: wall clock to push N large batches
    through a compute-heavy jitted step when batches arrive via the
    PrefetchingDeviceFeed (host -> device copy on a worker thread,
    overlapped with compute) vs. when they are pre-resident in HBM.
    Both modes dispatch per batch WITHOUT intermediate synchronization
    (one materialization at the end), so link latency appears once, not
    per batch; the step is sized so compute per batch dominates transfer
    at any plausible link bandwidth. A ratio near 1.0 means the feed
    pipeline fully hides the copy; the gap above 1.0 is the framework's
    streaming overhead (queue handoff + unhidden copy tail)."""
    _setup_jax_cache()
    import jax
    import jax.numpy as jnp
    from flinkml_tpu.iteration.datacache import PrefetchingDeviceFeed

    rng = np.random.default_rng(0)
    host_batches = [
        rng.normal(size=(bs, dim)).astype(np.float32)
        for _ in range(n_batches)
    ]
    cent0 = jnp.asarray(rng.normal(size=(k, dim)).astype(np.float32))

    @jax.jit
    def step(x, c):
        xsq = (x * x).sum(1, keepdims=True)

        def one(c, _):
            d = xsq - 2.0 * (x @ c.T) + (c * c).sum(1)[None, :]
            oh = jax.nn.one_hot(jnp.argmin(d, axis=1), c.shape[0],
                                dtype=x.dtype)
            counts = oh.sum(0)[:, None]
            newc = (oh.T @ x) / jnp.maximum(counts, 1.0)
            return jnp.where(counts > 0, newc, c), None

        c, _ = jax.lax.scan(one, c, None, length=inner_iters)
        return c

    _log("feed_overlap: compiling + warm-up dispatch ...")
    np.asarray(step(jnp.asarray(host_batches[0]), cent0))

    def run(batch_iter):
        start = time.perf_counter()
        c = cent0
        for b in batch_iter:
            c = step(b, c)
        np.asarray(c)  # single synchronization: latency appears once
        return time.perf_counter() - start

    _log("feed_overlap: resident pass ...")
    dev_batches = [jax.device_put(b) for b in host_batches]
    jax.block_until_ready(dev_batches)
    resident_s = run(dev_batches)
    del dev_batches
    _log("feed_overlap: fed pass ...")
    feed = PrefetchingDeviceFeed(iter(host_batches), depth=2)
    try:
        fed_s = run(feed)
    finally:
        feed.close()
    return {
        "ratio": round(fed_s / resident_s, 3),
        "resident_s": round(resident_s, 3),
        "fed_s": round(fed_s, 3),
    }


def _input_pipeline_stage(n=262_144, d=64, bs=4_096,
                          inner_iters=48) -> dict:
    """Stage: input-pipeline throughput — a shuffled
    ``flinkml_tpu.data.Dataset`` (array source → seeded shuffle buffer →
    bucketed async device prefetch) feeding a compute-heavy jitted step,
    the subsystem's production shape (ISSUE 5). All batches share one
    power-of-two row bucket, so the steady state is zero-retrace; the
    prefetcher's double buffering is what keeps the step from ever
    waiting on ingest. Metrics: ``input_rows_per_sec`` (consumer-side,
    first batch → final sync) and ``prefetch_stall_fraction`` (fraction
    of consumer wall spent blocked on the queue — the 'is the producer
    keeping up' number)."""
    import jax
    import jax.numpy as jnp

    from flinkml_tpu.data import Dataset
    from flinkml_tpu.table import Table

    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, d)).astype(np.float32)
    ds = (
        Dataset.from_arrays(Table({"features": x}), bs)
        .shuffle(8, seed=0)
        .prefetch(depth=2, metrics_group="data.prefetch.bench")
    )

    @jax.jit
    def step(xb, acc):
        def one(a, _):
            return a + 1e-3 * jnp.tanh(xb.T @ (xb @ a)), None

        a, _ = jax.lax.scan(one, acc, None, length=inner_iters)
        return a

    acc0 = jnp.zeros(d, jnp.float32)
    warm = next(iter(ds.iterate()))
    np.asarray(step(warm.device_column_padded("features", bs), acc0))

    it = ds.iterate()
    acc = acc0
    rows = 0
    start = time.perf_counter()
    for t in it:
        # The prefetcher's buffers are exactly bucket-height, so this is
        # a zero-copy handoff into the compiled step (no per-batch
        # slicing, no retrace).
        acc = step(t.device_column_padded("features", bs), acc)
        rows += t.num_rows
    np.asarray(acc)  # single end-of-run synchronization
    elapsed = time.perf_counter() - start
    stall = it._prefetcher.stall_fraction if it._prefetcher else 0.0
    return {
        "input_rows_per_sec": round(rows / elapsed, 1),
        "prefetch_stall_fraction": round(stall, 4),
        "rows": rows,
        "batch_size": bs,
        "shuffle_buffer": 8,
    }


def _inner_input_pipeline() -> dict:
    _setup_jax_cache()
    return _input_pipeline_stage()


def _inner_input_pipeline_cpu() -> dict:
    """The input-pipeline measurement pinned to the host CPU backend
    (CI's smoke stage parses it)."""
    _force_cpu()
    return _input_pipeline_stage()


def _sharded_train_stage(n=16_384, dim=512, iters=24) -> dict:
    """Stage: plan-sharded training throughput — full-batch momentum-SGD
    logreg through ``sharding.apply.train_linear_plan`` under each plan
    preset (dp / FSDP / FSDP×TP), one number per preset
    (``sharded_samples_per_sec``). The ISSUE-7 trajectory: the same
    jitted plan-sharded step the product trains with, batch sharded
    along the plan's batch axes, parameters + momentum sharded per the
    plan, GSPMD collectives included in the wall. The replicated preset
    is measured too so the sharding overhead/benefit is one division
    away."""
    import jax

    from flinkml_tpu.parallel import DeviceMesh
    from flinkml_tpu.sharding import PRESETS
    from flinkml_tpu.sharding.apply import train_linear_plan

    x, y, w = make_data(n, dim)
    rates = {}
    for name in ("replicated", "batch_parallel", "fsdp", "fsdp_tp"):
        plan = PRESETS[name]
        mesh = DeviceMesh.for_plan(plan)

        def run(max_iter):
            return train_linear_plan(
                x, y, w, plan, mesh, loss="logistic", optimizer="sgd",
                max_iter=max_iter, learning_rate=0.1,
            )

        run(2)  # compile + warm the window upload path
        start = time.perf_counter()
        coef = run(iters)
        elapsed = time.perf_counter() - start
        assert np.isfinite(coef).all()
        rates[name] = round(n * iters / elapsed, 1)
        _log(f"sharded_train[{name}]: {rates[name]} samples/s "
             f"({len(jax.devices())} devices)")
    return {
        "sharded_samples_per_sec": rates,
        "rows": n,
        "dim": dim,
        "devices": len(jax.devices()),
    }


def _inner_sharded_train() -> dict:
    _setup_jax_cache()
    return _sharded_train_stage()


def _inner_sharded_train_cpu() -> dict:
    """The plan-preset measurement pinned to an 8-virtual-device host
    CPU mesh (CI's sharding stage parses it); the device variant above
    runs the same programs on the chip."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    _force_cpu()
    return _sharded_train_stage()


def _sharded_embedding_stage(vocab=1 << 20, dim=16, batch=1 << 13,
                             reps=8, budget=24 << 20) -> dict:
    """Stage: sharded-embedding lookup+update rows/s (ISSUE 14).

    The vocab is chosen to PROVABLY exceed the per-device budget
    replicated (table + one optimizer slot = 2 x vocab x dim x 4 B >
    ``budget``) AND under fsdp-only row sharding (/4 on the 8-device
    mesh still exceeds it), so the stage first proves the contract:
    FML503 refuses the replicated placement, ``infer_plan`` routes past
    fsdp to the embedding plan (the full fsdp x tp product), and the
    per-shard slice fits. Then each exchange strategy's
    lookup and update rates are measured through the real
    ``EmbeddingTable`` programs, with the analytic per-step exchange
    traffic emitted next to them — linear in ``batch``, independent of
    vocab (the number that makes "never a vocab-sized psum" auditable;
    the dense placement's psum bytes are emitted for contrast)."""
    import jax

    from flinkml_tpu.analysis.sharding_check import check_plan
    from flinkml_tpu.embeddings import EmbeddingTable
    from flinkml_tpu.parallel import DeviceMesh
    from flinkml_tpu.sharding import EMBEDDING, REPLICATED, infer_plan

    rng = np.random.default_rng(0)
    mesh = DeviceMesh.for_plan(EMBEDDING)
    param = {"bench/embedding": (vocab, dim)}
    replicated_bytes = vocab * dim * 4 * 2
    assert replicated_bytes > budget, "vocab does not exceed the budget"
    refusal = check_plan(REPLICATED, mesh, param_shapes=param,
                         hbm_budget_bytes=budget, optimizer_slots=1)
    assert any(f.rule == "FML503" for f in refusal), \
        "FML503 must refuse the replicated placement"
    plan = infer_plan(mesh, param, budget, optimizer_slots=1)
    assert plan.name == "embedding", plan.name

    ids = rng.integers(0, vocab, batch).astype(np.int32)
    delta = (rng.normal(size=(batch, dim)) * 1e-3).astype(np.float32)
    lookup_rates, update_rates, traffic = {}, {}, {}
    table = None
    for strategy in ("ring", "all_to_all"):
        table = EmbeddingTable(
            "bench", vocab, dim, mesh=mesh, plan=plan,
            hbm_budget_bytes=budget, optimizer_slots=1, scale=0.01,
        )
        np.asarray(table.lookup(ids))                     # compile
        table.scatter_add(ids, delta, strategy=strategy)  # compile
        start = time.perf_counter()
        for _ in range(reps):
            np.asarray(table.lookup(ids))
        lookup_rates[strategy] = round(
            batch * reps / (time.perf_counter() - start), 1)
        start = time.perf_counter()
        for _ in range(reps):
            table.scatter_add(ids, delta, strategy=strategy)
        np.asarray(table.lookup(ids[:1]))                 # sync
        update_rates[strategy] = round(
            batch * reps / (time.perf_counter() - start), 1)
        traffic[strategy] = table.exchange_bytes_per_step(batch, strategy)
        _log(f"sharded_embedding[{strategy}]: lookup "
             f"{lookup_rates[strategy]} rows/s, update "
             f"{update_rates[strategy]} rows/s "
             f"({len(jax.devices())} devices)")
    assert np.isfinite(table.to_host()).all()
    return {
        "embedding_lookup_rows_per_sec": lookup_rates,
        "embedding_update_rows_per_sec": update_rates,
        "exchange_bytes_per_step": traffic,
        "exchange_bytes_per_row": {
            s: round(b / batch, 1) for s, b in traffic.items()
        },
        "dense_psum_bytes_per_step": 2 * vocab * dim * 4,
        "vocab": vocab,
        "dim": dim,
        "batch": batch,
        "per_device_budget_bytes": budget,
        "replicated_bytes": replicated_bytes,
        "per_shard_bytes": table.per_device_bytes(),
        "plan": plan.name,
        "n_shards": table.n_shards,
        "devices": len(jax.devices()),
    }


def _inner_sharded_embedding_cpu() -> dict:
    """8-virtual-device CPU-mesh variant — what CI's ``embedding
    smoke`` stage parses."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    _force_cpu()
    return _sharded_embedding_stage()


def _recovery_stage(n_batches=24, rows=16_384, dim=256, reps=5) -> dict:
    """Stage: numerics-sentinel overhead + time-to-recover (ISSUE 9).

    The sentinel's armed cost is one fused verdict reduction + one
    scalar transfer per epoch boundary, on a loop that already syncs a
    host loss every epoch — the acceptance number is <2% throughput
    overhead on a realistic online-batch shape (measured check cost:
    ~0.2 ms vs a ~30 ms step). Measures the SAME
    OnlineLogisticRegression.fit_stream with the sentinel off vs on,
    INTERLEAVED (off/on alternating per round, best-of-``reps`` each) —
    two sequential blocks would fold host-load drift between them into
    the ratio, which is exactly the 20%-either-direction noise the
    interleaving cancels. Then demos a full heal — a NaN batch
    mid-stream under the recovery policy — and reports the
    rollback-to-retrained time-to-recover.
    """
    from flinkml_tpu.models import OnlineLogisticRegression
    from flinkml_tpu.recovery import NumericsSentinel, RecoveryPolicy
    from flinkml_tpu.table import Table

    rng = np.random.default_rng(0)
    true = rng.normal(size=dim)
    batches = []
    for _ in range(n_batches):
        x = rng.normal(size=(rows, dim))
        batches.append(Table({
            "features": x, "label": (x @ true > 0).astype(np.float64),
        }))

    def fit(sentinel=None):
        return OnlineLogisticRegression().set_alpha(0.5).fit_stream(
            batches, sentinel=sentinel,
        )

    fit()                              # compile the FTRL step
    fit(sentinel=NumericsSentinel())   # compile the verdict program

    def timed(mk_sentinel):
        start = time.perf_counter()
        model = fit(sentinel=mk_sentinel())
        wall = time.perf_counter() - start
        assert np.isfinite(model.coefficient).all()
        return wall

    walls_off, walls_on = [], []
    for _ in range(reps):
        walls_off.append(timed(lambda: None))
        walls_on.append(timed(NumericsSentinel))
    wall_off, wall_on = min(walls_off), min(walls_on)
    # Per-round PAIRED ratios (adjacent off/on fits see the same host
    # conditions), best round taken: a ~1s fit on a time-shared CI box
    # sees 10-20% multiplicative scheduler noise, so the mean/median of
    # the paired ratios still jitters past any honest bound — the least
    # contended round is the measurement (the same reasoning that made
    # the serving stage's continuous-vs-FIFO assert a slack tripwire,
    # CHANGES PR 8). The direct per-check cost below is the noise-free
    # ground truth the ratio must agree with.
    overhead = max(0.0, min(on / off for off, on
                            in zip(walls_off, walls_on)) - 1.0)
    total_rows = n_batches * rows
    off_rps = total_rows / wall_off
    on_rps = total_rows / wall_on

    # Ground truth for the acceptance bound: the sentinel's per-check
    # cost measured directly (one fused verdict + one scalar sync;
    # median-of-calls — a scheduler stall inflates a mean) against the
    # per-batch step wall.
    import jax.numpy as jnp

    from flinkml_tpu.recovery.sentinel import NumericsSentinel as _S

    probe = _S()
    carry = {"z": jnp.zeros(dim), "n": jnp.zeros(dim),
             "coef": jnp.zeros(dim), "version": 0}
    probe.check(carry, 0.5, epoch=0, source_index=0)  # compile
    n_checks = 200
    calls = []
    for i in range(n_checks):
        start = time.perf_counter()
        probe.check(carry, 0.5, epoch=i, source_index=i)
        calls.append(time.perf_counter() - start)
    check_ms = sorted(calls)[n_checks // 2] * 1000.0
    step_ms = wall_off / n_batches * 1000.0
    check_frac = check_ms / step_ms
    _log(f"recovery: sentinel off {off_rps:,.0f} rows/s, on "
         f"{on_rps:,.0f} rows/s, best-paired overhead "
         f"{overhead * 100:.2f}% (direct check cost {check_ms:.3f} ms "
         f"vs {step_ms:.1f} ms/step = {check_frac * 100:.2f}%)")

    # Heal demo: poison one mid-stream batch, measure the healed fit and
    # the engine's recorded time-to-recover.
    import tempfile

    from flinkml_tpu.iteration import CheckpointManager

    poisoned = list(batches)
    p = n_batches // 2
    poisoned[p] = Table({
        "features": np.full((rows, dim), np.nan),
        "label": np.zeros(rows),
    })
    with tempfile.TemporaryDirectory(prefix="bench-recovery-") as td:
        mgr = CheckpointManager(td, max_to_keep=4)
        start = time.perf_counter()
        healed = OnlineLogisticRegression().set_alpha(0.5).fit_stream(
            poisoned, checkpoint_manager=mgr, checkpoint_interval=4,
            recovery=RecoveryPolicy(backoff_s=0.0),
        )
        heal_wall = time.perf_counter() - start
    assert np.isfinite(healed.coefficient).all()
    assert healed.recovery_summary["quarantined"] == [p]
    from flinkml_tpu.utils.metrics import metrics

    ttr = metrics.group("recovery").snapshot()["gauges"].get(
        "time_to_recover_p50_ms"
    )
    return {
        "recovery_rows_per_sec_sentinel_off": round(off_rps, 1),
        "recovery_rows_per_sec_sentinel_on": round(on_rps, 1),
        "sentinel_overhead_frac": round(overhead, 5),
        "sentinel_check_ms": round(check_ms, 4),
        "sentinel_check_frac_of_step": round(check_frac, 5),
        "healed_fit_wall_s": round(heal_wall, 3),
        "time_to_recover_p50_ms": (None if ttr is None
                                   else round(float(ttr), 2)),
        "rows": rows,
        "dim": dim,
        "batches": n_batches,
    }


def _inner_recovery() -> dict:
    _setup_jax_cache()
    return _recovery_stage()


def _inner_recovery_cpu() -> dict:
    """The sentinel-overhead measurement pinned to the host CPU backend
    (CI's chaos-soak stage parses it and asserts the <2% acceptance
    bound); the device variant runs the same programs on the chip."""
    _force_cpu()
    return _recovery_stage()


# Epoch-mean logistic-loss target for the convergence stage. Calibrated on
# the seeded a9a-shaped config (CPU, f32): loss 0.599 after 1 epoch, 0.219
# after 25, 0.169 after 50 — tol 0.20 lands at ~30 epochs: long enough to
# be a convergence measurement, short enough to fit any stage cap.
_CONVERGE_TOL = 0.20


def _converge_stage() -> dict:
    """Stage: dense LR epochs/wall-to-converge on the a9a-shaped config
    (n=65_536, d=123, global batch 8_192), seeded, to fixed tol. Steps
    and epochs are hardware-independent (same seeded program); wall_s is
    the device's half of the metric."""
    _setup_jax_cache()
    n, dim, gbs = 65_536, 123, 8_192
    x, y, w = make_data(n, dim)
    steps, wall = bench_convergence(
        x, y, w, gbs, tol=_CONVERGE_TOL, max_steps=4_000
    )
    return {
        "epochs_to_tol": round(steps * gbs / n, 2),
        "wall_s_to_tol": round(wall, 3),
        "tol": _CONVERGE_TOL,
        "steps": steps,
    }


def _inner_converge() -> dict:
    return _converge_stage()


def _inner_converge_sparse() -> dict:
    """Stage: sparse (Criteo-profile) LR epochs/wall-to-converge — dim =
    1e6, 39 nnz/row, n=65_536, global batch 16_384, lr=20, seeded. Tol
    calibrated on the seeded config (CPU, f32): loss 0.693 at start,
    0.265 after 80 epochs, 0.153 after 160 — tol 0.25 lands at ~85
    epochs. Uses the product sparse trainer."""
    _setup_jax_cache()
    import jax.numpy as jnp
    from flinkml_tpu.models import _linear_sgd
    from flinkml_tpu.parallel import DeviceMesh

    n, dim, gbs, tol, max_steps = 65_536, 1_000_000, 16_384, 0.25, 2_000
    indptr, indices, values, y, w = make_criteo_csr(n, dim)
    mesh = DeviceMesh()
    place, local_bss, slot_plan = _linear_sgd.prepare_sparse_buckets(
        indptr, indices, values, dim, y, w, mesh, gbs, seed=0,
    )
    data_args = _linear_sgd._placed(place(0, max_steps))
    trainer = _linear_sgd._sparse_trainer_bucketed(
        mesh.mesh, "logistic", local_bss, DeviceMesh.DATA_AXIS, dim,
        slot_plan=slot_plan,
    )
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    carry0 = (
        jnp.zeros(dim, jnp.float32),
        jnp.asarray(0, jnp.int32),
        jnp.asarray(jnp.inf, jnp.float32),
    )
    hy = (f32(20.0), f32(0.0), f32(0.0), f32(tol))
    _log("converge_sparse: compiling + warm-up dispatch ...")
    np.asarray(trainer(*carry0, *data_args, *hy,
                       jnp.asarray(2, jnp.int32))[0])
    _log("converge_sparse: measuring steps-to-tol ...")
    start = time.perf_counter()
    coef_out, steps_out, loss_out = trainer(
        *carry0, *data_args, *hy, jnp.asarray(max_steps, jnp.int32)
    )
    np.asarray(coef_out)
    wall = time.perf_counter() - start
    steps = int(steps_out)
    if steps >= max_steps or not math.isfinite(float(loss_out)):
        raise RuntimeError(
            f"sparse did not converge: steps={steps}/{max_steps} "
            f"loss={float(loss_out)} tol={tol}"
        )
    return {
        "epochs_to_tol": round(steps * gbs / n, 2),
        "wall_s_to_tol": round(wall, 3),
        "tol": tol,
        "steps": steps,
        "layout": layout,
    }


def _inner_converge_cpu() -> dict:
    """The same convergence program pinned to the host CPU backend:
    epochs_to_tol is hardware-independent; its wall_s is a CPU time."""
    _force_cpu()
    return _converge_stage()


def _precision_stage(n=65_536, d=64, reps=3, train_n=16_384, train_dim=256,
                     iters=24) -> dict:
    """Stage: policy-gated mixed precision A/B — the
    bf16-roofline-gap attribution number. Two measurements, each a
    same-program ratio:

      - the fused 5-stage chain (4 scalers + LogisticRegressionModel)
        under ``precision_scope("mixed_inference")`` vs no policy;
      - the plan-sharded SGD trainer under ``precision="mixed"`` (bf16
        compute, f32 accum + params) vs no policy.

    Emits ``bf16_vs_f32_samples_per_sec_ratio`` per path plus the bf16
    trainer's max-abs coefficient deviation from its f32 twin (what the
    CI smoke stage asserts is finite and tolerance-bounded). On the CPU
    mesh the ratio measures XLA's CPU bf16 lowering (often < 1 — CPUs
    emulate bf16), NOT the TPU MXU story; the device variant runs the
    same programs on the chip."""
    import jax

    from flinkml_tpu import pipeline_fusion
    from flinkml_tpu.parallel import DeviceMesh
    from flinkml_tpu.sharding.plan import REPLICATED
    from flinkml_tpu.sharding.apply import train_linear_plan
    from flinkml_tpu.table import Table

    # -- fused 5-stage chain ------------------------------------------------
    model, x = _five_stage_model(n, d)
    apply_table = Table({"features": x})

    def chain_rows_per_sec():
        np.asarray(
            model.transform(apply_table)[0].column("prediction")
        )  # warm-up: compiles + upload
        start = time.perf_counter()
        for _ in range(reps):
            out = model.transform(apply_table)[0]
            np.asarray(out.column("prediction"))
        return n * reps / (time.perf_counter() - start)

    full_chain = chain_rows_per_sec()
    with pipeline_fusion.precision_scope("mixed_inference"):
        bf16_chain = chain_rows_per_sec()
    _log(f"precision[fused_chain]: f32 {full_chain:.0f} rows/s, "
         f"bf16 {bf16_chain:.0f} rows/s "
         f"(ratio {bf16_chain / full_chain:.3f})")

    # -- plan-sharded SGD trainer ------------------------------------------
    xt, yt, wt = make_data(train_n, train_dim)
    mesh = DeviceMesh.for_plan(REPLICATED)

    def train(precision, max_iter):
        return train_linear_plan(
            xt, yt, wt, REPLICATED, mesh, loss="logistic", optimizer="sgd",
            max_iter=max_iter, learning_rate=0.1, precision=precision,
        )

    rates = {}
    coefs = {}
    for label, precision in (("f32", None), ("bf16", "mixed")):
        train(precision, 2)  # compile + window upload
        start = time.perf_counter()
        coefs[label] = train(precision, iters)
        rates[label] = train_n * iters / (time.perf_counter() - start)
    coef_dev = float(np.max(np.abs(coefs["bf16"] - coefs["f32"])))
    assert np.isfinite(coefs["bf16"]).all(), "bf16 trainer went non-finite"
    _log(f"precision[sgd_train]: f32 {rates['f32']:.0f} samples/s, "
         f"bf16 {rates['bf16']:.0f} samples/s "
         f"(ratio {rates['bf16'] / rates['f32']:.3f}, "
         f"coef max|Δ| {coef_dev:.2e})")

    return {
        "bf16_vs_f32_samples_per_sec_ratio": {
            "fused_chain": round(bf16_chain / full_chain, 3),
            "sgd_train": round(rates["bf16"] / rates["f32"], 3),
        },
        "fused_chain_rows_per_sec": {
            "f32": round(full_chain, 1), "bf16": round(bf16_chain, 1),
        },
        "sgd_train_samples_per_sec": {
            "f32": round(rates["f32"], 1), "bf16": round(rates["bf16"], 1),
        },
        "sgd_coef_max_abs_dev": coef_dev,
        "rows": n,
        "dim": d,
        "devices": len(jax.devices()),
    }


def _inner_precision() -> dict:
    _setup_jax_cache()
    return _precision_stage()


def _inner_precision_cpu() -> dict:
    """The mixed-precision A/B pinned to an 8-virtual-device host CPU
    mesh (CI's precision smoke stage parses it); the device variant
    runs the same programs on the chip."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    _force_cpu()
    return _precision_stage(n=16_384, train_n=8_192, train_dim=128)


def _inner_cold_start_child() -> dict:
    """One cold-start measurement process: load the published model from
    the registry the parent stage set up, then (mode ``engine``) start a
    serving engine — load + per-bucket warmup, the compile or cache-load
    cost — and take one prediction, or (mode ``pool``) spin a 2-replica
    pool the same way. Reports time-to-first-prediction plus a sha256
    over the prediction bytes (the parent's bitwise-parity check across
    cache modes). Whether this process compiles (cold), loads AOT
    artifacts (warm), or runs the plain jit path (parity baseline) is
    decided entirely by the ``FLINKML_TPU_COMPILE_CACHE`` env var the
    parent did or didn't set; each mode runs in its own process so one
    phase's in-memory artifacts can never subsidize the other's
    measurement."""
    import hashlib

    _force_cpu()
    mode = os.environ.get("_FLINKML_COLDSTART_MODE", "engine")
    from flinkml_tpu.serving.engine import ServingConfig, ServingEngine
    from flinkml_tpu.serving.pool import ReplicaPool
    from flinkml_tpu.serving.registry import ModelRegistry
    from flinkml_tpu.table import Table

    registry = ModelRegistry(os.environ["_FLINKML_COLDSTART_REGISTRY"])
    rng = np.random.default_rng(7)
    x = rng.normal(size=(256, 16))
    example = Table({"features": x[:4], "label": np.zeros(4)})
    req = {"features": x[:37], "label": np.zeros(37)}
    cfg = ServingConfig(max_batch_rows=2048, max_wait_ms=1.0)

    def sha(columns: dict) -> str:
        h = hashlib.sha256()
        for name in sorted(columns):
            h.update(name.encode())
            h.update(np.ascontiguousarray(columns[name]).tobytes())
        return h.hexdigest()

    if mode == "pool":
        t0 = time.perf_counter()
        pool = ReplicaPool(registry, example, config=cfg, n_replicas=2,
                           name="coldpool")
        pool.start()
        resp = pool.predict(req)
        ttfp = time.perf_counter() - t0
        digest = sha(resp.columns)
        pool.stop(drain=False)
    else:
        t0 = time.perf_counter()
        engine = ServingEngine(registry, example, cfg,
                               name="coldstart").start()
        resp = engine.predict(req)
        ttfp = time.perf_counter() - t0
        digest = sha(resp.columns)
        engine.stop()
    return {"ttfp_s": round(ttfp, 4), "pred_sha": digest}


def _cold_start_stage() -> dict:
    """Cold-vs-warm time-to-first-prediction for the fused 5-stage chain
    behind a serving engine, and for a 2-replica pool spin-up — the
    tentpole's acceptance measurement (ROADMAP item 5). Publishes the
    chain once, then runs THREE fresh child processes over one shared
    AOT cache directory:

      1. parity baseline — no compile cache (the plain jit path);
      2. cold — empty cache: full XLA compiles, artifacts stored;
      3. warm — the same cache: every program loads from disk.

    Fresh processes, because that IS the scenario (replica spin-up,
    rolling swap, recovery restart); the children share no jit caches.
    Asserts the three runs' predictions are bitwise identical before
    reporting, so a speedup can never come from computing something
    else.

    CPU-only: the stage process fits the chain and then spawns five
    children that each need a backend. On a TPU host the chip belongs
    to the stage process, so the children would fail or hang; the
    one-process chip check of the same store is ``chip_smoke.py``
    phase 4 (retarget loads across replicas)."""
    import shutil
    import tempfile

    tmp = tempfile.mkdtemp(prefix="flinkml-coldstart-")
    try:
        reg_dir = os.path.join(tmp, "registry")
        cache_dir = os.path.join(tmp, "aot")
        from flinkml_tpu.serving.registry import ModelRegistry

        pm, _ = _five_stage_model(n=4_096, d=16)
        ModelRegistry(reg_dir).publish(pm)

        def child(mode: str, cache: "str | None") -> dict:
            env = dict(os.environ)
            env[_INNER_ENV] = "cold_start_child"
            env["_FLINKML_COLDSTART_REGISTRY"] = reg_dir
            env["_FLINKML_COLDSTART_MODE"] = mode
            flags = env.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                env["XLA_FLAGS"] = (
                    flags + " --xla_force_host_platform_device_count=8"
                ).strip()
            if cache is not None:
                env["FLINKML_TPU_COMPILE_CACHE"] = cache
            else:
                env.pop("FLINKML_TPU_COMPILE_CACHE", None)
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                env=env, capture_output=True, text=True, timeout=420,
            )
            if proc.returncode != 0:
                raise RuntimeError(
                    f"cold-start child ({mode}) failed "
                    f"rc={proc.returncode}:\n{proc.stderr[-2000:]}"
                )
            return json.loads(proc.stdout.strip().splitlines()[-1])

        # Engine and pool get DISJOINT cache dirs: the pool's cold run
        # must pay real compiles, not reads of the engine runs' entries.
        engine_cache = os.path.join(cache_dir, "engine")
        pool_cache = os.path.join(cache_dir, "pool")
        baseline = child("engine", None)
        cold = child("engine", engine_cache)
        warm = child("engine", engine_cache)
        pool_cold = child("pool", pool_cache)
        pool_warm = child("pool", pool_cache)
        shas = {r["pred_sha"] for r in
                (baseline, cold, warm, pool_cold, pool_warm)}
        if len(shas) != 1:
            raise RuntimeError(
                "cold-start parity violation: predictions differ across "
                f"jit/cold/warm engine+pool runs ({sorted(shas)})"
            )
        aot_entries = sum(
            1 for _, _, files in os.walk(cache_dir)
            for f in files if f.endswith(".aot")
        )
        return {
            "jit_ttfp_s": baseline["ttfp_s"],
            "cold_ttfp_s": cold["ttfp_s"],
            "warm_ttfp_s": warm["ttfp_s"],
            "ttfp_speedup": round(cold["ttfp_s"] / warm["ttfp_s"], 2),
            "pool_cold_s": pool_cold["ttfp_s"],
            "pool_warm_s": pool_warm["ttfp_s"],
            "pool_speedup": round(
                pool_cold["ttfp_s"] / pool_warm["ttfp_s"], 2
            ),
            "parity_bitwise": 1,
            "aot_entries": aot_entries,
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _inner_cold_start_cpu() -> dict:
    """Cold-start A/B on the 8-virtual-device CPU host — what CI's
    cold-start smoke stage parses."""
    _force_cpu()
    return _cold_start_stage()


def _inner_autotune_cpu() -> dict:
    """Smoke-size CPU-mesh knob search (CI parses it; the committed
    table's values come from the full `python -m flinkml_tpu.autotune
    --commit` run, not from this)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    _force_cpu()
    from flinkml_tpu.autotune import load_table, mesh_key
    from flinkml_tpu.autotune.search import search_knobs

    results = search_knobs(quick=True)
    table = load_table()
    mesh = mesh_key()
    return {
        knob: {
            "winner": rec["value"],
            "committed": table.value(mesh, knob),
            "candidates": rec["candidates"],
        }
        for knob, rec in results.items()
    }


def _pallas_stage() -> dict:
    """Kernel-vs-XLA A/B for the three Pallas sites (ROADMAP item 2 /
    ISSUEs 13, 16): per-site ``pallas/xla`` throughput ratio through the same
    measurers the autotune search commits from, gated by a bitwise
    parity probe per site — a wrong kernel must never emit a ratio. On
    the CPU mesh the Pallas candidates run under the interpreter
    (``interpret: 1`` in the record — the number audits the harness,
    not the hardware). CPU-only: on a v5e two of the three sites
    refuse these shapes compiled (``main``'s comment on the order)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from flinkml_tpu import kernels, pipeline_fusion
    from flinkml_tpu.autotune.search import (
        _env,
        _serving_model,
        measure_kernel_backend_fused_chain,
        measure_kernel_backend_segment_sum,
        measure_kernel_backend_topk,
    )
    from flinkml_tpu.table import Table

    # -- parity gates (bitwise at f32) --------------------------------
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 512, 4096), jnp.int32)
    vals = jnp.asarray(rng.normal(size=4096).astype(np.float32))
    a = np.asarray(jax.ops.segment_sum(vals, ids, num_segments=512))
    b = np.asarray(kernels.segment_sum(vals, ids, 512, backend="pallas"))
    assert a.tobytes() == b.tobytes(), "segment_sum parity violation"
    sids = jnp.sort(ids)
    a = np.asarray(jax.ops.segment_sum(
        vals, sids, num_segments=512, indices_are_sorted=True))
    b = np.asarray(kernels.segment_sum(
        vals, sids, 512, indices_are_sorted=True, backend="pallas"))
    assert a.tobytes() == b.tobytes(), "sorted segment_sum parity violation"
    xq = jnp.asarray(rng.normal(size=(64, 512)).astype(np.float32))
    rv, ri = jax.lax.top_k(xq, 8)
    pv, pi = kernels.top_k(xq, 8, backend="pallas")
    assert np.asarray(rv).tobytes() == np.asarray(pv).tobytes() and \
        np.asarray(ri).tobytes() == np.asarray(pi).tobytes(), \
        "topk parity violation"
    model, xs = _serving_model()
    batch = Table({"features": xs[:256], "label": np.zeros(256)})

    def chain_outputs():
        pipeline_fusion.reset_cache()
        (out,) = model.transform(batch)
        return {c: np.asarray(out.column(c)) for c in out.column_names
                if c not in ("features", "label")}

    with _env("FLINKML_TPU_KERNELS", "fused_chain=xla"):
        ref = chain_outputs()
    with _env("FLINKML_TPU_KERNELS", "fused_chain=pallas"):
        got = chain_outputs()
    pipeline_fusion.reset_cache()
    for c in ref:
        assert ref[c].tobytes() == got[c].tobytes(), \
            f"fused_chain parity violation on column {c!r}"

    # -- ratios -------------------------------------------------------
    sites = {
        "fused_chain": measure_kernel_backend_fused_chain,
        "segment_sum": measure_kernel_backend_segment_sum,
        "topk": measure_kernel_backend_topk,
    }
    ratios, rates = {}, {}
    for site, measure in sites.items():
        cand = measure(True)
        ratios[site] = round(cand["pallas"] / cand["xla"], 4)
        rates[site] = {name: round(v, 1) for name, v in cand.items()}
    return {
        "kernel_vs_xla_samples_per_sec_ratio": ratios,
        "rates": rates,
        "parity_bitwise": 1,
        "interpret": int(kernels.interpret_mode()),
    }


def _inner_pallas_cpu() -> dict:
    """CPU-mesh variant (interpret-mode pallas) — what CI's ``pallas
    smoke`` stage parses."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    _force_cpu()
    return _pallas_stage()


def _sparse_hot_loops_stage() -> dict:
    """Sorted-by-design sparse hot loops (ISSUE 16): sparse-LR rows/s
    through the SortedSparseColumn stream (prefetcher pack + gated SpMV
    forward + sorted segment-sum gradient, zero densify / zero runtime
    sort) against the PRODUCT densified baseline (the same batches as
    ``[n, dim]`` through the dense stream trainer). Moderate ``dim`` so
    the densified baseline is feasible to run at all; the ratio is the
    headline — CI's ``sparse smoke`` trips if the sorted path ever
    loses to densification (>= 1.0 expected: the sparse step moves and
    multiplies O(nnz), the dense one O(n*dim))."""
    import numpy as np

    from flinkml_tpu.data.prefetch import pad_place_table
    from flinkml_tpu.linalg import SparseVector
    from flinkml_tpu.models._linear_sgd import (
        train_linear_model_sorted_stream,
        train_linear_model_stream,
    )
    from flinkml_tpu.parallel import DeviceMesh
    from flinkml_tpu.table import Table

    n_batches, batch, dim, nnz = 8, 512, 4_096, 16
    epochs = 3
    rng = np.random.default_rng(0)
    host_tables, dense_batches = [], []
    for _ in range(n_batches):
        vecs = np.empty(batch, object)
        xd = np.zeros((batch, dim), np.float32)
        for i in range(batch):
            idx = np.sort(rng.choice(dim, size=nnz, replace=False))
            val = rng.normal(size=nnz).astype(np.float32)
            vecs[i] = SparseVector(dim, idx, val)
            xd[i, idx] = val
        y = (rng.random(batch) > 0.5).astype(np.float32)
        w = np.ones(batch, np.float32)
        host_tables.append(Table({"features": vecs, "y": y, "w": w}))
        dense_batches.append({"x": xd, "y": y, "w": w})
    dev_tables = [pad_place_table(t) for t in host_tables]
    mesh = DeviceMesh()
    hyper = dict(loss="logistic", learning_rate=0.5, reg=1e-4,
                 elastic_net=0.0, tol=0.0)

    def sorted_fit(iters):
        return train_linear_model_sorted_stream(
            list(dev_tables), "features", "y", "w", max_iter=iters, **hyper,
        )

    def dense_fit(iters):
        return train_linear_model_stream(
            iter([dict(b) for b in dense_batches]), mesh=mesh,
            max_iter=iters, **hyper,
        )

    rows = n_batches * batch
    out = {"dim": dim, "nnz_per_row": nnz, "rows_per_epoch": rows,
           "epochs_timed": epochs}
    for name, fit in (("sparse_sorted", sorted_fit),
                      ("densified", dense_fit)):
        fit(1)  # compile + warm (module-level stepper caches persist)
        t0 = time.perf_counter()
        fit(epochs)
        out[f"{name}_rows_per_sec"] = round(
            rows * epochs / (time.perf_counter() - t0), 1
        )
    out["sparse_vs_densified_ratio"] = round(
        out["sparse_sorted_rows_per_sec"] / out["densified_rows_per_sec"], 4
    )
    return out


def _inner_sparse_hot_loops() -> dict:
    """The DEVICE sorted-sparse measurement (queued in stage_order):
    the sorted-column stream vs densification on real hardware."""
    _setup_jax_cache()
    return _sparse_hot_loops_stage()


def _inner_sparse_hot_loops_cpu() -> dict:
    """CPU-mesh variant — what CI's ``sparse smoke`` stage parses for
    the no-regression tripwire."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    _force_cpu()
    return _sparse_hot_loops_stage()


def _memory_stage(n=8192, d=32, state_dim=65536) -> dict:
    """Stage: memory-model calibration — the pass-7 static peak-live
    estimate (flinkml_tpu.analysis.memory) measured against XLA's own
    ``Compiled.memory_analysis()`` (temp + argument + output bytes) on
    two real programs: (1) the bench's fused 5-stage chain math
    (4 scalers + logistic head, the ``pipeline_fused`` spine) and
    (2) the plan-sharded SGD step on the 8-way mesh. CI pins both
    ratios inside a 0.5x-2.0x band, so the static model is measured,
    not guessed. Also demonstrates the FML703 donation finding LIVE on
    the real (deliberately undonated) step, and its absence once the
    state buffer is donated."""
    import jax
    import jax.numpy as jnp

    from flinkml_tpu.analysis.memory import (
        check_memory_fn,
        estimate_fn_memory,
    )
    from flinkml_tpu.parallel import DeviceMesh
    from flinkml_tpu.sharding import FSDP
    from flinkml_tpu.sharding.apply import (
        batch_sharding,
        init_linear_state,
        linear_step_fn,
        state_shardings,
    )

    def _xla_bytes(compiled):
        ma = compiled.memory_analysis()
        return (int(ma.temp_size_in_bytes)
                + int(ma.argument_size_in_bytes)
                + int(ma.output_size_in_bytes))

    # -- twin 1: the fused 5-stage chain (single device) -------------------
    def chain(x, mean, std, dmin, dmax, maxabs, median, rng_, coef):
        h = (x - mean) / std
        h = (h - dmin) / (dmax - dmin)
        h = h / maxabs
        h = (h - median) / rng_
        return jax.nn.sigmoid(h @ coef)

    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, d)).astype(np.float32)
    row = np.ones((1, d), np.float32)
    coef = rng.normal(size=(d,)).astype(np.float32)
    chain_args = (x, row, row, 0 * row, row, row, 0 * row, row, coef)
    chain_actual = _xla_bytes(jax.jit(chain).lower(*chain_args).compile())
    chain_est = estimate_fn_memory(chain, *chain_args).peak_bytes

    # -- twin 2: the plan-sharded SGD step (8-way mesh) --------------------
    mesh = DeviceMesh.for_plan(FSDP)
    step = linear_step_fn(
        loss="logistic", optimizer="sgd", dtype_name="float32",
        learning_rate=0.1, momentum=0.9, reg_l2=0.0, reg_l1=0.0,
    )
    state = init_linear_state(state_dim, "sgd", np.float32)
    bs = 256
    xb = rng.normal(size=(bs, state_dim)).astype(np.float32)
    yb = (rng.random(bs) > 0.5).astype(np.float32)
    wb = np.ones(bs, np.float32)
    b_shard = batch_sharding(FSDP, mesh)
    compiled = jax.jit(
        step,
        in_shardings=(state_shardings(FSDP, mesh, state),
                      b_shard, b_shard, b_shard),
        donate_argnums=(0,),
    ).lower(state, xb, yb, wb).compile()
    axes = dict(mesh.mesh.shape)
    sgd_actual = _xla_bytes(compiled)
    sgd_est = estimate_fn_memory(
        step, state, xb, yb, wb, plan=FSDP, mesh=axes,
        param_argnums=(0,), donate_argnums=(0,),
    ).peak_bytes

    # -- FML703 live: the same step, donated vs not ------------------------
    undonated = check_memory_fn(
        step, state, xb, yb, wb, plan=FSDP, mesh=axes,
        param_argnums=(0,), program="sgd_step",
    )
    donated = check_memory_fn(
        step, state, xb, yb, wb, plan=FSDP, mesh=axes,
        param_argnums=(0,), donate_argnums=(0,), program="sgd_step",
    )
    return {
        "memory_calibration_ratio": {
            "fused_chain": round(chain_est / chain_actual, 3),
            "sgd_step": round(sgd_est / sgd_actual, 3),
        },
        "memory_estimate_bytes": {
            "fused_chain": int(chain_est), "sgd_step": int(sgd_est),
        },
        "xla_memory_analysis_bytes": {
            "fused_chain": int(chain_actual), "sgd_step": int(sgd_actual),
        },
        "fml703_live_finding": sorted(
            f.column for f in undonated if f.rule == "FML703"
        ),
        "fml703_after_donation": sorted(
            f.column for f in donated if f.rule == "FML703"
        ),
        "rows": n,
        "state_dim": state_dim,
    }


def _inner_memory_cpu() -> dict:
    """CPU-mesh calibration — what CI's ``memory smoke`` stage parses
    for the 0.5x-2.0x ratio tripwire."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    _force_cpu()
    return _memory_stage()


def _feature_freshness_stage() -> dict:
    """Stage: the streaming feature platform's train-to-serve freshness
    loop end-to-end (ISSUE 18). A hashed-id FM trainer consumes a
    synthetic click stream, publishes incremental row deltas, and a
    2-replica pool follows the registry through in-place row patches.
    Reports trainer throughput, the delta-vs-snapshot payload ratio, and
    the time-to-freshness distribution (publish call until EVERY replica
    serves the new version — the roll is synchronous in the publishing
    thread, so each sample times the full save + patch fan-out)."""
    _setup_jax_cache()
    import tempfile

    from flinkml_tpu.features import (
        DeltaPublisher,
        StreamingHashedFMTrainer,
        hash_buckets,
    )
    from flinkml_tpu.serving.engine import ServingConfig
    from flinkml_tpu.serving.pool import ReplicaPool
    from flinkml_tpu.serving.registry import ModelRegistry
    from flinkml_tpu.table import Table
    from flinkml_tpu.utils.metrics import metrics

    num_buckets, rows, length, publishes = 1 << 16, 512, 4, 32
    rng = np.random.default_rng(0)
    trainer = StreamingHashedFMTrainer(
        num_buckets=num_buckets, factor_size=16, hash_seed=7,
        learning_rate=0.05,
    )

    def batch():
        keys = rng.integers(0, 1 << 22, size=(rows, length))
        ids = hash_buckets(
            keys.reshape(-1), seed=7, num_buckets=num_buckets,
        ).reshape(rows, length)
        labels = (keys.sum(axis=1) % 2).astype(np.float32)
        return ids, labels

    with tempfile.TemporaryDirectory() as root:
        registry = ModelRegistry(os.path.join(root, "reg"))
        publisher = DeltaPublisher(
            registry, trainer, every_n_batches=1, max_depth=publishes + 1,
            name="bench_freshness",
        )
        ids, labels = batch()
        trainer.fit_batch(ids, labels)
        publisher.publish_now()  # the base snapshot
        example = Table({"hashed_ids": np.zeros((2, length), np.int32)})
        pool = ReplicaPool(
            registry, example,
            config=ServingConfig(max_batch_rows=256, max_wait_ms=1.0),
            n_replicas=2, name="bench_freshness",
        ).start().follow_registry()
        try:
            t_train = 0.0
            fresh_ms = []
            for _ in range(publishes):
                ids, labels = batch()
                t0 = time.perf_counter()
                trainer.fit_batch(ids, labels)
                t_train += time.perf_counter() - t0
                t0 = time.perf_counter()
                publisher.publish_now()  # delta + synchronous 2-replica roll
                fresh_ms.append((time.perf_counter() - t0) * 1e3)
            lag = pool.freshness_lag(trainer.watermark)
        finally:
            pool.stop()
        reg_counters = registry._metrics.snapshot()["counters"]
    gauges = metrics.group(
        "features.publisher", labels={"publisher": "bench_freshness"},
    ).snapshot()["gauges"]
    return {
        "train_rows_per_sec": round(rows * publishes / t_train, 1),
        "delta_publishes": int(reg_counters.get("delta_publishes", 0)),
        "full_publishes": int(reg_counters.get("full_publishes", 0)),
        "delta_bytes": int(gauges["delta_bytes"]),
        "full_snapshot_bytes": int(gauges["full_bytes"]),
        "delta_ratio": round(float(gauges["delta_ratio"]), 4),
        "time_to_freshness_ms_p50": round(
            float(np.percentile(fresh_ms, 50)), 2),
        "time_to_freshness_ms_p99": round(
            float(np.percentile(fresh_ms, 99)), 2),
        "freshness_lag_batches": lag,
        "num_buckets": num_buckets,
    }


def _inner_feature_freshness() -> dict:
    return _feature_freshness_stage()


def _inner_feature_freshness_cpu() -> dict:
    """CPU variant — what CI's ``freshness smoke`` bench
    companion parses. The trainer/publisher/pool path is host-resident,
    so this IS the product path, not a proxy; the device variant exists
    to time the roll when replicas hold device-placed tables."""
    _force_cpu()
    return _feature_freshness_stage()


_INNER_STAGES = {
    "dense": _inner_dense,
    "dense_bf16": _inner_dense_bf16,
    "svc": _inner_svc,
    "ftrl": _inner_ftrl,
    "sparse": _inner_sparse,
    "kmeans": _inner_kmeans,
    "kmeans_mnist": _inner_kmeans_mnist,
    "pipeline_fused": _inner_pipeline_fused,
    "pipeline_fused_cpu": _inner_pipeline_fused_cpu,
    "serving": _inner_serving,
    "serving_cpu": _inner_serving_cpu,
    "serving_scaleout": _inner_serving_scaleout,
    "serving_scaleout_cpu": _inner_serving_scaleout_cpu,
    "multiproc_pool_cpu": _inner_multiproc_pool_cpu,
    "serving_autoscale": _inner_serving_autoscale,
    "serving_autoscale_cpu": _inner_serving_autoscale_cpu,
    "serving_grayfail": _inner_serving_grayfail,
    "serving_grayfail_cpu": _inner_serving_grayfail_cpu,
    "feed_overlap": _inner_feed_overlap,
    "input_pipeline": _inner_input_pipeline,
    "input_pipeline_cpu": _inner_input_pipeline_cpu,
    "sharded_train": _inner_sharded_train,
    "sharded_train_cpu": _inner_sharded_train_cpu,
    "sharded_embedding_cpu": _inner_sharded_embedding_cpu,
    "precision": _inner_precision,
    "precision_cpu": _inner_precision_cpu,
    "cold_start_cpu": _inner_cold_start_cpu,
    "cold_start_child": _inner_cold_start_child,
    "autotune_cpu": _inner_autotune_cpu,
    "pallas_cpu": _inner_pallas_cpu,
    "sparse_hot_loops": _inner_sparse_hot_loops,
    "sparse_hot_loops_cpu": _inner_sparse_hot_loops_cpu,
    "memory_cpu": _inner_memory_cpu,
    "feature_freshness": _inner_feature_freshness,
    "feature_freshness_cpu": _inner_feature_freshness_cpu,
    "recovery": _inner_recovery,
    "recovery_cpu": _inner_recovery_cpu,
    "converge": _inner_converge,
    "converge_cpu": _inner_converge_cpu,
    "converge_sparse": _inner_converge_sparse,
    "gbt": _inner_gbt,
    "als": _inner_als,
    "word2vec": _inner_word2vec,
}


#: Stages pinned to the host CPU backend: what ``tools/ci.sh`` parses.
#: They never print under a device metric's name.
_CPU_STAGES = frozenset(
    name for name in _INNER_STAGES if name.endswith("_cpu")
) | {"cold_start_child"}


def _require_tpu() -> None:
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(
            f"bench.py measures the TPU and jax.default_backend() is "
            f"{backend!r}; there is no CPU fallback (the *_cpu stages "
            f"are the CPU-pinned CI checks)"
        )


def _run_stage(stage: str, timeout_s: float):
    """Run one device stage in a child process and return its result;
    any failure ends the whole run non-zero. A child per stage because a
    chip belongs to one process at a time: this parent never touches
    JAX, and each child releases the chip when it exits."""
    _log(f"stage={stage} timeout={timeout_s:.0f}s")
    t0 = time.perf_counter()
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env={**os.environ, _INNER_ENV: stage},
            stdout=subprocess.PIPE,
            stderr=sys.stderr,  # stream child progress live
            text=True,
            timeout=timeout_s,
        )
    except subprocess.TimeoutExpired:
        sys.exit(f"bench: stage={stage} timed out after {timeout_s:.0f}s")
    if proc.returncode != 0:
        sys.exit(f"bench: stage={stage} failed rc={proc.returncode}")
    last = proc.stdout.strip().splitlines()[-1]
    # Scalar stages print one float; structured stages (converge,
    # feed_overlap) print one JSON object.
    value = json.loads(last) if last.startswith("{") else float(last)
    _log(f"stage={stage} ok in {time.perf_counter() - t0:.1f}s -> {value}")
    return value


def main():
    inner = os.environ.get(_INNER_ENV)
    if inner:
        if inner not in _CPU_STAGES:
            _require_tpu()
        out = _INNER_STAGES[inner]()
        print(json.dumps(out) if isinstance(out, dict) else f"{out:.1f}")
        return

    stage_cap = float(os.environ.get("FLINKML_BENCH_STAGE_TIMEOUT", "600"))
    # Not in the device order: cold_start and multiproc_pool spawn JAX
    # children from a process that holds the backend (see their
    # docstrings) — on a TPU host those children cannot reach the chip;
    # autotune and pallas time each kernel site's Pallas backend at
    # shapes the compiled kernels refuse on a v5e (segment_sum at
    # [65536, 1], the chain's float64 constants — see
    # docs/development/kernels.md), so they exist as *_cpu stages only;
    # sharded_embedding proves "128 MiB does not fit a 24 MiB budget
    # until split eight ways", which no 1- or 4-chip mesh satisfies.
    # The dim=1e6 sparse compiles are the heaviest and run last.
    stage_order = ["dense", "dense_bf16", "svc", "converge", "ftrl",
                   "kmeans", "kmeans_mnist", "pipeline_fused",
                   "feed_overlap", "input_pipeline", "sharded_train",
                   "precision", "sparse_hot_loops",
                   "serving_autoscale", "serving_grayfail",
                   "feature_freshness", "gbt",
                   "als", "word2vec", "converge_sparse", "sparse"]
    # The first stage fails fast on a machine without a TPU, before
    # anything is measured or printed.
    results = {name: _run_stage(name, stage_cap) for name in stage_order}

    _log("measuring CPU reference-style baseline ...")
    x_cpu, y_cpu, w_cpu = make_data(200_000, 123)
    cpu_sps = bench_reference_style_cpu(x_cpu, y_cpu, w_cpu, 16_384)
    record = {
        "metric": "logreg_train_samples_per_sec_per_chip",
        "value": round(results["dense"], 1),
        "unit": "samples/sec",
        "vs_baseline": round(results["dense"] / cpu_sps, 2),
    }
    # Secondary measurements ride inside the single JSON line under the
    # names below; the workload for each is documented on its stage.
    scalar_stages = {
        "sparse": "sparse_logreg_samples_per_sec_per_chip",
        "svc": "svc_proximal_samples_per_sec_per_chip",
        "ftrl": "ftrl_online_samples_per_sec_per_chip",
        "dense_bf16": "dense_bf16_logreg_samples_per_sec_per_chip",
        "kmeans": "kmeans_points_per_sec_per_chip",
        "kmeans_mnist": "kmeans_mnist_points_per_sec_per_chip",
        "gbt": "gbt_row_trees_per_sec_per_chip",
        "als": "als_rating_visits_per_sec_per_chip",
        "word2vec": "word2vec_pairs_per_sec_per_chip",
    }
    structured_stages = {
        "pipeline_fused": "pipeline_transform",
        "converge": "convergence",
        "converge_sparse": "convergence_sparse",
    }
    extras = {}
    for stage, value in results.items():
        if stage == "dense":
            continue
        if stage in scalar_stages:
            extras[scalar_stages[stage]] = round(value, 1)
        else:
            extras[structured_stages.get(stage, stage)] = value
    record["extras"] = extras
    print(json.dumps(record), flush=True)


if __name__ == "__main__":
    main()
