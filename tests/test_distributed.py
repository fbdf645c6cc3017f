"""Tests for the multi-host control-plane helpers.

Single-process here; multi-host behavior is exercised through
``process_slice``'s explicit-argument form and the barrier riding the
8-device CPU mesh (participation of every device = participation of every
host's devices on a real pod).
"""

import os

import jax
import pytest

from flinkml_tpu.parallel import (
    DeviceMesh,
    host_barrier,
    init_distributed,
    process_slice,
)


def test_init_distributed_single_process_noop():
    idx, count = init_distributed()
    assert (idx, count) == (0, 1)


# -- rendezvous retry-with-backoff (ISSUE 4 satellite) ----------------------

def _patch_rendezvous(monkeypatch, outcomes, sleeps):
    """Route the initialize/is_initialized pair through a script:
    ``outcomes`` is a list of exceptions to raise (None = succeed)."""
    calls = []

    def fake_initialize(**kwargs):
        calls.append(kwargs)
        outcome = outcomes[len(calls) - 1]
        if outcome is not None:
            raise outcome

    monkeypatch.setattr(jax.distributed, "initialize", fake_initialize)
    monkeypatch.setattr(jax.distributed, "is_initialized", lambda: False)
    import flinkml_tpu.parallel.distributed as dist

    monkeypatch.setattr(dist.time, "sleep", lambda s: sleeps.append(s))
    return calls


def test_init_distributed_retries_transient_rendezvous(monkeypatch):
    sleeps = []
    calls = _patch_rendezvous(monkeypatch, [
        RuntimeError("DEADLINE_EXCEEDED: barrier timed out"),
        RuntimeError("UNAVAILABLE: failed to connect to coordinator"),
        None,
    ], sleeps)
    idx, count = init_distributed("10.0.0.1:8476", 2, 0,
                                  max_attempts=3, backoff_s=0.5)
    assert len(calls) == 3
    # Exponential base PLUS jitter (ISSUE 9 satellite): each sleep lies
    # in [base, base * (1 + jitter)] — N ranks never retry in lockstep.
    assert 0.5 <= sleeps[0] <= 0.5 * 1.25
    assert 1.0 <= sleeps[1] <= 1.0 * 1.25
    # The real backend is still the single local process.
    assert (idx, count) == (jax.process_index(), jax.process_count())


def test_init_distributed_backoff_jitter_decorrelates():
    """The jitter draw is per-call uniform: two processes retrying the
    same attempt get different delays (with overwhelming probability
    over 32 draws), always inside [base, base*(1+jitter)]."""
    from flinkml_tpu.parallel.distributed import retry_backoff_s

    draws = {retry_backoff_s(3, 1.0, jitter=0.5) for _ in range(32)}
    assert len(draws) > 1, "jitter produced identical delays"
    assert all(4.0 <= d <= 6.0 for d in draws)
    assert retry_backoff_s(1, 0.0) == 0.0  # disabled backoff stays 0
    import random

    assert (retry_backoff_s(2, 1.0, jitter=0.5, rng=random.Random(7))
            == retry_backoff_s(2, 1.0, jitter=0.5, rng=random.Random(7)))


def test_init_distributed_fails_fast_on_non_transient(monkeypatch):
    sleeps = []
    calls = _patch_rendezvous(monkeypatch, [
        RuntimeError("INVALID_ARGUMENT: process id 7 out of range"),
        None,
    ], sleeps)
    with pytest.raises(RuntimeError, match="INVALID_ARGUMENT"):
        init_distributed("10.0.0.1:8476", 2, 0, max_attempts=5)
    assert len(calls) == 1 and sleeps == []


def test_init_distributed_exhausts_attempts(monkeypatch):
    sleeps = []
    err = RuntimeError("connection refused")
    calls = _patch_rendezvous(monkeypatch, [err, err], sleeps)
    with pytest.raises(RuntimeError, match="connection refused"):
        init_distributed("10.0.0.1:8476", 2, 0,
                         max_attempts=2, backoff_s=0.25)
    assert len(calls) == 2 and len(sleeps) == 1
    assert 0.25 <= sleeps[0] <= 0.25 * 1.25


def test_init_distributed_total_deadline_cap(monkeypatch):
    """ISSUE 9 satellite: a total-deadline cap bounds the whole retry
    ladder — when the next (jittered) backoff would overrun it, the
    last transient failure is raised instead of sleeping toward an
    unbounded rendezvous."""
    sleeps = []
    err = RuntimeError("connection refused")
    calls = _patch_rendezvous(monkeypatch, [err] * 10, sleeps)
    import flinkml_tpu.parallel.distributed as dist

    t = [0.0]
    monkeypatch.setattr(dist.time, "monotonic", lambda: t[0])
    with pytest.raises(RuntimeError, match="connection refused"):
        # backoff 10s, deadline 5s: the FIRST retry sleep (>= 10s)
        # already overruns the budget — exactly one attempt, no sleep.
        init_distributed("10.0.0.1:8476", 2, 0,
                         max_attempts=10, backoff_s=10.0, deadline_s=5.0)
    assert len(calls) == 1 and sleeps == []

    with pytest.raises(ValueError, match="deadline_s"):
        init_distributed("10.0.0.1:8476", 2, 0, deadline_s=-1.0)


def test_host_barrier_sums_over_all_devices():
    mesh = DeviceMesh()
    assert host_barrier(mesh, tag=1) == mesh.axis_size()
    assert host_barrier(mesh, tag=3) == 3 * mesh.axis_size()


def test_host_barrier_default_mesh():
    assert host_barrier(tag=1) == len(jax.devices())


@pytest.mark.parametrize(
    "n,count,expected",
    [
        (10, 2, [(0, 5), (5, 10)]),
        (10, 3, [(0, 4), (4, 7), (7, 10)]),  # remainder to low hosts
        (2, 4, [(0, 1), (1, 2), (2, 2), (2, 2)]),
    ],
)
def test_process_slice_partitions_exactly(n, count, expected):
    slices = [process_slice(n, p, count) for p in range(count)]
    assert [(s.start, s.stop) for s in slices] == expected
    # Exact cover: concatenation of slices is 0..n.
    rows = [i for s in slices for i in range(s.start, s.stop)]
    assert rows == list(range(n))


def test_process_slice_defaults_to_this_process():
    s = process_slice(100)
    assert s == slice(0, 100)  # single-process: everything


def test_two_process_control_plane(tmp_path):
    """Launch 2 real processes through jax.distributed (Gloo over localhost).

    Covers the branch no single-process test can: ``init_distributed``
    actually calling ``jax.distributed.initialize`` (the reference's
    MiniCluster ITs exercise SharedProgressAligner the same way —
    SURVEY.md §4 tier 3), ``host_barrier`` over a mesh with
    non-addressable devices, ``process_slice`` with a real process
    count, a cross-process all-reduce, and barrier-ordered checkpoint
    manifest commit. See tests/_dist_worker.py for the worker body.
    """
    # One local device per process: the mesh must span processes, not be
    # satisfiable host-locally.
    _launch_multiprocess_workers(tmp_path, local_devices=1)


def test_two_process_multi_device_data_plane(tmp_path):
    """2 processes × 2 local CPU devices = a 4-device global mesh with
    mixed addressable/non-addressable shards per process — the layout a
    real multi-host pod has. Exercises all_reduce_sum, keyed_aggregate,
    and map_partition across the process boundary."""
    _launch_multiprocess_workers(tmp_path, local_devices=2)


@pytest.mark.parametrize("n_procs", [2, 4])
def test_sustained_cross_process_dispatch(tmp_path, n_procs):
    """Regression: ≥60 sustained collective steps on a multi-process mesh.

    An unsynchronized host loop deadlocks the Gloo backend between 20 and
    60 in-flight ``psum`` dispatches; ``synced_loop`` (the framework's
    bounded-dispatch policy) must sustain 80 — on 2 processes AND on a
    4-process pod (the control plane is not a pairwise special case). See
    tests/_sync_cadence_worker.py for the worker body.
    """
    _launch_multiprocess_workers(
        tmp_path, local_devices=1,
        worker_script="_sync_cadence_worker.py",
        ok_token="CADENCE_OK", check_artifacts=False, n_procs=n_procs,
    )


def test_two_process_streamed_fit(tmp_path):
    """Streamed out-of-core training across 2 real processes (× 2 local
    devices): per-process stream partitions, agreed SPMD schedule with
    unequal batch counts/heights, pooled init sampling, shared-directory
    checkpoint + exact resume. The fitted models must (a) be identical
    on every rank (replicated training state), and (b) match the
    single-process fit over the concatenated per-step batches — the
    equivalence contract of `iteration/stream_sync.py`. Reference: the
    partitioned-stream training the reference runs across TaskManagers
    (`ReplayOperator.java:62-250`, `LogisticRegression.java:334-386`)."""
    _streamed_fit_check(tmp_path, nproc=2, local_devices=2)


# slow (PR 21): a process-spawning case of 20-30 s; tier-1's 870 s limit is
# tight with a cold compile cache. tools/ci.sh's full suite still runs it.
@pytest.mark.slow
def test_four_process_streamed_fit(tmp_path):
    """The same full streamed/online catalog on a 4-process pod: the
    agreement layer (schedules, vocab unions, pooled init, failure
    agreement) is not a pairwise special case."""
    _streamed_fit_check(tmp_path, nproc=4, local_devices=1)


def _streamed_fit_check(tmp_path, nproc, local_devices):
    import sys

    import numpy as np

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import _stream_mp_common as C
    from flinkml_tpu.models._linear_sgd import train_linear_model_stream
    from flinkml_tpu.models.kmeans import train_kmeans_stream

    workdir = _launch_multiprocess_workers(
        tmp_path, local_devices=local_devices,
        worker_script="_stream_mp_worker.py",
        ok_token="STREAM_OK", check_artifacts=False, n_procs=nproc,
        timeout_s=90 * nproc,
    )

    results = [
        np.load(workdir / f"result_{p}.npz") for p in range(nproc)
    ]
    # (a) replicated training state: every rank fitted the same model.
    for key in ("coef", "cents", "cents_rand", "cents_empty", "gmm_means",
                "gmm_weights", "mlp_w0", "gbt_feats", "gbt_leaves",
                "pca_components", "pca_variances", "lda_topics",
                "als_user_f", "als_item_f", "olr_coef", "okm_cents",
                "osc_mean", "osc_std", "w2v_vocab", "w2v_vecs",
                "als_empty_uf", "als_empty_if", "w2v_empty_vecs"):
        for p in range(1, nproc):
            assert np.array_equal(results[0][key], results[p][key]), (
                key, p
            )

    # Word2Vec: same-group tokens (shared contexts) embed closer than
    # cross-group ones; the vocabulary is the union of ALL ranks'
    # partitions.
    vocab = list(results[0]["w2v_vocab"])
    assert set(vocab) == {f"{g}{i}" for g in "ab" for i in range(5)}
    vecs = results[0]["w2v_vecs"]
    unit = vecs / np.linalg.norm(vecs, axis=1, keepdims=True)
    a0, a1 = vocab.index("a0"), vocab.index("a1")
    b0 = vocab.index("b0")
    assert unit[a0] @ unit[a1] > unit[a0] @ unit[b0]

    # ALS: the factors reconstruct the planted low-rank ratings.
    assert float(results[0]["als_rmse"]) < 0.05, results[0]["als_rmse"]

    # Online FTRL learns the separable target's sign pattern; versions
    # count GLOBAL steps (max of the ranks' batch counts, not the sum).
    x_g, y_g = C.global_data()
    acc = float(
        (((x_g @ results[0]["olr_coef"]) > 0) == (y_g > 0.5)).mean()
    )
    assert acc > 0.8, acc
    max_batches = max(
        len(C.local_batches(p, nproc)) for p in range(nproc)
    )
    assert int(results[0]["olr_version"]) == max_batches
    assert int(results[0]["osc_version"]) == sum(
        len(C.local_batches(p, nproc)) for p in range(nproc)
    )

    # GMM: pooled moments + pooled init recover the planted components.
    got = np.sort(results[0]["gmm_means"], axis=0)
    np.testing.assert_allclose(got, C.GMM_MEANS, atol=0.3)

    # LDA: the two fitted topics separate the planted vocab halves.
    topics = results[0]["lda_topics"]  # [2, V], rows sum to 1
    first_half = topics[:, : C.LDA_VOCAB // 2].sum(axis=1)
    assert sorted(first_half) == pytest.approx([0.0, 1.0], abs=0.1), (
        first_half
    )
    # MLP (streamed-Adam runner) and GBT learn the separable target.
    assert float(results[0]["mlp_acc"]) > 0.9, results[0]["mlp_acc"]
    assert float(results[0]["gbt_acc"]) > 0.85, results[0]["gbt_acc"]

    # (b) single-process equivalence on the concatenated-step stream.
    mesh = DeviceMesh()
    exp_coef = train_linear_model_stream(
        iter(C.combined_batches(nproc)), mesh=mesh, **C.LINEAR_HP
    )
    np.testing.assert_allclose(
        results[0]["coef"], exp_coef, rtol=2e-4, atol=2e-5
    )
    # (b2) sparse-native CSR streaming: the 2-rank fit over SparseVector
    # partitions must match the single-process fit whose step-t batch
    # concatenates every rank's batch t.
    from flinkml_tpu.models.logistic_regression import LogisticRegression

    sp_est = LogisticRegression(mesh=mesh)
    for k, v in C.SPARSE_HP.items():
        getattr(sp_est, f"set_{k}")(v)
    exp_sp = sp_est.fit(iter(C.sparse_combined_tables(nproc)))._coefficient
    np.testing.assert_allclose(
        results[0]["sp_coef"], exp_sp, rtol=2e-4, atol=2e-5
    )
    exp_cents = train_kmeans_stream(
        iter({"x": b["x"]} for b in C.combined_batches(nproc)),
        k=C.K_CLUSTERS, mesh=mesh,
        initial_centroids=C.initial_centroids(), **C.KMEANS_HP,
    )
    np.testing.assert_allclose(
        results[0]["cents"], exp_cents, rtol=2e-4, atol=2e-4
    )


def test_two_process_rank_local_failures_abort_all_ranks(tmp_path):
    """Regression for the rank-local-failure hang class: a failure on ONE
    rank (raising source iterator, ragged batch in streamed ingest, a
    missing/corrupt rank-scoped checkpoint shard) must abort EVERY rank
    together through the agreement layer — never strand the healthy rank
    in its next collective. Also pins the straddled-checkpoint resume
    protocol (newest COMMON tree, or an agreed restart when the rank
    checkpoint sets are disjoint). See tests/_hang_guard_worker.py for
    the cases; a hang fails this test's subprocess timeout."""
    _launch_multiprocess_workers(
        tmp_path, local_devices=1,
        worker_script="_hang_guard_worker.py",
        ok_token="GUARD_OK", check_artifacts=False,
    )


def _launch_multiprocess_workers(
    tmp_path, local_devices, worker_script="_dist_worker.py",
    ok_token="WORKER_OK", check_artifacts=True, n_procs=2,
    timeout_s=180,
):
    import shutil
    import socket
    import subprocess
    import sys

    worker = os.path.join(os.path.dirname(__file__), worker_script)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if local_devices > 1:
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={local_devices}"
        )
    else:
        env.pop("XLA_FLAGS", None)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    # Workers share the suite's persistent XLA cache: repeat runs (and
    # retries) skip recompiling the cross-process programs, which
    # otherwise dominate these tests' wall clock.
    from flinkml_tpu.utils import jax_cache

    env.setdefault("JAX_COMPILATION_CACHE_DIR", jax_cache.cache_dir())
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

    def attempt(workdir):
        # Probe a free ephemeral port. The bind-then-close window is racy
        # (another process can claim it before the coordinator binds), so
        # the whole launch retries on a fresh port below.
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        procs = [
            subprocess.Popen(
                [sys.executable, worker, str(port), str(p), str(n_procs),
                 workdir],
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
                env=env,
            )
            for p in range(n_procs)
        ]
        outputs = []
        try:
            for p in procs:
                out, _ = p.communicate(timeout=timeout_s)
                outputs.append(out)
        except subprocess.TimeoutExpired:
            # Kill the stragglers, then drain EVERY remaining pipe:
            # ranks after the wedged one may have finished and printed —
            # that output is the evidence for diagnosing which rank
            # wedged.
            for p in procs:
                if p.poll() is None:
                    p.kill()
            while len(outputs) < n_procs:
                try:
                    out, _ = procs[len(outputs)].communicate(timeout=5)
                except Exception:  # noqa: BLE001 — diagnostics only
                    out = "<timeout>"
                outputs.append(out)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        ok = all(
            p.returncode == 0 and f"{ok_token} {rank}" in out
            for rank, (p, out) in enumerate(zip(procs, outputs))
        )
        return ok, outputs

    for retry in range(3):
        workdir = tmp_path / f"run{retry}"
        workdir.mkdir()
        ok, outputs = attempt(str(workdir))
        if ok:
            break
        shutil.rmtree(workdir, ignore_errors=True)
    assert ok, "all attempts failed; last outputs:\n" + "\n----\n".join(outputs)
    if check_artifacts:
        # The committed artifacts exist on the shared filesystem.
        assert (workdir / "manifest.json").exists()
        assert (workdir / "ckpt").is_dir()
    return workdir
