"""Sorted-by-design sparse hot loops (ISSUE 16): the
SortedSparseColumn pack/prefetch format with zero retraces across
buckets, the sorted-column stream fit's bitwise parity with the CSR
stream, and the FML404 sorted-scatter provenance gate."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flinkml_tpu.linalg import SparseVector
from flinkml_tpu.table import SortedSparseColumn, Table


def _sparse_table(rng, rows, dim, nnz, weight=True):
    vecs = np.empty(rows, object)
    for i in range(rows):
        idx = np.sort(rng.choice(dim, size=nnz, replace=False))
        vecs[i] = SparseVector(
            dim, idx, rng.normal(size=nnz).astype(np.float32)
        )
    cols = {"features": vecs,
            "y": (rng.random(rows) > 0.5).astype(np.float32)}
    if weight:
        cols["w"] = rng.uniform(0.5, 1.5, rows).astype(np.float32)
    return Table(cols)


# -- SortedSparseColumn pack + prefetch --------------------------------------


def test_pad_place_table_emits_sorted_columns_round_trip():
    """The prefetcher's pack step: all-SparseVector object columns
    become SortedSparseColumns — power-of-two bucket/width, recorded
    ``indices_are_sorted``, pack-time sort tables covering the FULL
    padded block, and a to_host() that reconstructs the vectors."""
    from flinkml_tpu.data.prefetch import pad_place_table

    rng = np.random.default_rng(5)
    t = _sparse_table(rng, rows=11, dim=256, nnz=6)
    dev = pad_place_table(t)
    col = dev._raw_column("features")
    assert isinstance(col, SortedSparseColumn)
    assert col.indices_are_sorted is True
    assert col.dim == 256 and col.rows == 11
    bucket, width = col.buf.shape
    assert bucket & (bucket - 1) == 0 and width & (width - 1) == 0
    assert col.indptr.shape == (bucket + 1,)
    assert col.perm.shape == col.segment_ids.shape == (bucket * width,)
    # the sort tables really are sorted — the scatter's entitlement.
    seg = np.asarray(col.segment_ids)
    assert np.all(np.diff(seg) >= 0)
    # round trip: host view reconstructs every vector exactly.
    for vec, orig in zip(dev.column("features"), t.column("features")):
        np.testing.assert_array_equal(vec.indices, orig.indices)
        np.testing.assert_array_equal(vec.values, orig.values)
    # dense siblings keep the plain padded contract.
    assert dev._raw_column("y").rows == 11


@pytest.mark.no_retrace(allow_compiles=3)
def test_prefetcher_sorted_columns_zero_retraces_across_buckets():
    """ISSUE 16 acceptance: the prefetch feed emits SortedSparseColumns
    across three row buckets and the sorted-column step compiles once
    per bucket and NEVER again — batch-size jitter inside a bucket is
    neutralized by the traced n_valid mask, and the pack-time tables
    are bucket-shaped, not batch-shaped. The budget of 3 is exactly the
    per-bucket warmup (8, 16, 32); the guarded replay must add zero."""
    from flinkml_tpu.data.prefetch import DevicePrefetcher
    from flinkml_tpu.models._linear_sgd import _sorted_column_stepper

    rng = np.random.default_rng(6)
    dim, nnz = 128, 4
    # rows hitting buckets 8, 16, 32; two row counts per bucket.
    tables = [_sparse_table(rng, rows, dim, nnz)
              for rows in (5, 8, 12, 16, 20, 31)]
    step = _sorted_column_stepper("logistic", dim)
    hy = (jnp.float32(0.5), jnp.float32(1e-4), jnp.float32(0.0))
    coef = jnp.zeros(dim, jnp.float32)

    def drive(coef):
        batches = list(DevicePrefetcher(iter(tables), depth=2))
        assert len(batches) == 6
        for t in batches:
            col = t._raw_column("features")
            assert isinstance(col, SortedSparseColumn)
            coef, _, _ = step(
                coef, col.indices, col.buf, col.perm, col.segment_ids,
                t._raw_column("y").buf, t._raw_column("w").buf,
                jnp.asarray(col.rows, jnp.int32), *hy,
            )
        return coef.block_until_ready()

    coef = drive(coef)       # warmup: one compile per bucket (3 total)
    drive(coef)              # guarded replay: zero new compiles


_STREAM_HYPER = dict(loss="logistic", max_iter=4, learning_rate=0.5,
                     reg=1e-3, elastic_net=0.3, tol=0.0)


def test_sorted_stream_fit_bitwise_matches_csr_stream():
    """End-to-end acceptance: the sorted-column stream (device Tables
    from pad_place_table, zero densify / zero step-time sort) produces
    the BIT-IDENTICAL model to the CSR stream reference over a
    multi-epoch weighted elastic-net logistic fit, for batches both
    paths pad to the same row count (ragged batches: the next test)."""
    from flinkml_tpu.data.prefetch import pad_place_table
    from flinkml_tpu.models._linear_sgd import (
        streamed_linear_fit,
        train_linear_model_sorted_stream,
    )
    from flinkml_tpu.parallel import DeviceMesh

    rng = np.random.default_rng(7)
    dim, nnz = 512, 8
    tabs = [_sparse_table(rng, rows, dim, nnz) for rows in (32, 64, 16)]
    # The contract is at the pipeline's f32 dtype on a single-device
    # reference mesh: the conftest's global x64 flag and 8-device psum
    # order would each perturb the CSR reference in the last bit.
    mesh1 = DeviceMesh(devices=jax.devices()[:1])
    with jax.enable_x64(False):
        ref = streamed_linear_fit(
            list(tabs), features_col="features", label_col="y",
            weight_col="w", mesh=mesh1, **_STREAM_HYPER,
        )
        dev = [pad_place_table(t) for t in tabs]
        got = train_linear_model_sorted_stream(dev, "features", "y", "w",
                                               **_STREAM_HYPER)
        assert np.asarray(ref, np.float32).tobytes() == \
            np.asarray(got, np.float32).tobytes()
        # routing: streamed_linear_fit recognizes the device tables too.
        routed = streamed_linear_fit(
            [t for t in dev], features_col="features", label_col="y",
            weight_col="w", mesh=mesh1, **_STREAM_HYPER,
        )
        assert np.asarray(routed, np.float32).tobytes() == \
            np.asarray(got, np.float32).tobytes()


def test_sorted_stream_ragged_batches_differ_by_row_padding_only():
    """Ragged batches (24/48/33 rows): the CSR stream pads rows to a
    multiple of 8 per device (24/48/40), the sorted column to its
    power-of-two bucket (32/64/64). On the installed XLA:CPU a 1-D sum
    is a tree (reduce-window + reduce in the optimized HLO), so a longer
    zero tail re-associates ``sum(w)`` and the loss sum in the last bit,
    and ``step = lr / sum(w)`` carries that into every coefficient. Both
    halves are pinned: the two step PROGRAMS are bit-equal at every step
    on the same padded block, and the end-to-end fits agree to 2 ulp of
    the largest coefficient per step."""
    from flinkml_tpu.data.prefetch import pad_place_table
    from flinkml_tpu.models import _linear_sgd as sgd
    from flinkml_tpu.parallel import DeviceMesh

    rng = np.random.default_rng(7)
    dim, nnz = 512, 8
    tabs = [_sparse_table(rng, rows, dim, nnz) for rows in (24, 48, 33)]
    mesh1 = DeviceMesh(devices=jax.devices()[:1])
    with jax.enable_x64(False):
        dev = [pad_place_table(t) for t in tabs]
        f32 = jnp.float32
        hy = (jnp.asarray(0.5, f32), jnp.asarray(1e-3 * 0.7, f32),
              jnp.asarray(1e-3 * 0.3, f32))
        csr_step = sgd._sparse_stream_stepper(
            mesh1.mesh, "logistic", DeviceMesh.DATA_AXIS, dim)
        sorted_step = sgd._sorted_column_stepper("logistic", dim)
        coef = jnp.zeros(dim, f32)
        for t in dev * 2:
            col = t._raw_column("features")
            yb = t._raw_column("y").buf.astype(f32)
            wb = t._raw_column("w").buf.astype(f32)
            masked = jnp.where(jnp.arange(wb.shape[0]) < col.rows, wb, 0)
            a = csr_step(coef, col.indices, col.buf, yb, masked, *hy)
            b = sorted_step(coef, col.indices, col.buf, col.perm,
                            col.segment_ids, yb, wb,
                            jnp.asarray(col.rows, jnp.int32), *hy)
            for x, y in zip(a, b):      # new coef, loss sum, weight sum
                assert np.asarray(x).tobytes() == np.asarray(y).tobytes()
            coef = a[0]

        ref = sgd.streamed_linear_fit(
            list(tabs), features_col="features", label_col="y",
            weight_col="w", mesh=mesh1, **_STREAM_HYPER,
        )
        got = sgd.train_linear_model_sorted_stream(
            dev, "features", "y", "w", **_STREAM_HYPER)
    steps = _STREAM_HYPER["max_iter"] * len(tabs)
    ulp = float(np.spacing(np.abs(np.asarray(ref, np.float32)).max()))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=0, atol=2 * steps * ulp)


def test_sorted_stream_refuses_checkpointing():
    from flinkml_tpu.models._linear_sgd import (
        train_linear_model_sorted_stream,
    )

    with pytest.raises(ValueError, match="checkpoint"):
        train_linear_model_sorted_stream(
            [], "features", "y", loss="logistic", max_iter=1,
            learning_rate=0.1, reg=0.0, elastic_net=0.0, tol=0.0,
            checkpoint_interval=2,
        )


# -- FML404: sorted-scatter provenance ---------------------------------------


def test_fml404_fires_on_unsorted_flag_over_sorted_input():
    from flinkml_tpu.analysis import check_sorted_scatter_fn

    def bad(v, i):
        return jax.ops.segment_sum(v, i, num_segments=16,
                                   indices_are_sorted=False)

    args = (jnp.zeros(64, jnp.float32), jnp.zeros(64, jnp.int32))
    findings = check_sorted_scatter_fn(bad, args, sorted_argnums=(1,))
    assert [f.rule for f in findings] == ["FML404"]
    assert "sorted" in findings[0].message


def test_fml404_clean_when_flag_asserted_or_no_provenance():
    from flinkml_tpu.analysis import check_sorted_scatter_fn

    def good(v, i):
        return jax.ops.segment_sum(v, i, num_segments=16,
                                   indices_are_sorted=True)

    def bad(v, i):
        return jax.ops.segment_sum(v, i, num_segments=16,
                                   indices_are_sorted=False)

    args = (jnp.zeros(64, jnp.float32), jnp.zeros(64, jnp.int32))
    assert check_sorted_scatter_fn(good, args, sorted_argnums=(1,)) == []
    # unsorted flag over ids WITHOUT provenance is legitimate.
    assert check_sorted_scatter_fn(bad, args, sorted_argnums=()) == []


def test_fml404_walks_through_pjit():
    """The trainers wrap their scatters in jit — the walk must recurse
    one call level or every real consumer would be false-clean."""
    from flinkml_tpu.analysis import check_sorted_scatter_fn

    @jax.jit
    def bad(v, i):
        return jax.ops.segment_sum(v, i, num_segments=16,
                                   indices_are_sorted=False)

    args = (jnp.zeros(64, jnp.float32), jnp.zeros(64, jnp.int32))
    findings = check_sorted_scatter_fn(bad, args, sorted_argnums=(1,))
    assert [f.rule for f in findings] == ["FML404"]


def test_fml404_sorted_column_stepper_traces_clean():
    """The acceptance trace: the production sorted-column SGD step,
    with the column's perm/segment_ids declared sorted-provenance, has
    ZERO FML404 findings — the pipeline never re-pays the sort."""
    from flinkml_tpu.analysis import check_sorted_scatter_fn
    from flinkml_tpu.models._linear_sgd import _sorted_column_stepper

    dim, bucket, width = 64, 16, 8
    step = _sorted_column_stepper("logistic", dim)
    args = (
        jnp.zeros(dim, jnp.float32),                 # coef
        jnp.zeros((bucket, width), jnp.int32),       # ib
        jnp.zeros((bucket, width), jnp.float32),     # vb
        jnp.zeros(bucket * width, jnp.int32),        # perm
        jnp.zeros(bucket * width, jnp.int32),        # segment_ids
        jnp.zeros(bucket, jnp.float32),              # yb
        jnp.ones(bucket, jnp.float32),               # wb
        jnp.asarray(12, jnp.int32),                  # n_valid
        jnp.float32(0.5), jnp.float32(1e-4), jnp.float32(0.0),
    )
    assert check_sorted_scatter_fn(step, args, sorted_argnums=(3, 4)) == []


def test_fml404_scatter_fixture_files():
    from flinkml_tpu.analysis import check_scatter_file

    bad = check_scatter_file(
        "tests/analysis_fixtures/"
        "bad_scatter_fml404_unsorted_flag_on_sorted_input.scatter.json"
    )
    assert [f.rule for f in bad] == ["FML404"]
    good = check_scatter_file(
        "tests/analysis_fixtures/"
        "good_scatter_sorted_flag_on_sorted_input.scatter.json"
    )
    assert good == []
    malformed = check_scatter_file("tests/analysis_fixtures/nope.json")
    assert [f.rule for f in malformed] == ["FML404"]
    assert "unreadable or malformed" in malformed[0].message
