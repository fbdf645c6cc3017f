"""The in-RAM KMeans fit (PR 32) against ``benchmark/reference/kmeans.py``
(NumPy float64 Lloyd from the same start rows): centroids within a
stated tolerance that the same fit with its operands rounded to bfloat16
fails; four row shares joined by the real ``psum`` give the reference's
round over the whole; an empty cluster, a tie, a padded tail; the
table's one upload; the column as the table holds it; the stated
precision of the one distance expansion in every caller; the spans."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import datagen_mnist
from benchmark.reference import kmeans as reference
from flinkml_tpu.linalg import DenseVector
from flinkml_tpu.models import KMeans, kmeans, knn
from flinkml_tpu.models import _data
from flinkml_tpu.ops import blas
from flinkml_tpu.ops.distance import DistanceMeasure
from flinkml_tpu.parallel import DeviceMesh
from flinkml_tpu.table import Table
from flinkml_tpu.utils.metrics import metrics

K, ROUNDS, ROWS = 10, 20, 6_000
#: Widest centroid coordinate gap to float64 Lloyd that a float32 fit of
#: these rows may show. A coordinate is a mean of ~ 600 pixels in [0, 1]:
#: float32 sums of them are good to ~ 600 * 2**-24 of the sum, 4e-5 of
#: the mean at worst and 1e-7 as read; a row that float32 rounding moves
#: over a boundary shifts a mean by 1 / 600 of a pixel difference, and
#: these rows have none within the rounding (the reference counts them).
#: One bfloat16 pass moves hundreds of rows a round: 1e-3 and more.
TOL = 2e-5


def _digits(seed, rows=ROWS):
    """``level / 255`` float32 rows of the cell's generator (NOT exact
    in bfloat16)."""
    return datagen_mnist.images(seed, datagen_mnist.TAG_TRAIN, rows)[0]


def _blobs(rng, rows, dim, centres):
    """Well-separated ``level / 255`` float32 rows: Lloyd settles on them
    in a few rounds."""
    at = rng.integers(40, 216, (centres, dim))
    levels = at[rng.integers(0, centres, rows)] + rng.integers(-12, 13, (rows, dim))
    return (levels / 255).astype(np.float32)


def _fit(x, seed, mesh=None, k=K, rounds=ROUNDS):
    est = KMeans(mesh=mesh) if mesh is not None else KMeans()
    return est.set_k(k).set_max_iter(rounds).set_seed(seed).fit(
        Table({"features": x}))


def _want(x, seed, k=K, rounds=ROUNDS):
    return reference.lloyd(x, x[reference.start_rows(seed, x.shape[0], k)], rounds)[0]


def _counters(group):
    return dict(metrics.group(group).snapshot()["counters"])


def _moved(after, before, name):
    return after.get(name, 0.0) - before.get(name, 0.0)


@pytest.mark.parametrize("seed", [3, 2 ** 31 - 5])
def test_fit_is_float64_lloyd_from_the_same_start_rows(seed):
    x = _digits(seed)
    got = _fit(x, seed).centroids
    want = _want(x, seed)
    assert np.abs(got - want).max() <= TOL
    assert np.abs(want - x[reference.start_rows(seed, ROWS, K)]).max() > 0.1


@pytest.mark.parametrize("seed", [3, 2 ** 31 - 5])
def test_operands_in_bfloat16_fail_the_same_comparison(seed):
    """What one bfloat16 pass of the MXU computes: both operands of both
    products rounded to bfloat16, float32 sums; everything else the
    trainer's own round."""
    x = _digits(seed)
    low = lambda a: a.astype(jnp.bfloat16).astype(a.dtype)

    def round_in_bfloat16(c, _):
        xd, w = jnp.asarray(x), jnp.ones(x.shape[0], jnp.float32)
        d2 = blas.squared_distances(low(xd), low(c), xs_sq=jnp.sum(xd * xd, -1),
                                    ys_sq=jnp.sum(c * c, -1))
        onehot = jax.nn.one_hot(jnp.argmin(d2, -1), K, dtype=xd.dtype) * w[:, None]
        return kmeans._moved(onehot.T @ low(xd), onehot.sum(0), c), None

    start = jnp.asarray(x[reference.start_rows(seed, ROWS, K)])
    got = np.asarray(jax.jit(lambda c: jax.lax.scan(
        round_in_bfloat16, c, None, length=ROUNDS)[0])(start))
    assert np.abs(got - _want(x, seed)).max() > 5 * TOL


def test_expansion_is_direct_float64_arithmetic():
    """The reference's noted departure, held to what it departs from."""
    x = _digits(11, 500)
    c = x[:K].astype(np.float64) + 0.01
    np.testing.assert_allclose(reference.squared_distances(x, c),
                               reference.squared_distances(x, c, direct=True),
                               rtol=0, atol=1e-10)


@pytest.mark.parametrize("rows", [4_000, 4_003])
def test_four_shares_joined_give_the_whole_round(rows):
    """Each of four devices assigns and reduces its own rows; the real
    ``psum`` joins the four ``[k, d]`` sums and ``[k]`` counts: the
    reference's round over the whole table (a deployment's four chips;
    the cell runs one of them)."""
    x = _digits(5, rows)
    mesh = DeviceMesh(devices=jax.devices()[:4])
    placed = kmeans._place_rows(x, mesh)
    assert placed.rows.shape[0] == 4 * -(-rows // 4)
    assert len(placed.rows.sharding.device_set) == 4
    centroids = x[reference.start_rows(5, rows, K)]
    sums, counts = kmeans._kmeans_partial_fn(mesh.mesh, K, DeviceMesh.DATA_AXIS)(
        placed.rows, placed.mask, jnp.asarray(centroids))
    want_sums, want_counts, _, _ = reference._over_blocks(x, centroids)
    np.testing.assert_array_equal(np.asarray(counts), want_counts)
    # The rows are summed as deviations from a pivot and the pivot is
    # added back, so a dark pixel's sum is a float32 zero-ish, not 0.
    np.testing.assert_allclose(np.asarray(sums), want_sums, rtol=2e-6, atol=1e-4)
    want, _, _ = reference.lloyd_round(x, centroids)
    got = kmeans._lloyd(placed, centroids, mesh, K, 1)
    assert np.abs(got - want).max() <= TOL


@pytest.mark.parametrize("devices", [1, 4, 8])
def test_whole_loop_over_a_mesh_is_the_reference(devices):
    x = _digits(9, 5_001)           # a padded tail on 4 and 8 devices
    mesh = DeviceMesh(devices=jax.devices()[:devices])
    got = _fit(x, 9, mesh).centroids
    assert np.abs(got - _want(x, 9)).max() <= TOL


def test_empty_cluster_keeps_its_last_centroid(mesh):
    x = _digits(13, 2_000)
    start = x[reference.start_rows(13, 2_000, K)].copy()
    start[4] = 9.0                  # nearer to no row than any other
    got = kmeans.train_kmeans(x, K, mesh, ROUNDS, seed=0, initial_centroids=start)
    want, counts, _ = reference.lloyd(x, start, ROUNDS)
    assert counts[4] == 0
    np.testing.assert_array_equal(got[4], start[4])
    assert np.abs(got - want).max() <= TOL


def test_a_tie_goes_to_the_lower_cluster(mesh):
    """Small integers: every distance is exact, and every row of the
    middle group is as far from centroid 0 as from centroid 1."""
    x = np.repeat(np.array([[0.0, 0.0], [2.0, 0.0], [4.0, 0.0], [40.0, 0.0]],
                           np.float32), 8, axis=0)
    start = np.array([[1.0, 0.0], [3.0, 0.0], [40.0, 0.0]], np.float32)
    got = kmeans.train_kmeans(x, 3, mesh, 1, seed=0, initial_centroids=start)
    want, counts, _ = reference.lloyd(x, start, 1)
    np.testing.assert_array_equal(counts, [16, 8, 8])    # the tied rows went to 0
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, [[1.0, 0.0], [4.0, 0.0], [40.0, 0.0]])


@pytest.mark.parametrize("rows", [1_001, 1_003])
def test_padded_rows_count_for_nothing(rows, mesh):
    """Eight devices, rows that do not divide: the zero rows past the
    table's end would otherwise pull a dark cluster's mean down."""
    x = _digits(17, rows)
    placed = kmeans._place_rows(x, mesh)
    assert placed.rows.shape[0] > rows
    assert float(placed.mask.sum()) == rows
    np.testing.assert_array_equal(np.asarray(placed.rows)[rows:], 0.0)
    got = _fit(x, 17, mesh, k=4).centroids
    assert np.abs(got - _want(x, 17, k=4)).max() <= TOL


def test_a_second_fit_on_the_table_uploads_nothing_and_is_bit_equal():
    x = _digits(19, 3_000)
    table = Table({"features": x})
    est = KMeans().set_k(K).set_max_iter(ROUNDS)
    c0 = _counters("kmeans")
    first = est.set_seed(19).fit(table).centroids
    c1 = _counters("kmeans")
    assert _moved(c1, c0, "table_uploads") == 1
    assert _moved(c1, c0, "table_h2d_bytes") >= x.nbytes
    other = est.set_seed(20).fit(table).centroids
    again = est.set_seed(19).fit(table).centroids
    c2 = _counters("kmeans")
    assert _moved(c2, c1, "table_h2d_bytes") == 0 and _moved(c2, c1, "table_uploads") == 0
    assert _moved(c2, c0, "fits") == 3 and _moved(c2, c0, "rounds") == 3 * ROUNDS
    assert again.tobytes() == first.tobytes()
    assert other.tobytes() != first.tobytes()
    # another Table over the same rows is another table: its own copy
    est.fit(Table({"features": x}))
    assert _moved(_counters("kmeans"), c2, "table_uploads") == 1
    # and a table made FROM this one does not carry the copy along
    assert not any(isinstance(key, tuple) and key[0] == "rows_on_mesh"
                   for key in table.select("features")._device_cache)


@pytest.mark.parametrize("x64", [True, False])
def test_a_float32_column_is_placed_as_the_table_holds_it(x64, monkeypatch):
    """No float64 and no padded host copy of the table: the fit takes the
    column through ``features_matrix(dtype=None)``, which hands back the
    table's own array, and the rows on the mesh are float32 whatever
    ``jax_enable_x64`` says."""
    x = _digits(23, 1_001)
    table = Table({"features": x})
    taken = []

    def spy(table, col, dtype=np.float64):
        out = _data.features_matrix(table, col, dtype)
        taken.append((dtype, out))
        return out

    monkeypatch.setattr(kmeans, "features_matrix", spy)
    with jax.enable_x64(x64):
        model = KMeans().set_k(4).set_seed(23).fit(table)
    assert [dtype for dtype, _ in taken] == [None]
    assert taken[0][1] is x
    (placed,) = [v for key, v in table._device_cache.items()
                 if isinstance(key, tuple) and key[0] == "rows_on_mesh"]
    assert placed.rows.dtype == jnp.float32 and placed.norms.dtype == jnp.float32
    assert placed.mask.dtype == jnp.float32
    assert np.abs(model.centroids - _want(x, 23, k=4)).max() <= TOL


def test_an_object_column_is_densified_and_its_rows_kept_too(mesh):
    x = _blobs(np.random.default_rng(29), 64, 5, 3).astype(np.float64)
    rows = np.empty(64, object)
    rows[:] = [DenseVector(r) for r in x]
    table = Table({"features": rows})
    est = KMeans(mesh=mesh).set_k(3).set_seed(29)
    got = est.fit(table).centroids
    np.testing.assert_allclose(got, _want(x, 29, k=3), atol=1e-12)
    before = _counters("kmeans")
    np.testing.assert_array_equal(est.fit(table).centroids, got)
    assert _moved(_counters("kmeans"), before, "table_uploads") == 0


def test_transform_of_the_training_rows_is_the_last_rounds_assignment():
    """On rows Lloyd has settled on, the model's ``transform`` (the same
    distance expansion, through ``DistanceMeasure.nearest``) gives every
    training row the cluster the fit's last round gave it."""
    x = _blobs(np.random.default_rng(31), 3_000, 24, 5)
    table = Table({"features": x})
    model = KMeans().set_k(5).set_max_iter(ROUNDS).set_seed(31).fit(table)
    start = x[reference.start_rows(31, 3_000, 5)]
    before_last = reference.lloyd(x, start, ROUNDS - 1)[0]
    last_round = reference.assignments(x, before_last)
    (out,) = model.transform(table)
    np.testing.assert_array_equal(out["prediction"], last_round)
    np.testing.assert_array_equal(
        out["prediction"], reference.assignments(x, model.centroids))
    assert len(np.unique(last_round)) >= 3


def test_the_fit_counts_its_spans(mesh):
    x = _digits(37, 1_000)
    before = _counters("span")
    _fit(x, 37, mesh, k=3, rounds=4)
    after = _counters("span")
    for name in ("fit", "kmeans.table_to_device", "kmeans.init", "kmeans.loop",
                 "kmeans.dispatch", "kmeans.readback"):
        assert _moved(after, before, f"{name}.calls") == 1, name
    assert _moved(after, before, "kmeans.table_to_device.bytes") >= x.nbytes
    inside = sum(_moved(after, before, f"{name}.seconds") for name in
                 ("kmeans.table_to_device", "kmeans.init", "kmeans.loop",
                  "kmeans.readback"))
    assert inside <= _moved(after, before, "fit.seconds")


def _precisions(fn, *args):
    """The ``precision`` of every product in ``fn``'s traced program."""
    return re.findall(r"precision=([^\n]*)", str(jax.make_jaxpr(fn)(*args)))


_F32 = lambda *shape: jnp.ones(shape, jnp.float32)
_HIGHEST = "(Precision.HIGHEST, Precision.HIGHEST)"


@pytest.mark.parametrize("caller, products", [
    ("expansion", 1), ("measure", 1), ("whole_loop", 2), ("streamed", 2), ("knn_tiled", 1)])
def test_every_caller_of_the_expansion_states_float32_accuracy(caller, products, mesh):
    """One expansion, one stated precision: KMeans' two trainers (their
    sums' product with it), the distance measure the models score
    through, KNN's tiled search. A CPU computes every precision alike,
    so the traced programs are read."""
    axis = DeviceMesh.DATA_AXIS
    traced = {
        "expansion": lambda: _precisions(blas.squared_distances, _F32(8, 4), _F32(3, 4)),
        "measure": lambda: _precisions(
            DistanceMeasure.get_instance("euclidean").nearest, _F32(8, 4), _F32(3, 4)),
        "whole_loop": lambda: _precisions(
            kmeans._kmeans_trainer(mesh.mesh, 3, axis), _F32(16, 4), _F32(16), _F32(16),
            _F32(3, 4), jnp.int32(2)),
        "streamed": lambda: _precisions(
            kmeans._kmeans_partial_fn(mesh.mesh, 3, axis), _F32(16, 4), _F32(16),
            _F32(3, 4)),
        "knn_tiled": lambda: _precisions(
            lambda q, x: knn.nearest(q, x, jnp.sum(x * x, -1), 2, chunk=8, tile=16,
                                     precision=knn.PRODUCT_PRECISION),
            _F32(8, 4), _F32(32, 4)),
    }[caller]()
    assert traced == [_HIGHEST] * products
    assert kmeans.PRODUCT_PRECISION is blas.DISTANCE_PRECISION is knn.PRODUCT_PRECISION


def test_a_control_may_state_one_pass(mesh):
    one_pass = jax.lax.Precision.DEFAULT
    assert _precisions(
        lambda a, b: blas.squared_distances(a, b, precision=one_pass),
        _F32(8, 4), _F32(3, 4)) == ["(Precision.DEFAULT, Precision.DEFAULT)"]
    assert _precisions(
        kmeans._kmeans_trainer(mesh.mesh, 3, DeviceMesh.DATA_AXIS, one_pass),
        _F32(16, 4), _F32(16), _F32(16), _F32(3, 4), jnp.int32(2),
    ) == ["(Precision.DEFAULT, Precision.DEFAULT)"] * 2


def test_norms_handed_in_are_the_norms_computed(rng):
    x = jnp.asarray(rng.random((50, 7)), jnp.float32)
    y = jnp.asarray(rng.random((6, 7)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(blas.squared_distances(x, y)),
        np.asarray(blas.squared_distances(x, y, xs_sq=jnp.sum(x * x, -1),
                                          ys_sq=jnp.sum(y * y, -1))))
